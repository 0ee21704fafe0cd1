"""Decimals on the device lanes (ISSUE 20): p<=18 decimal128 rides the
int lanes as scaled int64 (int32 for p<=9), unequal-scale comparisons
rescale through the two-limb int128 kernels, and the device exchange
carries decimals as unscaled longs — all bit-identical to the exact
host `decimal.Decimal` path, with overflow promoting to host (null per
Spark CheckOverflow), never wrapping.  Knob off = byte-identical seed
behaviour with the eviction reason accounted."""

import decimal as pydec
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu import config, faults
from blaze_tpu.batch import ColumnBatch, DeviceColumn, decimal_from_unscaled
from blaze_tpu.bridge import xla_stats
from blaze_tpu.cache import reset_cache
from blaze_tpu.exprs.base import ColVal, col
from blaze_tpu.kernels import decimal128 as d128
from blaze_tpu.memory import MemManager
from blaze_tpu.plan.stages import DagScheduler
from blaze_tpu.schema import decimal

_U64 = (1 << 64) - 1
_M128 = 1 << 128


@pytest.fixture(autouse=True)
def clean_slate():
    faults.clear()
    MemManager.init(4 << 30)
    reset_cache()
    try:
        yield
    finally:
        faults.clear()
        reset_cache()


@pytest.fixture(autouse=True)
def knob_off_unless_set():
    """The knob is on by default since PR 40; this file's off-legs (the
    host decimal path the on-legs are compared with) switch it off."""
    config.conf.set(config.ENCODING_DECIMAL_ENABLE.key, False)
    try:
        yield
    finally:
        config.conf.unset(config.ENCODING_DECIMAL_ENABLE.key)


@pytest.fixture
def dec_on():
    config.conf.set(config.ENCODING_DECIMAL_ENABLE.key, True)
    try:
        yield
    finally:
        config.conf.unset(config.ENCODING_DECIMAL_ENABLE.key)


@pytest.fixture
def staged_path():
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    try:
        yield
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)


# -- int128 reference helpers ------------------------------------------------

def _signed128(h, l):
    """(hi int64, lo int64) limb pair -> python int."""
    v = ((int(h) << 64) + (int(l) & _U64)) & (_M128 - 1)
    return v - _M128 if v >= (1 << 127) else v


def _pair(vals):
    """python ints -> (hi, lo) int64 numpy limb arrays."""
    hs, ls = [], []
    for v in vals:
        u = int(v) & (_M128 - 1)
        lo, hi = u & _U64, (u >> 64) & _U64
        ls.append(lo - (1 << 64) if lo >= (1 << 63) else lo)
        hs.append(hi - (1 << 64) if hi >= (1 << 63) else hi)
    return (np.array(hs, dtype=np.int64), np.array(ls, dtype=np.int64))


def _rand128(rng, n):
    """Mixed-magnitude int128 sample: full-range, int64-range, tiny,
    and the limb-boundary seams (+-2^63, +-2^64, 0, -1)."""
    out = [0, -1, 1, (1 << 63) - 1, -(1 << 63), 1 << 63, 1 << 64,
           -(1 << 64), (1 << 126), -(1 << 126)]
    for _ in range(n - len(out)):
        bits = int(rng.integers(1, 127))
        v = int(rng.integers(0, 1 << min(bits, 62))) << max(0, bits - 62)
        out.append(-v if rng.random() < 0.5 else v)
    return out


# -- kernel properties vs python-int reference -------------------------------

def test_add_sub_128_matches_python_ints():
    rng = np.random.default_rng(3)
    a = _rand128(rng, 64)
    b = _rand128(rng, 64)
    rng.shuffle(b)
    ah, al = _pair(a)
    bh, bl = _pair(b)
    rh, rl = d128.add128(np, ah, al, bh, bl)
    sh, sl = d128.sub128(np, ah, al, bh, bl)
    for i, (x, y) in enumerate(zip(a, b)):
        want_add = ((x + y) + (1 << 127)) % _M128 - (1 << 127)
        want_sub = ((x - y) + (1 << 127)) % _M128 - (1 << 127)
        assert _signed128(rh[i], rl[i]) == want_add, (x, y)
        assert _signed128(sh[i], sl[i]) == want_sub, (x, y)


def test_neg_fits_and_overflow_flags():
    vals = [0, 1, -1, 1 << 63, -(1 << 63), (1 << 63) - 1, 1 << 100]
    h, l = _pair(vals)
    nh, nl = d128.neg128(np, h, l)
    for i, v in enumerate(vals):
        assert _signed128(nh[i], nl[i]) == -v
    fits = d128.fits_int64(np, h, l)
    assert fits.tolist() == [True, True, True, False, True, True, False]
    # same-sign add whose result flips sign = overflow; mixed signs never
    ah, al = _pair([1 << 126, 1 << 126, -(1 << 126) - 5, 5])
    bh, bl = _pair([1 << 126, -(1 << 126), -(1 << 126) - 5, -7])
    rh, _ = d128.add128(np, ah, al, bh, bl)
    ovf = d128.add_overflows(np, ah, bh, rh)
    assert ovf.tolist() == [True, False, True, False]


def test_mul_pow10_matches_python_ints():
    rng = np.random.default_rng(11)
    vals = [0, 1, -1, 10 ** 18 - 1, -(10 ** 18) + 1] + \
        [int(rng.integers(-10 ** 18, 10 ** 18)) for _ in range(40)]
    for k in (0, 1, 9, 10, 18, 20):
        h, l = d128.from_int64(np, np.array(vals, dtype=np.int64))
        rh, rl = d128.mul_pow10(np, h, l, k)
        for i, v in enumerate(vals):
            # contract: |v| < 10^18, k <= 20 -> exact inside int128
            assert _signed128(rh[i], rl[i]) == v * 10 ** k, (v, k)


def test_compare128_matches_python_ints():
    rng = np.random.default_rng(29)
    a = _rand128(rng, 80)
    b = list(a[:20]) + _rand128(rng, 60)  # force some equal pairs
    rng.shuffle(a)
    ah, al = _pair(a)
    bh, bl = _pair(b)
    lt = d128.lt128(np, ah, al, bh, bl)
    eq = d128.eq128(np, ah, al, bh, bl)
    for i, (x, y) in enumerate(zip(a, b)):
        assert bool(lt[i]) == (x < y), (x, y)
        assert bool(eq[i]) == (x == y), (x, y)


def test_u_lt_unsigned_semantics():
    a = np.array([0, -1, 1, -(1 << 63)], dtype=np.int64)
    b = np.array([-1, 0, 2, 0], dtype=np.int64)
    # as unsigned: 0 < 2^64-1;  2^64-1 > 0;  1 < 2;  2^63 > 0
    assert d128.u_lt(np, a, b).tolist() == [True, False, True, False]


# -- BigInteger minimal bytes + wide-decimal hash ----------------------------

def _ref_biginteger_bytes(v: int) -> bytes:
    """java.math.BigInteger.toByteArray (two's complement, minimal)."""
    n = (v.bit_length() // 8 + 1) if v >= 0 \
        else ((v + 1).bit_length() // 8 + 1)
    return v.to_bytes(n, "big", signed=True)


_BYTE_EDGE_VALS = [0, 1, -1, 127, 128, -128, -129, 255, 256, -256,
                   (1 << 63) - 1, -(1 << 63), 1 << 63, 1 << 64,
                   -(1 << 64), 10 ** 18, -(10 ** 18),
                   (10 ** 18) * (10 ** 20), -((10 ** 18) * (10 ** 20))]


def test_minimal_be_bytes_matches_biginteger():
    h, l = _pair(_BYTE_EDGE_VALS)
    mat, lengths = d128.minimal_be_bytes(h, l)
    for i, v in enumerate(_BYTE_EDGE_VALS):
        ref = _ref_biginteger_bytes(v)
        assert int(lengths[i]) == len(ref), v
        assert bytes(mat[i, :len(ref)]) == ref, v
        assert not mat[i, len(ref):].any()  # left-aligned, zero padding


def test_spark_decimal128_hash_matches_reference():
    from blaze_tpu.kernels.hashing import murmur3_hash_bytes
    rng = np.random.default_rng(17)
    vals = _BYTE_EDGE_VALS + _rand128(rng, 40)
    n = len(vals)
    ref_mat = np.zeros((n, 16), dtype=np.uint8)
    ref_len = np.zeros(n, dtype=np.int32)
    for i, v in enumerate(vals):
        b = _ref_biginteger_bytes(v)
        ref_len[i] = len(b)
        ref_mat[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
    want = murmur3_hash_bytes(ref_mat, ref_len,
                              np.full(n, 42, dtype=np.uint32), np)
    h, l = _pair(vals)
    got = d128.spark_decimal128_hash(h, l)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# -- unequal-scale comparisons: limb lane vs decimal.Decimal -----------------

_OPS = ("==", "!=", "<", "<=", ">", ">=", "<=>")


@pytest.mark.parametrize("lp,ls,rp,rs", [
    (18, 2, 18, 6),    # moderate scale delta with crafted equal pairs
    (18, 0, 18, 18),   # the extreme: delta 18 at full p=18 magnitudes
])
def test_compare_colvals_all_ops_vs_decimal(dec_on, lp, ls, rp, rs):
    rng = np.random.default_rng(41)
    n = 96
    lmax = 10 ** lp - 1
    rmax = 10 ** rp - 1
    a = rng.integers(-lmax, lmax, n).astype(np.int64)
    b = rng.integers(-rmax, rmax, n).astype(np.int64)
    # limb-boundary extremes and equal-value pairs across scales
    a[:6] = [lmax, -lmax, 0, 1, -1, 150 if ls == 2 else lmax]
    b[:6] = [rmax, -rmax, 0, 1, -1,
             1500000 if rs == 6 else rmax]  # 1.50 == 1.500000
    av = rng.random(n) > 0.12
    bv = rng.random(n) > 0.12
    ldt, rdt = decimal(lp, ls), decimal(rp, rs)
    a_cv = ColVal(ldt, data=a, validity=av)
    b_cv = ColVal(rdt, data=b, validity=bv)
    ref_a = [Decimal(int(x)).scaleb(-ls) for x in a]
    ref_b = [Decimal(int(y)).scaleb(-rs) for y in b]
    before = xla_stats.encoding_stats()["decimal_limb_dispatches"]
    for op in _OPS:
        out = d128.compare_colvals(op, a_cv, b_cv, ldt, rdt)
        for i in range(n):
            x, y = ref_a[i], ref_b[i]
            if op == "<=>":
                want = (x == y and av[i] and bv[i]) or \
                    (not av[i] and not bv[i])
                assert bool(out.validity[i])
                assert bool(out.data[i]) == want, (op, i, x, y)
                continue
            if not (av[i] and bv[i]):
                assert not bool(out.validity[i])
                assert not bool(out.data[i])  # null rows read False
                continue
            want = {"==": x == y, "!=": x != y, "<": x < y,
                    "<=": x <= y, ">": x > y, ">=": x >= y}[op]
            assert bool(out.data[i]) == want, (op, i, x, y)
    assert xla_stats.encoding_stats()["decimal_limb_dispatches"] > before


def test_binary_expr_routes_unequal_scale_compare_to_limbs(dec_on):
    """Through the real expression layer: a device-form unequal-scale
    decimal predicate stays vectorized (limb counter fires) and agrees
    with the exact host Decimal answer."""
    from blaze_tpu.exprs.binary import BinaryExpr
    vals_a = [Decimal("1.50"), Decimal("-7.25"), None, Decimal("0.01")]
    vals_b = [Decimal("1.500000"), Decimal("-7.250001"), Decimal("2.0"),
              None]
    t = pa.table({"a": pa.array(vals_a, type=pa.decimal128(12, 2)),
                  "b": pa.array(vals_b, type=pa.decimal128(12, 6))})
    batch = ColumnBatch.from_arrow(t)
    before = xla_stats.encoding_stats()["decimal_limb_dispatches"]
    got = BinaryExpr("<=", col(0), col(1)).evaluate(batch) \
        .to_host(batch.num_rows)
    assert xla_stats.encoding_stats()["decimal_limb_dispatches"] > before
    assert got.to_pylist() == [True, False, None, None]


def test_equal_scale_device_add_matches_exact_host():
    """p<=18 equal-scale '+' takes the vectorized unscaled-int64 path;
    it must agree digit-for-digit with the exact host path."""
    from blaze_tpu.exprs.binary import BinaryExpr
    rng = np.random.default_rng(53)
    n = 200
    ua = rng.integers(-10 ** 9, 10 ** 9, n)
    ub = rng.integers(-10 ** 9, 10 ** 9, n)
    da = [Decimal(int(v)).scaleb(-2) if rng.random() > 0.1 else None
          for v in ua]
    db = [Decimal(int(v)).scaleb(-2) if rng.random() > 0.1 else None
          for v in ub]
    t = pa.table({"a": pa.array(da, type=pa.decimal128(10, 2)),
                  "b": pa.array(db, type=pa.decimal128(10, 2))})
    batch = ColumnBatch.from_arrow(t)
    out = BinaryExpr("+", col(0), col(1)).evaluate(batch)
    assert out.dtype.precision == 11 and out.dtype.scale == 2
    want = [None if (x is None or y is None) else x + y
            for x, y in zip(da, db)]
    assert out.to_host(batch.num_rows).to_pylist() == want


def test_decimal_overflow_promotes_to_host_null_never_wraps():
    """'/' widens past the device contract -> exact host path; rows
    whose result exceeds the capped precision go NULL (Spark
    CheckOverflow), they never wrap; /0 is NULL non-ANSI."""
    from blaze_tpu.exprs.binary import BinaryExpr
    a_vals = [Decimal(10 ** 17), Decimal(4), Decimal(10)]
    b_vals = [Decimal(1).scaleb(-18), Decimal(0), Decimal("0.5")]
    t = pa.table({"a": pa.array(a_vals, type=pa.decimal128(18, 0)),
                  "b": pa.array(b_vals, type=pa.decimal128(18, 18))})
    batch = ColumnBatch.from_arrow(t)
    out = BinaryExpr("/", col(0), col(1)).evaluate(batch)
    assert not out.is_device  # promoted to the exact host form
    got = out.to_host(batch.num_rows).to_pylist()
    assert got[0] is None          # 10^35 overflows decimal(38,6)
    assert got[1] is None           # divide by zero -> null (non-ANSI)
    assert got[2] == Decimal("20")  # in-range rows stay exact


# -- arrow boundary: unscaled rebuild + tier counters ------------------------

def test_decimal_from_unscaled_round_trip():
    rng = np.random.default_rng(61)
    unscaled = rng.integers(-10 ** 14, 10 ** 14, 64)
    unscaled[:4] = [10 ** 18 - 1, -(10 ** 18) + 1, 0, -1]
    valid = rng.random(64) > 0.2
    t = pa.decimal128(18, 4)
    got = decimal_from_unscaled(unscaled.astype(np.int64), valid, t)
    want = pa.array([Decimal(int(v)).scaleb(-4) if ok else None
                     for v, ok in zip(unscaled, valid)], type=t)
    assert got.equals(want)
    # all-valid fast path drops the validity buffer entirely
    got2 = decimal_from_unscaled(unscaled.astype(np.int64), None, t)
    assert got2.null_count == 0
    assert got2.to_pylist() == [Decimal(int(v)).scaleb(-4)
                                for v in unscaled]


def test_scaled_int_tier_counters_and_round_trip(dec_on):
    rng = np.random.default_rng(71)
    narrow = pa.array([Decimal(int(v)).scaleb(-2)
                       for v in rng.integers(-10 ** 4, 10 ** 4, 50)],
                      type=pa.decimal128(7, 2))
    wide = pa.array([Decimal(int(v)).scaleb(-2)
                     for v in rng.integers(-10 ** 9, 10 ** 9, 50)],
                    type=pa.decimal128(12, 2))
    before = xla_stats.encoding_stats()
    c7 = DeviceColumn.from_arrow(narrow, decimal(7, 2), 64)
    c12 = DeviceColumn.from_arrow(wide, decimal(12, 2), 64)
    after = xla_stats.encoding_stats()
    assert np.asarray(c7.data).dtype == np.int32   # narrow tier
    assert np.asarray(c12.data).dtype == np.int64
    assert after["decimal_scaled_int32_dispatches"] > \
        before["decimal_scaled_int32_dispatches"]
    assert after["decimal_scaled_int64_dispatches"] > \
        before["decimal_scaled_int64_dispatches"]
    assert c7.to_arrow(50).equals(narrow)
    assert c12.to_arrow(50).equals(wide)


def test_tier_counters_silent_when_knob_off():
    rng = np.random.default_rng(73)
    arr = pa.array([Decimal(int(v)).scaleb(-2)
                    for v in rng.integers(-10 ** 4, 10 ** 4, 20)],
                   type=pa.decimal128(7, 2))
    before = xla_stats.encoding_stats()
    c = DeviceColumn.from_arrow(arr, decimal(7, 2), 32)
    assert np.asarray(c.data).dtype == np.int64  # no narrow tier
    assert xla_stats.encoding_stats() == before
    assert c.to_arrow(20).equals(arr)


# -- partition-id parity -----------------------------------------------------

def test_pid_parity_host_decimal_vs_device_int64():
    """The host file shuffle hashes p<=18 decimals with the 'decimal'
    tid (long path); the device collective sees plain int64 unscaled
    values.  Both must route every row to the same reducer."""
    import jax.numpy as jnp

    from blaze_tpu.kernels import hashing as H
    from blaze_tpu.parallel.collective import partition_ids_for_keys
    rng = np.random.default_rng(83)
    vals = rng.integers(-10 ** 15, 10 ** 15, 256).astype(np.int64)
    valid = rng.random(256) > 0.1
    for p in (3, 8):
        host = H.spark_partition_ids([(vals, valid)], ["decimal"], p,
                                     xp=np)
        dev = partition_ids_for_keys(
            [(jnp.asarray(vals), jnp.asarray(valid))], p)
        assert np.array_equal(np.asarray(dev), np.asarray(host))


# -- planner admission + eviction accounting ---------------------------------

def _dec_out_schema(precision, scale):
    return {"fields": [
        {"name": "k", "type": {"id": "int64"}, "nullable": True},
        {"name": "v", "type": {"id": "decimal", "precision": precision,
                               "scale": scale}, "nullable": True}]}


def test_exchange_device_spec_decimal_admission():
    from blaze_tpu.plan.planner import exchange_device_spec
    part = {"kind": "hash", "exprs": [{"kind": "column", "index": 0}],
            "num_partitions": 3}
    config.conf.set(config.SHUFFLE_DEVICE.key, "on")
    try:
        before = xla_stats.encoding_stats()["host_evictions_decimal"]
        # knob off: the decimal column evicts the boundary, with reason
        assert exchange_device_spec(part, _dec_out_schema(12, 2)) is None
        mid = xla_stats.encoding_stats()["host_evictions_decimal"]
        assert mid == before + 1
        config.conf.set(config.ENCODING_DECIMAL_ENABLE.key, True)
        spec = exchange_device_spec(part, _dec_out_schema(12, 2))
        assert spec and spec["key_indices"] == [0]
        # wide decimals never take the int64 wire even with the knob on
        assert exchange_device_spec(part, _dec_out_schema(38, 10)) is None
        assert xla_stats.encoding_stats()["host_evictions_decimal"] == \
            mid + 1
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)
        config.conf.unset(config.ENCODING_DECIMAL_ENABLE.key)


# -- end-to-end: scheduler + device exchange ---------------------------------

def _decimal_table(n=3000, seed=7, precision=12, scale=2, null_rate=0.08):
    rng = np.random.default_rng(seed)
    lim = 10 ** min(precision - 1, 6)
    vals = [Decimal(int(rng.integers(-lim, lim))).scaleb(-scale)
            if rng.random() > null_rate else None for _ in range(n)]
    return pa.table({
        "k": pa.array(rng.integers(0, 120, n), type=pa.int64()),
        "v": pa.array(vals, type=pa.decimal128(precision, scale))})


def _decimal_plan(tmp_path, t, precision, scale, tag="", n_reduce=3):
    """sum(decimal(p,s)) is decimal(p+10,s) as Spark types it (PR 40), so
    only p <= 8 keeps the partial sum on the exchange's int64 wire."""
    paths = []
    half = t.num_rows // 2
    for i in range(2):
        p = str(tmp_path / f"dec{tag}-{i}.parquet")
        pq.write_table(t.slice(i * half, half), p)
        paths.append(p)
    schema = _dec_out_schema(precision, scale)
    return {
        "kind": "hash_agg",
        "groupings": [{"expr": {"kind": "column", "index": 0},
                       "name": "k"}],
        "aggs": [{"fn": "sum", "mode": "final", "name": "s",
                  "args": [{"kind": "column", "index": 1}]}],
        "input": {
            "kind": "local_exchange",
            "partitioning": {"kind": "hash",
                             "exprs": [{"kind": "column", "index": 0}],
                             "num_partitions": n_reduce},
            "input": {
                "kind": "hash_agg",
                "groupings": [{"expr": {"kind": "column", "name": "k"},
                               "name": "k"}],
                "aggs": [{"fn": "sum", "mode": "partial", "name": "s",
                          "args": [{"kind": "column", "name": "v"}]}],
                "input": {"kind": "parquet_scan", "schema": schema,
                          "file_groups": [[paths[0]], [paths[1]]]}}}}


def _sorted_df(tbl):
    return (tbl.to_pandas().sort_values("k", na_position="first")
            .reset_index(drop=True))


def _run_clean(tmp_path, plan, sub="clean"):
    """Reference run: encodings off, host file shuffle."""
    config.conf.set(config.SHUFFLE_DEVICE.key, "off")
    try:
        return _sorted_df(DagScheduler(
            work_dir=str(tmp_path / sub)).run_collect(plan))
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)


def test_decimal_exchange_device_resident_bit_identical(tmp_path,
                                                        staged_path):
    plan = _decimal_plan(tmp_path, _decimal_table(precision=8), 8, 2, tag="ex")
    clean = _run_clean(tmp_path, plan)
    config.conf.set(config.SHUFFLE_DEVICE.key, "on")
    config.conf.set(config.ENCODING_DECIMAL_ENABLE.key, True)
    try:
        before = xla_stats.snapshot()
        got = _sorted_df(DagScheduler(
            work_dir=str(tmp_path / "dev")).run_collect(plan))
        d = xla_stats.delta(before)
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)
        config.conf.unset(config.ENCODING_DECIMAL_ENABLE.key)
    assert d["shuffle_device_exchanges"] >= 1
    assert d["shuffle_device_fallbacks"] == 0
    assert d["decimal_scaled_int64_dispatches"] > 0
    assert got.equals(clean)


def test_decimal_int32_tier_e2e_bit_identical(tmp_path, staged_path):
    t = _decimal_table(precision=7, scale=2, seed=13)
    plan = _decimal_plan(tmp_path, t, 7, 2, tag="n32")
    clean = _run_clean(tmp_path, plan)
    config.conf.set(config.SHUFFLE_DEVICE.key, "on")
    config.conf.set(config.ENCODING_DECIMAL_ENABLE.key, True)
    try:
        before = xla_stats.snapshot()
        got = _sorted_df(DagScheduler(
            work_dir=str(tmp_path / "dev32")).run_collect(plan))
        d = xla_stats.delta(before)
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)
        config.conf.unset(config.ENCODING_DECIMAL_ENABLE.key)
    assert d["decimal_scaled_int32_dispatches"] > 0  # narrow scan tier
    assert d["shuffle_device_fallbacks"] == 0
    assert got.equals(clean)


def test_injected_collective_fault_falls_back_lossless(tmp_path,
                                                       staged_path):
    plan = _decimal_plan(tmp_path, _decimal_table(seed=19, precision=8), 8, 2,
                         tag="ft")
    clean = _run_clean(tmp_path, plan)
    config.conf.set(config.SHUFFLE_DEVICE.key, "on")
    config.conf.set(config.ENCODING_DECIMAL_ENABLE.key, True)
    try:
        before = xla_stats.snapshot()
        with faults.scoped(("device-collective", dict(p=1.0))):
            got = _sorted_df(DagScheduler(
                work_dir=str(tmp_path / "flt")).run_collect(plan))
        d = xla_stats.delta(before)
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)
        config.conf.unset(config.ENCODING_DECIMAL_ENABLE.key)
    assert d["shuffle_device_fallbacks"] >= 1
    assert got.equals(clean)  # the file path reruns the stage losslessly


def test_decimal_zero_steady_state_recompiles(tmp_path, staged_path):
    plan = _decimal_plan(tmp_path, _decimal_table(seed=23, precision=8), 8, 2,
                         tag="rc")
    config.conf.set(config.SHUFFLE_DEVICE.key, "on")
    config.conf.set(config.ENCODING_DECIMAL_ENABLE.key, True)
    try:
        DagScheduler(work_dir=str(tmp_path / "r0")).run_collect(plan)
        before = xla_stats.snapshot()
        DagScheduler(work_dir=str(tmp_path / "r1")).run_collect(plan)
        d = xla_stats.delta(before)
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)
        config.conf.unset(config.ENCODING_DECIMAL_ENABLE.key)
    assert d["shuffle_device_fallbacks"] == 0
    assert d["total_compiles"] == 0, \
        f"steady-state recompiles: {d['total_compiles']}"


def test_knob_off_eviction_accounting(tmp_path, staged_path):
    """With the decimal knob off the boundary stays on the host file
    shuffle — and the stats plane records WHY (decimal_column), which is
    what the advisor's host_eviction finding keys off."""
    plan = _decimal_plan(tmp_path, _decimal_table(seed=31, precision=8), 8, 2,
                         tag="ev")
    clean = _run_clean(tmp_path, plan)
    config.conf.set(config.SHUFFLE_DEVICE.key, "on")
    try:
        before = xla_stats.snapshot()
        got = _sorted_df(DagScheduler(
            work_dir=str(tmp_path / "off")).run_collect(plan))
        d = xla_stats.delta(before)
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)
    assert d["host_evictions_decimal"] >= 1
    assert d["shuffle_device_exchanges"] == 0
    assert got.equals(clean)  # disabled path is byte-identical

"""Device join-probe kernels (kernels/join.py): jit'd match counting +
scan-based bounded pair expansion — the no-per-batch-host-loop probe the
reference does natively (ref joins/join_hash_map.rs:277, VERDICT r3 #2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.kernels.join import (build_runs, expand_pairs,
                                    probe_counts, probe_expand_device)


def _naive_pairs(build_hashes, probe_hashes, probe_null):
    p_idx, b_idx = [], []
    for i, (h, nn) in enumerate(zip(probe_hashes, probe_null)):
        if nn:
            continue
        for j, bh in enumerate(build_hashes):
            if bh == h:
                p_idx.append(i)
                b_idx.append(j)
    return np.array(p_idx, dtype=np.int64), np.array(b_idx, dtype=np.int64)


def test_probe_expand_matches_naive():
    rng = np.random.default_rng(0)
    build = rng.integers(0, 40, 300).astype(np.int64)
    probe = rng.integers(0, 60, 500).astype(np.int64)
    null = rng.random(500) < 0.1
    order = np.argsort(build, kind="stable")
    sh = build[order]
    uh, start, count = build_runs(sh)
    p, b = probe_expand_device(jnp.asarray(uh), jnp.asarray(start),
                               jnp.asarray(count), order,
                               jnp.asarray(probe), jnp.asarray(null))
    want_p, want_b = _naive_pairs(build, probe, null)
    got = sorted(zip(p.tolist(), b.tolist()))
    want = sorted(zip(want_p.tolist(), want_b.tolist()))
    assert got == want


def test_expansion_is_one_traced_program_no_host_loop():
    """The pair expansion must trace to ONE XLA program: data-dependent
    work happens via scan/scatter INSIDE the program, not a Python loop
    over rows.  make_jaxpr succeeding over abstract tracers proves no
    per-row host iteration exists on the path."""
    n = 64
    jaxpr = jax.make_jaxpr(
        lambda s, c: expand_pairs(s, c, 256))(
        jnp.zeros(n, jnp.int64), jnp.ones(n, jnp.int64))
    assert jaxpr is not None  # traced fully abstract: no host loops
    jaxpr2 = jax.make_jaxpr(probe_counts)(
        jnp.arange(8, dtype=jnp.int64), jnp.zeros(8, jnp.int64),
        jnp.ones(8, jnp.int64), jnp.arange(32, dtype=jnp.int64),
        jnp.zeros(32, bool))
    assert jaxpr2 is not None


def test_overflow_grows_bucket():
    # every probe row matches every build row: total = 64*64 = 4096 > 1024
    build = np.zeros(64, dtype=np.int64)
    probe = np.zeros(64, dtype=np.int64)
    order = np.argsort(build, kind="stable")
    uh, start, count = build_runs(build[order])
    p, b = probe_expand_device(jnp.asarray(uh), jnp.asarray(start),
                               jnp.asarray(count), order,
                               jnp.asarray(probe),
                               jnp.zeros(64, dtype=bool))
    assert len(p) == 64 * 64
    assert len(np.unique(p * 64 + b)) == 64 * 64


def test_joinmap_device_path_equals_host_path(monkeypatch):
    """JoinMap.lookup through the jit'd device kernels must produce the
    same verified pairs as the Arrow/numpy host path."""
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.joins.exec import JoinMap, _device_hash_keys
    from blaze_tpu.schema import Schema
    rng = np.random.default_rng(1)
    build_t = pa.table({"k": pa.array(rng.integers(0, 50, 400)),
                        "v": pa.array(rng.random(400))})
    probe_t = pa.table({"k": pa.array(
        np.where(rng.random(800) < 0.05, None,
                 rng.integers(0, 70, 800)).tolist(), type=pa.int64())})
    schema = Schema.from_arrow(build_t.schema)

    def pairs():
        from blaze_tpu.batch import ColumnBatch
        jmap = JoinMap(build_t, [col(0, "k")], schema)
        cb = ColumnBatch.from_arrow(probe_t)
        h, nn, keys = _device_hash_keys(cb, [col(0, "k")])
        p, b = jmap.lookup(h, nn, keys)
        return sorted(zip(np.asarray(p).tolist(), np.asarray(b).tolist()))

    host = pairs()
    import blaze_tpu.bridge.placement as P
    monkeypatch.setattr(P, "host_resident", lambda: False)
    dev = pairs()
    assert host == dev and len(host) > 0


def test_float_key_normalization_all_paths():
    """-0.0 joins 0.0 and NaN joins NaN on BOTH the Acero host path and
    the vectorized JoinMap path (Spark NormalizeFloatingNumbers runs
    upstream of join hashing); HashPartitioning sends the variants to
    one reducer."""
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.ops.joins import JoinType
    from blaze_tpu.ops.joins.exec import ShuffledHashJoinExec
    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.shuffle import HashPartitioning

    left = pa.table({"lk": pa.array([-0.0, float("nan")]),
                     "lv": pa.array([1, 2], type=pa.int64())})
    right = pa.table({"rk": pa.array([0.0, float("nan")]),
                      "rv": pa.array([10, 20], type=pa.int64())})

    def rows(join):
        out = []
        for p in range(join.num_partitions):
            out.extend(b.compact().to_arrow() for b in join.execute(p))
        t = pa.Table.from_batches([b for b in out if b.num_rows])
        return sorted(t.column("lv").to_pylist())

    def build():
        return ShuffledHashJoinExec(
            MemoryScanExec.from_arrow(left),
            MemoryScanExec.from_arrow(right),
            [col(0)], [col(0)], JoinType.INNER)

    assert rows(build()) == [1, 2]  # Acero host path
    import blaze_tpu.bridge.placement as P
    orig = P.host_resident
    P.host_resident = lambda: False
    try:
        assert rows(build()) == [1, 2]  # jit'd JoinMap path
    finally:
        P.host_resident = orig

    # partitioning: -0.0 vs 0.0 and both NaN encodings -> same partition
    hp = HashPartitioning([col(0)], 4)
    pos = ColumnBatch.from_arrow(pa.table({"k": pa.array([0.0, -0.0])}))
    pids = hp.partition_ids(pos)
    assert pids[0] == pids[1]
    nans = ColumnBatch.from_arrow(pa.table(
        {"k": pa.array(np.array([np.nan, -np.nan]))}))
    pids2 = hp.partition_ids(nans)
    assert pids2[0] == pids2[1]


# ---------------------------------------------------------------------------
# the device-resident probe (kernels/join.probe_gather): an inner join on a
# unique fixed-width build key keeps its rows on the device
# ---------------------------------------------------------------------------

def _place_on_device(monkeypatch):
    """From here on batches live on the device, as on the chip (on the
    CPU the default placement is the host's, and the joins take Arrow's)."""
    import blaze_tpu.bridge.placement as P
    from blaze_tpu.memory import MemManager
    MemManager.init(4 << 30)
    monkeypatch.setattr(P, "host_resident", lambda: False)


@pytest.fixture
def on_device(monkeypatch):
    _place_on_device(monkeypatch)


def _i64(values):
    return pa.array(values, type=pa.int64())


def _probe_cases():
    """name -> (build table, [(probe table, selection or None)], number of
    key columns): the leading columns of both tables are the keys."""
    rng = np.random.default_rng(7)
    uniq = rng.permutation(5000)[:1000]
    build = pa.table({"bk": _i64(uniq), "bv": pa.array(rng.random(1000))})

    def probe(n, lo=0, hi=5000):
        return pa.table({"pk": _i64(rng.integers(lo, hi, n)),
                         "pv": _i64(rng.integers(0, 9, n))})

    cases = {"int64_key": (build, [(probe(3000), None)], 1)}
    pairs = rng.permutation(400)[:300]
    cases["two_column_key"] = (
        pa.table({"ba": _i64(pairs // 20), "bb": pa.array(
            (pairs % 20).astype(np.int32)), "bv": _i64(pairs)}),
        [(pa.table({"a": _i64(rng.integers(0, 22, 2000)),
                    "b": pa.array(rng.integers(0, 22, 2000)
                                  .astype(np.int32)),
                    "pv": _i64(np.arange(2000))}), None)], 2)
    nan = float("nan")
    cases["float64_key_nan_and_negative_zero"] = (
        pa.table({"bk": pa.array([0.0, nan, 1.5, 2.5]),
                  "bv": _i64([1, 2, 3, 4])}),
        [(pa.table({"pk": pa.array([-0.0, nan, -nan, 1.5, 3.0, None, 0.0]),
                    "pv": _i64(range(7))}), None)], 1)
    cases["null_keys_on_both_sides"] = (
        pa.table({"bk": _i64([3, None, 5, 9]), "bv": _i64([30, 0, 50, 90])}),
        [(pa.table({"pk": _i64([None, 5, 3, None, 0, 9, 7]),
                    "pv": _i64(range(7))}), None)], 1)
    cases["selection_already_set"] = (
        build, [(probe(3000), rng.random(3000) < 0.4),
                (probe(500), np.zeros(500, dtype=bool))], 1)
    cases["empty_build_side"] = (build.slice(0, 0), [(probe(300), None)], 1)
    cases["no_match"] = (build, [(probe(700, 6000, 7000), None),
                                 (probe(700), None)], 1)
    cases["build_size_not_a_power_of_two"] = (
        build.slice(0, 777), [(probe(3000), None)], 1)
    cases["tail_batch_of_another_capacity"] = (
        build, [(probe(4096), None), (probe(4096), None),
                (probe(130), None)], 1)
    return cases


_PROBE_CASES = _probe_cases()


def _broadcast_join(build_t, probe_batches, nkeys, how=None, flt=None,
                    place=True):
    """probe ⋈ build on their leading `nkeys` columns, the probe side as
    prepared batches (selection and all where `place`, else filtered)."""
    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.ops.joins import BroadcastJoinExec, JoinType
    from blaze_tpu.schema import Schema
    batches = []
    for t, sel in probe_batches:
        if sel is not None and not place:
            t, sel = t.filter(pa.array(sel)), None
        cb = ColumnBatch.from_arrow(t)
        if sel is not None:
            mask = np.zeros(cb.capacity, dtype=bool)
            mask[:len(sel)] = sel
            cb = cb.with_selection(jnp.asarray(mask))
        batches.append(cb)
    probe = MemoryScanExec(Schema.from_arrow(probe_batches[0][0].schema),
                           [batches])
    keys = [col(i) for i in range(nkeys)]
    return BroadcastJoinExec(probe, MemoryScanExec.from_arrow(build_t),
                             keys, keys, how or JoinType.INNER,
                             join_filter=flt)


def _answer(plan):
    """(sorted frame, counter deltas) of partition 0."""
    from blaze_tpu.bridge import xla_stats
    before = xla_stats.snapshot()
    out = [b.compact().to_arrow() for b in plan.execute(0)]
    moved = xla_stats.delta(before)
    frame = pa.Table.from_batches(
        out, schema=plan.schema.to_arrow()).to_pandas()
    return (frame.sort_values(list(frame.columns)).reset_index(drop=True),
            moved)


@pytest.mark.parametrize("case", list(_PROBE_CASES))
def test_device_probe_gives_the_host_paths_answer(case, monkeypatch):
    """Each shape the device-resident probe takes, against Arrow's join
    of the same tables under host placement."""
    build_t, probe_batches, nkeys = _PROBE_CASES[case]
    want, host = _answer(_broadcast_join(build_t, probe_batches, nkeys,
                                         place=False))
    assert host["join_probe_device_rows"] == 0
    _place_on_device(monkeypatch)
    got, moved = _answer(_broadcast_join(build_t, probe_batches, nkeys))
    assert got.equals(want), (got, want)
    assert len(want) > 0 or case == "empty_build_side"
    # every probe row went through the device-resident probe
    assert moved["join_probe_device_rows"] == sum(
        t.num_rows for t, _sel in probe_batches)
    assert moved["join_probe_host_rows"] == 0


def test_device_probe_reads_back_one_scalar_a_batch(on_device):
    """Hashes, pairs and columns stay on the device: what a probe of four
    batches reads back is four counts."""
    from blaze_tpu.bridge import xla_stats
    build_t, probe_batches, nkeys = _PROBE_CASES["int64_key"]
    four = [(probe_batches[0][0].slice(i * 700, 700), None)
            for i in range(4)]
    plan = _broadcast_join(build_t, four, nkeys)
    rows = sum(b.num_rows for b in plan.execute(0))  # builds the map once
    before = xla_stats.snapshot()
    again = list(plan.execute(0))
    moved = xla_stats.delta(before)
    assert sum(b.num_rows for b in again) == rows > 0
    assert moved["join_probe_device_rows"] == 2800
    assert moved["d2h_transfers"] == 4
    assert moved["d2h_bytes"] == 4 * 4
    assert moved["h2d_bytes"] == 0   # the build side was placed once
    for b in again:   # dense, on the device, nothing deselected
        assert b.selection is None
        assert all(isinstance(c.data, jax.Array) for c in b.columns)


def _not_taken():
    """name -> (build, probe batches, keys, join type, filter): joins the
    device-resident probe does not take."""
    from blaze_tpu.exprs import BinaryExpr, col
    from blaze_tpu.ops.joins import JoinType
    build_t, probe_batches, nkeys = _PROBE_CASES["null_keys_on_both_sides"]
    cases = {
        "utf8_build_column": (
            build_t.append_column("name", pa.array(["a", "b", None, "d"])),
            probe_batches, nkeys, JoinType.INNER, None),
        "duplicate_build_keys": (
            pa.concat_tables([build_t, build_t.slice(0, 1)]),
            probe_batches, nkeys, JoinType.INNER, None),
        "join_filter": (build_t, probe_batches, nkeys, JoinType.INNER,
                        BinaryExpr("<", col(1), col(3))),
    }
    for how in JoinType:
        if how != JoinType.INNER:
            cases[how.value] = (build_t, probe_batches, nkeys, how, None)
    return cases


_NOT_TAKEN = _not_taken()


@pytest.mark.parametrize("case", list(_NOT_TAKEN))
def test_joins_the_device_probe_does_not_take(case, monkeypatch):
    """A utf8 build column, duplicate build keys, a join filter and every
    join type but inner go through the pair expansion and the host, as
    before, and answer as Arrow's join does under host placement."""
    build_t, probe_batches, nkeys, how, flt = _NOT_TAKEN[case]
    want, _host = _answer(_broadcast_join(build_t, probe_batches, nkeys,
                                          how, flt, place=False))
    _place_on_device(monkeypatch)
    got, moved = _answer(_broadcast_join(build_t, probe_batches, nkeys,
                                         how, flt))
    assert got.equals(want), (got, want)
    assert moved["join_probe_device_rows"] == 0
    assert moved["join_probe_host_rows"] == probe_batches[0][0].num_rows


def test_host_placement_takes_no_device_probe():
    """On the host's placement (this test's default) the inner join on a
    unique key is Arrow's, and its rows count as the host's."""
    build_t, probe_batches, nkeys = _PROBE_CASES["int64_key"]
    got, moved = _answer(_broadcast_join(build_t, probe_batches, nkeys))
    assert len(got) > 0
    assert moved["join_probe_device_rows"] == 0
    assert 0 < moved["join_probe_host_rows"] <= 3000
    assert moved["d2h_bytes"] == 0


def test_probe_rows_add_up_over_both_paths(on_device):
    from blaze_tpu.ops.joins import JoinType
    build_t, probe_batches, nkeys = _PROBE_CASES["tail_batch_of_another_capacity"]
    n = sum(t.num_rows for t, _sel in probe_batches)
    _got, inner = _answer(_broadcast_join(build_t, probe_batches, nkeys))
    _got, left = _answer(_broadcast_join(build_t, probe_batches, nkeys,
                                         JoinType.LEFT))
    assert inner["join_probe_device_rows"] == n == left["join_probe_host_rows"]
    assert inner["join_probe_host_rows"] == left["join_probe_device_rows"] == 0
    assert inner["chip0_join_probe_device_rows"] == n
    assert left["chip0_join_probe_host_rows"] == n


def test_pack_front_is_a_stable_compaction():
    """The shift network against numpy's boolean index, at a capacity that
    is not a power of two and at every density."""
    from blaze_tpu.kernels.join import pack_front
    rng = np.random.default_rng(3)
    for cap, p in ((128, 0.0), (128, 1.0), (384, 0.5), (4096, 0.4),
                   (4096, 0.97), (1, 1.0)):
        keep = rng.random(cap) < p
        a = rng.integers(-9, 9, cap)
        b = rng.random(cap) < 0.5
        pa_, pb = jax.jit(pack_front)(jnp.asarray(keep),
                                      [jnp.asarray(a), jnp.asarray(b)])
        n = int(keep.sum())
        assert np.array_equal(np.asarray(pa_)[:n], a[keep])
        assert np.array_equal(np.asarray(pb)[:n], b[keep])

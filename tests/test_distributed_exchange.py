"""Raw-row ICI exchange + the operators built on it (distributed sort,
shuffled hash join) on the 8-virtual-device CPU mesh.

Parity target: the reference's repartitioner moves arbitrary operator
output (shuffle/mod.rs:55-123), feeding range-partitioned global sort
(NativeShuffleExchangeBase.scala:313) and the shuffled hash join
(joins/join_hash_map.rs).  These tests check the on-mesh equivalents end
to end: multiset preservation, global ordering, and exact inner-join
results against a numpy oracle, with nulls and duplicate keys present.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.parallel import (DP_AXIS, all_to_all_rows,
                                distributed_hash_join, distributed_sort,
                                make_mesh, shard_rows)
from jax.sharding import PartitionSpec as P

NDEV = 8


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < NDEV:
        pytest.skip("needs 8 virtual devices")
    return make_mesh(NDEV)


def test_all_to_all_rows_roundtrip(mesh):
    rng = np.random.default_rng(7)
    rows_per_dev = 512
    n = NDEV * rows_per_dev
    keys = rng.integers(0, 1000, n).astype(np.int64)
    vals = rng.random(n)
    valid = rng.random(n) < 0.85
    pid = (keys % NDEV).astype(np.int32)
    cap = 2 * rows_per_dev

    def stage(k, v, ok, p):
        cols, valid_r, ovf = all_to_all_rows([k, v], ok, p, DP_AXIS,
                                             NDEV, cap)
        return cols[0], cols[1], valid_r, ovf.reshape(1)

    fn = jax.jit(jax.shard_map(stage, mesh=mesh, in_specs=P(DP_AXIS),
                               out_specs=P(DP_AXIS), check_vma=False))
    k, v, ok, p = shard_rows(mesh, jnp.asarray(keys), jnp.asarray(vals),
                             jnp.asarray(valid), jnp.asarray(pid))
    rk, rv, rvalid, ovf = fn(k, v, ok, p)
    rk, rv, rvalid, ovf = map(np.asarray, (rk, rv, rvalid, ovf))
    assert ovf.sum() == 0

    # multiset of (key, val) pairs survives the exchange exactly
    sent = sorted(zip(keys[valid], vals[valid]))
    got = sorted(zip(rk[rvalid], rv[rvalid]))
    assert len(sent) == len(got)
    assert np.allclose([a for a, _ in sent], [a for a, _ in got])
    assert np.allclose([b for _, b in sent], [b for _, b in got])

    # routing: device d received exactly the rows with pid == d
    per_dev = NDEV * cap
    for d in range(NDEV):
        lo, hi = d * per_dev, (d + 1) * per_dev
        dk = rk[lo:hi][rvalid[lo:hi]]
        assert (dk % NDEV == d).all()


def test_all_to_all_rows_overflow_detected(mesh):
    rows_per_dev = 128
    n = NDEV * rows_per_dev
    keys = np.zeros(n, dtype=np.int64)  # everything to device 0
    valid = np.ones(n, dtype=bool)
    pid = np.zeros(n, dtype=np.int32)
    cap = 16  # far under rows_per_dev

    def stage(k, ok, p):
        cols, valid_r, ovf = all_to_all_rows([k], ok, p, DP_AXIS,
                                             NDEV, cap)
        return cols[0], valid_r, ovf.reshape(1)

    fn = jax.jit(jax.shard_map(stage, mesh=mesh, in_specs=P(DP_AXIS),
                               out_specs=P(DP_AXIS), check_vma=False))
    k, ok, p = shard_rows(mesh, jnp.asarray(keys),
                          jnp.asarray(valid), jnp.asarray(pid))
    rk, rvalid, ovf = fn(k, ok, p)
    ovf = np.asarray(ovf)
    rvalid = np.asarray(rvalid)
    assert ovf.sum() == n - NDEV * cap  # dropped rows all reported
    assert rvalid.sum() == NDEV * cap   # survivors all delivered


@pytest.mark.parametrize("descending", [False, True])
def test_distributed_sort_global_order(mesh, descending):
    rng = np.random.default_rng(11)
    rows_per_dev = 1024
    n = NDEV * rows_per_dev
    keys = rng.integers(-10_000, 10_000, n).astype(np.int64)
    payload = rng.random(n)
    valid = rng.random(n) < 0.9
    cap = 2 * rows_per_dev

    fn = distributed_sort(mesh, num_payloads=1, capacity=cap,
                          descending=descending)
    k, ok, pay = shard_rows(mesh, jnp.asarray(keys), jnp.asarray(valid),
                            jnp.asarray(payload))
    out_k, out_v, out_p, ovf = fn(k, ok, pay)
    out_k, out_v, out_p, ovf = map(np.asarray, (out_k, out_v, out_p, ovf))
    assert ovf.sum() == 0

    # multiset preserved, payload rides with its key
    want = np.sort(keys[valid])
    got_all = out_k[out_v]
    assert np.array_equal(np.sort(got_all), want)
    pair_want = sorted(zip(keys[valid], payload[valid]))
    pair_got = sorted(zip(out_k[out_v], out_p[out_v]))
    assert np.allclose([b for _, b in pair_want],
                       [b for _, b in pair_got])

    # per-device locally sorted; device boundaries globally ordered
    per_dev = NDEV * cap
    prev_extreme = None
    for d in range(NDEV):
        seg = out_k[d * per_dev:(d + 1) * per_dev]
        sv = out_v[d * per_dev:(d + 1) * per_dev]
        dk = seg[sv]
        if len(dk) == 0:
            continue
        step = np.diff(dk)
        assert (step <= 0).all() if descending else (step >= 0).all()
        if prev_extreme is not None:
            if descending:
                assert prev_extreme >= dk.max()
            else:
                assert prev_extreme <= dk.min()
        prev_extreme = dk.min() if descending else dk.max()


def test_distributed_hash_join_matches_oracle(mesh):
    rng = np.random.default_rng(23)
    rows_per_dev = 512
    n = NDEV * rows_per_dev
    # duplicate keys on both sides + nulls: the full inner-join matrix
    bkeys = rng.integers(0, 300, n).astype(np.int64)
    bvals = rng.random(n)
    bvalid = rng.random(n) < 0.9
    pkeys = rng.integers(0, 300, n).astype(np.int64)
    pvals = rng.random(n)
    pvalid = rng.random(n) < 0.9

    cap = 4 * rows_per_dev
    pair_cap = 1 << 17

    fn = distributed_hash_join(mesh, num_build_payloads=1,
                               num_probe_payloads=1, capacity=cap,
                               pair_cap=pair_cap)
    args = shard_rows(mesh, jnp.asarray(bkeys), jnp.asarray(bvalid),
                      jnp.asarray(bvals), jnp.asarray(pkeys),
                      jnp.asarray(pvalid), jnp.asarray(pvals))
    jk, jv, jb, jp, counts = fn(*args)
    jk, jv, jb, jp, counts = map(np.asarray, (jk, jv, jb, jp, counts))
    counts = counts.reshape(NDEV, 3)
    assert counts[:, 1].sum() == 0 and counts[:, 2].sum() == 0, \
        "exchange overflowed"

    # numpy oracle: every (build, probe) pair with equal valid keys
    import collections
    build_by_key = collections.defaultdict(list)
    for k, v, ok in zip(bkeys, bvals, bvalid):
        if ok:
            build_by_key[k].append(v)
    want = []
    for k, v, ok in zip(pkeys, pvals, pvalid):
        if ok:
            for bv in build_by_key.get(k, ()):
                want.append((k, round(bv, 9), round(v, 9)))
    got = [(k, round(b, 9), round(p, 9))
           for k, b, p in zip(jk[jv], jb[jv], jp[jv])]
    assert sorted(got) == sorted(want)
    assert counts[:, 0].sum() == len(want)


def test_distributed_join_then_sort_pipeline(mesh):
    """Join output feeds the distributed sort — the two-exchange pipeline
    dryrun_multichip validates at scale (VERDICT r4 #4)."""
    rng = np.random.default_rng(31)
    rows_per_dev = 256
    n = NDEV * rows_per_dev
    bkeys = rng.integers(0, 64, n).astype(np.int64)
    bvals = rng.random(n)
    pkeys = rng.integers(0, 64, n).astype(np.int64)
    pvals = rng.random(n)
    ones = np.ones(n, dtype=bool)

    cap = 4 * rows_per_dev
    pair_cap = 1 << 16
    jfn = distributed_hash_join(mesh, 1, 1, cap, pair_cap)
    args = shard_rows(mesh, jnp.asarray(bkeys), jnp.asarray(ones),
                      jnp.asarray(bvals), jnp.asarray(pkeys),
                      jnp.asarray(ones), jnp.asarray(pvals))
    jk, jv, jb, jp, counts = jfn(*args)

    sfn = distributed_sort(mesh, num_payloads=2, capacity=pair_cap,
                           samples_per_device=64)
    out = sfn(jk, jv, jb, jp)
    out_k, out_v = np.asarray(out[0]), np.asarray(out[1])
    assert np.asarray(out[-1]).sum() == 0
    # valid rows, concatenated in device order, are globally sorted and
    # carry the same multiset the join emitted
    got = out_k[out_v]
    want = np.sort(np.asarray(jk)[np.asarray(jv)])
    assert np.array_equal(np.sort(got), want)
    assert (np.diff(got) >= 0).all()


def test_distributed_sort_int64_min_descending(mesh):
    """Descending integer order must not negate (INT64_MIN wraps)."""
    rows_per_dev = 64
    n = NDEV * rows_per_dev
    rng = np.random.default_rng(41)
    keys = rng.integers(-100, 100, n).astype(np.int64)
    keys[0] = np.iinfo(np.int64).min
    keys[1] = np.iinfo(np.int64).max
    ones = np.ones(n, dtype=bool)
    fn = distributed_sort(mesh, num_payloads=0, capacity=n,
                          descending=True)
    out_k, out_v, ovf = fn(*shard_rows(mesh, jnp.asarray(keys),
                                       jnp.asarray(ones)))
    assert np.asarray(ovf).sum() == 0
    got = np.asarray(out_k)[np.asarray(out_v)]
    assert got[0] == np.iinfo(np.int64).max
    assert got[-1] == np.iinfo(np.int64).min
    assert (np.diff(got) <= 0).all()


@pytest.mark.parametrize("descending", [False, True])
def test_distributed_sort_float_nan_is_largest(mesh, descending):
    """Spark NaN ordering: NaN is the largest value — last on ASC,
    first on DESC — and never corrupts the range bounds."""
    rows_per_dev = 128
    n = NDEV * rows_per_dev
    rng = np.random.default_rng(43)
    keys = rng.normal(size=n) * 100
    nan_at = rng.choice(n, size=17, replace=False)
    keys[nan_at] = np.nan
    valid = rng.random(n) < 0.95
    fn = distributed_sort(mesh, num_payloads=0, capacity=n,
                          descending=descending)
    out_k, out_v, ovf = fn(*shard_rows(mesh, jnp.asarray(keys),
                                       jnp.asarray(valid)))
    assert np.asarray(ovf).sum() == 0
    got = np.asarray(out_k)[np.asarray(out_v)]
    n_nan = int(np.isnan(keys[valid]).sum())
    assert int(np.isnan(got).sum()) == n_nan
    finite = got[~np.isnan(got)]
    if descending:
        assert np.isnan(got[:n_nan]).all()   # NaN first
        assert (np.diff(finite) <= 0).all()
    else:
        assert np.isnan(got[-n_nan:]).all()  # NaN last
        assert (np.diff(finite) >= 0).all()


def test_distributed_hash_join_nan_keys_never_match(mesh):
    """NaN float keys are nulls to the exchange primitive (callers
    canonicalize for Spark's NaN == NaN); padding must never surface."""
    rows_per_dev = 64
    n = NDEV * rows_per_dev
    rng = np.random.default_rng(47)
    bkeys = rng.integers(0, 40, n).astype(np.float64)
    bkeys[::7] = np.nan
    bvals = rng.random(n)
    pkeys = rng.integers(0, 40, n).astype(np.float64)
    pkeys[::5] = np.nan
    pvals = rng.random(n)
    ones = np.ones(n, dtype=bool)
    fn = distributed_hash_join(mesh, 1, 1, capacity=4 * rows_per_dev,
                               pair_cap=1 << 15)
    jk, jv, jb, jp, counts = fn(*shard_rows(
        mesh, jnp.asarray(bkeys), jnp.asarray(ones), jnp.asarray(bvals),
        jnp.asarray(pkeys), jnp.asarray(ones), jnp.asarray(pvals)))
    counts = np.asarray(counts).reshape(NDEV, 3)
    assert counts[:, 1:].sum() == 0
    import collections
    bb = collections.defaultdict(int)
    for k in bkeys[~np.isnan(bkeys)]:
        bb[k] += 1
    want = sum(bb.get(k, 0) for k in pkeys[~np.isnan(pkeys)])
    got_k = np.asarray(jk)[np.asarray(jv)]
    assert len(got_k) == want == counts[:, 0].sum()
    assert not np.isnan(got_k).any()

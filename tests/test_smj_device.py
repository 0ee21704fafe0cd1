"""The device merge join (ops/joins/merge.py) against a plain reference.

`placement.host_resident` is patched to false, as tests/test_join_device.py
does, so `SortMergeJoinExec` takes the path it takes on a chip: batches are
jax arrays padded to bucket capacities, the sort's permutation and the join
run as jitted programs (on the CPU backend here).  The reference is two
nested loops over the rows, written from SQL's join semantics: a NULL key
joins nothing, NaN joins NaN, -0.0 joins 0.0.
"""

import math

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.bridge import xla_stats
from blaze_tpu.exprs import BinaryExpr, col
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.ops.joins import JoinType
from blaze_tpu.ops.joins.exec import SortMergeJoinExec


@pytest.fixture
def on_device(monkeypatch):
    import blaze_tpu.bridge.placement as P
    monkeypatch.setattr(P, "host_resident", lambda: False)


def _nullable(values, null_share, rng, typ):
    mask = rng.random(len(values)) < null_share
    return pa.array([None if m else v for v, m in zip(values.tolist(), mask)],
                    type=typ)


def _sides(shape: str, seed: int = 11):
    """(left, right, number of key columns): keys first, then a payload."""
    rng = np.random.default_rng(seed)
    nl, nr = 1500, 1100
    if shape == "one_key_long_runs":      # runs of many rows on both sides
        lk = [_nullable(rng.integers(0, 40, nl), 0, rng, pa.int64())]
        rk = [_nullable(rng.integers(5, 45, nr), 0, rng, pa.int64())]
    elif shape == "two_keys":
        lk = [_nullable(rng.integers(0, 60, nl), 0, rng, pa.int64()),
              _nullable(rng.integers(0, 25, nl), 0, rng, pa.int32())]
        rk = [_nullable(rng.integers(0, 60, nr), 0, rng, pa.int64()),
              _nullable(rng.integers(0, 25, nr), 0, rng, pa.int32())]
    elif shape == "null_keys":
        lk = [_nullable(rng.integers(-20, 20, nl), .1, rng, pa.int64()),
              _nullable(rng.integers(0, 6, nl), .1, rng, pa.int64())]
        rk = [_nullable(rng.integers(-20, 20, nr), .1, rng, pa.int64()),
              _nullable(rng.integers(0, 6, nr), .1, rng, pa.int64())]
    elif shape == "nan_keys":
        pool = np.array([float("nan"), -0.0, 0.0, 1.5, -2.25, 1e300, -1e300]
                        + list(np.arange(40) / 4.0))
        lk = [_nullable(pool[rng.integers(0, len(pool), nl)], .05, rng,
                        pa.float64())]
        rk = [_nullable(pool[rng.integers(0, len(pool), nr)], .05, rng,
                        pa.float64())]
    elif shape == "unique_keys":          # every run one row: q93's shape
        lk = [pa.array(rng.permutation(4 * nl)[:nl])]
        rk = [pa.array(rng.permutation(4 * nl)[:nr])]
    elif shape in ("empty_left", "empty_right"):
        lk = [_nullable(rng.integers(0, 40, nl), .05, rng, pa.int64())]
        rk = [_nullable(rng.integers(0, 40, nr), .05, rng, pa.int64())]
    else:
        raise KeyError(shape)
    left = pa.table(lk + [pa.array(np.round(rng.random(nl) * 10, 3)),
                          pa.array([f"l{i}" for i in range(nl)])],
                    names=[f"lk{i}" for i in range(len(lk))] + ["lv", "ls"])
    right = pa.table(rk + [pa.array(np.round(rng.random(nr) * 10, 3))],
                     names=[f"rk{i}" for i in range(len(rk))] + ["rv"])
    if shape == "empty_left":
        left = left.slice(0, 0)
    if shape == "empty_right":
        right = right.slice(0, 0)
    return left, right, len(lk)


def _same_key(a, b) -> bool:
    for x, y in zip(a, b):
        if x is None or y is None:
            return False
        if isinstance(x, float) and math.isnan(x) and math.isnan(y):
            continue
        if x != y:
            return False
    return True


def _reference(left, right, nk, jt, flt):
    """The join's rows, by two nested loops."""
    L, R = left.to_pylist(), right.to_pylist()
    L = [tuple(r.values()) for r in L]
    R = [tuple(r.values()) for r in R]
    nl_cols, nr_cols = left.num_columns, right.num_columns
    hits_l, hits_r, pairs = set(), set(), []
    by_key = {}
    for j, r in enumerate(R):
        by_key.setdefault(repr(tuple(0.0 if v == 0 else v
                                     for v in r[:nk])), []).append(j)
    for i, l in enumerate(L):
        for j in by_key.get(repr(tuple(0.0 if v == 0 else v
                                       for v in l[:nk])), []):
            r = R[j]
            if _same_key(l[:nk], r[:nk]) and (flt is None or flt(l, r)):
                hits_l.add(i)
                hits_r.add(j)
                pairs.append(l + r)
    no_l, no_r = (None,) * nl_cols, (None,) * nr_cols
    JT = JoinType
    if jt == JT.INNER:
        return pairs
    if jt == JT.LEFT:
        return pairs + [l + no_r for i, l in enumerate(L) if i not in hits_l]
    if jt == JT.RIGHT:
        return pairs + [no_l + r for j, r in enumerate(R) if j not in hits_r]
    if jt == JT.FULL:
        return pairs \
            + [l + no_r for i, l in enumerate(L) if i not in hits_l] \
            + [no_l + r for j, r in enumerate(R) if j not in hits_r]
    if jt == JT.LEFT_SEMI:
        return [l for i, l in enumerate(L) if i in hits_l]
    if jt == JT.LEFT_ANTI:
        return [l for i, l in enumerate(L) if i not in hits_l]
    if jt == JT.RIGHT_SEMI:
        return [r for j, r in enumerate(R) if j in hits_r]
    if jt == JT.RIGHT_ANTI:
        return [r for j, r in enumerate(R) if j not in hits_r]
    if jt == JT.EXISTENCE:
        return [l + (i in hits_l,) for i, l in enumerate(L)]
    raise KeyError(jt)


def _canon(rows):
    return sorted(repr(tuple("nan" if isinstance(v, float) and math.isnan(v)
                             else v for v in r)) for r in rows)


def _order_key(values):
    """Ascending, NULLs first, NaN last: how the join's output is ordered."""
    return tuple((0, 0) if v is None else
                 (2, 0) if isinstance(v, float) and math.isnan(v) else
                 (1, v) for v in values)


def _run(plan):
    rows = []
    for b in plan.execute(0):
        rows.extend(tuple(r.values())
                    for r in b.compact().to_arrow().to_pylist())
    return rows


SHAPES = ["one_key_long_runs", "two_keys", "null_keys", "nan_keys",
          "unique_keys", "empty_left", "empty_right"]
# a join filter over the payloads of both sides, on the shape with runs of
# many rows, where it splits runs
CASES = [(jt, shape, False) for jt in JoinType for shape in SHAPES] \
    + [(jt, "one_key_long_runs", True) for jt in JoinType]
# what merge.declines sends through the run cursor whatever arrives
STREAMS = {JoinType.LEFT, JoinType.RIGHT, JoinType.FULL}


@pytest.mark.parametrize(
    "jt,shape,filtered", CASES,
    ids=[f"{jt.value}-{shape}{'-filter' if f else ''}"
         for jt, shape, f in CASES])
def test_device_merge_join_matches_reference(on_device, jt, shape, filtered):
    left, right, nk = _sides(shape)
    lv, rv = nk, left.num_columns + nk   # the payloads, in the joined schema
    smj = SortMergeJoinExec(
        MemoryScanExec.from_arrow(left, batch_rows=400),
        MemoryScanExec.from_arrow(right, batch_rows=400),
        [col(i) for i in range(nk)], [col(i) for i in range(nk)], jt,
        join_filter=BinaryExpr(">", col(lv), col(rv)) if filtered else None)
    before = xla_stats.snapshot()
    got = _run(smj)
    d = xla_stats.delta(before)
    want = _reference(left, right, nk, jt,
                      (lambda l, r: l[nk] > r[nk]) if filtered else None)
    assert len(got) == len(want)
    assert _canon(got) == _canon(want)
    if filtered and jt in STREAMS:
        assert d["smj_streamed_runs"] > 0 and d["smj_device_rows"] == 0
    else:
        assert d["smj_streamed_runs"] == 0
        assert d["smj_device_rows"] == left.num_rows + right.num_rows
        assert d["sort_device_rows"] == sum(
            t.num_rows for t in (left, right) if t.num_rows >= 1024)
    # the operator's contract: output in key order
    if jt in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
        keys = [r[:nk] for r in got]
    elif jt in (JoinType.RIGHT, JoinType.FULL):
        keys = [tuple(a if a is not None else b for a, b in
                      zip(r[:nk], r[left.num_columns:left.num_columns + nk]))
                for r in got]
    else:
        keys = [r[:nk] for r in got]
    ordered = [_order_key(tuple(0.0 if v == 0 else v for v in k))
               for k in keys]
    assert ordered == sorted(ordered)


@pytest.mark.parametrize("jt", [JoinType.INNER, JoinType.FULL],
                         ids=lambda jt: jt.value)
def test_sides_sorted_on_the_device_join_as_arrow_joins_them(on_device, jt):
    """Both sides of fixed-width columns alone, over 1,024 rows and in
    ragged batches: each `SortExec` keeps its partition on the device and
    hands `_merge_device` ONE sorted batch, which `merge._concat` passes
    through; the pairs are Arrow's hash join's."""
    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.bridge import tracing
    from blaze_tpu.schema import Schema
    rng = np.random.default_rng(23)
    nl, nr = 2600, 1700
    left = pa.table({
        "lk0": _nullable(rng.integers(0, 900, nl), .05, rng, pa.int64()),
        "lk1": _nullable(rng.integers(0, 3, nl), .05, rng, pa.int32()),
        "lv": pa.array(np.round(rng.random(nl) * 10, 3)),
        "lid": pa.array(np.arange(nl))})
    right = pa.table({
        "rk0": _nullable(rng.integers(0, 900, nr), .05, rng, pa.int64()),
        "rk1": _nullable(rng.integers(0, 3, nr), .05, rng, pa.int32()),
        "rid": pa.array(np.arange(nr))})

    def scan(t, cuts):
        at, batches = 0, []
        for n in cuts:
            batches.append(ColumnBatch.from_arrow(
                t.slice(at, n).combine_chunks().to_batches()[0]))
            at += n
        assert at == t.num_rows
        return MemoryScanExec(Schema.from_arrow(t.schema), [batches])

    smj = SortMergeJoinExec(scan(left, [1024, 1024, 552]),
                            scan(right, [900, 1, 799]),
                            [col(0), col(1)], [col(0), col(1)], jt)
    before = xla_stats.snapshot()
    tracing.start_tracing()
    try:
        got = _run(smj)
    finally:
        spans = tracing.stop_tracing()
    d = xla_stats.delta(before)
    want = left.join(right, keys=["lk0", "lk1"], right_keys=["rk0", "rk1"],
                     join_type="inner" if jt == JoinType.INNER
                     else "full outer", coalesce_keys=False) \
        .select(left.column_names + right.column_names)
    assert len(got) == want.num_rows > 1000
    assert _canon(got) == _canon(tuple(r.values()) for r in want.to_pylist())
    assert d["sort_resident_rows"] == d["sort_device_rows"] == nl + nr
    assert d["smj_device_rows"] == nl + nr and d["smj_streamed_runs"] == 0
    assert sorted((s["attrs"]["rows"], s["attrs"]["lane"]) for s in spans
                  if s["name"] == "sort_device") == [
        (nr, "resident"), (nl, "resident")]
    # no side was cut and concatenated again on its way into the join
    assert not [s for s in spans if s["name"] == "coalesce"
                and s["attrs"]["batches"] > 1]
    keys = [tuple(a if a is not None else b for a, b in zip(r[:2], r[4:6]))
            for r in got]
    ordered = [_order_key(k) for k in keys]
    assert ordered == sorted(ordered)


@pytest.mark.parametrize("budget,denied", [
    (20_000, "a side"),       # under the left side's bytes
    (400_000, "the pairs"),   # over both sides' and the sorts', under the pairs'
])
def test_partition_the_memory_manager_sheds_streams(on_device, monkeypatch,
                                                    budget, denied):
    """The spill discipline: what the merge holds on the device is declared
    to the memory manager (`merge.Hold`); a partition it sheds, while
    collecting a side or at the pairs' reservation, goes through the run
    cursor, resumed from the batches already collected."""
    from blaze_tpu.memory import MemManager
    from blaze_tpu.ops.joins import merge
    left, right, nk = _sides("one_key_long_runs")
    keys = [col(i) for i in range(nk)]
    reserved = []
    real = merge.Hold.reserve

    def reserve(self, nbytes):
        ok = real(self, nbytes)
        reserved.append((nbytes, ok))
        return ok

    monkeypatch.setattr(merge.Hold, "reserve", reserve)

    def run():
        op = SortMergeJoinExec(
            MemoryScanExec.from_arrow(left, batch_rows=400),
            MemoryScanExec.from_arrow(right, batch_rows=400),
            keys, keys, JoinType.INNER)
        return _run(op), op.metrics.values.get("mem_used", 0)

    monkeypatch.setattr(MemManager, "_instance", MemManager(1 << 30))
    want, peak = run()
    # both sides and the pairs were declared, and nothing stays registered
    sides = sum(n for n, _ok in reserved[:-1])
    assert all(ok for _n, ok in reserved) and reserved[-1][0] > sides > 0
    assert peak == sides + reserved[-1][0]
    assert MemManager.get().mem_used == 0
    assert not MemManager.get()._consumers

    del reserved[:]
    manager = MemManager(budget)
    monkeypatch.setattr(MemManager, "_instance", manager)
    before = xla_stats.snapshot()
    got, _peak = run()
    d = xla_stats.delta(before)
    assert _canon(got) == _canon(want) and len(want) > 0
    assert d["smj_streamed_runs"] > 0
    assert d["smj_device_pairs"] == 0
    assert [ok for _n, ok in reserved].count(False) == 1 \
        and not reserved[-1][1]
    assert (reserved[-1][0] > sides) == (denied == "the pairs")
    assert manager.total_spill_count > 0 and not manager._consumers


def test_spans_counters_and_program_names(on_device):
    """What the benchmark's per-layer metrics read: spans `sort_device` and
    `smj_merge` on the thread that does the work, programs named
    `jit_<fn>__smj_<part>` and `jit_<fn>__sort_<part>`."""
    import threading

    from blaze_tpu.bridge import tracing
    from blaze_tpu.kernels import join as J, sort as ksort
    from blaze_tpu.ops.joins import merge
    left, right, nk = _sides("unique_keys")
    keys = [col(i) for i in range(nk)]
    tracing.start_tracing()
    try:
        rows = _run(SortMergeJoinExec(
            MemoryScanExec.from_arrow(left), MemoryScanExec.from_arrow(right),
            keys, keys, JoinType.INNER))
    finally:
        spans = tracing.stop_tracing()
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert len(by_name["sort_device"]) == 2 and len(by_name["smj_merge"]) == 1
    me = threading.current_thread().name
    assert all(s["thread"] == me
               for s in by_name["sort_device"] + by_name["smj_merge"])
    assert by_name["smj_merge"][0]["attrs"] == {
        "rows": left.num_rows + right.num_rows, "pairs": len(rows)}
    assert sorted(s["attrs"]["rows"] for s in by_name["sort_device"]) == \
        sorted([left.num_rows, right.num_rows])
    for fn in (J.merge_bounds, J.merge_expand_pairs, merge.gather,
               merge.outer_counts, merge.full_layout):
        assert "__smj_" in fn._blaze_jitted.__name__
    assert J.merge_expand_pairs._blaze_jitted.__name__ == \
        "expand_pairs__smj_expand_pairs"
    assert ksort.sort_pass._blaze_jitted.__name__ == "lsd_pass__sort_pass"

"""`WindowExec`'s resident lane (ops/window.py, kernels/window.py) against
`tests/window_reference.py`, a row-at-a-time Python loop.

The lane is the operator with `placement.host_resident` patched to false,
as tests/test_sort_device.py does for the sort: batches are jax arrays at
bucket capacities, the sorted run stays where it lies, and flags and every
function's scan are ONE jitted program (on the CPU backend here).  The host
lane is the same operator at the CPU's default placement.
"""

import contextlib
import decimal
import functools
import math

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

import blaze_tpu.bridge.placement as P
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.exprs import col
from blaze_tpu.ops import MemoryScanExec, make_agg
from blaze_tpu.ops.window import (LeadLagFunc, RankFunc, WindowAggFunc,
                                  WindowExec, WindowRankType, _WindowBuffer)
from blaze_tpu.schema import Schema
from tests import window_reference as ref

ROWS = 3000
ONE_TILE = [ROWS]
RAGGED = [700, 1, 1299, 130, 870]       # five batches of four capacities
DTYPES = ("float64", "int64", "decimal")
NULLS = ("none", "values", "keys")
# (name, reference's function, running frame)
AGG_FORMS = [("sum_run", "sum", True), ("sum_all", "sum", False),
             ("count_run", "count", True), ("count_all", "count", False),
             ("count_star", "count", True), ("min_run", "min", True),
             ("max_run", "max", True), ("max_all", "max", False),
             ("avg_run", "avg", True)]
REL = 1e-12     # a tree of float64 sums against the same sums in row order


@contextlib.contextmanager
def device_placement():
    with pytest.MonkeyPatch.context() as m:
        m.setattr(P, "host_resident", lambda: False)
        yield


def _values(dtype: str, rng, n: int) -> pa.Array:
    if dtype == "float64":
        return pa.array(np.round(rng.random(n) * 200 - 50, 2))
    if dtype == "int64":
        return pa.array(rng.integers(-10**12, 10**12, n))
    cents = rng.integers(-99999_99, 99999_99, n)
    return pa.array([decimal.Decimal(int(c)).scaleb(-2) for c in cents],
                    type=pa.decimal128(7, 2))


def _with_nulls(arr: pa.Array, rng, share: float) -> pa.Array:
    drop = rng.random(len(arr)) < share
    return pa.array([None if d else v for v, d in zip(arr.to_pylist(), drop)],
                    type=arr.type)


def _table(dtype: str, nulls: str, n: int = ROWS, seed: int = 11,
           part_card: int = 40) -> pa.Table:
    """`p` (int64) and `o` (date32, ties) sorted ascending NULLs first,
    `v` of `dtype`, `rid` the row's place."""
    rng = np.random.default_rng(seed)
    p = pa.array(rng.integers(0, part_card, n))
    o = pa.array(rng.integers(0, 25, n).astype(np.int32)).cast(pa.date32())
    v = _values(dtype, rng, n)
    if nulls == "values":
        # whole stretches too, so that some partitions start with NULLs
        v = _with_nulls(v, rng, 0.4)
    if nulls == "keys":
        p, o = _with_nulls(p, rng, 0.1), _with_nulls(o, rng, 0.15)
    t = pa.table({"p": p, "o": o, "v": v})
    t = t.sort_by([("p", "ascending", "at_start"),
                   ("o", "ascending", "at_start")])
    return t.append_column("rid", pa.array(np.arange(n, dtype=np.int64)))


def _scan(table: pa.Table, cuts) -> MemoryScanExec:
    batches, at = [], 0
    for n in cuts:
        batches.append(ColumnBatch.from_arrow(
            table.slice(at, n).combine_chunks().to_batches()[0]))
        at += n
    assert at == table.num_rows
    return MemoryScanExec(Schema.from_arrow(table.schema), [batches])


def _collect(plan) -> pa.Table:
    return pa.Table.from_batches(
        [b.compact().to_arrow() for b in plan.execute(0)],
        schema=plan.schema.to_arrow())


def _rank_funcs():
    return [RankFunc(k.value, k) for k in WindowRankType]


def _agg_funcs(dtype: str):
    out = []
    for name, fn, running in AGG_FORMS:
        if dtype == "decimal" and fn == "avg":
            continue    # a decimal quotient: the host lane's (ROADMAP M8)
        args = [] if name == "count_star" else [col(2)]
        out.append(WindowAggFunc(name, make_agg(fn, args), running=running))
    return out


def _node(child, funcs, ordered=True, partitioned=True, group_limit=None):
    return WindowExec(child, funcs, [col(0)] if partitioned else [],
                      [(col(1), False, True)] if ordered else [],
                      group_limit=group_limit)


def _keys(table, ordered=True, partitioned=True):
    n = table.num_rows
    p = [(x,) for x in table.column("p").to_pylist()] if partitioned \
        else [()] * n
    o = [(x,) for x in table.column("o").to_pylist()] if ordered \
        else [()] * n
    return p, o


def _same(got, want, what=""):
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        if isinstance(w, float) and g is not None:
            assert math.isclose(g, w, rel_tol=REL, abs_tol=1e-9 * REL), \
                (what, i, g, w)
        else:
            assert g == w, (what, i, g, w)


@functools.lru_cache(maxsize=None)
def _resident(dtype: str, nulls: str):
    """Every function the lane takes in ONE node over one table: (table,
    the lane's answer, counters)."""
    table = _table(dtype, nulls)
    funcs = _rank_funcs() + _agg_funcs(dtype)
    with device_placement():
        before = xla_stats.snapshot()
        got = _collect(_node(_scan(table, ONE_TILE), funcs))
        moved = xla_stats.delta(before)
    assert moved["window_resident_rows"] == moved["window_rows"] == ROWS
    return table, got


@pytest.mark.parametrize("nulls", NULLS)
@pytest.mark.parametrize(
    "form,dtype",
    [pytest.param(f, d, id=f"{f[0]}-{d}") for f in AGG_FORMS for d in DTYPES
     if (f[1], d) != ("avg", "decimal")])   # that one: the host lane's
def test_an_aggregate_over_a_frame_is_the_reference(form, dtype, nulls):
    name, fn, running = form
    table, got = _resident(dtype, nulls)
    p, o = _keys(table)
    values = None if name == "count_star" \
        else table.column("v").to_pylist()
    want = ref.window(p, o, fn, values, running=running)
    _same(got.column(name).to_pylist(), want, name)
    # the input's columns pass untouched, in order
    assert got.column("rid").to_pylist() == list(range(ROWS))


@pytest.mark.parametrize("nulls", NULLS)
@pytest.mark.parametrize("kind", [k.value for k in WindowRankType])
def test_a_rank_is_the_reference(kind, nulls):
    table, got = _resident("int64", nulls)
    p, o = _keys(table)
    _same(got.column(kind).to_pylist(), ref.window(p, o, kind), kind)
    want_type = pa.float64() if kind in ("percent_rank", "cume_dist") \
        else pa.int32()
    assert got.schema.field(kind).type == want_type


def _both_lanes(table, make, cuts=ONE_TILE):
    """(host lane's answer, resident lane's answer, its counters)."""
    want = _collect(make(_scan(table, cuts)))
    with device_placement():
        before = xla_stats.snapshot()
        got = _collect(make(_scan(table, cuts)))
        moved = xla_stats.delta(before)
    return want, got, moved


def _assert_tables(want: pa.Table, got: pa.Table):
    assert got.schema.equals(want.schema)
    for name in want.schema.names:
        _same(got.column(name).to_pylist(), want.column(name).to_pylist(),
              name)


@pytest.mark.parametrize("shape", ["single_row_partitions", "one_partition",
                                   "no_order", "no_partition_keys"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_partition_shapes(shape, dtype):
    """Every partition one row; the whole run one partition; no ORDER BY
    (every frame the whole partition's, every row its own run); no
    PARTITION BY."""
    n = 1500
    table = _table(dtype, "values", n=n,
                   part_card=1 if shape == "one_partition" else 40)
    if shape == "single_row_partitions":
        table = table.set_column(0, "p", pa.array(np.arange(n)))
    ordered = shape != "no_order"
    partitioned = shape != "no_partition_keys"
    funcs = _rank_funcs() + _agg_funcs(dtype)
    with device_placement():
        before = xla_stats.snapshot()
        got = _collect(_node(_scan(table, [n]), funcs, ordered=ordered,
                             partitioned=partitioned))
        moved = xla_stats.delta(before)
    assert moved["window_resident_rows"] == n
    p, o = _keys(table, ordered, partitioned)
    values = table.column("v").to_pylist()
    for kind in ref.RANKS:
        _same(got.column(kind).to_pylist(),
              ref.window(p, o, kind, ordered=ordered), kind)
    for f in funcs[len(ref.RANKS):]:
        name, fn, running = next(a for a in AGG_FORMS if a[0] == f.name)
        want = ref.window(p, o, fn, None if name == "count_star" else values,
                          running=running, ordered=ordered)
        _same(got.column(name).to_pylist(), want, name)


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_partition_across_batches(dtype):
    """The resident lane takes a run that is ONE batch (what a `SortExec`'s
    resident lane emits).  A run that arrives as five batches of four
    capacities goes through the host lane, whose buffer joins partitions
    across the seams: equal answers."""
    table = _table(dtype, "values")
    funcs = _rank_funcs() + _agg_funcs(dtype)
    want, got, moved = _both_lanes(table, lambda c: _node(c, funcs), RAGGED)
    _assert_tables(want, got)
    assert moved["window_rows"] == ROWS
    assert moved["window_resident_rows"] == 0
    one = _resident(dtype, "values")[1]
    _assert_tables(one, got)


def test_ties_under_a_running_frame_share_the_frame_end_value():
    """Spark's RANGE frame, which the host lane gives: rows of equal order
    keys read the sum through the LAST of them.  Pinned by hand, then held
    against the host lane on a run of 1,024 rows."""
    p = [1, 1, 1, 1, 2, 2]
    o = [5, 5, 7, 7, 5, 9]
    v = [1.0, 2.0, 4.0, 8.0, 16.0, 32.0]
    want = [3.0, 3.0, 15.0, 15.0, 16.0, 48.0]
    assert ref.window([(x,) for x in p], [(x,) for x in o], "sum", v) == want
    reps = 200      # 1,200 rows: over the lane's floor
    table = pa.table({
        "p": pa.array(np.repeat(np.arange(reps), 6) * 10 + np.tile(p, reps)),
        "o": pa.array(np.tile(o, reps).astype(np.int32)).cast(pa.date32()),
        "v": pa.array(np.tile(v, reps))})
    funcs = [WindowAggFunc("s", make_agg("sum", [col(2)]), running=True),
             WindowAggFunc("m", make_agg("max", [col(2)]), running=True),
             RankFunc("rk", WindowRankType.RANK)]
    host, got, moved = _both_lanes(table, lambda c: _node(c, funcs),
                                   [6 * reps])
    assert moved["window_resident_rows"] == 6 * reps
    assert got.column("s").to_pylist() == want * reps
    assert got.column("m").to_pylist() == [2.0, 2.0, 8.0, 8.0, 16.0,
                                           32.0] * reps
    assert got.column("rk").to_pylist() == [1, 1, 3, 3, 1, 2] * reps
    _assert_tables(host, got)


@pytest.mark.parametrize("lane", ["resident", "host"])
def test_a_sum_restarts_at_a_partition_boundary(lane):
    """One partition of values near 1e12, then one of values near 1e-3: no
    value of the first may enter the second's sums.  `cumsum(all) - cumsum
    at the partition's start` carries an absolute error of an ulp of 1e15,
    nine orders past the second partition's values."""
    n = 1100
    rng = np.random.default_rng(3)
    big = np.round(rng.random(n) * 1e12 + 1e12, 2)
    small = rng.random(n) * 1e-3 + 1e-3
    table = pa.table({
        "p": pa.array(np.repeat([1, 2], n)),
        "o": pa.array(np.tile(np.arange(n, dtype=np.int32), 2))
        .cast(pa.date32()),
        "v": pa.array(np.concatenate([big, small]))})
    funcs = [WindowAggFunc("s", make_agg("sum", [col(2)]), running=True)]
    with device_placement() if lane == "resident" \
            else contextlib.nullcontext():
        before = xla_stats.snapshot()
        got = _collect(_node(_scan(table, [2 * n]), funcs))
        moved = xla_stats.delta(before)
    assert moved["window_resident_rows"] == (2 * n if lane == "resident"
                                             else 0)
    p, o = _keys(table)
    want = ref.window(p, o, "sum", table.column("v").to_pylist())
    s = got.column("s").to_numpy()
    rel = np.abs(s - np.array(want)) / np.array(want)
    assert rel[n:].max() < 1e-15 * math.log2(n), rel[n:].max()
    assert rel[:n].max() < 1e-15 * math.log2(n)
    # what the subtracted cumsum does to the same rows
    v = table.column("v").to_numpy()
    total = np.cumsum(v)
    subtracted = total[n:] - total[n - 1]
    assert (np.abs(subtracted - want[n:]) / want[n:]).max() > 1e-9


def test_a_utf8_column_takes_the_host_lane_with_equal_answers():
    table = _table("float64", "values")
    funcs = _rank_funcs() + _agg_funcs("float64")
    resident = _resident("float64", "values")[1]
    named = table.append_column(
        "name", pa.array([f"n{i % 7}" for i in range(ROWS)]))
    with device_placement():
        before = xla_stats.snapshot()
        got = _collect(_node(_scan(named, ONE_TILE), funcs))
        moved = xla_stats.delta(before)
    assert moved["window_rows"] == ROWS
    assert moved["window_resident_rows"] == 0
    assert moved["d2h_bytes"] > 0     # the host lane reads back, counted
    _assert_tables(resident, got.drop_columns(["name"]))


@pytest.mark.parametrize("spill_after", [1, 3])
def test_a_spilled_buffer_takes_the_host_lane_with_equal_answers(
        monkeypatch, spill_after):
    """The memory manager takes the host lane's buffered batches in
    mid-run: they come back from the spill at the next flush, and the
    answers are the resident lane's."""
    table = _table("int64", "values")
    funcs = _rank_funcs() + _agg_funcs("int64")
    want = _resident("int64", "values")[1]
    added = []
    real_add = _WindowBuffer.add

    def add(self, rb):
        real_add(self, rb)
        added.append(rb.num_rows)
        if len(added) == spill_after:
            assert self.spill() > 0

    monkeypatch.setattr(_WindowBuffer, "add", add)
    with device_placement():
        before = xla_stats.snapshot()
        got = _collect(_node(_scan(table, RAGGED), funcs))
        moved = xla_stats.delta(before)
    assert len(added) >= spill_after
    assert moved["window_rows"] == ROWS
    assert moved["window_resident_rows"] == 0
    _assert_tables(want, got)


def test_a_small_run_and_a_node_with_lag_take_the_host_lane():
    table = _table("float64", "none", n=600)
    funcs = _agg_funcs("float64")
    with device_placement():
        before = xla_stats.snapshot()
        _collect(_node(_scan(table, [600]), funcs))
        small = xla_stats.delta(before)
    assert small["window_rows"] == 600 and small["window_resident_rows"] == 0
    table = _table("float64", "none")
    lag = funcs + [LeadLagFunc("prev", col(2), -1, None)]
    want, got, moved = _both_lanes(table, lambda c: _node(c, lag))
    assert moved["window_resident_rows"] == 0
    _assert_tables(want, got)


def test_a_decimal_sum_past_18_digits_leaves_as_the_host_column_it_is():
    rng = np.random.default_rng(5)
    n = 1200
    cents = rng.integers(0, 10**11, n)
    table = pa.table({
        "p": pa.array(np.sort(rng.integers(0, 9, n))),
        "o": pa.array(np.arange(n, dtype=np.int32)).cast(pa.date32()),
        "v": pa.array([decimal.Decimal(int(c)).scaleb(-2) for c in cents],
                      type=pa.decimal128(12, 2))})
    funcs = [WindowAggFunc("s", make_agg("sum", [col(2)]), running=True)]
    want, got, moved = _both_lanes(table, lambda c: _node(c, funcs), [n])
    assert got.schema.field("s").type == pa.decimal128(22, 2)
    assert moved["window_resident_rows"] == 0
    p, o = _keys(table)
    assert got.column("s").to_pylist() == want.column("s").to_pylist() == \
        ref.window(p, o, "sum", table.column("v").to_pylist())


def test_group_limit_is_a_selection_on_the_device():
    table = _table("int64", "none")
    funcs = [RankFunc("rk", WindowRankType.RANK)]
    want = _collect(_node(_scan(table, ONE_TILE), funcs, group_limit=3))
    with device_placement():
        out = list(_node(_scan(table, ONE_TILE), funcs,
                         group_limit=3).execute(0))
    assert len(out) == 1 and out[0].num_rows == ROWS
    assert isinstance(out[0].selection, jnp.ndarray)
    got = pa.Table.from_batches([out[0].compact().to_arrow()])
    assert got.to_pandas().equals(want.to_pandas())
    assert 0 < got.num_rows < ROWS and max(got.column("rk").to_pylist()) <= 3


def test_counters_and_the_span():
    """`window_rows`, `window_resident_rows`, `window_partitions`,
    `window_scan_bytes`, by chip too and 0 after `reset()`; one
    `window_device` span a run with its lane, and no `d2h` inside a
    resident one."""
    table = _table("float64", "values")
    funcs = [WindowAggFunc("s", make_agg("sum", [col(2)]), running=True),
             WindowAggFunc("m", make_agg("max", [col(2)]), running=True)]
    xla_stats.reset()
    for k in ("window_rows", "window_resident_rows", "window_partitions",
              "window_scan_bytes"):
        assert xla_stats.snapshot()[k] == 0
    tracing.start_tracing()
    try:
        with device_placement():
            out = list(_node(_scan(table, ONE_TILE), funcs).execute(0))
    finally:
        spans = tracing.stop_tracing()
    moved = xla_stats.snapshot()
    assert moved["window_rows"] == moved["window_resident_rows"] == ROWS
    assert moved["window_partitions"] == 1
    # p int64, o date32, v float64 read with their validity; two float64
    # results written with theirs
    per_row = (8 + 1) + (4 + 1) + 2 * (8 + 1) + 2 * (8 + 1)
    assert moved["window_scan_bytes"] == ROWS * per_row
    chip = xla_stats.chip_stats()[0]
    assert chip["window_resident_rows"] == ROWS
    assert chip["window_scan_bytes"] == ROWS * per_row
    win = [s for s in spans if s["name"] == "window_device"]
    assert len(win) == 1
    assert win[0]["attrs"] == {"lane": "resident", "rows": ROWS,
                               "partitions": 1, "functions": 2}
    inside = [s for s in spans if s["name"] == "d2h"
              and win[0]["t0_ns"] <= s["t0_ns"] < win[0]["t1_ns"]]
    assert not inside
    assert "window_device" in tracing.SPAN_NAMES
    # and the results are device columns: nothing was read back
    assert all(isinstance(c.data, jnp.ndarray) for c in out[0].columns)
    assert moved["d2h_bytes"] == 0
    # the host lane's span says so
    tracing.start_tracing()
    try:
        _collect(_node(_scan(table, ONE_TILE), funcs))
    finally:
        spans = tracing.stop_tracing()
    lanes = [s["attrs"]["lane"] for s in spans
             if s["name"] == "window_device"]
    assert lanes == ["host"]
    xla_stats.reset()
    assert xla_stats.snapshot()["window_rows"] == 0


def test_the_scan_is_one_program_named_for_the_trace():
    from blaze_tpu.kernels import window as kwin
    assert kwin.segmented_scan._blaze_metered_jit == "window.scan"
    assert xla_stats.program_name("_segmented_scan", "window.scan") == \
        "_segmented_scan__window_scan"
    table = _table("float64", "none")
    funcs = _rank_funcs() + _agg_funcs("float64")
    with device_placement():
        _collect(_node(_scan(table, ONE_TILE), funcs))   # warm
        before = xla_stats.compile_report()["kernels"]["window.scan"]["calls"]
        _collect(_node(_scan(table, ONE_TILE), funcs))
        after = xla_stats.compile_report()["kernels"]["window.scan"]
    assert after["calls"] - before == 1


def test_the_explain_footer_says_how_many_rows_stayed():
    from blaze_tpu.plan.explain import explain_analyze
    table = _table("float64", "none")
    funcs = [WindowAggFunc("s", make_agg("sum", [col(2)]), running=True)]
    with device_placement():
        text = str(explain_analyze(_node(_scan(table, ONE_TILE), funcs),
                                   record=False))
    line, = [ln for ln in text.splitlines() if ln.startswith("window=")]
    assert line.startswith(f"window={ROWS}/{ROWS} rows resident runs=1 ")
    plain = str(explain_analyze(_scan(table, ONE_TILE), record=False))
    assert "window=" not in plain

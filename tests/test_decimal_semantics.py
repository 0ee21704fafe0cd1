"""Spark 3's decimal rules (non-ANSI, `DecimalType.bounded`) as this
engine holds them since PR 40, one parametrised test a rule, on tables of a
few rows with the expectation written out by hand beside the benchmark's
reference (`benchmark/queries/q01_dec.py`, Python integers over unscaled
values, nothing of the program):

  result types     sum (p+10, s) in every mode, avg (p+4, s+4) of its INPUT
                   with a (p+10, s) partial sum, multiply (p1+p2+1, s1+s2),
                   a comparison at the larger scale;
  rounding         an average is sum * 10^4 / count rounded HALF_UP;
  NULL             a NULL amount is skipped, a group of NULLs sums to NULL;
  overflow         a sum past its type's bound is NULL (and counted), one
                   that 64 bits might not hold is taken off the stage loop,
                   and nothing wraps;
  beside a double  the decimal is cast to double, as Spark casts it;
  the stage loop   fold, partial pass-through, rehash and a window's padded
                   tail with a decimal value lane, each against the
                   reference.
"""

import importlib.util
import os
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu import config
from blaze_tpu import schema as S
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.exprs import BinaryExpr, col, lit
from blaze_tpu.memory import MemManager
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.ops.agg import AggExec, AggMode, make_agg
from blaze_tpu.plan.fused import FusedPartialAggExec, fuse_plan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP_ON = {config.STAGE_DEVICE_LOOP_ENABLE.key: "on"}


def _reference():
    spec = importlib.util.spec_from_file_location(
        "benchmark.queries.q01_dec",
        os.path.join(ROOT, "benchmark", "queries", "q01_dec.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


@pytest.fixture(autouse=True)
def big_budget():
    MemManager.init(4 << 30)
    yield
    MemManager.init(4 << 30)


def dec(p, s):
    return S.DataType(S.TypeId.DECIMAL, p, s)


def amounts(unscaled, p=7, s=2):
    return pa.array([None if v is None else Decimal(v).scaleb(-s)
                     for v in unscaled], type=pa.decimal128(p, s))


def unscaled_of(column):
    """A decimal column's unscaled values as Python ints (None = NULL)."""
    s = column.type.scale
    return [None if v is None else int(v.scaleb(s))
            for v in column.to_pylist()]


def agg_plan(table, fn, mode, loop, arg_cols=("v",), batch_rows=None):
    """`fn` by `k` in `mode`, as the eager AggExec or as the fused node
    that folds in the stage loop."""
    scan = MemoryScanExec.from_arrow(table, batch_rows=batch_rows)
    sch = S.Schema.from_arrow(table.schema)
    args = [col(sch.index_of(c), c) for c in arg_cols]
    plan = AggExec(scan, [(col(0, "k"), "k")],
                   [(make_agg(fn, args), mode, "a")])
    if loop:
        plan = fuse_plan(plan)
        assert isinstance(plan, FusedPartialAggExec), type(plan).__name__
    return plan


def run(plan, **conf):
    with config.scoped(**dict(LOOP_ON, **conf)):
        before = xla_stats.snapshot()
        out = plan.execute_collect().to_arrow()
        return out, xla_stats.delta(before)


def by_key(batch, name):
    return dict(zip(batch.column("k").to_pylist(),
                    unscaled_of(batch.column(name))))


def two_stage(table, fn, loop, batch_rows=None):
    """partial -> final over `table`; ({k: unscaled}, final type, delta)."""
    partial, d1 = run(agg_plan(table, fn, AggMode.PARTIAL, loop,
                               batch_rows=batch_rows))
    nacc = partial.num_columns - 1
    final_loop = loop and fn != "avg"   # a final average is never fused
    final, d2 = run(agg_plan(pa.Table.from_batches([partial]), fn,
                             AggMode.FINAL, final_loop,
                             arg_cols=partial.schema.names[1:1 + nacc]))
    return (by_key(final, "a"), final.schema.field("a").type, partial,
            {k: d1[k] + d2[k] for k in d1})


LANES = [pytest.param(False, id="eager"), pytest.param(True, id="loop")]


# -- result types -----------------------------------------------------------

@pytest.mark.parametrize("loop", LANES)
@pytest.mark.parametrize("fn,mode,in_type,want", [
    ("sum", AggMode.PARTIAL, (7, 2), ["decimal128(17, 2)"]),
    ("sum", AggMode.FINAL, (17, 2), ["decimal128(17, 2)"]),      # not (27,2)
    ("sum", AggMode.PARTIAL_MERGE, (17, 2), ["decimal128(17, 2)"]),
    ("sum", AggMode.PARTIAL, (8, 0), ["decimal128(18, 0)"]),
    ("sum", AggMode.PARTIAL, (12, 2), ["decimal128(22, 2)"]),    # uncapped
    ("avg", AggMode.PARTIAL, (17, 2), ["decimal128(27, 2)", "int64"]),
    ("avg", AggMode.PARTIAL, (7, 2), ["decimal128(17, 2)", "int64"]),
])
def test_result_types_of_aggregates(fn, mode, in_type, want, loop):
    t = pa.table({"k": pa.array([1, 1, 2], pa.int64()),
                  "v": amounts([100, 250, -75], *in_type)})
    plan = agg_plan(t, fn, mode, loop)
    assert [str(f.data_type.to_arrow()) for f in list(plan.schema)[1:]] \
        == want


@pytest.mark.parametrize("in_type,want", [((27, 2), "decimal128(21, 6)"),
                                          ((17, 2), "decimal128(11, 6)")])
def test_a_final_average_is_p4_s4_of_its_input(in_type, want):
    t = pa.table({"k": pa.array([1], pa.int64()),
                  "s": amounts([100], *in_type),
                  "c": pa.array([1], pa.int64())})
    plan = agg_plan(t, "avg", AggMode.FINAL, False, arg_cols=("s", "c"))
    assert str(plan.schema[1].data_type.to_arrow()) == want


@pytest.mark.parametrize("op,lt,rt,want", [
    ("*", (21, 6), (2, 1), "decimal(24,7)"),
    ("*", (7, 2), (7, 2), "decimal(15,4)"),
    (">", (17, 2), (24, 7), "bool"),
    ("+", (7, 2), (7, 2), "decimal(8,2)"),
])
def test_result_types_of_expressions(op, lt, rt, want):
    sch = S.Schema([S.Field("l", dec(*lt)), S.Field("r", dec(*rt))])
    t = BinaryExpr(op, col(0, "l"), col(1, "r")).data_type(sch)
    got = "bool" if t.id == S.TypeId.BOOL \
        else f"decimal({t.precision},{t.scale})"
    assert got == want


# -- HALF_UP ------------------------------------------------------------------

@pytest.mark.parametrize("loop", LANES)
@pytest.mark.parametrize("cents,want", [
    ([1, 2], 15000),                      # 0.015 exactly
    ([1, 1, 2], 13333),                   # 0.01333.. rounds down
    ([2, 2, 1], 16667),                   # 0.01666.. rounds up
    ([-2, -2, -1], -16667),               # and away from zero below it
    ([1] + [0] * 31, 313),                # 0.0003125: the tie goes up
    ([-1] + [0] * 31, -313),              # -0.0003125: and down below zero
    ([3] + [0] * 31, 938),                # 0.0009375: a tie, not to even
], ids=["exact", "down", "up", "negative", "tie", "negative-tie",
        "tie-not-even"])
def test_an_average_rounds_half_up(cents, want, loop):
    t = pa.table({"k": pa.array([7] * len(cents), pa.int64()),
                  "v": amounts(cents)})
    got, typ, _partial, _d = two_stage(t, "avg", loop)
    assert str(typ) == "decimal128(11, 6)"
    assert got == {7: want}
    # the reference's rule, beside the hand-written number
    assert REF._div_half_up(sum(cents) * 10 ** 4, len(cents)) == want


# -- NULL ----------------------------------------------------------------------

@pytest.mark.parametrize("loop", LANES)
@pytest.mark.parametrize("fn,want", [
    ("sum", {1: 350, 2: None, 3: -75}),
    ("avg", {1: 1750000, 2: None, 3: -750000}),
    ("count", {1: 2, 2: 0, 3: 1}),
])
def test_null_amounts_are_skipped_and_a_group_of_nulls_is_null(fn, want,
                                                               loop):
    t = pa.table({"k": pa.array([1, 1, 2, 2, 3, 1], pa.int64()),
                  "v": amounts([100, None, None, None, -75, 250])})
    if fn == "count":
        partial, _ = run(agg_plan(t, fn, AggMode.PARTIAL, loop))
        assert dict(zip(partial.column("k").to_pylist(),
                        partial.column(1).to_pylist())) == want
        return
    got, _typ, _partial, d = two_stage(t, fn, loop)
    assert got == want
    assert d["decimal_overflow_groups"] == 0


# -- overflow ----------------------------------------------------------------

BOUND = 10 ** 17


@pytest.mark.parametrize("loop", LANES)
@pytest.mark.parametrize("mode", [AggMode.PARTIAL_MERGE, AggMode.FINAL],
                         ids=["merge", "final"])
def test_a_sum_past_its_types_bound_is_null_and_counted(mode, loop):
    """Partial sums of decimal(17,2): group 1 passes 10^17 - 1, group 2
    reaches it exactly, group 3 cancels back under it."""
    big = 6 * 10 ** 16
    t = pa.table({"k": pa.array([1, 1, 2, 2, 3, 3, 3], pa.int64()),
                  "v": amounts([big, big, BOUND - 2, 1, big, big, -big],
                               17, 2)})
    out, d = run(agg_plan(t, "sum", mode, loop))
    assert str(out.schema.field(1).type) == "decimal128(17, 2)"
    assert by_key(out, out.schema.names[1]) == {1: None, 2: BOUND - 1,
                                                3: big}
    assert d["decimal_overflow_groups"] == 1
    assert REF._bounded(2 * big, REF.TOTAL) is None
    assert REF._bounded(BOUND - 1, REF.TOTAL) == BOUND - 1


def test_a_partial_sum_that_64_bits_might_not_hold_leaves_the_stage_loop():
    """Four amounts of 9 x 10^17 sum to 3.6 x 10^18: inside int64, but
    past the quarter of it the loop's bound is held to.  The loop declines
    the partition before it emits, the eager aggregation takes it, and the
    answer is exact at decimal(28,0)."""
    t = pa.table({"k": pa.array([1, 1, 1, 1], pa.int64()),
                  "v": amounts([9 * 10 ** 17] * 4, 18, 0)})
    plan = agg_plan(t, "sum", AggMode.PARTIAL, True)
    reasons = xla_stats.stage_loop_fallback_reasons().get(
        "a decimal sum may pass 64 bits", 0)
    out, d = run(plan)
    assert str(out.schema.field(1).type) == "decimal128(28, 0)"
    assert by_key(out, out.schema.names[1]) == {1: 36 * 10 ** 17}
    assert d["stage_loop_fallbacks"] == 1 and d["stage_loop_tasks"] == 0
    assert d["decimal_overflow_groups"] == 1
    assert d["stage_loop_decimal_rows"] == 0
    assert d["agg_decimal_rows_host"] == 4
    assert xla_stats.stage_loop_fallback_reasons()[
        "a decimal sum may pass 64 bits"] == reasons + 1


@pytest.mark.parametrize("loop", LANES)
def test_a_sum_that_would_wrap_is_refused_never_wrapped(loop):
    """Eleven amounts of 9 x 10^17 pass 2^63: no lane of this engine holds
    that sum, and it says so."""
    t = pa.table({"k": pa.array([1] * 11, pa.int64()),
                  "v": amounts([9 * 10 ** 17] * 11, 18, 0)})
    with pytest.raises(ArithmeticError, match="64 bits"):
        run(agg_plan(t, "sum", AggMode.PARTIAL, loop))


def test_an_average_past_64_bits_is_exact_and_one_past_its_type_is_null():
    """avg(decimal(17,2)) from partial (sum, count): the quotient scaled by
    10^4 leaves int64 but not decimal(21,6) (group 1: exact, in Python
    integers), or leaves its type (group 3: the decimal(11,6) of a
    decimal(7,2)'s average, NULL)."""
    t = pa.table({"k": pa.array([1], pa.int64()),
                  "s": amounts([4 * 10 ** 16], 27, 2),
                  "c": pa.array([3], pa.int64())})
    out, d = run(agg_plan(t, "avg", AggMode.FINAL, False,
                          arg_cols=("s", "c")))
    want = REF._div_half_up(4 * 10 ** 16 * 10 ** 4, 3)
    assert 2 ** 63 < want < 10 ** 21 and by_key(out, "a") == {1: want}
    assert d["decimal_overflow_groups"] == 1      # the wide path, counted
    narrow = pa.table({"k": pa.array([3], pa.int64()),
                       "s": amounts([5 * 10 ** 8], 17, 2),
                       "c": pa.array([2], pa.int64())})
    out, d = run(agg_plan(narrow, "avg", AggMode.FINAL, False,
                          arg_cols=("s", "c")))
    assert str(out.schema.field("a").type) == "decimal128(11, 6)"
    assert by_key(out, "a") == {3: None}          # 2.5 x 10^12 >= 10^11
    assert d["decimal_overflow_groups"] == 1


# -- a decimal beside a double -----------------------------------------------

TOTALS = [44189, 24654, 15020, 32547, 27122]          # decimal(17,2)
AVERAGE = 271225234                                    # decimal(21,6)


def _joined():
    return pa.table({"total": amounts(TOTALS, 17, 2),
                     "avg": amounts([AVERAGE] * 5, 21, 6)})


def _filter(table, factor):
    from blaze_tpu.ops.basic import FilterExec
    sch = S.Schema.from_arrow(table.schema)
    pred = BinaryExpr(">", col(0, "total"),
                      BinaryExpr("*", col(1, "avg"), factor))
    assert pred.data_type(sch).id == S.TypeId.BOOL
    plan = FilterExec(MemoryScanExec.from_arrow(table), [pred])
    return unscaled_of(plan.execute_collect().compact().to_arrow()
                       .column("total"))


def test_a_decimal_beside_a_float64_is_cast_to_double():
    """`total > avg * 1.2` with the plan's float64 literal: 271.225234 *
    1.2 = 325.47..., so 441.89 and 325.47 + 0.01 pass.  The tree before
    PR 40 multiplied the UNSCALED average by 1.2 and compared cents with
    it: 0 rows."""
    sch = S.Schema.from_arrow(_joined().schema)
    product = BinaryExpr("*", col(1, "avg"), lit(1.2))
    assert product.data_type(sch) == S.FLOAT64
    assert _filter(_joined(), lit(1.2)) == [44189]
    cb = MemoryScanExec.from_arrow(_joined()).execute_collect()
    got = np.asarray(product.evaluate(cb).data)[:5]
    np.testing.assert_allclose(got, [271.225234 * 1.2] * 5, rtol=1e-15)


def test_a_decimal_literal_evaluates_and_the_threshold_is_exact():
    """The same filter as Spark types it: the literal decimal(2,1), the
    product decimal(24,7) = 3254702808 exactly, the comparison at scale 7
    (32547 cents = 3254700000 does not pass, 32548 would)."""
    sch = S.Schema.from_arrow(_joined().schema)
    factor = lit(Decimal("1.2"), dec(2, 1))
    product = BinaryExpr("*", col(1, "avg"), factor)
    t = product.data_type(sch)
    assert (t.precision, t.scale) == (24, 7)
    cb = MemoryScanExec.from_arrow(_joined()).execute_collect()
    got = product.evaluate(cb).to_host(5)
    assert got.type == pa.decimal128(24, 7)
    assert unscaled_of(got) == [AVERAGE * 12] * 5 == [3254702808] * 5
    assert _filter(_joined(), factor) == [44189]
    edge = pa.table({"total": amounts([32547, 32548], 17, 2),
                     "avg": amounts([AVERAGE] * 2, 21, 6)})
    assert _filter(edge, factor) == [32548]


def test_decimal_expressions_run_inside_a_device_program_and_say_so():
    before = xla_stats.snapshot()
    _filter(_joined(), lit(Decimal("1.2"), dec(2, 1)))
    d = xla_stats.delta(before)
    assert d["expr_decimal_device_batches"] == 1
    assert d["expr_decimal_host_batches"] == 0
    assert d["expr_fused_batches"] == 1 and d["host_evictions_decimal"] == 0
    # a division has no exact lane: the eager evaluator takes it, inside a
    # decimal_host_eval span
    from blaze_tpu.ops.basic import ProjectExec
    t = _joined()
    plan = ProjectExec(MemoryScanExec.from_arrow(t),
                       [BinaryExpr("/", col(0, "total"), col(1, "avg"))],
                       ["q"])
    before = xla_stats.snapshot()
    tracing.start_tracing()
    try:
        plan.execute_collect()
    finally:
        spans = tracing.stop_tracing()
    d = xla_stats.delta(before)
    assert d["expr_decimal_host_batches"] == 1
    assert d["host_evictions_decimal"] == 1
    held = [s for s in spans if s["name"] == "decimal_host_eval"]
    assert [s["attrs"]["op"] for s in held] == ["project"]
    assert held[0]["attrs"]["rows"] == 5 and held[0]["dur_ns"] > 0


# -- the stage loop's modes with a decimal value lane ---------------------------

def _reference_sums(keys, cents):
    out = {}
    for k, v in zip(keys, cents):
        if v is None:
            out.setdefault(k, None)
        else:
            out[k] = (out.get(k) or 0) + v
    return {k: REF._bounded(v, REF.TOTAL) for k, v in out.items()}


def _loop_table(n, groups, seed, nulls=0.05):
    rng = np.random.default_rng(seed)
    # wide keys: a compact range is no hash table's business
    keys = (rng.integers(0, groups, n) * 1000003 + 17).tolist()
    cents = [None if rng.random() < nulls else int(v)
             for v in rng.integers(-4_999_999, 5_000_000, n)]
    return pa.table({"k": pa.array(keys, pa.int64()),
                     "v": amounts(cents)}), keys, cents


SMALL = {config.BATCH_SIZE.key: 128, config.ON_DEVICE_AGG_CAPACITY.key: 1024,
         config.STAGE_DEVICE_LOOP_CHUNK.key: 8}


@pytest.mark.parametrize("case", ["fold", "passthrough", "rehash",
                                  "padded_tail"])
def test_the_stage_loops_modes_with_a_decimal_lane(case):
    conf = dict(SMALL)
    n, groups, mode = 3000, 200, AggMode.PARTIAL
    if case == "passthrough":
        groups = 50_000
        conf.update({config.PARTIAL_AGG_SKIPPING_MIN_ROWS.key: 256,
                     config.PARTIAL_AGG_SKIPPING_RATIO.key: 0.5})
    elif case == "rehash":
        n, groups, mode = 6000, 5000, AggMode.PARTIAL_MERGE
    elif case == "padded_tail":
        n = 128 * 9 + 37        # a second window of one batch and a tail
    table, keys, cents = _loop_table(n, groups, seed=len(case))
    if mode == AggMode.PARTIAL_MERGE:   # partial sums are decimal(17,2)
        table = table.set_column(1, "v", amounts(cents, 17, 2))
    plan = agg_plan(table, "sum", mode, True, batch_rows=128)
    tracing.start_tracing()
    try:
        out, d = run(plan, **conf)
    finally:
        spans = tracing.stop_tracing()
    assert str(out.schema.field(1).type) == "decimal128(17, 2)"
    assert d["stage_loop_tasks"] == 1 and d["stage_loop_fallbacks"] == 0
    assert d["agg_decimal_rows_host"] == 0
    assert d["decimal_overflow_groups"] == 0
    # merged over whatever rows left un-aggregated, the partial output is
    # the reference's group-by, to the cent
    got = _reference_sums(out.column("k").to_pylist(),
                          unscaled_of(out.column(1)))
    assert got == _reference_sums(keys, cents)
    if case == "passthrough":
        assert d["partial_agg_skip_events"] == 1
        assert d["partial_agg_skipped_rows"] > 0
        assert out.num_rows > len(got)          # rows left un-aggregated
        assert d["stage_loop_decimal_rows"] < n
    else:
        assert out.num_rows == len(got)
        assert d["stage_loop_decimal_rows"] == d["stage_loop_rows"] == n
    if case == "rehash":
        assert any(s["name"] == "table_rehash" for s in spans)
        assert d["stage_loop_rehash_groups"] > 0
    if case == "padded_tail":
        windows = [s["attrs"] for s in spans if s["name"] == "loop_window"]
        # the second window: one full batch and the 37-row tail, widened
        # to the chunk with masked-out batches whose lanes are not counted
        assert [w["batches"] for w in windows] == [8, 2]
        assert n < d["stage_loop_lanes"] <= 128 * 10

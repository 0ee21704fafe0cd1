"""The chip's path reads only the row groups its consumer can use: a fused
aggregation's leading filters and a hash join's build-key range go down to
the parquet scan beneath them as a statistics-only pruning predicate, for
that read alone (`ExecutionPlan.execute_pruned`, `ParquetScanExec.execute`'s
`extra_prune`).  Every case runs a plan through `DagScheduler` on the device
path over date-sorted files with small row groups and holds it, row for row,
to the same plan with `auron.parquet.enable.pageFiltering` off; each pins
`scan_row_groups_pruned`, so a path that stops pruning, or starts where it
may not, fails here."""

import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.queries.ir import (Ids, binop, c, ci, filter_, join,  # noqa: E402
                                  lit, partial_final, project)
from blaze_tpu import config  # noqa: E402
from blaze_tpu.bridge import xla_stats  # noqa: E402
from blaze_tpu.plan.stages import DagScheduler  # noqa: E402
from blaze_tpu.plan.types import schema_to_dict  # noqa: E402
from blaze_tpu.schema import Schema  # noqa: E402

FILES, FILE_ROWS, GROUP_ROWS = 4, 4000, 500     # 8 row groups a file
DATES = 1000                                    # the fact table's key range


def _fact(null_dates: bool = False) -> pa.Table:
    rng = np.random.default_rng(7)
    n = FILES * FILE_ROWS
    date = np.sort(rng.integers(0, DATES, n))
    dates = pa.array(date, pa.int64())
    if null_dates:
        dates = pa.array(np.where(rng.random(n) < 0.03, None, date).tolist(),
                         pa.int64())
    return pa.table({"date": dates,
                     "k": pa.array(rng.integers(0, 20, n), pa.int64()),
                     "v": pa.array(np.round(rng.random(n), 6))})


def _dim(lo: int, hi: int, null_keys: bool = False) -> pa.Table:
    sk = np.arange(lo, hi)
    keys = pa.array(sk, pa.int64())
    if null_keys:
        keys = pa.array([None if i % 7 == 0 else int(s)
                         for i, s in enumerate(sk)], pa.int64())
    return pa.table({"d_sk": keys,
                     "d_tag": pa.array(sk % 5, pa.int32())})


class Case:
    """Tables on disk and the plan's leaves over them."""

    def __init__(self, root, fact: pa.Table, dim: pa.Table,
                 no_stats_file: int = -1):
        self.tables = {"fact": fact, "dim": dim}
        self.paths = {"fact": [], "dim": [[os.path.join(root, "dim.parquet")]]}
        for i in range(FILES):
            path = os.path.join(root, f"fact{i}.parquet")
            pq.write_table(fact.slice(i * FILE_ROWS, FILE_ROWS), path,
                           row_group_size=GROUP_ROWS,
                           write_statistics=i != no_stats_file)
            self.paths["fact"].append([path])
        pq.write_table(dim, self.paths["dim"][0][0])
        self.ids = Ids(self.paths)

    def scan(self, name: str, **extra) -> dict:
        schema = Schema.from_arrow(self.tables[name].schema)
        return dict({"kind": "parquet_scan",
                     "schema": schema_to_dict(schema),
                     "file_groups": self.paths[name]}, **extra)

    def date_join(self, probe: dict, key: dict, jt: str = "inner",
                  dim: dict = None) -> dict:
        return join(self.ids, "broadcast_join", probe,
                    dim or self.scan("dim"), [key], [c("d_sk")], jt=jt)


def _not_null(col: dict) -> dict:
    return {"kind": "is_not_null", "child": col}


# a case: (fact and dimension tables, a plan over them, the row groups of
# the fact table's 32 that the device path leaves undecoded)

def agg_range_filter(root):
    """Dates 300-450 lie in file 1 and the head of file 2: the cold map
    tasks read nothing and the reduce side still answers."""
    case = Case(root, _fact(), _dim(0, 1))
    src = filter_(case.scan("fact"), binop(">=", c("date"), lit(300)),
                  binop("<=", c("date"), lit(450)))
    return partial_final(case.ids, src, [(c("k"), "k")],
                         [("sum", "s", [c("v")])], 4), 26


def agg_filter_by_ordinal(root):
    """A projected scan numbers `date` 1 where the file has it at 0, and
    the filter names no column."""
    case = Case(root, _fact(), _dim(0, 1))
    src = filter_(case.scan("fact", projection=["v", "date", "k"]),
                  binop(">=", ci(1), lit(300)), binop("<=", ci(1), lit(450)))
    return partial_final(case.ids, src, [(ci(2), "k")],
                         [("sum", "s", [ci(0)])], 4), 26


def agg_every_partition_pruned(root):
    case = Case(root, _fact(), _dim(0, 1))
    src = filter_(case.scan("fact"), binop(">", c("date"), lit(DATES + 5)))
    return partial_final(case.ids, src, [(c("k"), "k")],
                         [("sum", "s", [c("v")])], 4), 32


def join_probes_scan(root):
    case = Case(root, _fact(), _dim(300, 400))
    return case.date_join(case.scan("fact"), c("date")), 28


def join_probes_filter_over_projected_scan(root):
    """q51's and q67's shape: `is_not_null` conjuncts between the date
    join and a projected scan."""
    case = Case(root, _fact(null_dates=True), _dim(300, 400))
    sales = filter_(case.scan("fact", projection=["v", "date"]),
                    _not_null(c("date")), _not_null(c("v")))
    joined = case.date_join(sales, c("date"))
    return project(joined, [c("v"), c("d_tag")], ["v", "tag"]), 28


def join_project_between(root):
    case = Case(root, _fact(), _dim(300, 400))
    narrowed = project(case.scan("fact"), [c("date"), c("v")], ["date", "v"])
    return case.date_join(narrowed, c("date")), 0


def join_probe_side_semi(root):
    case = Case(root, _fact(), _dim(300, 400))
    return case.date_join(case.scan("fact"), c("date"), jt="left_semi"), 28


def join_left_outer(root):
    case = Case(root, _fact(), _dim(300, 400))
    return case.date_join(case.scan("fact"), c("date"), jt="left"), 0


def join_anti(root):
    case = Case(root, _fact(), _dim(300, 400))
    return case.date_join(case.scan("fact"), c("date"), jt="left_anti"), 0


def join_file_without_statistics(root):
    """File 1 holds the build side's range and has no statistics: its
    eight groups are read, the other files' pruned."""
    case = Case(root, _fact(), _dim(300, 400), no_stats_file=1)
    return case.date_join(case.scan("fact"), c("date")), 24


def join_null_keys_either_side(root):
    case = Case(root, _fact(null_dates=True), _dim(300, 400, null_keys=True))
    return case.date_join(case.scan("fact"), c("date")), 28


def join_key_is_an_expression(root):
    case = Case(root, _fact(), _dim(300, 400))
    key = binop("+", c("date"), lit(0))
    return case.date_join(case.scan("fact"), key), 0


def join_empty_build_side(root):
    case = Case(root, _fact(), _dim(300, 400))
    # (a condition statistics cannot decide: the dimension is read)
    nothing = filter_(case.scan("dim"),
                      binop("<", binop("+", c("d_sk"), lit(0)), lit(0)))
    return case.date_join(case.scan("fact"), c("date"), dim=nothing), 32


CASES = [agg_range_filter, agg_filter_by_ordinal, agg_every_partition_pruned,
         join_probes_scan, join_probes_filter_over_projected_scan,
         join_project_between, join_probe_side_semi, join_left_outer,
         join_anti, join_file_without_statistics, join_null_keys_either_side,
         join_key_is_an_expression, join_empty_build_side]


@pytest.fixture
def device_path(monkeypatch):
    """Batches on the devices, every plan staged, one chip's mesh."""
    import blaze_tpu.bridge.placement as P
    monkeypatch.setattr(P, "host_resident", lambda: False)
    keys = {config.DAG_SINGLE_TASK_BYTES.key: 0, config.MESH_DEVICES.key: 1}
    for key, value in keys.items():
        config.conf.set(key, value)
    try:
        yield
    finally:
        for key in list(keys) + [config.PARQUET_ENABLE_PAGE_FILTERING.key]:
            config.conf.unset(key)


def _run(plan: dict, page_filtering: bool):
    config.conf.set(config.PARQUET_ENABLE_PAGE_FILTERING.key, page_filtering)
    before = xla_stats.snapshot()
    with DagScheduler() as sched:
        got = sched.run_collect(plan)
    delta = xla_stats.delta(before)
    rows = sorted(zip(*(got.column(i).to_pylist()
                        for i in range(got.num_columns))),
                  key=lambda r: tuple((v is None, v) for v in r))
    return got.schema, rows, delta


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__)
def test_device_path_prunes_by_its_consumers_condition(case, tmp_path,
                                                       device_path):
    plan, pruned = case(str(tmp_path))
    schema, rows, delta = _run(plan, True)
    # the pruned run first: whatever the second run finds cached (a
    # broadcast map) was built by a read that pruned
    want_schema, want_rows, whole = _run(plan, False)
    assert schema == want_schema
    assert rows == want_rows
    assert whole["scan_row_groups_pruned"] == 0
    assert delta["scan_row_groups_pruned"] == pruned
    assert delta["chip0_scan_row_groups_pruned"] == pruned
    fact_groups = FILES * FILE_ROWS // GROUP_ROWS
    assert delta["scan_row_groups"] >= fact_groups
    if pruned:
        assert delta["h2d_bytes"] < whole["h2d_bytes"]
    if case is not agg_every_partition_pruned \
            and case is not join_empty_build_side:
        assert rows


@pytest.mark.parametrize("case", [join_probes_scan,
                                  join_probes_filter_over_projected_scan,
                                  join_probe_side_semi, join_left_outer,
                                  join_key_is_an_expression,
                                  join_empty_build_side],
                         ids=lambda f: f.__name__)
def test_host_path_prunes_the_joins_the_device_path_prunes(case, tmp_path):
    """One derivation of the build side's key ranges, one reach: under
    host placement (Acero's lane collects the probe as Arrow) the same
    joins leave the same row groups unread."""
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    try:
        plan, pruned = case(str(tmp_path))
        schema, rows, delta = _run(plan, True)
        want_schema, want_rows, whole = _run(plan, False)
    finally:
        for key in (config.DAG_SINGLE_TASK_BYTES.key,
                    config.PARQUET_ENABLE_PAGE_FILTERING.key):
            config.conf.unset(key)
    assert (schema, rows) == (want_schema, want_rows)
    assert whole["scan_row_groups_pruned"] == 0
    assert delta["scan_row_groups_pruned"] == pruned
    assert delta["join_probe_device_rows"] == 0


@pytest.mark.parametrize("case", [agg_range_filter,
                                  agg_every_partition_pruned],
                         ids=lambda f: f.__name__)
def test_a_pruned_map_task_is_still_a_task_on_its_chip(case, tmp_path,
                                                       device_path):
    """Over four chips the exchange takes a map task without a batch as
    it takes an empty split: partition p on chip p mod 4, no fallback."""
    config.conf.set(config.MESH_DEVICES.key, 4)
    plan, pruned = case(str(tmp_path))
    _schema, rows, delta = _run(plan, True)
    assert rows == _run(plan, False)[1]
    assert delta["scan_row_groups_pruned"] == pruned
    assert delta["shuffle_device_fallbacks"] == 0
    assert delta["cross_chip_bytes"] == 0
    assert delta["placed_tasks_off_chip0"] == 6
    by_chip = [delta[f"chip{chip}_scan_row_groups"] for chip in range(4)]
    assert by_chip == [FILE_ROWS // GROUP_ROWS] * 4


def test_counters_start_at_zero_and_show_in_the_footer():
    xla_stats.reset()
    stats = xla_stats.pipeline_stats()
    assert stats["scan_row_groups"] == stats["scan_row_groups_pruned"] == 0
    xla_stats.note_scan_groups(2, 11, 5)
    assert xla_stats.chip_stats()[2]["scan_row_groups"] == 11
    assert xla_stats.chip_stats()[2]["scan_row_groups_pruned"] == 5
    from blaze_tpu.plan.explain import QueryProfile
    from blaze_tpu.bridge.metrics import MetricNode
    text = QueryProfile(query_id="q", wall_ns=1, tree=MetricNode(),
                        partitions=1, exec_mode="staged",
                        xla=xla_stats.snapshot()).render_text()
    assert "scan: groups=11 pruned=5" in text
    xla_stats.reset()
    assert xla_stats.pipeline_stats()["scan_row_groups"] == 0


def test_produce_span_carries_row_groups_and_pruned(tmp_path):
    from blaze_tpu.bridge import tracing
    from blaze_tpu.exprs import col
    from blaze_tpu.exprs.base import Literal
    from blaze_tpu.exprs.binary import BinaryExpr
    from blaze_tpu.ops.scan import ParquetScanExec
    from blaze_tpu.schema import INT64
    fact = _fact()
    path = os.path.join(str(tmp_path), "f.parquet")
    pq.write_table(fact.slice(0, FILE_ROWS), path, row_group_size=GROUP_ROWS)
    scan = ParquetScanExec(Schema.from_arrow(fact.schema), [[path]],
                           projection=["v", "date"])
    hi = int(fact["date"][FILE_ROWS - 1].as_py())
    # by ordinal alone, in the projected scan's numbering
    pred = BinaryExpr(">", col(1), Literal(hi, INT64))
    tracing.start_tracing()
    try:
        assert not list(scan.execute(0, extra_prune=pred))
    finally:
        spans = tracing.stop_tracing()
    produce = [s for s in spans if s["name"] == "produce:parquet_scan"]
    assert sum(s["attrs"].get("row_groups", 0) for s in produce) == 8
    assert sum(s["attrs"].get("pruned", 0) for s in produce) == 8
    assert scan.metrics.values["pruned_row_groups"] == 8


# -- the predicate itself ---------------------------------------------------

def _prune_exprs():
    from blaze_tpu.exprs import col
    from blaze_tpu.exprs.base import Literal
    from blaze_tpu.exprs.binary import BinaryExpr
    from blaze_tpu.exprs.conditional import InList, IsNotNull
    from blaze_tpu.schema import INT64
    return col, (lambda v: Literal(v, INT64)), BinaryExpr, InList, IsNotNull


BY_NAME = [
    # (predicate over the OUTPUT schema [b, a], what is left, named)
    ("a reference without a name takes its ordinal's",
     lambda col, lit, B, In, NN: B(">=", col(1), lit(3)), "(#1(a) >= lit(3))"),
    ("a name that contradicts the ordinal loses",
     lambda col, lit, B, In, NN: B("<", col(0, "a"), lit(3)),
     "(#0(b) < lit(3))"),
    ("a conjunct statistics cannot decide is dropped",
     lambda col, lit, B, In, NN: B(
         "and", B("<", B("+", col(0), lit(1)), lit(3)),
         B("==", lit(4), col(1))), "(lit(4) == #1(a))"),
    ("a disjunction with an undecidable side says nothing",
     lambda col, lit, B, In, NN: B(
         "or", B("<", B("+", col(0), lit(1)), lit(3)),
         B("==", col(1), lit(4))), None),
    ("NOT IN proves nothing",
     lambda col, lit, B, In, NN: In(col(1), (1, 2), negated=True), None),
    ("IN and IS NOT NULL pass",
     lambda col, lit, B, In, NN: B("and", In(col(1), (1, 2)), NN(col(0))),
     None),
    ("an ordinal past the schema is no column",
     lambda col, lit, B, In, NN: B(">", col(7), lit(0)), None),
]


@pytest.mark.parametrize("what, make, want", BY_NAME,
                         ids=[c[0] for c in BY_NAME])
def test_by_name_keeps_what_statistics_decide(what, make, want):
    from blaze_tpu.ops.pruning import by_name
    from blaze_tpu.schema import INT64, Field
    out = Schema([Field("b", INT64), Field("a", INT64)])
    got = by_name(make(*_prune_exprs()), out)
    if what.startswith("IN and"):
        assert [type(e).__name__ for e in (got.left, got.right)] \
            == ["InList", "IsNotNull"]
        assert (got.left.child.name, got.right.child.name) == ("a", "b")
    else:
        assert (repr(got) if got is not None else None) == want


def test_a_float_literal_meets_decimal_statistics_as_its_decimal(tmp_path):
    """`x <= 1.2` over a decimal column whose group starts at 1.20: the
    double nearest to 1.2 lies under 1.20, the literal does not."""
    import decimal
    from blaze_tpu.exprs import col
    from blaze_tpu.exprs.base import Literal
    from blaze_tpu.exprs.binary import BinaryExpr
    from blaze_tpu.ops.pruning import prune_with_stats
    from blaze_tpu.schema import DataType
    values = [decimal.Decimal("1.20"), decimal.Decimal("3.50")]
    t = pa.table({"x": pa.array(values, pa.decimal128(7, 2))})
    path = os.path.join(str(tmp_path), "d.parquet")
    pq.write_table(t, path)
    md = pq.ParquetFile(path).metadata
    schema = Schema.from_arrow(t.schema)
    dec = schema[0].data_type
    assert isinstance(dec, DataType)
    for op, value, kept in (("<=", 1.2, [0]), ("<", 1.2, []),
                            (">=", 3.5, [0]), (">", 3.5, []),
                            ("==", 1.2, [0]), ("==", 0.7, [])):
        pred = BinaryExpr(op, col(0, "x"), Literal(value, dec))
        assert prune_with_stats(md, schema, pred, [0]) == kept, (op, value)


def test_is_null_keeps_a_group_whose_writer_left_null_count_out():
    """Statistics with min and max but no `null_count` (a writer may omit
    it) do not say the group has no NULL: `is_null(x)` reads it, while a
    group that counts 0 nulls is pruned."""
    from types import SimpleNamespace as NS
    from blaze_tpu.exprs import col
    from blaze_tpu.exprs.conditional import IsNull
    from blaze_tpu.ops.pruning import prune_with_stats
    from blaze_tpu.schema import INT64, Field

    def group(null_count):
        stats = NS(has_min_max=True, min=1, max=9, null_count=null_count)
        return NS(column=lambda ci: NS(statistics=stats))

    groups = [group(None), group(0), group(3)]
    names = [NS(name="x")]
    file_schema = type("FileSchema", (), {
        "column": staticmethod(lambda i: names[i]),
        "__len__": lambda self: len(names)})()
    md = NS(schema=file_schema, row_group=lambda g: groups[g])
    schema = Schema([Field("x", INT64)])
    assert prune_with_stats(md, schema, IsNull(col(0, "x")),
                            [0, 1, 2]) == [0, 2]

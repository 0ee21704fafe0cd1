"""The benchmark's q01 over decimal(7,2) amounts
(benchmark/queries/q01_dec.py on benchmark/data/tpcds_decimal.py through
benchmark/entries/dag_scheduler_dec.py) at scale 0.05: the generator's
promises, the plan and its full answer through `DagScheduler` on the device
path against the oracles, value for value and type for type, the counters
the cell's entry demands, and the float twin's path untouched: q01's float
plan at the same scale asks for the programs and reads the counters it did
before this configuration existed."""

import importlib.util
import os
import sys

import numpy as np
import pyarrow as pa
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402
from blaze_tpu import config  # noqa: E402
from blaze_tpu.bridge import xla_stats  # noqa: E402
from blaze_tpu.plan.stages import DagScheduler  # noqa: E402

SCALE, DATA_SEED, SPLITS, PARTITIONS = 0.05, 20260927, 4, 4
SEED = 2_900_000_123
DECIMAL_COUNTERS = ("stage_loop_decimal_rows", "agg_decimal_rows_host",
                    "decimal_overflow_groups", "expr_decimal_device_batches",
                    "expr_decimal_host_batches", "host_evictions_decimal",
                    "decimal_scaled_int32_dispatches",
                    "decimal_scaled_int64_dispatches",
                    "decimal_limb_dispatches")
# q01's float plan at this scale, a task at a time, as the parent commit
# (e20583a) ran it (each broadcast map built once: 25 fused and 3 eager
# expression batches and 9 string evictions, where tasks that arrive
# together build a map each and read up to 91, 6 and 21), and the three
# programs of `SortExec`'s resident lane (PR 42: the merge join's 2,373-row
# side stays on the device while it is sorted).  Since PR 48 strings are
# dictionary columns by default: the expression programs' names carry the
# encoding (new digests), and the customer join's utf8 payload
# (`c_customer_id`) rides the device probe as codes, so no probe row goes
# through the host's pair expansion and its 179 rows are laid by the tile
# lane.  Since PR 49 the date join hands its build side's key range to the
# fact scan beneath it: the row groups (4,096 rows here) that hold no date
# of the year are never read and 14,374 fewer rows reach the probe; and the
# `d_year = 2000` filter prunes `date_dim`'s own scan, which lies in date
# order too: 19 fewer batches through its expression program
FLOAT_PROGRAMS = [
    "coalesce.lay", "coalesce.tail",
    "expr_program_5624fbfe8f85", "expr_program_79fd57fed3c8",
    "expr_program_a67b25f53eed", "expr_program_d460fd360439",
    "join.hash_valid",
    "join.probe_gather", "mesh.exchange_rows", "runtime.stage_loop",
    "runtime.stage_loop_window", "smj.bounds", "smj.expand_pairs",
    "smj.gather", "sort.pass", "sort.assemble", "sort.digits", "sort.gather"]
FLOAT_COUNTERS = {
    "stage_loop_tasks": 16, "stage_loop_rows": 11718,
    "stage_loop_lanes": 16384, "stage_loop_calls": 12,
    "stage_loop_windows": 12, "stage_loop_windows_fused": 12,
    "stage_loop_full_rounds": 18, "stage_loop_final_slots": 4194304,
    "expr_fused_batches": 6, "expr_eager_batches": 3,
    "join_probe_device_rows": 15699, "join_probe_host_rows": 0,
    "smj_device_rows": 2876, "smj_device_pairs": 2864,
    "sort_device_rows": 2373, "sort_resident_rows": 2373,
    "shuffle_device_exchanges": 4,
    "shuffle_device_rows": 11524, "host_evictions_string": 9,
    "chip0_tasks": 21}


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}",
        os.path.join(ROOT, "benchmark", kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def gen():
    return _load("data", "tpcds_decimal")


@pytest.fixture(scope="module")
def q():
    return _load("queries", "q01_dec")


@pytest.fixture(scope="module")
def tables(gen, q):
    return gen.make_tables(q.TABLES, SCALE, DATA_SEED, SPLITS, SEED)


@pytest.fixture
def device_path(monkeypatch):
    """Batches on the devices, every plan staged, one chip's mesh: the
    cell's deployment as the CPU can rehearse it."""
    import blaze_tpu.bridge.placement as P
    monkeypatch.setattr(P, "host_resident", lambda: False)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    config.conf.set(config.MESH_DEVICES.key, 1)
    try:
        yield
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)
        config.conf.unset(config.MESH_DEVICES.key)


def collect(plan, **scheduler):
    before = xla_stats.snapshot()
    with DagScheduler(**scheduler) as sched:
        got = sched.run_collect(plan)
        assert sched.exec_mode == "staged"
    return got, xla_stats.delta(before)


# -- the generator ----------------------------------------------------------

def test_every_amount_is_decimal_7_2_of_the_float_generators_cents(gen, q,
                                                                   tables):
    floats = _load("data", "tpcds_data").make_tables(
        q.TABLES, SCALE, DATA_SEED, SPLITS, SEED)
    money = 0
    for name, t in tables.items():
        f = floats[name]
        assert t.num_rows == f.num_rows and t.schema.names == f.schema.names
        for col in t.schema.names:
            if not pa.types.is_float64(f.schema.field(col).type):
                assert t[col].equals(f[col]), (name, col)
                continue
            money += 1
            assert t.schema.field(col).type == pa.decimal128(7, 2)
            cents = np.asarray([int(v.scaleb(2)) for v in
                                t[col].to_pylist()])
            assert (cents == np.rint(f[col].to_numpy() * 100)).all()
    assert money == 2   # sr_return_amt, sr_net_loss
    assert not any(pa.types.is_floating(f.type)
                   for t in tables.values() for f in t.schema)


def test_seed_changes_order_and_no_value(gen, q, tables):
    other = gen.make_tables(q.TABLES, SCALE, DATA_SEED, SPLITS, 7)
    a, b = (t["store_returns"].to_pandas() for t in (tables, other))
    assert not a.equals(b)
    cols = list(a.columns)
    assert a.sort_values(cols).reset_index(drop=True).equals(
        b.sort_values(cols).reset_index(drop=True))
    redrawn = gen.make_tables(q.TABLES, SCALE, DATA_SEED + 1, SPLITS, SEED)
    assert not redrawn["store_returns"].equals(tables["store_returns"])


def test_the_files_hold_decimals_as_spark_writes_them(gen, tables,
                                                      tmp_path):
    import pyarrow.parquet as pq
    paths = gen.write_parquet_splits(tables, str(tmp_path), SPLITS, 4096)
    assert [len(g) for g in paths["store_returns"]] == [1] * SPLITS
    md = pq.ParquetFile(paths["store_returns"][0][0])
    col = md.schema.column(md.schema.names.index("sr_return_amt"))
    assert col.physical_type == "INT32"
    assert str(col.logical_type) == "Decimal(precision=7, scale=2)"
    back = pa.concat_tables(pq.read_table(g[0])
                            for g in paths["store_returns"])
    assert back.equals(tables["store_returns"])


# -- the oracles -------------------------------------------------------------

def test_the_oracles_call_nothing_of_the_program(q):
    import inspect
    src = inspect.getsource(q)
    assert "import blaze_tpu" not in src and "from blaze_tpu" not in src
    # the exact path: Python integers alone, no float and no numpy math
    for fn in (q._bounded, q._div_half_up, q._totals, q._passing):
        body = inspect.getsource(fn)
        assert "float" not in body and "np." not in body, fn.__name__
    assert q._div_half_up(-5, 2) == -3 and q._div_half_up(5, 2) == 3
    assert q._bounded(10 ** 17, q.TOTAL) is None
    assert q._bounded(-(10 ** 17) + 1, q.TOTAL) == -(10 ** 17) + 1


def test_the_full_answer_in_float32_differs_and_the_first_100_ids_do_not(
        q, tables):
    """Why the cell compares the full answer: money held and summed in
    float32 gives the same 100 ids and other totals and thresholds."""
    ok, _ = check.verdict(check.compare(
        q.oracle(tables, money=np.float32), q.oracle(tables), q.KEYS,
        q.ORDERED))
    assert ok
    want = q.full_oracle(tables)
    assert want.num_rows > 1000
    nums = check.compare(q.full_oracle(tables, money=np.float32), want,
                         q.FULL_KEYS, False)
    held, line = check.verdict(nums)
    assert not held, line
    # the oracle's columns are decimals, so the comparison is exact: a
    # float that is not the decimal's own value is a mismatch
    assert nums["row_count_diff"] == nums["key_mismatches"] == 0
    assert nums["exact_value_mismatches"] > 1000
    # float64 in the decimals' place is told by its values too (a sum of
    # doubles is not the sum of the cents, bit for bit), and by the schema
    as_double = q.full_oracle(tables, money=np.float64)
    assert not check.verdict(check.compare(as_double, want, q.FULL_KEYS,
                                           False))[0]
    assert as_double.schema.types != want.schema.types
    assert want.schema.types == [pa.int64(), pa.int64(),
                                 pa.decimal128(17, 2), pa.decimal128(24, 7)]


def test_a_lost_split_fails_the_answer(q, tables):
    from benchmark.controls import lost_split
    got = q.oracle(lost_split(tables, q.FACT, SPLITS))
    assert not check.verdict(check.compare(got, q.oracle(tables), q.KEYS,
                                           q.ORDERED))[0]


# -- the plan through the scheduler, on the device path -------------------------

@pytest.fixture
def paths(gen, tables, tmp_path):
    return gen.write_parquet_splits(tables, str(tmp_path / "t"), SPLITS,
                                    4096)


def test_the_answer_and_the_full_answer_equal_the_oracles(
        q, tables, paths, device_path):
    got, d = collect(q.plan(paths, tables, PARTITIONS))
    ok, line = check.verdict(check.compare(got, q.oracle(tables), q.KEYS,
                                           q.ORDERED))
    assert ok and got.num_rows == 100, line
    # both sums (twice: the plan has the subquery twice) and the average's
    # partial aggregation fold in the stage loop: the float plan's 16
    # tasks and four more, where it runs an eager AggExec over 2.8K rows
    assert d["stage_loop_tasks"] == FLOAT_COUNTERS["stage_loop_tasks"] + 4
    assert d["stage_loop_fallbacks"] == 0
    assert d["stage_loop_decimal_rows"] == d["stage_loop_rows"] \
        == FLOAT_COUNTERS["stage_loop_rows"] + 2864
    # outside the loop: the 12 stores' final average alone
    assert d["agg_decimal_rows_host"] == 12
    assert d["host_evictions_decimal"] == 0
    assert d["decimal_overflow_groups"] == 0
    # the filter's multiply and compare ran inside a device program
    assert d["expr_decimal_device_batches"] > 0
    assert d["expr_decimal_host_batches"] == 0
    assert d["smj_device_pairs"] == FLOAT_COUNTERS["smj_device_pairs"]
    assert d["smj_streamed_runs"] == 0

    want = q.full_oracle(tables)
    full, _ = collect(q.plan_full(paths, tables, PARTITIONS))
    assert full.schema.types == want.schema.types
    assert full.schema.names == want.schema.names
    nums = check.compare(full, want, q.FULL_KEYS, False)
    assert check.verdict(nums)[0], nums
    assert full.num_rows == want.num_rows == 1143


def test_the_entry_holds_a_run_to_the_cells_conditions(
        q, tables, paths, device_path, tmp_path, monkeypatch):
    entry_mod = _load("entries", "dag_scheduler_dec")
    entry = entry_mod.Entry(q, paths, tables, {"partitions": PARTITIONS},
                            str(tmp_path))
    entry.begin()
    got = entry.run()
    entry.end()
    assert got.num_rows == 100
    assert entry.problem() is None
    # an answer of other types is a problem, whatever its values
    plan, want = entry.full
    entry.full = (plan, want.cast(pa.schema(
        [want.schema.field(0), want.schema.field(1),
         pa.field("ctr_total_return", pa.decimal128(18, 2)),
         want.schema.field(3)])))
    assert "types" in entry.problem()
    entry.full = (plan, want)
    # a run whose aggregations left the loop is a problem
    entry._moved = dict(entry._moved, agg_decimal_rows_host=5000)
    assert "outside the stage loop" in entry.problem()
    entry._moved = dict(entry._moved, agg_decimal_rows_host=12,
                        stage_loop_decimal_rows=0)
    assert "no stage-loop task" in entry.problem()


def test_the_entry_refuses_a_program_that_declines_decimal_sums(
        q, tables, paths, tmp_path, monkeypatch):
    """The parent commit's `_try_fuse_agg`: a sum over a decimal is no
    fused aggregation.  The constructor says so before any query."""
    import blaze_tpu.plan.fused as fused
    monkeypatch.setattr(fused, "_decimal_lane", lambda t: False)
    entry_mod = _load("entries", "dag_scheduler_dec")
    with pytest.raises(RuntimeError, match="AggExec, not as a fused"):
        entry_mod.Entry(q, paths, tables, {"partitions": PARTITIONS},
                        str(tmp_path))


def test_the_cells_manifest_entries():
    from benchmark.manifest import Cell
    cell = Cell("sf10_q01_dec_x1", ROOT)
    twin = Cell("sf10_q01_x1", ROOT)
    assert cell.chips == 1 and cell.config["generator"] == "tpcds_decimal"
    for key in ("scale", "data_seed", "tables", "splits", "partitions",
                "row_group_rows", "chips", "program_settings",
                "agg_table_slots"):
        assert cell.config[key] == twin.config[key], key
    assert cell.config["reduced"] == twin.config["reduced"]
    assert cell.config["program_settings"] == {}
    assert cell.traffic["query"] == "q01_dec"
    assert cell.traffic["entry"] == "dag_scheduler_dec"
    mine = [m["name"] for m, _spec in cell.layer_metrics()
            if m.get("workloads") == ["sf10_q01_dec_x1"]]
    assert mine == ["dec_fold_rows_share", "dec_host_evictions",
                    "dec_expr_device_share", "dec_idle_host_eval_s",
                    "dec_expr_eager_share"]
    assert {m["name"] for m in cell.end_to_end()} == {"query_wall_s",
                                                      "setup_s"}


# -- the float twin's path is the parent's --------------------------------------

def test_q01s_float_plan_asks_for_the_programs_it_did(device_path,
                                                      tmp_path):
    float_gen, q01 = _load("data", "tpcds_data"), _load("queries", "q01")
    tables = float_gen.make_tables(q01.TABLES, SCALE, DATA_SEED, SPLITS,
                                   SEED)
    paths = float_gen.write_parquet_splits(tables, str(tmp_path), SPLITS,
                                           4096)
    known = set(xla_stats.compile_report()["kernels"])
    # a task at a time: tasks that arrive together at a broadcast join each
    # build its map (`bridge/resource.get_or_create` runs the factory
    # outside its lock), so how often a build side's expressions run is
    # the threads' timing; one after another each map is built once
    got, d = collect(q01.plan(paths, tables, PARTITIONS),
                     max_task_parallelism=1)
    assert check.verdict(check.compare(got, q01.oracle(tables), q01.KEYS,
                                       q01.ORDERED))[0]
    # every program the query asked for has the name it had (an expression
    # program's name is a digest of what it computes and over what), and it
    # asked for no other
    now = set(xla_stats.compile_report()["kernels"])
    assert set(FLOAT_PROGRAMS) <= now
    assert now - known <= set(FLOAT_PROGRAMS), sorted(now - known)
    assert {k: d[k] for k in FLOAT_COUNTERS} == FLOAT_COUNTERS
    assert {k: d[k] for k in DECIMAL_COUNTERS} \
        == dict.fromkeys(DECIMAL_COUNTERS, 0)

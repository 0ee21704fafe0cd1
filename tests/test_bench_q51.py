"""The benchmark's q51 (benchmark/queries/q51.py on
benchmark/data/tpcds_web.py through
benchmark/entries/dag_scheduler_window.py) at scale 0.05: the generator's
promises, the oracle's controls, the plan and its full answer through
`DagScheduler` on the device path with every window on the chip, what the
entry refuses and says, the manifest's new entries and their readers, and
that a plan without a window asks for the programs it did."""

import importlib.util
import json
import os
import shutil
import sys
import time

import jax
import numpy as np
import pyarrow as pa
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402
from benchmark.manifest import Cell, load_json  # noqa: E402
from blaze_tpu import config  # noqa: E402
from blaze_tpu.bridge import xla_stats  # noqa: E402
from blaze_tpu.plan.stages import DagScheduler  # noqa: E402

SCALE, DATA_SEED, SPLITS, PARTITIONS = 0.05, 20260927, 4, 4
SEED = 2_900_000_123
CELL, CONFIG = "sf1_q51_x1", "tpcds-sf1-window-x1"
NEW = ("window_device_s", "window_roofline", "window_resident_share",
       "idle_window_s", "q51_sort_device_s", "q51_sort_resident_share",
       "q51_smj_device_s", "q51_smj_streamed_runs",
       "q51_probe_gather_device_s", "q51_join_direct_probe_share",
       "q51_join_device_probe_share", "q51_scan_decode_s", "q51_idle_h2d_s",
       "q51_idle_d2h_s", "q51_idle_prefetch_wait_s", "q51_idle_task_other_s",
       "q51_expr_eager_share")
# the accepted entries name their cells inside themselves: same `read`
# blocks in new files
TWINS = {name: name[len("q51_"):] for name in NEW if name.startswith("q51_")}


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}",
        os.path.join(ROOT, "benchmark", kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def gen():
    return _load("data", "tpcds_web")


@pytest.fixture(scope="module")
def q():
    return _load("queries", "q51")


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


@pytest.fixture
def paths(gen, tables, tmp_path):
    return gen.write_parquet_splits(tables, str(tmp_path / "t"), SPLITS,
                                    4096)


def collect(plan, **scheduler):
    before = xla_stats.snapshot()
    with DagScheduler(**scheduler) as sched:
        got = sched.run_collect(plan)
        assert sched.exec_mode == "staged"
    return got, xla_stats.delta(before)


# -- the generator ----------------------------------------------------------

def test_the_store_tables_are_the_float_generators_row_for_row(gen):
    base = _load("data", "tpcds_data")
    for name in ("store_sales", "item"):
        mine = gen.make_tables([name], 0.01, DATA_SEED, SPLITS, SEED)[name]
        theirs = base.make_tables([name], 0.01, DATA_SEED, SPLITS,
                                  SEED)[name]
        assert mine.equals(theirs)
    with pytest.raises(KeyError, match="tpcds_web makes"):
        gen.make_tables(["store_returns"], 0.01, DATA_SEED, SPLITS, SEED)


def test_web_sales_and_the_calendar(gen, tables):
    assert gen.rows("web_sales", 1.0) == 719_384
    ws = tables["web_sales"].to_pandas()
    assert len(ws) == gen.rows("web_sales", SCALE) == 35_969
    assert list(ws.columns) == [
        "ws_sold_date_sk", "ws_item_sk", "ws_bill_customer_sk",
        "ws_order_number", "ws_quantity", "ws_sales_price",
        "ws_ext_sales_price", "ws_net_profit"]
    assert 0.015 < ws.ws_sold_date_sk.isna().mean() < 0.025
    assert not ws.drop(columns="ws_sold_date_sk").isna().any().any()
    dates = ws.ws_sold_date_sk.dropna()
    assert dates.min() >= gen.D0
    assert dates.max() < gen.D0 + gen.SALES_DATE_DAYS
    assert ws.ws_item_sk.between(1, gen.rows("item", SCALE)).all()
    assert ws.groupby("ws_order_number").size().iloc[:-1].between(8, 16).all()
    assert tables["web_sales"].schema.field("ws_sales_price").type \
        == pa.float64()
    # date order inside each file's blocks (NULL dates last)
    per = -(-len(ws) // SPLITS)
    first = ws.ws_sold_date_sk.to_numpy()[:per]
    blocks = [first[i:i + gen.SEED_BLOCK_ROWS]
              for i in range(0, len(first), gen.SEED_BLOCK_ROWS)]
    assert all(np.nanmax(x) <= np.nanmin(y)
               for x, y in zip(blocks, blocks[1:])
               if not np.isnan(y).all())

    dd = tables["date_dim"].to_pandas().sort_values("d_date_sk")
    assert len(dd) == 73_049
    assert tables["date_dim"].schema.field("d_date").type == pa.date32()
    assert (dd.d_month_seq == (dd.d_year - 1900) * 12 + dd.d_moy - 1).all()
    year = dd[(dd.d_month_seq >= 1200) & (dd.d_month_seq <= 1211)]
    assert len(year) == 365 and (year.d_year == 2000).all()
    assert (year.d_date_sk - gen.D0).tolist() == list(range(730, 1095))
    assert str(dd.d_date.iloc[0]) == "1998-01-01"
    day = dd.d_date.map(lambda x: x.toordinal()).to_numpy()
    assert (np.diff(day) == 1).all()        # the calendar has no gap
    # the year is a fifth of the facts' 1,826 days
    assert 0.18 < dates.between(year.d_date_sk.min(),
                                year.d_date_sk.max()).mean() < 0.22


def test_seed_changes_order_and_no_value(gen, q, tables):
    other = gen.make_tables(q.TABLES, SCALE, DATA_SEED, SPLITS, 7)
    for name in ("web_sales", "store_sales"):
        a, b = tables[name].to_pandas(), other[name].to_pandas()
        assert not a.equals(b)
        cols = list(a.columns)
        assert a.sort_values(cols).reset_index(drop=True).equals(
            b.sort_values(cols).reset_index(drop=True))
    assert tables["date_dim"].sort_by("d_date_sk").equals(
        other["date_dim"].sort_by("d_date_sk"))


# -- the oracle and its controls ------------------------------------------------

def test_the_oracles_call_nothing_of_the_program(q):
    import inspect
    src = inspect.getsource(q._view) + inspect.getsource(q.full_answer) \
        + inspect.getsource(q._table)
    assert "blaze_tpu" not in src


def test_the_answers_keys_are_unique_and_no_float_decides_a_row(q, tables):
    """q51.py's promises at this scale: (item, date) is unique in the full
    answer, so the order is by keys alone and ROWS = RANGE; and no row
    passes or fails `web_cumulative > store_cumulative` by less than
    1e-6."""
    full = q.full_answer(tables)
    assert len(full) > 100
    assert not full.duplicated(["item_sk", "d_date"]).any()
    assert not full.item_sk.isna().any() and not full.d_date.isna().any()
    assert (full.web_cumulative - full.store_cumulative).min() > 1e-6
    assert full.web_sales.isna().any() and full.store_sales.isna().any()
    for side in ("web", "store"):
        v = q._view(tables, side, np.float64)
        assert not v.duplicated(["item_sk", "d_date"]).any()


def test_money_in_float32_and_a_lost_split_fail_the_answer(q, tables):
    from benchmark.controls import control_answers
    want = q.oracle(tables)
    assert want.num_rows == 100
    for name, got in control_answers(q, tables, SPLITS).items():
        nums = check.compare(got, want, q.KEYS, q.ORDERED)
        assert not check.verdict(nums)[0], (name, nums)
    f32 = check.compare(q.full_oracle(tables, money=np.float32),
                        q.full_oracle(tables), q.KEYS, False)
    assert not check.verdict(f32)[0]


# -- the plan through the scheduler, on the device path -------------------------

def test_the_answer_and_the_full_answer_equal_the_oracles(
        q, tables, paths, device_path):
    got, d = collect(q.plan(paths, tables, PARTITIONS))
    ok, line = check.verdict(check.compare(got, q.oracle(tables), q.KEYS,
                                           q.ORDERED))
    assert ok and got.num_rows == 100, line
    assert got.schema.names == q.OUT
    assert got.schema.types == q.oracle(tables).schema.types
    # three window nodes, four partitions each, every row on the chip
    assert d["window_partitions"] == 12
    assert d["window_resident_rows"] == d["window_rows"] > 0
    assert d["window_scan_bytes"] > 0
    # the full outer join ran as device programs, every sort stayed
    assert d["smj_streamed_runs"] == 0 and d["smj_device_rows"] > 0
    assert d["sort_resident_rows"] == d["sort_device_rows"] > 0
    assert d["stage_loop_fallbacks"] == 0 and d["stage_loop_tasks"] == 16
    assert d["join_probe_host_rows"] == 0
    # the join's rows are both views' rows less the pairs on both sides
    web, store = (len(q._view(tables, s, np.float64))
                  for s in ("web", "store"))
    assert d["smj_device_rows"] == web + store
    third = d["smj_device_pairs"]
    assert max(web, store) < third < web + store
    assert d["window_rows"] == web + store + third

    want = q.full_oracle(tables)
    full, _ = collect(q.plan_full(paths, tables, PARTITIONS))
    nums = check.compare(full, want, q.KEYS, False)
    assert check.verdict(nums)[0], nums
    assert full.num_rows == want.num_rows > 100


def test_the_counter_is_window_min_bytes_of_each_node(q, tables, paths,
                                                      device_path):
    """The program's own count, `window_scan_bytes`, against
    `kernel_costs_window.window_min_bytes` at the widths the query file
    states (what `window_roofline` prices a run at): two sums (int64 item
    and date32 day read with their validity, a float64 argument read, a
    float64 result written) and the two maxima over the joined rows."""
    from benchmark.kernel_costs_window import window_min_bytes
    assert window_min_bytes(1000, 14, 9, 9) == 32_000
    assert (q.WINDOW_KEY_BYTES, q.WINDOW_ARG_BYTES,
            q.WINDOW_OUT_BYTES) == (14, 9, 9)
    _got, d = collect(q.plan_full(paths, tables, PARTITIONS))
    web, store = (len(q._view(tables, s, np.float64))
                  for s in ("web", "store"))
    third = d["window_rows"] - web - store

    def priced(rows, functions):
        return window_min_bytes(rows, q.WINDOW_KEY_BYTES,
                                functions * q.WINDOW_ARG_BYTES,
                                functions * q.WINDOW_OUT_BYTES)

    assert d["window_scan_bytes"] == priced(web + store, 1) + priced(third, 2)


def _entry(q, paths, tables, tmp_path):
    return _load("entries", "dag_scheduler_window").Entry(
        q, paths, tables, {"partitions": PARTITIONS}, str(tmp_path))


def test_the_entry_holds_a_run_to_the_cells_conditions(
        q, tables, paths, device_path, tmp_path):
    entry = _entry(q, paths, tables, tmp_path)
    entry.begin()
    got = entry.run()
    entry.end()
    assert got.num_rows == 100
    assert entry.problem() is None          # the full answer compared too
    moved = dict(entry._moved)
    entry._moved = dict(moved, window_resident_rows=moved["window_rows"] - 5)
    assert "5 of" in entry.problem() and "left the chip" in entry.problem()
    entry._moved = dict(moved, sort_resident_rows=0)
    assert "sort's host lane" in entry.problem()
    entry._moved = dict(moved, stage_loop_fallbacks=1)
    assert "left the stage loop" in entry.problem()
    entry._moved = dict(moved, window_rows=0, window_resident_rows=0)
    assert "no window node ran" in entry.problem()
    entry._moved = moved
    entry._streamed = 3
    assert "streamed 3 key runs" in entry.problem()
    entry._streamed = 0
    # a sum off by a cent's millionth part in the full answer
    plan, want = entry.full
    col = want.column("web_cumulative").to_numpy().copy()
    col[int(np.nanargmax(col))] *= 1 + 1e-8
    entry.full = (plan, want.set_column(4, "web_cumulative", [col]))
    why = entry.problem()
    assert why and "float_max_rel_err" in why and "EXCEEDED" in why


def test_the_entry_says_so_when_compute_is_on_the_host(q, tables, paths,
                                                       tmp_path):
    entry = _entry(q, paths, tables, tmp_path)
    entry._moved = dict.fromkeys(
        _load("entries", "dag_scheduler_window").WATCHED, 1)
    assert entry.problem() == "compute is placed on the host"


def test_the_entry_refuses_a_program_without_the_resident_lane(
        q, tables, paths, tmp_path, monkeypatch):
    """The parent commit's `WindowExec` has no device forms: the
    constructor says so before any query."""
    from blaze_tpu.ops.window import WindowExec
    monkeypatch.setattr(WindowExec, "_device_forms", lambda self: None)
    with pytest.raises(RuntimeError, match="no resident lane"):
        _entry(q, paths, tables, tmp_path)


# -- the manifest's new entries and their readers ---------------------------------

def test_the_cells_manifest_entries():
    cell = Cell(CELL, ROOT)
    twin = Cell("sf1_q93_x1", ROOT)
    assert cell.chips == 1 and cell.entry["config"] == CONFIG
    assert cell.config["generator"] == "tpcds_web"
    assert cell.config["guarantees"] == twin.config["guarantees"]
    for key in ("scale", "data_seed", "splits", "partitions",
                "row_group_rows", "chips", "program_settings",
                "agg_table_slots"):
        assert cell.config[key] == twin.config[key], key
    assert cell.config["tables"] == {"store_sales": 2_880_404,
                                     "web_sales": 719_384,
                                     "date_dim": 73_049}
    assert list(cell.config["reduced"]) == ["scale"]
    assert cell.traffic == dict(twin.traffic, query="q51",
                                entry="dag_scheduler_window",
                                why=cell.traffic["why"])
    for kind, name in (("data", "tpcds_web"), ("queries", "q51"),
                       ("entries", "dag_scheduler_window")):
        assert cell.module(kind, name) is not None
    entry, = [c for c in cell.manifest["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["scale"]
    assert entry["source"] == cell.config["source"]
    mine = [m for m, _spec in cell.layer_metrics()
            if m.get("workloads") == [CELL]]
    assert tuple(m["name"] for m in mine) == NEW
    assert all(m["moves"] == "query_wall_s" for m in mine)
    assert {m["name"] for m in cell.end_to_end()} == {"query_wall_s",
                                                      "setup_s"}
    # the twins read what the accepted metrics read
    specs = {m["name"]: spec for m, spec in cell.layer_metrics()}
    for name, of in TWINS.items():
        accepted = load_json(os.path.join(cell.bench_dir, "layer_metrics",
                                          f"{of}.json"))
        assert specs[name]["read"] == accepted["read"]
        assert specs[name]["source"] == accepted["source"]
    # and every source file is there
    for spec in specs.values():
        assert cell.module("sources", spec["source"]).read


def _read(cell, name, ctx):
    spec = load_json(os.path.join(cell.bench_dir, "layer_metrics",
                                  f"{name}.json"))
    return cell.module("sources", spec["source"]).read(spec, dict(ctx))


def test_the_new_readers_on_a_synthetic_trace(tmp_path):
    """Program seconds by name, the roofline from the scanned runs' spans
    at the query file's widths, the shares from counters; a program
    without the names, the spans or the counters (the parent) reads as
    absent."""
    cell = Cell(CELL, ROOT)
    q = _load("queries", "q51")
    peaks = load_json(os.path.join(cell.bench_dir, "peaks.json"))[
        "devices"]["TPU v5 lite"]
    programs = {"jit__segmented_scan__window_scan": 0.005,
                "jit_window_impl__runtime_stage_loop_window": 0.5,
                "jit_lsd_pass__sort_pass": 0.06,
                "jit__gather__smj_gather": 0.02,
                "jit_probe_gather__join_probe_gather": 0.08}
    counters = {"window_rows": 1000, "window_resident_rows": 900,
                "sort_device_rows": 50, "sort_resident_rows": 50,
                "smj_streamed_runs": 4, "join_probe_device_rows": 80,
                "join_probe_host_rows": 20, "join_probe_direct_rows": 60,
                "expr_eager_batches": 1, "expr_fused_batches": 3}

    def run(lane, rows, functions):
        return {"name": "window_device", "dur_ns": 1000,
                "attrs": {"lane": lane, "rows": rows, "partitions": 1,
                          "functions": functions}}

    # 10,000 x (14 + 9 + 9) + 10,900 x (14 + 18 + 18) = 865,000 bytes on
    # the chip; the host lane's run moves none of them
    spans = [run("resident", 10_000, 1), run("resident", 10_900, 2),
             run("host", 500, 2),
             {"name": "produce:parquet_scan", "dur_ns": 3_000_000_000,
              "attrs": {}}]
    ctx = {"trace": {"programs": programs}, "counters": counters,
           "queries": 2, "peaks": peaks, "spans": spans, "query": q}
    assert _read(cell, "window_device_s", ctx) == pytest.approx(0.0025)
    # 865,000 bytes in 5 ms against the HBM peak
    assert _read(cell, "window_roofline", ctx) == pytest.approx(
        100 * 865_000 / 0.005 / peaks["hbm_bytes_per_s"])
    assert _read(cell, "window_resident_share", ctx) == pytest.approx(90.0)
    assert _read(cell, "q51_sort_device_s", ctx) == pytest.approx(0.03)
    assert _read(cell, "q51_sort_resident_share", ctx) == 100.0
    assert _read(cell, "q51_smj_device_s", ctx) == pytest.approx(0.01)
    assert _read(cell, "q51_smj_streamed_runs", ctx) == 2.0
    assert _read(cell, "q51_probe_gather_device_s", ctx) == \
        pytest.approx(0.04)
    assert _read(cell, "q51_join_device_probe_share", ctx) == 80.0
    assert _read(cell, "q51_join_direct_probe_share", ctx) == 75.0
    assert _read(cell, "q51_expr_eager_share", ctx) == 25.0
    assert _read(cell, "q51_scan_decode_s", ctx) == pytest.approx(1.5)
    parent = dict(ctx, counters={}, spans=[], trace={"programs": {
        "jit_window_impl__runtime_stage_loop_window": 0.5}})
    for name in ("window_device_s", "window_roofline",
                 "window_resident_share", "q51_sort_resident_share",
                 "q51_smj_streamed_runs"):
        assert _read(cell, name, parent) is None, name
    assert _read(cell, "window_roofline", dict(ctx, trace={})) is None
    assert _read(cell, "window_roofline",
                 dict(ctx, spans=[run("host", 500, 2)])) is None
    assert _read(cell, "window_roofline", dict(ctx, query=None)) is None

    # idle seconds inside a `window_device` span, by span_idle
    def span(sid, name, a, b, parent=None):
        return {"sid": sid, "name": name, "t0_ns": a, "t1_ns": b,
                "dur_ns": b - a, "thread": "task-0", "tid": 1,
                "parent": parent, "attrs": {}}

    rec = {"events": {"annotations": [["bench_query", 0, 1000]],
                      "devices": {"0": {"busy": [[100, 200], [600, 700]],
                                        "programs": []}}},
           "query_starts_ns": [0]}
    trace = tmp_path / ".bench_work" / "cell.trace"
    trace.mkdir(parents=True)
    (trace / "trace_events.json").write_text(json.dumps(rec))
    spans = [span(1, "task", 0, 1000), span(2, "op:WindowExec", 150, 500, 1),
             span(3, "window_device", 250, 450, 2)]
    spec = load_json(os.path.join(cell.bench_dir, "layer_metrics",
                                  "idle_window_s.json"))
    reader = cell.module("sources", spec["source"])
    got = reader.read(spec, {"spans": spans, "queries": 1},
                      root=str(tmp_path))
    assert got == pytest.approx(400e-9)     # the gap 200-600, midpoint 400
    other = load_json(os.path.join(cell.bench_dir, "layer_metrics",
                                   "op_idle_other_s.json"))
    assert reader.read(other, {"spans": spans, "queries": 1},
                       root=str(tmp_path)) == pytest.approx(400e-9)
    no_ops = [s for s in spans if s["name"] == "task"]
    assert reader.read(spec, {"spans": no_ops, "queries": 1},
                       root=str(tmp_path)) is None
    # the transfer-idle twins: the gap's midpoint by the first category of
    # `gap_categories_task.json` whose span covers it
    for inner, name in (("h2d", "q51_idle_h2d_s"), ("d2h", "q51_idle_d2h_s"),
                        ("prefetch_wait", "q51_idle_prefetch_wait_s"),
                        (None, "q51_idle_task_other_s")):
        held = [span(1, "task", 0, 1000), span(2, "d2h", 10, 20, 1)]
        if inner:
            held.append(span(3, inner, 300, 500, 1))
        twin = load_json(os.path.join(cell.bench_dir, "layer_metrics",
                                      f"{name}.json"))
        got = cell.module("sources", twin["source"]).read(
            twin, {"spans": held, "queries": 1}, root=str(tmp_path))
        # under `task` alone the gaps before and behind the busy
        # intervals are glue too: 100 + 400 + 300
        assert got == pytest.approx(400e-9 if inner else 800e-9), name


def test_the_cells_traced_line_holds_what_the_manifest_lists_for_it(
        device_path, tmp_path):
    """`run.drive` over a copy of the benchmark whose configuration is cut
    to this file's scale, on one of the CPU's devices: correct, and every
    metric the manifest has for the cell that needs no device plane is in
    the traced run's line."""
    from benchmark import run as bench_run
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, "benchmark", "configs", f"{CONFIG}.json")
    cfg = load_json(path)
    cfg.update(scale=SCALE, tables={})
    with open(path, "w") as f:
        json.dump(cfg, f)
    cell = Cell(CELL, root)
    peaks = load_json(os.path.join(cell.bench_dir, "peaks.json"))
    res = bench_run.drive(cell, SEED, 0.3, 1, jax.devices()[:1],
                          peaks["devices"]["TPU v5 lite"],
                          time.perf_counter())
    assert res["correct"] is True and res["failed"] == 0
    listed = {m["name"]: m for m in cell.manifest["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    missing = set(listed) - set(res["metrics"])
    assert all(listed[name]["source"] == "device_trace" for name in missing)
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert got["window_resident_share"] == 100.0
    assert got["q51_sort_resident_share"] == 100.0
    assert got["q51_smj_streamed_runs"] == 0
    assert got["stage_loop_fallbacks"] == 0
    assert got["compiles_in_window"] == 0


# -- a plan without a window is the parent's --------------------------------------

def test_a_plan_without_a_window_asks_for_no_window_program(device_path,
                                                            tmp_path):
    """q93 at scale 0.01: the sort's programs keep their names (a cached
    program of another cell is found again), no window program is asked
    for and no window counter moves."""
    gen93, q93 = _load("data", "tpcds_returns"), _load("queries", "q93")
    tables = gen93.make_tables(q93.TABLES, 0.01, DATA_SEED, SPLITS, SEED)
    paths = gen93.write_parquet_splits(tables, str(tmp_path), SPLITS, 4096)
    known = set(xla_stats.compile_report()["kernels"])
    got, d = collect(q93.plan(paths, tables, PARTITIONS))
    assert check.verdict(check.compare(got, q93.oracle(tables), q93.KEYS,
                                       q93.ORDERED))[0]
    asked = set(xla_stats.compile_report()["kernels"]) - known
    assert not any(k.startswith("window.") for k in asked), asked
    sort_programs = {"sort.assemble", "sort.digits", "sort.pass",
                     "sort.gather"}
    assert sort_programs <= set(xla_stats.compile_report()["kernels"])
    assert {k for k in asked if k.startswith("sort.")} <= \
        sort_programs | {"sort.widen"}
    assert d["sort_resident_rows"] == d["sort_device_rows"] > 0
    for k in ("window_rows", "window_resident_rows", "window_partitions",
              "window_scan_bytes"):
        assert d[k] == 0
    from blaze_tpu.kernels import sort as ksort
    assert ksort.assemble_tiles._blaze_jitted.__name__ == \
        "_assemble_tiles__sort_assemble"

"""The benchmark's q67 (benchmark/queries/q67.py on
benchmark/data/tpcds_rollup.py through
benchmark/entries/dag_scheduler_rollup.py) at scale 0.05: the generator's
promises, the oracle's controls, the plan and its full answer through
`DagScheduler` on the device path with the Expand inside the stage loop and
every string an int32 code until the rank filter, what the entry refuses and
says, the manifest's new entries and their readers."""

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
CELL, CONFIG = "sf1_q67_x1", "tpcds-sf1-rollup-x1"
NEW = ("expand_rows_out", "dict_coded_share", "dict_decoded_rows",
       "dict_remap_rows", "q67_fold_device_s", "q67_fold_roofline",
       "q67_sort_resident_share", "q67_sort_device_s",
       "q67_window_resident_share", "q67_window_device_s",
       "q67_join_device_probe_share", "q67_probe_gather_device_s",
       "q67_expr_eager_share", "q67_scan_decode_s", "q67_idle_h2d_s",
       "q67_idle_d2h_s")
# the accepted entries name their cells inside themselves: same `read`
# blocks in new files
TWINS = {"q67_sort_resident_share": "sort_resident_share",
         "q67_sort_device_s": "q51_sort_device_s",
         "q67_window_resident_share": "window_resident_share",
         "q67_window_device_s": "window_device_s",
         "q67_join_device_probe_share": "join_device_probe_share",
         "q67_probe_gather_device_s": "q51_probe_gather_device_s",
         "q67_expr_eager_share": "q51_expr_eager_share",
         "q67_scan_decode_s": "q51_scan_decode_s",
         "q67_idle_h2d_s": "q51_idle_h2d_s",
         "q67_idle_d2h_s": "q51_idle_d2h_s"}


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}",
        os.path.join(ROOT, "benchmark", kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def gen():
    return _load("data", "tpcds_rollup")


@pytest.fixture(scope="module")
def q():
    return _load("queries", "q67")


@pytest.fixture(scope="module")
def tables(gen, q):
    return gen.make_tables(q.TABLES, SCALE, DATA_SEED, SPLITS, SEED)


@pytest.fixture(scope="module")
def ranked(q, tables):
    return q.rollup(tables)


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

@pytest.mark.parametrize("name,base", [("store_sales", "tpcds_data"),
                                       ("date_dim", "tpcds_web")])
def test_the_fact_table_and_the_calendar_are_the_accepted_ones(gen, name,
                                                               base):
    theirs = _load("data", base).make_tables([name], 0.01, DATA_SEED, SPLITS,
                                             SEED)[name]
    assert gen.make_tables([name], 0.01, DATA_SEED, SPLITS,
                           SEED)[name].equals(theirs)


def test_the_generator_makes_its_own_tables_only(gen):
    with pytest.raises(KeyError, match="tpcds_rollup makes"):
        gen.make_tables(["customer"], 0.01, DATA_SEED, SPLITS, SEED)


def test_the_item_hierarchy_is_dsdgens(gen):
    """A brand lies in one class, a class in one category; 18,000 distinct
    product names at scale 1; no string is NULL."""
    it = gen.gen_item(1.0, DATA_SEED).to_pandas()
    assert len(it) == 18_000 == it.i_product_name.nunique()
    assert it.i_category.nunique() == 10
    assert it.i_class.nunique() == 100
    assert it.i_brand.nunique() == 700
    assert (it.groupby("i_brand").i_class.nunique() == 1).all()
    assert (it.groupby("i_class").i_category.nunique() == 1).all()
    assert not it.isna().any().any()
    assert it.i_product_name.str.len().between(3, 30).all()
    assert gen.product_name(1) == "ought"
    assert gen.product_name(18_000) == "barbarbareingought"
    st = gen.gen_store(1.0, DATA_SEED).to_pandas()
    assert len(st) == 12 == st.s_store_id.nunique()
    assert (st.s_store_id.str.len() == 16).all()


def test_seed_changes_order_and_no_value(gen, q, tables):
    other = gen.make_tables(q.TABLES, SCALE, DATA_SEED, SPLITS, SEED + 1)
    for name in ("store_sales", "item"):
        a, b = tables[name].to_pandas(), other[name].to_pandas()
        assert not a.equals(b)
        cols = list(a.columns)
        assert a.sort_values(cols).reset_index(drop=True).equals(
            b.sort_values(cols).reset_index(drop=True))


# -- the oracle and its controls ------------------------------------------------

def test_the_oracles_call_nothing_of_the_program(q):
    import inspect
    assert "blaze_tpu" not in inspect.getsource(q).split(
        "def _sales(")[1]


def test_the_rollup_is_what_the_sql_says(q, tables, ranked):
    """Nine levels, each the distinct prefixes of the eight keys among the
    year's sales; the keys identify a row; the levels' sums agree."""
    sales = q._sales(tables, np.float64).to_pandas()
    assert 0.18 < len(sales) / tables["store_sales"].num_rows < 0.22
    for kept, _gid in q.LEVELS:
        level = ranked[ranked[q.KEYS[kept:]].isna().all(axis=1)
                       & ranked[q.KEYS[:kept]].notna().all(axis=1)]
        want = len(sales.drop_duplicates(q.KEYS[:kept])) if kept else 1
        assert len(level) == want, kept
        assert level.sumsales.sum() == pytest.approx(sales.amount.sum(),
                                                     rel=1e-12)
    assert not ranked.duplicated(q.KEYS).any()
    assert len(ranked) == sum(
        len(sales.drop_duplicates(q.KEYS[:k])) if k else 1
        for k, _ in q.LEVELS)


def test_rank_is_by_category_with_ties_sharing_the_lower_rank(q, ranked):
    total = ranked[ranked.i_category.isna()]
    assert len(total) == 1 and int(total.rk.iloc[0]) == 1
    books = ranked[ranked.i_category == "Books"] \
        .sort_values("sumsales", ascending=False)
    assert int(books.rk.iloc[0]) == 1 and books.i_class.iloc[0] is None
    s, rk = books.sumsales.to_numpy(), books.rk.to_numpy()
    assert (rk == 1 + np.searchsorted(-s, -s, side="left")).all()
    # the product level and the product-and-year level sum the same rows
    # in the same order: equal bit for bit, one rank
    by_product = ranked[ranked.i_product_name.notna()
                        & ranked.d_qoy.isna()]
    pairs = by_product.groupby(q.KEYS[:4], dropna=False)
    assert (pairs.sumsales.nunique() == 1).all()
    assert (pairs.rk.nunique() == 1).all() and (pairs.size() == 2).all()


def test_near_ties_span_their_clusters(q):
    full = pa.table({
        "i_category": ["a", "a", "a", "a", None, "b", "b"],
        "sumsales": [5.0, 3.0, 3.0 * (1 + 1e-12), 1.0, 9.0, 2.0, 2.0]})
    near, low, high = q.near_ties(full, 1e-9)
    assert near.tolist() == [False, True, True, False, False, True, True]
    assert low.tolist() == [1, 2, 2, 4, 1, 1, 1]
    assert high.tolist() == [1, 3, 3, 4, 1, 2, 2]


@pytest.mark.parametrize("control", ["float32", "lost_split", "lost_level",
                                     "dense_rank"])
def test_a_control_fails_the_answer(q, tables, ranked, control):
    from benchmark.controls import control_answers
    want = q.oracle(tables)
    assert want.num_rows == 100
    if control in ("float32", "lost_split"):
        got = control_answers(q, tables, SPLITS)[control]
    elif control == "lost_level":
        # the brand level is gone: its rows do not outrank the products
        got = q.answer(q.rollup(tables, levels=[
            lv for lv in q.LEVELS if lv[0] != 3]))
    else:
        dense = ranked.copy()
        dense["rk"] = dense.groupby("i_category", dropna=False).sumsales \
            .rank(method="dense", ascending=False).astype(np.int32)
        got = q.answer(dense)
    nums = check.compare(got, want, q.KEYS, q.ORDERED)
    assert not check.verdict(nums)[0], (control, nums)


# -- the plan through the scheduler, on the device path -------------------------

def test_the_answer_and_the_full_answer_equal_the_oracles(
        q, tables, ranked, paths, device_path):
    collect(q.plan(paths, tables, PARTITIONS))   # broadcasts collected
    got, d = collect(q.plan(paths, tables, PARTITIONS))
    want = q.oracle(tables)
    ok, line = check.verdict(check.compare(got, want, q.KEYS, q.ORDERED))
    assert ok and got.num_rows == 100, line
    assert got.schema.names == q.OUT
    assert got.schema.types == want.schema.types
    sales = q._sales(tables, np.float64).num_rows
    # the Expand ran inside the stage loop: nine lists a joined row, and
    # both aggregations were stage-loop tasks
    assert d["expand_rows_out"] == 9 * sales
    assert d["stage_loop_fallbacks"] == 0 and d["stage_loop_tasks"] == 8
    assert d["stage_loop_rows"] > d["expand_rows_out"]
    assert d["agg_eager_rows"] == 0
    # three joins, two of them with string payloads, all on the chip
    assert d["join_probe_host_rows"] == 0
    assert d["join_probe_device_rows"] == d["join_probe_direct_rows"] > sales
    # every rolled-up row was sorted and ranked as codes; the grand total
    # is alone in its partition, under the lanes' floor
    assert d["sort_device_rows"] == d["sort_resident_rows"] == len(ranked) - 1
    assert d["window_rows"] == len(ranked)
    assert d["window_resident_rows"] == len(ranked) - 1
    # strings were decoded where rows are shown and nowhere else
    shown = int((ranked.rk <= q.TOP).sum())
    assert shown * len(q.STRINGS) <= d["dict_rows_decoded"] \
        < (shown + 100) * len(q.STRINGS)
    assert d["dict_rows_coded"] > 100 * d["dict_rows_decoded"]
    assert d["dict_unified"] == d["dict_remap_rows"] == 0

    full, _ = collect(q.plan_full(paths, tables, PARTITIONS))
    nums = check.compare(full, q.full_oracle(tables), q.KEYS, False)
    assert check.verdict(nums)[0], nums
    assert full.num_rows == len(ranked) > 10_000


def _entry(q, paths, tables, tmp_path):
    return _load("entries", "dag_scheduler_rollup").Entry(
        q, paths, tables, {"partitions": PARTITIONS}, str(tmp_path))


def test_the_entry_holds_a_run_to_the_cells_conditions(
        q, tables, paths, device_path, tmp_path):
    entry = _entry(q, paths, tables, tmp_path)
    for _ in range(2):                       # the second finds the broadcasts
        entry.begin()
        got = entry.run()
        entry.end()
    assert got.num_rows == 100
    assert entry.problem() is None          # the full answer compared too
    moved = dict(entry._moved)
    for change, says in (
            ({"stage_loop_fallbacks": 1}, "left the stage loop"),
            ({"expand_rows_out": 0}, "no stage-loop task folded an Expand"),
            ({"agg_eager_rows": 5000}, "outside the stage loop took 5000"),
            ({"dict_rows_decoded": 10 ** 6}, "decoded to strings"),
            ({"join_probe_host_rows": 7}, "7 probe rows went through"),
            ({"window_resident_rows": 0}, "window rows left the chip"),
            ({"sort_resident_rows": 0}, "sort rows left the chip")):
        entry._moved = dict(moved, **change)
        assert says in entry.problem(), change
    entry._moved = moved
    # a sum off by a cent's millionth part in the full answer
    plan, want, near = entry.full
    col = want.column("sumsales").to_numpy().copy()
    col[int(np.argmax(col))] *= 1 + 1e-8
    at = want.column_names.index("sumsales")
    entry.full = (plan, want.set_column(at, "sumsales", [col]), near)
    why = entry.problem()
    assert why and "float_max_rel_err" in why and "EXCEEDED" in why
    # a rank outside its cluster's span
    rk = want.column("rk").to_numpy().copy()
    rk[int(np.flatnonzero(~near[0])[0])] += 1
    entry.full = (plan, want.set_column(want.column_names.index("rk"), "rk",
                                        [rk]), near)
    why = entry.problem()
    assert why and "exact_value_mismatches=1" in why


def test_the_entry_says_so_when_compute_is_on_the_host(q, tables, paths,
                                                       tmp_path):
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")
    try:
        entry = _entry(q, paths, tables, tmp_path)
    finally:
        config.conf.unset(config.STAGE_DEVICE_LOOP_ENABLE.key)
    entry.last = {"exec_mode": "staged", "stages": 4}
    entry._moved = dict.fromkeys(
        _load("entries", "dag_scheduler_rollup").WATCHED, 1)
    assert entry.problem() == "compute is placed on the host"


@pytest.mark.parametrize("parent", ["no_expand_in_the_stage",
                                    "no_stage_loop_task",
                                    "no_device_probe"])
def test_the_entry_refuses_the_parents_paths(q, tables, paths, tmp_path,
                                             device_path, monkeypatch,
                                             parent):
    """What the parent commit lacks, one piece at a time: the constructor
    says so before any query."""
    if parent == "no_expand_in_the_stage":
        from blaze_tpu.plan import fused
        monkeypatch.setattr(fused, "_expand_traceable", lambda node: False)
        match = "fuses no aggregation over the plan's Expand"
    elif parent == "no_stage_loop_task":
        from blaze_tpu.plan import stage_compiler
        monkeypatch.setattr(stage_compiler, "try_compile", lambda agg: None)
        match = "no stage-loop task"
    else:
        from blaze_tpu.ops.joins.exec import BaseJoinExec
        monkeypatch.delattr(BaseJoinExec, "device_probe_planned")
        match = "with no device probe"
    with pytest.raises(RuntimeError, match=match):
        _entry(q, paths, tables, tmp_path)


# -- the manifest's new entries and their readers ---------------------------------

def test_the_cells_manifest_entries():
    cell = Cell(CELL, ROOT)
    twin = Cell("sf1_q51_x1", ROOT)
    assert cell.chips == 1 and cell.entry["config"] == CONFIG
    assert cell.config["generator"] == "tpcds_rollup"
    assert cell.config["guarantees"] == twin.config["guarantees"]
    for key in ("scale", "data_seed", "splits", "partitions",
                "row_group_rows", "chips", "program_settings",
                "agg_table_slots"):
        assert cell.config[key] == twin.config[key], key
    assert cell.config["tables"] == {"store_sales": 2_880_404,
                                     "date_dim": 73_049, "store": 12,
                                     "item": 18_000}
    assert list(cell.config["reduced"]) == ["scale"]
    # (the traced window holds two queries of ~6.5 s: a per-layer number
    # is not one query's)
    assert cell.traffic == dict(twin.traffic, query="q67",
                                entry="dag_scheduler_rollup",
                                trace_seconds=14,
                                why=cell.traffic["why"])
    for kind, name in (("data", "tpcds_rollup"), ("queries", "q67"),
                       ("entries", "dag_scheduler_rollup")):
        assert cell.module(kind, name) is not None
    entry, = [c for c in cell.manifest["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["scale"]
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len(cell.entry["why"]) <= 200
    mine = [m for m, _spec in cell.layer_metrics()
            if m.get("workloads") == [CELL]]
    assert tuple(m["name"] for m in mine) == NEW
    assert all(m["moves"] == "query_wall_s" for m in mine)
    # the configuration and the cell stand at the end of their lists; the
    # cell's own metrics are held by name, not by place: each is in the
    # manifest once, and in `mine` above as accepted, so a later PR's
    # entries go behind them or between them and nothing here moves
    assert cell.manifest["configs"][-1]["name"] == CONFIG
    assert cell.manifest["workloads"][-1]["name"] == CELL
    names = [m["name"] for m in cell.manifest["per_layer"]]
    assert all(names.count(name) == 1 for name in NEW)
    assert {m["name"] for m in cell.end_to_end()} == {"query_wall_s",
                                                      "setup_s"}
    specs = {m["name"]: spec for m, spec in cell.layer_metrics()}
    for name, of in TWINS.items():
        accepted = load_json(os.path.join(cell.bench_dir, "layer_metrics",
                                          f"{of}.json"))
        assert specs[name]["read"] == accepted["read"]
        assert specs[name]["source"] == accepted["source"]
    for spec in specs.values():
        assert cell.module("sources", spec["source"]).read


def _read(cell, name, ctx):
    spec = load_json(os.path.join(cell.bench_dir, "layer_metrics",
                                  f"{name}.json"))
    return cell.module("sources", spec["source"]).read(spec, dict(ctx))


def test_the_new_readers_on_synthetic_counters(q):
    """The rollup roofline prices `expand_rows_out` at the query file's
    lanes; on the parent (no counter, no such program) every new reader
    returns nothing and does not raise."""
    from benchmark import kernel_costs_rollup
    cell = Cell(CELL, ROOT)
    peaks = load_json(os.path.join(cell.bench_dir, "peaks.json"))[
        "devices"]["TPU v5 lite"]
    fold = "jit_fold_impl__runtime_stage_loop_fold_expand"
    ctx = {"trace": {"programs": {fold: 2.0, "jit_fold_impl__runtime_"
                                  "stage_loop": 1.0}, "gaps": {},
                     "busy_s": 3.0, "window_s": 6.0},
           "counters": {"expand_rows_out": 10_000_000,
                        "dict_rows_coded": 990, "dict_rows_decoded": 10,
                        "dict_remap_rows": 0},
           "queries": 2, "query": q, "peaks": peaks, "spans": []}
    least = kernel_costs_rollup.expand_fold_min_bytes(
        10_000_000, q.FOLD_KEY_LANES, q.FOLD_VALUE_BYTES)
    assert least == 10_000_000 * (4 * 11 + 24)
    assert _read(cell, "q67_fold_roofline", ctx) == pytest.approx(
        100.0 * least / 2.0 / peaks["hbm_bytes_per_s"])
    assert _read(cell, "q67_fold_device_s", ctx) == 1.0
    assert _read(cell, "expand_rows_out", ctx) == 5_000_000
    assert _read(cell, "dict_coded_share", ctx) == 99.0
    assert _read(cell, "dict_decoded_rows", ctx) == 5
    assert _read(cell, "dict_remap_rows", ctx) == 0
    parent = dict(ctx, counters={}, trace={
        "programs": {"jit_fold_impl__runtime_stage_loop": 1.0}, "gaps": {},
        "busy_s": 1.0, "window_s": 6.0})
    for name in NEW[:6]:
        assert _read(cell, name, parent) is None, name


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
    assert got["q67_join_device_probe_share"] == 100.0
    assert got["q67_sort_resident_share"] == 100.0
    assert 99.99 < got["q67_window_resident_share"] < 100.0
    assert got["dict_coded_share"] > 99.0
    assert got["dict_remap_rows"] == 0
    assert got["expand_rows_out"] > 0
    assert got["stage_loop_fallbacks"] == 0
    assert got["compiles_in_window"] == 0

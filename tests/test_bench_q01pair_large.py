"""The benchmark's q01 pair (benchmark/queries/q01pair.py through
benchmark/entries/runtime_pair.py on benchmark/data/tpcds_data.py) with
the stage loop's table walked as `sf100_q01pair_x1` walks it: the
table's floor and the batch lowered so that a chunk holds as many rows as
the floor has slots (8 x 32,768 = 2^18 at the defaults), the scale chosen
so that a reduce task receives between five and six chunks of nearly
distinct rows, as at scale factor 100.  A reduce task then allocates
8 x floor, rehashes once into 32 x floor at its third chunk and stays; the
two map tasks that hold the year's rows switch to pass-through; the other
two fold batches with no live row.  Also here: the ceiling and the budget,
which SF100 does not reach, and the cell's manifest entries."""

import importlib.util
import json
import os
import sys
from contextlib import contextmanager

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from blaze_tpu import config  # noqa: E402
from blaze_tpu.bridge import tracing, xla_stats  # noqa: E402
from blaze_tpu.memory import MemManager  # noqa: E402
from blaze_tpu.runtime import loop as device_loop  # noqa: E402

SCALE, DATA_SEED, SPLITS, PARTITIONS = 0.4, 20260927, 4, 4
# files 1 and 2 hold the year: since PR 49 the scans of files 0 and 3 read
# no row group, their map tasks fold nothing and drain an empty table of
# the floor's size
HOT = 2
COLD = SPLITS - HOT
FLOOR, BATCH, CHUNK = 1024, 128, 8
FIRST, LAST = 8 * FLOOR, 32 * FLOOR     # need / _TARGET_LOAD, as powers of 2
REHASH_LANES = 2 * FLOOR                # the power of two over ~1.9K groups
# two int64 keys as four 32-bit lanes, the int32 owner lane, a float64
# sum and its flag
SLOT_BYTES = 4 * 4 + 4 + 8 + 1
CELL = "sf100_q01pair_x1"
BUDGET = 4 << 30


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}",
        os.path.join(ROOT, "benchmark", kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def schedule(chunk_rows, groups_after, floor):
    """The capacities `_fold_partition` walks, from the rows of each chunk
    and the groups the table holds after it: [(from_slots, to_slots)],
    from_slots 0 for the first allocation.  Written from the rule in
    runtime/loop.py's docstring, not from its code."""
    out, slots, groups = [], 0, 0
    for rows, after in zip(chunk_rows, groups_after):
        need = groups + rows
        if slots == 0 or need > slots / 4:
            want = max(floor, 1 << (need * 8 - 1).bit_length())
            if want > slots:
                out.append((slots, want))
                slots = want
        groups = after
    return out


def reference(tables, query):
    """sum(sr_return_amt) by (customer, store) over the year's returns, in
    numpy: {(customer or None, store): sum}."""
    sr = tables["store_returns"]
    dd = tables["date_dim"]
    sk = dd["d_date_sk"].to_numpy()[dd["d_year"].to_numpy() == 2000]
    date = sr["sr_returned_date_sk"].to_numpy()
    live = (date >= sk.min()) & (date <= sk.max())
    cust = sr["sr_customer_sk"].to_numpy(zero_copy_only=False)[live]
    store = sr["sr_store_sk"].to_numpy()[live]
    amt = sr["sr_return_amt"].to_numpy()[live]
    packed = np.where(np.isnan(cust), 0, cust).astype(np.int64) * 64 + store
    keys, inv = np.unique(packed, return_inverse=True)
    sums = np.zeros(len(keys))
    np.add.at(sums, inv, amt)
    return {((int(k) // 64) or None, int(k) % 64): s
            for k, s in zip(keys, sums)}


def answer_of(table):
    return {(c, s): v for c, s, v in zip(
        table["ctr_customer_sk"].to_pylist(),
        table["ctr_store_sk"].to_pylist(),
        table["ctr_total_return"].to_pylist())}


def same_answer(got, want):
    assert got.keys() == want.keys()
    worst = max(abs(got[k] - want[k]) / max(abs(want[k]), 1e-300)
                for k in want)
    assert worst <= 1e-12, worst


@pytest.fixture(scope="module")
def case(tmp_path_factory):
    gen, query = _load("data", "tpcds_data"), _load("queries", "q01pair")
    tables = gen.make_tables(query.TABLES, SCALE, DATA_SEED, SPLITS,
                             2_900_000_123)
    paths = gen.write_parquet_splits(
        tables, str(tmp_path_factory.mktemp("tables")), SPLITS, 4096)
    return query, paths, tables, reference(tables, query)


@contextmanager
def settings_like_sf100(monkeypatch):
    """Batches on ONE device as on the cell's chip; the table's floor,
    the batch and the first look lowered in proportion; the dense lane
    (which q01's keys leave at SF10 already) closed."""
    import blaze_tpu.bridge.placement as P
    monkeypatch.setattr(P, "host_resident", lambda: False)
    lowered = {config.MESH_DEVICES.key: 1,
               config.BATCH_SIZE.key: BATCH,
               config.ON_DEVICE_AGG_CAPACITY.key: FLOOR,
               config.STAGE_DEVICE_LOOP_CHUNK.key: CHUNK,
               config.PARTIAL_AGG_SKIPPING_MIN_ROWS.key: 200,
               config.FUSED_STAGE_CAPACITY.key: 16}
    for key, value in lowered.items():
        config.conf.set(key, value)
    MemManager.init(BUDGET)
    try:
        yield
    finally:
        for key in lowered:
            config.conf.unset(key)
        MemManager.init(BUDGET)


@pytest.fixture
def like_sf100(monkeypatch):
    with settings_like_sf100(monkeypatch):
        yield


def run_pair(case, work_dir):
    """(answer, counters' delta, spans) of one query through the
    benchmark's own entry."""
    from benchmark.entries.runtime_pair import Entry
    query, paths, tables, _want = case
    entry = Entry(query, paths, tables, {"partitions": PARTITIONS},
                  str(work_dir))
    before = xla_stats.snapshot()
    tracing.start_tracing()
    entry.begin()
    try:
        got = entry.run()
    finally:
        entry.end()
        spans = tracing.stop_tracing()
    return got, xla_stats.delta(before), spans


def tables_charged():
    return [c for c in MemManager.get()._consumers
            if c.name == "stage_loop_table"]


@pytest.fixture(scope="module")
def two_passes(case, tmp_path_factory):
    """The pair twice under `like_sf100`'s settings, with what the
    manager held against the tables when each switched partition began
    to pass rows through."""
    mp = pytest.MonkeyPatch()
    at_switch = []
    charges = []
    real_charge, real_pass = device_loop._TableCharge, \
        device_loop._pass_through

    class Watched(real_charge):
        def __init__(self, program):
            super().__init__(program)
            self.program = program
            charges.append(self)

    def watched_pass(program, rest, partition, ctx):
        at_switch.extend(c.mem_used for c in charges
                         if c.program is program)
        return real_pass(program, rest, partition, ctx)

    try:
        with settings_like_sf100(mp):
            mp.setattr(device_loop, "_TableCharge", Watched)
            mp.setattr(device_loop, "_pass_through", watched_pass)
            used_before = MemManager.get().chip_used(0)
            work = tmp_path_factory.mktemp("pair")
            # the tally is the process's: a file that ran earlier on
            # this worker may have left reasons of its own
            reasons_before = xla_stats.stage_loop_fallback_reasons()
            passes = [run_pair(case, work) for _ in range(2)]
            made = {"passes": passes, "at_switch": at_switch,
                    "reasons_before": reasons_before,
                    "reasons_after": xla_stats.stage_loop_fallback_reasons(),
                    "used_before": used_before,
                    "used_after": MemManager.get().chip_used(0),
                    "left_charged": len(tables_charged()),
                    "peaks": sorted(c.peak for c in charges),
                    "by_chip": xla_stats.chip_stats()}
    finally:
        mp.undo()
    return made


def rehashes(spans):
    return [s["attrs"] for s in spans if s["name"] == "table_rehash"]


# -- satellite 1: floor -> rehash -> 4 x floor --------------------------------

def test_every_group_equals_the_numpy_group_by(case, two_passes):
    want = case[3]
    assert len(want) > 20_000
    for got, _moved, _spans in two_passes["passes"]:
        same_answer(answer_of(got), want)


def test_no_task_leaves_the_loop_and_two_map_tasks_stop_grouping(two_passes):
    _got, moved, _spans = two_passes["passes"][0]
    assert moved["stage_loop_tasks"] == SPLITS + PARTITIONS
    assert moved["stage_loop_fallbacks"] == 0
    assert moved["stage_loop_regrows"] == 0
    assert moved["partial_agg_skip_events"] == 2
    assert two_passes["reasons_after"] == two_passes["reasons_before"]


def test_a_reduce_task_rehashes_once_and_ends_at_four_times_its_first_table(
        two_passes):
    _got, moved, spans = two_passes["passes"][0]
    spans = rehashes(spans)
    assert sorted(s["partition"] for s in spans) == list(range(PARTITIONS))
    for s in spans:
        assert (s["from_slots"], s["to_slots"]) == (FIRST, LAST)
        assert s["from_slots"] < s["to_slots"]
        # at its third chunk, holding two chunks of nearly distinct rows
        assert s["chunk"] == 2
        assert 0.9 * 2 * FLOOR <= s["groups"] <= 2 * FLOOR
        # re-inserted over the power of two that holds them, a quarter
        # of the old table's slots
        assert s["lanes"] == REHASH_LANES >= s["groups"]
        assert s["device"] == 0
    # one first allocation a task that folds a row, one rehash a reduce task
    assert moved["stage_loop_reserves"] == HOT + 2 * PARTITIONS


def test_the_counters_read_what_the_schedule_says(two_passes):
    _got, moved, spans = two_passes["passes"][0]
    spans = rehashes(spans)
    # the old tables' slots, and the lanes their live slots were
    # compacted to before they were re-inserted
    assert moved["stage_loop_rehash_lanes"] == PARTITIONS * FIRST
    assert moved["stage_loop_rehash_probe_lanes"] \
        == PARTITIONS * REHASH_LANES == sum(s["lanes"] for s in spans)
    assert moved["stage_loop_rehash_new_slots"] == PARTITIONS * LAST
    assert moved["stage_loop_rehash_groups"] \
        == sum(s["groups"] for s in spans)
    # a map task stays at its first table: a cold one is handed no batch
    # and drains the floor's empty table, a hot one drains its table
    # before a third chunk
    assert moved["stage_loop_final_slots"] \
        == PARTITIONS * LAST + HOT * FIRST + COLD * FLOOR
    assert moved["stage_loop_table_bytes"] == SLOT_BYTES * (
        PARTITIONS * (FIRST + LAST) + HOT * FIRST + COLD * FLOOR)
    assert two_passes["peaks"] == sorted(
        2 * ([SLOT_BYTES * FLOOR] * COLD + [SLOT_BYTES * FIRST] * HOT
             + [SLOT_BYTES * (FIRST + LAST)] * PARTITIONS))
    for key in ("rehash_lanes", "rehash_groups", "rehash_new_slots",
                "rehash_probe_lanes", "final_slots", "table_bytes"):
        assert moved[f"chip0_stage_loop_{key}"] \
            == moved[f"stage_loop_{key}"]
        assert two_passes["by_chip"][0][f"stage_loop_{key}"] > 0


def test_the_cold_map_tasks_read_no_row_group(case, two_passes):
    """The chain's date filter prunes the scan beneath the stage loop:
    files 0 and 3 lie outside the year whole, files 1 and 2 keep the row
    groups that touch it, and a cold task's `produce:parquet_scan` span
    says that it looked at its file and decoded nothing."""
    import pyarrow.parquet as pq
    _query, paths, _tables, _want = case
    groups = [pq.ParquetFile(g[0]).metadata.num_row_groups
              for g in paths["store_returns"]]
    _got, moved, spans = two_passes["passes"][0]
    assert moved["scan_row_groups"] == sum(groups)
    assert moved["scan_row_groups_pruned"] >= groups[0] + groups[3]
    assert moved["scan_row_groups_pruned"] < sum(groups) - 2
    by_task = {}
    for s in spans:
        if s["name"] == "produce:parquet_scan":
            tally = by_task.setdefault(s["ctx"]["partition"], [0, 0, 0])
            for i, key in enumerate(("row_groups", "pruned", "rows")):
                tally[i] += s["attrs"].get(key, 0)
    assert by_task[0] == [groups[0], groups[0], 0]
    assert by_task[3] == [groups[3], groups[3], 0]
    assert all(0 < by_task[p][1] < groups[p] and by_task[p][2] > 0
               for p in (1, 2))


def test_the_manager_holds_nothing_after_the_drain_or_the_switch(two_passes):
    assert two_passes["used_before"] == two_passes["used_after"] == 0
    assert two_passes["left_charged"] == 0
    # two switched map tasks a pass, each with its table released before
    # the first row was passed through
    assert two_passes["at_switch"] == [0] * 4


def test_a_second_pass_walks_the_same_capacities_and_asks_for_no_program(
        two_passes):
    (_g1, first, spans1), (_g2, second, spans2) = two_passes["passes"]
    assert first["total_compiles"] > 0
    assert second["total_compiles"] == 0
    walk = [sorted((s["partition"], s["chunk"], s["from_slots"],
                    s["to_slots"], s["groups"], s["lanes"])
                   for s in rehashes(sp))
            for sp in (spans1, spans2)]
    assert walk[0] == walk[1]
    for key in ("stage_loop_reserves", "stage_loop_rehash_lanes",
                "stage_loop_rehash_groups", "stage_loop_rehash_probe_lanes",
                "stage_loop_final_slots",
                "stage_loop_table_bytes", "stage_loop_full_rounds",
                "stage_loop_narrow_rounds", "partial_agg_skipped_rows"):
        assert first[key] == second[key], key


@pytest.mark.parametrize("rows, groups, batch, floor, want", [
    # a reduce task of this file: 5,500 nearly distinct rows
    (5_500, 5_480, BATCH, FLOOR, [(0, FIRST), (FIRST, LAST)]),
    # a reduce task at scale factor 100: 1.43M rows, 1.38M groups
    (1_430_000, 1_380_000, 32_768, 1 << 18,
     [(0, 1 << 21), (1 << 21, 1 << 23)]),
    # the same at scale factor 10: one chunk, sized once
    (143_000, 140_000, 32_768, 1 << 18, [(0, 1 << 21)]),
    # what would pass the ceiling: more than 2^24 / 8 groups a task
    (2_200_000, 2_150_000, 32_768, 1 << 18,
     [(0, 1 << 21), (1 << 21, 1 << 23), (1 << 23, 1 << 25)]),
])
def test_the_schedule_by_hand(rows, groups, batch, floor, want):
    per_chunk = CHUNK * batch
    chunks = [min(per_chunk, rows - i) for i in range(0, rows, per_chunk)]
    after = np.minimum(np.cumsum(chunks) * groups // rows, groups)
    assert schedule(chunks, after.tolist(), floor) == want


# -- satellite 2: the ceiling and the budget -----------------------------------

@pytest.mark.parametrize("limit", ["ceiling", "budget"])
def test_past_the_ceiling_or_the_budget_the_task_leaves_before_emitting(
        limit, case, like_sf100, tmp_path, monkeypatch):
    if limit == "ceiling":
        # a reduce task needs 32 x floor: the map tasks stay in the loop
        monkeypatch.setattr(device_loop, "_MAX_SLOTS", FIRST)
        reason, leave = f"table would exceed {FIRST} slots", PARTITIONS
    else:
        # a byte below one first table, with the floor raised so that the
        # staged re-run's own state fits the budget many times over: no
        # task's table is held, and nothing has to spill
        config.conf.set(config.ON_DEVICE_AGG_CAPACITY.key, 1 << 19)
        MemManager.init(SLOT_BYTES * (1 << 19) - 1)
        reason, leave = "memory budget of", SPLITS + PARTITIONS
    xla_stats.reset()
    got, moved, _spans = run_pair(case, tmp_path)
    assert moved["stage_loop_fallbacks"] == leave
    reasons = xla_stats.stage_loop_fallback_reasons()
    assert sum(reasons.values()) == leave
    assert all(reason in r for r in reasons), reasons
    # only the tasks that stayed ended in the loop; the staged re-run of
    # the others answers (a task that had emitted before it left would
    # count its rows twice)
    assert moved["stage_loop_tasks"] == SPLITS + PARTITIONS - leave
    same_answer(answer_of(got), case[3])
    # and nothing stays charged
    assert tables_charged() == []
    assert MemManager.get().chip_used(0) == 0


# -- satellite 3: the cell's manifest entries ------------------------------------

@pytest.fixture(scope="module")
def cell():
    from benchmark.manifest import Cell
    return Cell(CELL, ROOT)


def test_the_cell_resolves_and_its_tables_are_the_generators(cell):
    gen = _load("data", "tpcds_data")
    cfg = cell.config
    assert cell.chips == 1 and cell.entry["config"] == "tpcds-sf100-x1"
    assert cell.traffic["query"] == "q01pair"
    assert cell.traffic["entry"] == "runtime_pair"
    assert cfg["scale"] == 100.0 and cfg["program_settings"] == {}
    assert cfg["tables"] == {
        "store_returns": gen.rows("store_returns", 100),
        "date_dim": gen.rows("date_dim", 100)}
    assert cfg["tables"]["store_returns"] == 28_751_400
    query = cell.module("queries", cell.traffic["query"])
    assert set(query.TABLES) == set(cfg["tables"])
    sf10 = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "tpcds-sf10-x1.json")))
    for key in ("data_seed", "splits", "partitions", "row_group_rows",
                "chips", "agg_table_slots", "generator", "guarantees"):
        assert cfg[key] == sf10[key], key
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == "tpcds-sf100-x1")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "query1.tpl" in entry["source"]
    assert "scale factor 100" in entry["source"]
    assert entry["reduced"] == ["scale"] == list(cfg["reduced"])
    assert len(cell.entry["why"]) <= 200


def test_every_metric_that_names_the_cell_has_its_file_and_its_reader(cell):
    named = [m for m in cell.manifest["per_layer"]
             if CELL in m.get("workloads", [])]
    assert {m["name"] for m in named} == {
        "rehash_device_s", "rehash_lanes", "rehash_roofline",
        "fold_final_slots", "table_charged_mb", "rehash_probe_lanes"}
    specs = dict((m["name"], spec) for m, spec in cell.layer_metrics())
    for m in named:
        spec = specs[m["name"]]
        assert spec["name"] == m["name"] and spec["unit"] == m["unit"]
        assert spec["manifest_source"] == m["source"]
        assert spec["better"] == m["better"]
        assert m["moves"] == "query_wall_s"
        assert callable(cell.module("sources", spec["source"]).read)
    # the metrics without a list report here by themselves: 26 until
    # PR 35, whose eleven by-operator idle metrics name no cell
    listless = [m["name"] for m in cell.manifest["per_layer"]
                if "workloads" not in m]
    assert len(listless) >= 37 and len(specs) == len(named) + len(listless)
    assert {n for n in listless if n.startswith("op_idle_")} == {
        f"op_idle_{f}_s" for f in (
            "scan", "exchange_write", "exchange_read", "join", "sort",
            "agg", "other", "no_op")}
    assert {"idle_coalesce_s", "idle_loop_glue_s",
            "gc_pause_idle_s"} <= set(listless)


def test_the_new_readers_read_the_counters_and_nothing_on_the_parent(cell):
    specs = dict((m["name"], spec) for m, spec in cell.layer_metrics())
    query = cell.module("queries", "q01pair")
    counters = {"stage_loop_rehash_lanes": 4 << 21,
                "stage_loop_rehash_groups": 4 * 500_000,
                "stage_loop_rehash_new_slots": 4 << 23,
                "stage_loop_rehash_probe_lanes": 4 << 19,
                "stage_loop_final_slots": (4 << 23) + (4 << 21),
                "stage_loop_tasks": 8,
                "stage_loop_table_bytes": 28 * ((4 << 23) + (8 << 21))}
    trace = {"programs": {"jit__lambda___fused_rehash": 6.0,
                          "jit_fold_impl__runtime_stage_loop": 12.0}}
    ctx = {"counters": counters, "queries": 1, "query": query,
           "trace": trace, "peaks": {"hbm_bytes_per_s": 819e9}}

    def read(name, ctx):
        return cell.module("sources", specs[name]["source"]).read(
            specs[name], ctx)

    assert read("rehash_lanes", ctx) == 4 << 21
    assert read("rehash_probe_lanes", ctx) == 4 << 19
    assert read("fold_final_slots", ctx) == ((4 << 23) + (4 << 21)) / 8
    assert read("table_charged_mb", ctx) == pytest.approx(1409.286144)
    assert read("rehash_device_s", ctx) == 6.0
    least = 4 * (500_000 * 2 * 25 + (1 << 23) * 25)
    assert read("rehash_roofline", ctx) == pytest.approx(
        100.0 * least / 6.0 / 819e9)
    # the parent counts the lanes and nothing else; a window without a
    # rehash has no such program
    parent = dict(ctx, counters={"stage_loop_rehash_lanes": 4 << 21,
                                 "stage_loop_tasks": 8})
    assert read("rehash_lanes", parent) == 4 << 21
    for name in ("rehash_roofline", "fold_final_slots", "table_charged_mb",
                 "rehash_probe_lanes"):
        assert read(name, parent) is None
    quiet = dict(ctx, trace={"programs": {"jit_fold_impl": 1.0}})
    assert read("rehash_device_s", quiet) is None
    assert read("rehash_roofline", quiet) is None


def test_rehash_min_bytes_on_a_hand_worked_case():
    from benchmark.kernel_costs_rehash import rehash_min_bytes
    # 1,000 groups read and written at 25 B, 4,096 new slots written once
    assert rehash_min_bytes(1_000, 4_096, 25) == 50_000 + 102_400
    assert rehash_min_bytes(0, 16, 25) == 400


def test_the_cells_traced_line_holds_what_the_manifest_lists_for_it(
        like_sf100, tmp_path):
    """`run.drive` over a copy of the benchmark whose configuration is cut
    to this file's scale: every metric the manifest has for the cell that
    needs no device plane is in the traced run's line, the compaction's
    lanes among them."""
    import shutil
    import time

    import jax
    from benchmark import run as bench_run
    from benchmark.manifest import Cell, load_json
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, "benchmark", "configs", "tpcds-sf100-x1.json")
    cfg = load_json(path)
    cfg.update(scale=SCALE, tables={})
    with open(path, "w") as f:
        json.dump(cfg, f)
    cell = Cell(CELL, root)
    peaks = load_json(os.path.join(cell.bench_dir, "peaks.json"))
    res = bench_run.drive(cell, 2_900_000_123, 0.3, 1, jax.devices()[:1],
                          peaks["devices"]["TPU v5 lite"],
                          time.perf_counter())
    assert res["correct"] is True and res["failed"] == 0
    listed = {m["name"]: m for m in cell.manifest["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    missing = set(listed) - set(res["metrics"])
    assert all(listed[name]["source"] == "device_trace" for name in missing)
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert got["rehash_probe_lanes"] == PARTITIONS * REHASH_LANES
    assert got["rehash_lanes"] == PARTITIONS * FIRST
    assert got["fold_final_slots"] \
        == (PARTITIONS * LAST + HOT * FIRST + COLD * FLOOR) \
        / (SPLITS + PARTITIONS)
    assert got["stage_loop_fallbacks"] == got["stage_loop_regrows"] == 0

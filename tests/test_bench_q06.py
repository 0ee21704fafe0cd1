"""The benchmark's q06 (benchmark/queries/q06.py on
benchmark/data/tpcds_data.py) at scale 0.1 through `DagScheduler` on the
device path, the batch size lowered in proportion (4,096 rows): the answer
against its oracle, and the counts the chip's seconds follow from.  The
probe keeps under half of each sales batch, so every probe batch is held by
the join's `CoalesceStream`: what the fold receives is tiles of exactly one
batch size and a tail at the same capacity, laid on the device by
`lay_tile`.  Counts and shapes, never a time."""

import importlib.util
import os
import sys
import threading

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402
from benchmark.manifest import Cell, load_json  # noqa: E402
from blaze_tpu import config  # noqa: E402
from blaze_tpu.bridge import tracing, xla_stats  # noqa: E402
from blaze_tpu.bridge.context import current_task  # noqa: E402
from blaze_tpu.ops.base import effective_batch_size  # noqa: E402
from blaze_tpu.plan import fused  # noqa: E402
from blaze_tpu.plan.stages import DagScheduler  # noqa: E402

SCALE, DATA_SEED, SPLITS, PARTITIONS = 0.1, 20260927, 4, 4
SEED = 2_900_000_123
BATCH = 4096
CELLS = ("sf1_q06_x1", "sf1_q06_x4")
# the cells that report `coalesce_tiled_share`: q51's date probes leave small
# batches at the edges of the year too
LISTED = CELLS + ("sf1_q51_x1",)


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}",
        os.path.join(ROOT, "benchmark", kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def gen():
    return _load("data", "tpcds_data")


@pytest.fixture(scope="module")
def q():
    return _load("queries", "q06")


@pytest.fixture(scope="module")
def tables(gen, q):
    return gen.make_tables(q.TABLES, SCALE, DATA_SEED, SPLITS, SEED)


def kept_by_task(tables):
    """Sales rows whose item is priced above 1.2 times its category's
    average, a scan file: what the sales join's probe lets through to each
    map task's fold."""
    it = tables["item"].select(
        ["i_item_sk", "i_category", "i_current_price"]).to_pandas()
    avg = it.groupby("i_category").i_current_price.transform("mean")
    dear = set(it.i_item_sk[it.i_current_price > 1.2 * avg])
    hit = tables["store_sales"].column("ss_item_sk").to_pandas() \
        .isin(dear).to_numpy()
    per = -(-len(hit) // SPLITS)
    return [int(hit[i:i + per].sum()) for i in range(0, len(hit), per)]


@pytest.fixture(scope="module")
def run(gen, q, tables, tmp_path_factory):
    """One query on the device path: (answer, counters, spans, the
    capacities each stage-loop task's windows were handed, in order)."""
    import blaze_tpu.bridge.placement as P
    paths = gen.write_parquet_splits(
        tables, str(tmp_path_factory.mktemp("q06")), SPLITS, 8192)
    plan = q.plan(paths, tables, PARTITIONS)
    handed, lock = {}, threading.Lock()
    assemble = fused._assemble_window

    def watched(items, width):
        ctx = current_task()
        with lock:
            handed.setdefault((ctx.stage_id, ctx.partition_id), []).extend(
                m.shape[0] for _cols, m in items)
        return assemble(items, width)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(P, "host_resident", lambda: False)
        m.setattr(fused, "_assemble_window", watched)
        for key, value in ((config.DAG_SINGLE_TASK_BYTES.key, 0),
                           (config.MESH_DEVICES.key, 1),
                           (config.BATCH_SIZE.key, BATCH)):
            config.conf.set(key, value)
        try:
            assert effective_batch_size() == BATCH
            before = xla_stats.snapshot()
            tracing.start_tracing()
            try:
                with DagScheduler() as sched:
                    got = sched.run_collect(plan)
                    assert sched.exec_mode == "staged"
            finally:
                spans = tracing.stop_tracing()
            d = xla_stats.delta(before)
        finally:
            for key in (config.DAG_SINGLE_TASK_BYTES.key,
                        config.MESH_DEVICES.key, config.BATCH_SIZE.key):
                config.conf.unset(key)
    return got, d, spans, handed


def test_the_answer_is_the_oracles(q, tables, run):
    got, d, _spans, _handed = run
    ok, line = check.verdict(check.compare(got, q.oracle(tables), q.KEYS,
                                           q.ORDERED))
    assert ok, line
    assert got.num_rows == 12
    # the sales probe stays on the chip; the item table's join on its utf8
    # category is the host's, once a map task that builds the broadcast side
    items = tables["item"].num_rows
    assert d["join_probe_host_rows"] in [items * k for k in range(SPLITS + 1)]
    assert d["join_probe_device_rows"] == d["join_probe_direct_rows"] \
        == tables["store_sales"].num_rows
    assert d["stage_loop_fallbacks"] == 0


def test_every_row_the_probes_kept_left_through_the_tile_program(tables, run):
    _got, d, spans, _handed = run
    kept = sum(kept_by_task(tables))
    sales = tables["store_sales"].num_rows
    assert 0.35 * sales < kept < 0.5 * sales      # under half of every batch
    assert d["coalesce_tiled_rows"] == d["chip0_coalesce_tiled_rows"] == kept
    # the item side's host batches (a utf8 column) take `concat`, at this
    # batch size twice a build: the join's output and the filter's
    assert d["coalesce_concat_rows"] <= 2 * d["join_probe_host_rows"]
    lays = [s["attrs"] for s in spans if s["name"] == "coalesce"
            and s["attrs"]["lane"] == "tile"]
    assert sum(a["rows"] for a in lays) == kept
    # fewer programs than probe batches: a lay takes the two or three
    # batches whose rows reach a tile, and one more a task lays its tail;
    # the parent ran some ten eager ops a column a concat
    probes = len([s for s in spans if s["name"] == "join_probe"])
    assert probes >= -(-sales // BATCH)
    assert probes - SPLITS <= sum(a["batches"] for a in lays) <= probes
    tiles = kept // BATCH
    assert tiles - SPLITS <= len(lays) <= tiles + SPLITS < probes / 2


def test_the_fold_receives_tiles_of_one_batch_size_and_fills_them(tables, run):
    _got, d, spans, handed = run
    assert d["stage_loop_rows"] / d["stage_loop_lanes"] >= 0.85
    assert d["stage_loop_windows_fused"] == d["stage_loop_windows"] > 0
    assert all(s["attrs"]["padded"] == 0 for s in spans
               if s["name"] == "loop_window")
    # the map tasks are the four that were handed the probes' rows: every
    # batch of theirs, the tail too, has one batch size of lanes
    maps = [caps for caps in handed.values() if len(caps) > 1]
    assert len(maps) == SPLITS
    for caps in maps:
        assert set(caps) == {BATCH}
    assert sorted(len(caps) for caps in maps) \
        == sorted(-(-n // BATCH) for n in kept_by_task(tables))
    # what is left is the final aggregation's few rows
    rest = [caps for caps in handed.values() if len(caps) == 1]
    assert all(caps[0] <= 128 for caps in rest)


# -- the metric -------------------------------------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_both_q06_cells_list_the_share_and_its_file_reads_the_counters(name):
    cell = Cell(name, ROOT)
    specs = {m["name"]: (m, spec) for m, spec in cell.layer_metrics()}
    entry, spec = specs["coalesce_tiled_share"]
    assert entry == {"name": "coalesce_tiled_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "plan decode + per-task runtime",
                     "moves": "query_wall_s", "workloads": list(LISTED)}
    # held by name, not by place: the manifest has it once, as accepted
    assert [m for m in cell.manifest["per_layer"]
            if m["name"] == "coalesce_tiled_share"] == [entry]
    assert spec["manifest_source"] == entry["source"]
    assert entry["layer"] == specs["idle_coalesce_s"][0]["layer"]
    read = cell.module("sources", spec["source"]).read
    ctx = {"counters": {"coalesce_tiled_rows": 950,
                        "coalesce_concat_rows": 50}, "queries": 3}
    assert read(spec, ctx) == 95.0
    # a program without the counter (the parent), and a window in which
    # nothing was re-batched, have nothing to read
    assert read(spec, {"counters": {"stage_loop_rows": 7}, "queries": 3}) \
        is None
    assert read(spec, {"counters": {"coalesce_tiled_rows": 0,
                                    "coalesce_concat_rows": 0},
                       "queries": 3}) is None


def test_no_other_cell_lists_the_share():
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in manifest["workloads"]:
        names = [m["name"] for m, _s in Cell(w["name"], ROOT).layer_metrics()]
        assert ("coalesce_tiled_share" in names) == (w["name"] in LISTED)

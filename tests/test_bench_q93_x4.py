"""The benchmark's q93 on four chips (ISSUE 38): cell `sf1_q93_x4`,
configuration `tpcds-sf1-returns-x4`, entry
`benchmark/entries/dag_scheduler_smj_x4.py`.

On the CPU's virtual devices at scale 0.02, batches resident on the
devices as on the chip: both fact tables go through the mesh collective
as STAGED waves (their scans have no aggregation, so each map task's
batches come back as Arrow, are gathered on the host and cut evenly over
the devices), are sorted and merge-joined a partition a device, and the
answer is the pandas oracle's.  Also here: the spans that cut a
`device_exchange` in three, the counters that say where an exchange took
its rows from, the capacity ladder's one climb under skew, and the
cell's manifest entries and metric files."""

import gzip
import importlib.util
import json
import os
import shutil
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402
from benchmark.manifest import Cell, load_json  # noqa: E402
from blaze_tpu import config  # noqa: E402
from blaze_tpu.bridge import tracing, xla_stats  # noqa: E402
from blaze_tpu.memory import MemManager  # noqa: E402
from blaze_tpu.parallel.mesh import current_mesh, make_mesh  # noqa: E402
from blaze_tpu.parallel.stage import DeviceExchange  # noqa: E402

SCALE, DATA_SEED, SPLITS, PARTITIONS, CHIPS = 0.02, 20260927, 4, 4, 4
CELL, CONFIG = "sf1_q93_x4", "tpcds-sf1-returns-x4"
NEW = ("exchange_stage_s", "exchange_unstage_s", "exchange_staged_share")
TWINS = {"q93x4_exchange_collective_s": "exchange_collective_s",
         "q93x4_mesh_exchange_mb": "mesh_exchange_mb",
         "q93x4_exchange_roofline": "exchange_roofline",
         "q93x4_chip_busy_min_share": "chip_busy_min_share",
         "q93x4_smj_device_s": "smj_device_s"}
CUTS = ("device_exchange", "exchange_stage", "exchange_unstage")


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}",
        os.path.join(ROOT, "benchmark", kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def on_devices(monkeypatch):
    """Batches live on the devices, as on the chip, and every plan runs
    staged; `mesh(n)` sets how many devices tasks are placed on."""
    import blaze_tpu.bridge.placement as P
    monkeypatch.setattr(P, "host_resident", lambda: False)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    MemManager.init(4 << 30)

    def mesh(n: int):
        config.conf.set(config.MESH_DEVICES.key, n)
        return current_mesh().devices.reshape(-1)

    try:
        yield mesh
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)
        config.conf.unset(config.MESH_DEVICES.key)


@pytest.fixture(scope="module")
def case(tmp_path_factory):
    """(query module, paths, tables) of q93 at this file's scale."""
    gen, query = _load("data", "tpcds_returns"), _load("queries", "q93")
    tables = gen.make_tables(query.TABLES, SCALE, DATA_SEED, SPLITS,
                             2_900_000_123)
    paths = gen.write_parquet_splits(
        tables, str(tmp_path_factory.mktemp("q93x4")), SPLITS, 4096)
    return query, paths, tables


def _entry(case, tmp_path, name="dag_scheduler_smj_x4"):
    query, paths, tables = case
    return _load("entries", name).Entry(
        query, paths, tables, {"partitions": PARTITIONS, "chips": CHIPS},
        str(tmp_path))


def _one_query(entry):
    """(answer, counters' delta, spans) of one query through the entry."""
    before = xla_stats.snapshot()
    tracing.start_tracing()
    entry.begin()
    try:
        got = entry.run()
    finally:
        entry.end()
        spans = tracing.stop_tracing()
    return got, xla_stats.delta(before), spans


@pytest.fixture
def one_run(on_devices, case, tmp_path):
    """The entry's first query on four devices: (entry, answer, counters,
    spans, what `problem()` said, which also ran the full answer)."""
    on_devices(CHIPS)
    entry = _entry(case, tmp_path)
    got, moved, spans = _one_query(entry)
    return entry, got, moved, spans, entry.problem()


# -- (a) the entry end to end ------------------------------------------------

def test_the_entry_answers_as_the_oracle_and_finds_no_problem(one_run, case):
    query, _paths, tables = case
    entry, got, moved, _spans, why = one_run
    assert why is None
    ok, line = check.verdict(check.compare(got, query.oracle(tables),
                                           query.KEYS, query.ORDERED))
    assert ok, line
    # problem() held `plan_full` to `full_oracle`: a sum off by a cent's
    # millionth part, which the first 100 rows would let through, speaks
    plan, want = entry.full
    assert want.num_rows > got.num_rows > 50
    sums = want.column("sumsales").to_numpy().copy()
    sums[int(np.argmax(sums))] *= 1 + 1e-8
    entry.full = (plan, want.set_column(1, "sumsales", [sums]))
    said = entry.problem()
    assert said and "float_max_rel_err" in said and "EXCEEDED" in said
    # the deployment: four map tasks a fact table, one a device; both
    # fact tables over the mesh; nothing to files, nothing streamed
    chips_of = {}
    for (sid, _p), chip in entry.last["task_chips"].items():
        chips_of.setdefault(sid, set()).add(chip)
    four = [sid for sid, n in entry.last["tasks"].items() if n >= CHIPS]
    assert len(four) >= 3 and all(len(chips_of[s]) == CHIPS for s in four)
    assert moved["shuffle_device_exchanges"] == 4
    assert moved["shuffle_device_fallbacks"] == 0
    assert moved["shuffle_host_bytes"] == 0
    assert moved["cross_chip_bytes"] == 0
    assert moved["smj_streamed_runs"] == 0
    assert moved["smj_device_pairs"] == tables["store_returns"].num_rows
    # each chip sorted its sales partition where it lay (PR 42)
    assert moved["sort_resident_rows"] == moved["sort_device_rows"] \
        >= tables["store_sales"].num_rows
    assert all(moved[f"chip{c}_sort_resident_rows"] > 0
               for c in range(CHIPS))
    assert entry.fact_rows == sum(
        tables[n].num_rows for n in ("store_sales", "store_returns"))
    assert moved["shuffle_device_rows"] >= entry.fact_rows


def test_the_entry_speaks_when_the_fact_tables_go_to_files(on_devices, case,
                                                           tmp_path):
    on_devices(CHIPS)
    config.conf.set(config.SHUFFLE_DEVICE.key, "off")
    try:
        entry = _entry(case, tmp_path)
        _one_query(entry)
        why = entry.problem()
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)
    assert why and "went to shuffle files" in why


def test_the_entry_speaks_when_a_stage_runs_on_one_chip(on_devices, case,
                                                        tmp_path):
    on_devices(1)
    entry = _entry(case, tmp_path)
    _one_query(entry)
    why = entry.problem()
    assert why and "tasks on chips [0] of 4" in why


def test_the_entry_speaks_when_a_small_exchange_alone_crosses_the_mesh(
        one_run):
    entry, _got, _moved, _spans, _why = one_run
    entry._moved = dict(entry._moved, shuffle_device_rows=320)
    why = entry._not_the_deployment()
    assert why and f"the two fact tables hold {entry.fact_rows}" in why
    entry._moved = dict(entry._moved, shuffle_device_rows=entry.fact_rows,
                        cross_chip_bytes=8)
    assert "changed chip" in entry._not_the_deployment()
    entry._moved = dict(entry._moved, cross_chip_bytes=0,
                        shuffle_device_fallbacks=1)
    assert "fell back" in entry._not_the_deployment()
    entry._moved = dict(entry._moved, shuffle_device_fallbacks=0,
                        smj_streamed_runs=3)
    entry._streamed = 3
    assert "streamed 3 key runs" in entry.problem()


def test_the_entry_refuses_a_program_whose_tasks_have_no_chip(
        monkeypatch, case, tmp_path):
    monkeypatch.delattr(xla_stats, "chip_stats")
    with pytest.raises(RuntimeError, match="which chip a task ran on"):
        _entry(case, tmp_path)


# -- (b) where an exchange took its rows from ---------------------------------

def test_q93_stages_its_fact_tables_and_places_the_small_exchanges(one_run,
                                                                   case):
    """The two scans are staged waves: every fact row enters the
    collective from host columns.  The two exchanges after the join
    (partial sums by customer, then all of them to one partition) are
    waves of stage-loop tasks and start from the devices."""
    _query, _paths, tables = case
    _entry_, _got, moved, _spans, _why = one_run
    facts = tables["store_sales"].num_rows + tables["store_returns"].num_rows
    assert moved["shuffle_device_staged_rows"] == facts
    small = moved["shuffle_device_rows"] - facts
    assert 0 < moved["shuffle_device_placed_rows"] == small < facts // 50
    assert moved["shuffle_device_redispatches"] == 0


def test_q06_places_every_exchanged_row(on_devices, tmp_path):
    on_devices(CHIPS)
    gen, query = _load("data", "tpcds_data"), _load("queries", "q06")
    tables = gen.make_tables(query.TABLES, 0.01, DATA_SEED, SPLITS,
                             2_900_000_123)
    paths = gen.write_parquet_splits(tables, str(tmp_path), SPLITS, 4096)
    entry = _load("entries", "dag_scheduler_x4").Entry(
        query, paths, tables, {"partitions": PARTITIONS, "chips": CHIPS},
        str(tmp_path))
    _got, moved, spans = _one_query(entry)
    assert entry.problem() is None
    assert moved["shuffle_device_staged_rows"] == 0
    assert moved["shuffle_device_placed_rows"] \
        == moved["shuffle_device_rows"] > 0
    whole = [s for s in spans if s["name"] == "device_exchange"]
    assert whole and all(s["attrs"]["staged"] is False for s in whole)
    # the metric reads 0 here, and nothing on a parent without counters
    spec = load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  "exchange_staged_share.json"))
    counter = _load("sources", "counter")
    assert counter.read(spec, {"counters": moved, "queries": 1}) == 0.0
    parent = {k: v for k, v in moved.items()
              if k not in ("shuffle_device_staged_rows",
                           "shuffle_device_placed_rows")}
    assert counter.read(spec, {"counters": parent, "queries": 1}) is None


# -- (c) the spans that cut a device exchange ---------------------------------

def test_stage_and_unstage_lie_inside_their_exchange_and_do_not_overlap(
        one_run, case):
    _query, _paths, tables = case
    _entry_, _got, moved, spans, _why = one_run
    by = {n: [s for s in spans if s["name"] == n] for n in CUTS}
    assert len(by["device_exchange"]) == len(by["exchange_stage"]) \
        == len(by["exchange_unstage"]) == moved["shuffle_device_exchanges"]
    sales = tables["store_sales"].num_rows
    for whole in by["device_exchange"]:
        st, = [s for s in by["exchange_stage"]
               if s.get("parent") == whole["sid"]]
        un, = [s for s in by["exchange_unstage"]
               if s.get("parent") == whole["sid"]]
        assert st["tid"] == un["tid"] == whole["tid"]
        # stage opens with the exchange, unstage closes with it, and the
        # wait for the collective lies between them
        assert whole["t0_ns"] <= st["t0_ns"] < st["t1_ns"] <= un["t0_ns"] \
            < un["t1_ns"] <= whole["t1_ns"]
        assert st["t0_ns"] - whole["t0_ns"] < 2_000_000
        assert whole["t1_ns"] - un["t1_ns"] < 2_000_000
        a, sa, ua = whole["attrs"], st["attrs"], un["attrs"]
        assert a["rows"] == sa["rows"] == ua["rows"] > 0
        assert sa["stage"] == a["stage"] and sa["tasks"] == a["tasks"]
        assert sa["device"] == a["device"] and a["chips"] == CHIPS
        assert a["staged"] == (sa["staged_tasks"] > 0)
        assert sa["staged_tasks"] in (0, sa["tasks"])
        assert ua["partitions"] == a["partitions"]
        # the readback is whole buffers: never fewer bytes than the rows
        assert ua["bytes_read"] > sa["bytes"] > 0
        # a staged wave's one H2D is counted inside its staging: the
        # padded columns, their validity bytes and the row mask
        up = [s for s in spans if s["name"] == "h2d"
              and s.get("parent") == st["sid"]]
        assert len(up) == int(a["staged"])
        if a["rows"] == sales:
            # five columns, 36 B of values a sale
            assert sa["bytes"] == 36 * sales and sa["staged_tasks"] == 4
            assert up[0]["attrs"]["bytes"] >= (36 + 5 + 1) * sales
    assert sales in [s["attrs"]["rows"] for s in by["device_exchange"]]


# -- (d) the two ways into the collective, and the ladder ---------------------

def _task_columns(n_tasks, rows=700, seed=11):
    rng = np.random.default_rng(seed)
    out = []
    for t in range(n_tasks):
        n = rows + 37 * t       # ragged: every chip pads differently
        k = rng.integers(0, 300, n, dtype=np.int64)
        out.append(([k, rng.random(n)],
                    [rng.random(n) > 0.1, np.ones(n, dtype=bool)], n))
    return out


@pytest.mark.parametrize("n_tasks", [4, 3, 8])
def test_staged_and_placed_waves_give_the_same_partitions_row_for_row(
        n_tasks, on_devices):
    """The same ragged tasks as host columns (concatenated in the order
    the chips hold them, cut evenly) and as device columns on their
    chips: the partitions are equal row for row, and each way counts
    its rows under its own name."""
    devices = on_devices(CHIPS)
    mesh = make_mesh(CHIPS)
    tasks = _task_columns(n_tasks)
    rows = sum(t[2] for t in tasks)
    by_chip = sorted(range(n_tasks), key=lambda t: (t % CHIPS, t))
    cols = [np.concatenate([tasks[t][0][i] for t in by_chip])
            for i in range(2)]
    vals = [np.concatenate([tasks[t][1][i] for t in by_chip])
            for i in range(2)]
    placed = [([jax.device_put(c, devices[t % CHIPS]) for c in cs],
               [jax.device_put(v, devices[t % CHIPS]) for v in vs], n)
              for t, (cs, vs, n) in enumerate(tasks)]
    ex = DeviceExchange(mesh)
    before = xla_stats.shuffle_stats()
    staged = ex.drain(ex.dispatch(cols, vals, [0], 3))
    mid = xla_stats.shuffle_stats()
    ticket = ex.dispatch_placed(placed, [0], 3)
    ex.settle(ticket)
    assert ticket.settled and ticket.datas is None
    ex.settle(ticket)                      # a ticket is settled once
    from_chips = ex.drain(ticket)
    after = xla_stats.shuffle_stats()
    assert len(staged) == len(from_chips) == 3
    for (sd, sv), (pd_, pv) in zip(staged, from_chips):
        for s, p in zip(sd + sv, pd_ + pv):
            np.testing.assert_array_equal(np.asarray(s), np.asarray(p))
    assert sum(len(d[0]) for d, _v in staged) == rows
    assert ticket.read_bytes > rows * (8 + 8 + 2 + 4 + 1)

    def moved(a, b, key):
        return b[key] - a[key]

    assert moved(before, mid, "shuffle_device_staged_rows") == rows
    assert moved(before, mid, "shuffle_device_placed_rows") == 0
    assert moved(mid, after, "shuffle_device_placed_rows") == rows
    assert moved(mid, after, "shuffle_device_staged_rows") == 0
    assert moved(before, after, "shuffle_device_exchanges") == 2
    assert moved(before, after, "shuffle_device_redispatches") == 0


def test_keys_skewed_to_one_destination_climb_one_rung(on_devices):
    """4,000 rows of one key: 1,024 rows a device against a first rung of
    512 slots a destination (`exchangeSkew` 2.0 x 256), all for one
    destination, so the first collective overflows and the next rung
    (1,024: a device's every row) holds them."""
    on_devices(CHIPS)
    n = 4000
    ex = DeviceExchange(make_mesh(CHIPS))
    before = xla_stats.shuffle_stats()
    parts = ex.exchange([np.full(n, 7, dtype=np.int64),
                         np.arange(n, dtype=np.float64)],
                        [np.ones(n, dtype=bool)] * 2, [0], 4)
    after = xla_stats.shuffle_stats()
    sizes = sorted(len(d[0]) for d, _v in parts)
    assert sizes == [0, 0, 0, n]
    full = next(d for d, _v in parts if len(d[0]))
    np.testing.assert_array_equal(full[1], np.arange(n))
    assert after["shuffle_device_redispatches"] \
        - before["shuffle_device_redispatches"] == 1
    assert after["shuffle_device_exchanges"] \
        - before["shuffle_device_exchanges"] == 1
    # both rungs' send buffers rode: 16 x (512 + 1,024) slots of 23 B
    assert after["shuffle_device_bytes"] - before["shuffle_device_bytes"] \
        == 16 * (512 + 1024) * (8 + 8 + 2 + 4 + 1)


def test_a_gather_to_one_partition_starts_at_the_ceiling(on_devices):
    """q93's last exchange at scale 1: every chip holds 1,930 of the
    7,723 sums in 2,048 lanes and all of them go to the ONE partition,
    on one device.  A first rung sized for four destinations (1,024
    slots) overflowed once a query; sized for the destinations the
    exchange has, the first collective holds them."""
    devices = on_devices(CHIPS)
    tasks = [([jax.device_put(np.arange(1930, dtype=np.int64) + 10_000 * t,
                              devices[t])],
              [jax.device_put(np.ones(1930, dtype=bool), devices[t])], 1930)
             for t in range(CHIPS)]
    ex = DeviceExchange(make_mesh(CHIPS))
    before = xla_stats.shuffle_stats()
    ticket = ex.dispatch_placed(tasks, [0], 1)
    assert ticket.rungs == []        # the first rung is the last
    (datas, _valids), = ex.drain(ticket)
    after = xla_stats.shuffle_stats()
    np.testing.assert_array_equal(datas[0], np.concatenate(
        [np.arange(1930) + 10_000 * t for t in range(CHIPS)]))
    assert after["shuffle_device_redispatches"] \
        == before["shuffle_device_redispatches"]
    assert after["shuffle_device_bytes"] - before["shuffle_device_bytes"] \
        == 16 * 2048 * (8 + 1 + 4 + 1)
    # four partitions over four devices start where they did
    assert ex.dispatch_placed(tasks, [0], 4).rungs == [2048]


# -- (e) the manifest's entries and the metric files --------------------------

@pytest.fixture(scope="module")
def cell():
    return Cell(CELL, ROOT)


def test_the_cell_resolves_to_the_twins_tables_on_the_x4_deployment(cell):
    cfg = cell.config
    x1 = load_json(os.path.join(ROOT, "benchmark", "configs",
                                "tpcds-sf1-returns-x1.json"))
    x4 = load_json(os.path.join(ROOT, "benchmark", "configs",
                                "tpcds-sf1-x4.json"))
    assert cell.chips == 4 == cfg["chips"]
    assert cell.entry["config"] == CONFIG
    assert cell.entry["traffic"] == "closed1_q93_x4"
    assert cell.traffic == dict(cell.traffic, loop="closed", clients=1,
                                query="q93", entry="dag_scheduler_smj_x4",
                                trace_seconds=6)
    for key in ("generator", "scale", "data_seed", "tables", "splits",
                "partitions", "row_group_rows", "agg_table_slots"):
        assert cfg[key] == x1[key], key
    assert cfg["tables"] == {"store_sales": 2_880_404,
                             "store_returns": 287_514, "reason": 35}
    for key in ("generator", "nulls", "plan", "data_seed"):
        assert cfg["assumed"][key] == x1["assumed"][key], key
    assert cfg["program_settings"] == {} and cfg["guarantees"] \
        == x4["guarantees"] and len(cfg["guarantees"]) == 6
    for key, value in x4["deployment"].items():
        if key != "exchange":
            assert cfg["deployment"][key] == value, key
    # the one sentence that differs says what q93's scans do
    said = cfg["deployment"]["exchange"]
    assert "staged" in said and "exchange_staged_share" in said
    assert list(cfg["reduced"]) == ["scale"]
    entry = next(c for c in cell.manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["scale"]
    assert "query93.tpl" in entry["source"] and "v5e-4" in entry["source"]
    for text in (entry["source"], entry["why"], cell.entry["why"]):
        assert 0 < len(text) <= 200
    files = [c["file"] for c in cell.manifest["configs"]]
    assert len(files) == len(set(files))
    four = [w for w in cell.manifest["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == ["sf1_q06_x4", CELL]
    assert len(four) <= len(cell.manifest["workloads"]) // 2


def test_every_new_metric_has_its_file_its_cells_and_its_unit(cell):
    entries = {m["name"]: m for m in cell.manifest["per_layer"]}
    # appended together, in this order (later PRs append after them)
    at = list(entries).index(NEW[0])
    assert list(entries)[at:at + 8] == list(NEW) + list(TWINS)
    specs = dict((m["name"], spec) for m, spec in cell.layer_metrics())
    for name in NEW + tuple(TWINS):
        m, spec = entries[name], specs[name]
        assert m["workloads"] == (["sf1_q06_x4", CELL] if name in NEW
                                  else [CELL])
        assert spec["name"] == name and spec["unit"] == m["unit"]
        assert spec["better"] == m["better"] and spec["layer"] == m["layer"]
        assert spec["manifest_source"] == m["source"]
        assert m["moves"] == "query_wall_s" and set(m) == {
            "name", "unit", "better", "source", "layer", "moves",
            "workloads"}
        assert callable(cell.module("sources", spec["source"]).read)
    # a twin reads what the accepted metric reads, under the cell's name
    for name, twin_of in TWINS.items():
        old = load_json(os.path.join(cell.bench_dir, "layer_metrics",
                                     f"{twin_of}.json"))
        assert specs[name]["read"] == old["read"]
        assert specs[name]["source"] == old["source"]
        for key in ("unit", "better", "layer", "source"):
            assert entries[name][key] == entries[twin_of][key], (name, key)
        assert CELL not in entries[twin_of]["workloads"]
    # and every metric without a list reports here by itself
    listless = [m["name"] for m in cell.manifest["per_layer"]
                if "workloads" not in m]
    # (and `sort_resident_share`, which PR 42 listed this cell under)
    assert "sort_resident_share" in specs
    assert len(specs) == len(listless) + len(NEW) + len(TWINS) + 1


def test_the_new_span_and_counter_metrics_read_a_rehearsals_context(
        one_run, cell):
    _entry_, _got, moved, spans, _why = one_run
    specs = dict((m["name"], spec) for m, spec in cell.layer_metrics())
    ctx = {"spans": spans, "counters": moved, "queries": 1}

    def read(name, ctx):
        return cell.module("sources", specs[name]["source"]).read(
            specs[name], ctx)

    def seconds(name):
        return sum(s["dur_ns"] for s in spans if s["name"] == name) / 1e9

    stage, unstage = read("exchange_stage_s", ctx), \
        read("exchange_unstage_s", ctx)
    assert stage == pytest.approx(seconds("exchange_stage")) and stage > 0
    assert unstage == pytest.approx(seconds("exchange_unstage"))
    assert 0 < stage + unstage < seconds("device_exchange")
    share = read("exchange_staged_share", ctx)
    assert share == pytest.approx(
        100.0 * moved["shuffle_device_staged_rows"]
        / moved["shuffle_device_rows"])
    assert 99.0 < share < 100.0
    assert read("q93x4_mesh_exchange_mb", ctx) == pytest.approx(
        moved["shuffle_device_bytes"] / 1e6)
    # two queries halve a query's seconds; the parent has neither span
    assert read("exchange_stage_s", dict(ctx, queries=2)) \
        == pytest.approx(stage / 2)
    parent = dict(ctx, spans=[s for s in spans if s["name"] not in CUTS[1:]],
                  counters={})
    for name in NEW + ("q93x4_mesh_exchange_mb",):
        assert read(name, parent) is None


def test_the_readers_read_a_recorded_trace_of_four_chips(cell, tmp_path):
    """`tests/data/trace_q93_x4_v5e.json.gz`: what `run.py` kept of this
    cell's first traced run on a v5e-4 host (one query, four device
    planes, the program's spans), and what its result line read.  The
    five twins and the two span metrics read the same from it here."""
    from benchmark.sources import device_trace
    with gzip.open(os.path.join(ROOT, "tests", "data",
                                "trace_q93_x4_v5e.json.gz"), "rt") as f:
        rec = json.load(f)
    want = rec["expected"]
    assert len(rec["events"]["devices"]) == CHIPS
    trace = tmp_path / ".bench_work" / f"{CELL}.trace"
    trace.mkdir(parents=True)
    (trace / "trace_events.json").write_text(json.dumps(
        {k: rec[k] for k in ("events", "query_starts_ns", "spans")}))
    specs = dict((m["name"], spec) for m, spec in cell.layer_metrics())
    summary = device_trace.reduce(rec["events"], rec["spans"],
                                  rec["query_starts_ns"])
    ctx = {"trace": summary, "queries": len(rec["query_starts_ns"]),
           "spans": rec["spans"], "peaks": {"hbm_bytes_per_s": 819e9},
           "counters": {"shuffle_device_row_bytes":
                        want["shuffle_device_row_bytes"]}}

    def read(name, ctx):
        mod = cell.module("sources", specs[name]["source"])
        if specs[name]["source"] == "chips":
            return mod.read(specs[name], ctx, root=str(tmp_path))
        return mod.read(specs[name], ctx)

    for name in ("q93x4_exchange_collective_s", "q93x4_smj_device_s",
                 "q93x4_exchange_roofline", "q93x4_chip_busy_min_share",
                 "exchange_stage_s", "exchange_unstage_s"):
        assert read(name, ctx) == pytest.approx(want[name], rel=1e-9), name
    assert 0 < read("q93x4_exchange_roofline", ctx) <= 100
    # the three parts tile every device_exchange span of the recording
    by = {n: [s for s in rec["spans"] if s["name"] == n] for n in CUTS}
    assert len(by["device_exchange"]) == 4
    for whole in by["device_exchange"]:
        st, = [s for s in by["exchange_stage"]
               if s.get("parent") == whole["sid"]]
        un, = [s for s in by["exchange_unstage"]
               if s.get("parent") == whole["sid"]]
        wait = un["t0_ns"] - st["t1_ns"]
        assert wait > 0 and st["dur_ns"] + wait + un["dur_ns"] \
            == pytest.approx(whole["dur_ns"], rel=0.02)
    # a program without the collective or its counter has nothing to read
    quiet = dict(ctx, counters={},
                 trace={"programs": {"jit_fold_impl": 1.0}})
    assert read("q93x4_exchange_roofline", quiet) is None
    assert read("q93x4_exchange_collective_s", quiet) is None
    assert read("q93x4_smj_device_s", quiet) is None


# -- the whole cell through the harness ---------------------------------------

def test_the_cells_traced_line_holds_what_the_manifest_lists_for_it(
        on_devices, tmp_path):
    """`run.drive` over a copy of the benchmark whose configuration is cut
    to this file's scale, on four of the CPU's devices: correct, and every
    metric the manifest has for the cell that needs no device plane is in
    the traced run's line."""
    from benchmark import run as bench_run
    on_devices(CHIPS)
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
    res = bench_run.drive(cell, 2_900_000_123, 0.3, 1,
                          jax.devices()[:CHIPS],
                          peaks["devices"]["TPU v5 lite"],
                          time.perf_counter())
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == CHIPS
    listed = {m["name"]: m for m in cell.manifest["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    missing = set(listed) - set(res["metrics"])
    assert all(listed[name]["source"] == "device_trace" for name in missing)
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert set(NEW) | {"q93x4_mesh_exchange_mb"} <= set(got)
    assert got["exchange_stage_s"] > 0 and got["exchange_unstage_s"] > 0
    assert 99.0 < got["exchange_staged_share"] < 100.0
    assert got["shuffle_host_mb"] == 0 and got["stage_loop_fallbacks"] == 0
    assert got["compiles_in_window"] == 0
    assert got["q93x4_mesh_exchange_mb"] > 1.0

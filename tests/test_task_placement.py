"""Task placement (ISSUE 30): task p of every stage runs on device p mod N
of the dp mesh, everything a task places or creates lands there, the
device exchange starts from the chips the map output lies on, and the
answer does not depend on the number of devices.

The benchmark's own queries at scale 0.01 (benchmark/queries/*.py, their
pandas and pyarrow oracles: the plain reference, independent of the
program) on the CPU's 8 virtual devices, with batches resident on the
devices as they are on the chip."""

import importlib.util
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402
from blaze_tpu import config, faults  # noqa: E402
from blaze_tpu.bridge import tracing, xla_stats  # noqa: E402
from blaze_tpu.bridge.context import (TaskContext, attempt_scope,  # noqa: E402
                                      current_task, task_scope)
from blaze_tpu.memory import MemConsumer, MemManager  # noqa: E402
from blaze_tpu.ops import MemoryScanExec  # noqa: E402
from blaze_tpu.parallel.mesh import (current_mesh, make_mesh,  # noqa: E402
                                     task_device)
from blaze_tpu.parallel.stage import DeviceExchange  # noqa: E402
from blaze_tpu.plan.stages import DagScheduler  # noqa: E402
from blaze_tpu.xputil import on_task_chip, to_device  # noqa: E402

SCALE, DATA_SEED, SPLITS, PARTITIONS = 0.01, 20260927, 4, 4
GENERATOR = {"q06": "tpcds_data", "q01pair": "tpcds_data",
             "q93": "tpcds_returns"}


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}",
        os.path.join(ROOT, "benchmark", kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def clean_slate():
    faults.clear()
    MemManager.init(4 << 30)
    yield
    faults.clear()


@pytest.fixture
def on_devices(monkeypatch):
    """Batches live on the devices, as on the chip, and every plan runs
    staged; `mesh(n)` sets how many devices tasks are placed on."""
    import blaze_tpu.bridge.placement as P
    monkeypatch.setattr(P, "host_resident", lambda: False)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)

    def mesh(n: int):
        config.conf.set(config.MESH_DEVICES.key, n)
        return current_mesh().devices.reshape(-1)

    try:
        yield mesh
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)
        config.conf.unset(config.MESH_DEVICES.key)


@pytest.fixture(scope="module")
def cases(tmp_path_factory):
    """query name -> (query module, paths, tables, the oracle's answer)."""
    made = {}

    def case(name):
        if name not in made:
            gen, query = _load("data", GENERATOR[name]), \
                _load("queries", name)
            tables = gen.make_tables(query.TABLES, SCALE, DATA_SEED, SPLITS,
                                     2_900_000_123)
            paths = gen.write_parquet_splits(
                tables, str(tmp_path_factory.mktemp(name)), SPLITS, 4096)
            made[name] = (query, paths, tables, query.oracle(tables))
        return made[name]

    return case


def _run(name, query, paths, tables, work_dir):
    """(answer, the scheduler or None) through the entry point the
    benchmark gives the query."""
    if name == "q01pair":
        from benchmark.entries.runtime_pair import Entry
        entry = Entry(query, paths, tables, {"partitions": PARTITIONS},
                      str(work_dir))
        entry.begin()
        try:
            return entry.run(), None
        finally:
            entry.end()
    with DagScheduler() as sched:
        got = sched.run_collect(query.plan(paths, tables, PARTITIONS))
        assert sched.exec_mode == "staged"
        return got, sched


def _holds(got, want, query):
    ok, line = check.verdict(check.compare(got, want, query.KEYS,
                                           query.ORDERED))
    assert ok, line


# -- q06 on four devices ------------------------------------------------------

def test_q06_on_four_devices_every_device_works_and_no_row_strays(
        on_devices, cases, tmp_path):
    devices = on_devices(4)
    query, paths, tables, want = cases("q06")
    before = xla_stats.snapshot()
    tracing.start_tracing()
    try:
        with jax.transfer_guard_device_to_device("disallow"):
            got, sched = _run("q06", query, paths, tables, tmp_path)
    finally:
        spans = tracing.stop_tracing()
    moved = xla_stats.delta(before)
    _holds(got, want, query)
    assert got.num_rows == want.num_rows > 0
    # every device ran a map task, and task p ran on device p mod 4
    ids = [d.id for d in devices]
    map_sid = sched.stages[0].sid
    assert sched.stages[0].num_tasks == 4
    for (sid, p), chip in sched.task_chips.items():
        assert chip == ids[p % 4], (sid, p, chip)
    assert {chip for (sid, _p), chip in sched.task_chips.items()
            if sid == map_sid} == set(ids)
    # the exchange went over the mesh from where the map output lay, and
    # nothing changed device outside it
    assert sched.stage_placement[map_sid] == {"compute": "device-loop",
                                              "exchange": "device"}
    assert moved["shuffle_device_exchanges"] >= 1
    assert moved["shuffle_device_fallbacks"] == 0
    assert moved["cross_chip_bytes"] == 0
    assert moved["placed_tasks"] == len(sched.task_chips)
    assert moved["placed_tasks_off_chip0"] == sum(
        chip != 0 for chip in sched.task_chips.values())
    for d in ids:
        assert moved[f"chip{d}_tasks"] >= 1
        assert moved[f"chip{d}_h2d_bytes"] > 0
        assert moved[f"chip{d}_d2h_bytes"] > 0
        # each map task's sales were joined on its own chip
        assert moved[f"chip{d}_join_probe_device_rows"] > 0
    assert moved["join_probe_device_rows"] == tables["store_sales"].num_rows
    # item ⋈ its category's average has a utf8 key: through the host
    # (by each map task that finds the build side not made yet)
    items = tables["item"].num_rows
    assert moved["join_probe_host_rows"] in [items * n for n in (1, 2, 3, 4)]
    # the spans say where
    by_name = {}
    for s in spans:
        if s["name"] in ("task", "h2d", "d2h", "stage_loop_chunk",
                         "device_exchange"):
            by_name.setdefault(s["name"], set()).add(s["attrs"]["device"])
    assert by_name["task"] == by_name["h2d"] == by_name["d2h"] \
        == by_name["stage_loop_chunk"] == set(ids)
    assert by_name["device_exchange"] == {0}


def test_a_loop_tasks_drain_runs_under_an_agg_drain_span(
        on_devices, cases, tmp_path):
    """`drain_device` of a `mode=loop` map task used to run under `task`
    alone, so the chip's idle during it read as no operator's."""
    on_devices(4)
    query, paths, tables, _want = cases("q06")
    tracing.start_tracing()
    try:
        _run("q06", query, paths, tables, tmp_path)
    finally:
        spans = tracing.stop_tracing()
    loops = {s["sid"]: s for s in spans
             if s["name"] == "task" and s["attrs"]["mode"] == "loop"}
    drains = [s for s in spans if s["name"] == "agg_drain"
              and s["attrs"]["table"] == "loop"]
    assert len(loops) >= 4 and len(drains) == len(loops)
    assert {s["parent"] for s in drains} == set(loops)
    for s in drains:
        task = loops[s["parent"]]
        assert task["t0_ns"] <= s["t0_ns"] < s["t1_ns"] <= task["t1_ns"]
        assert s["tid"] == task["tid"]
    # and the family table gives the span to the aggregations
    import json
    with open(os.path.join(ROOT, "benchmark", "sources",
                           "op_families.json")) as f:
        assert "agg_drain" in json.load(f)["families"]["agg"]


# -- the mapping ---------------------------------------------------------------

@pytest.mark.parametrize("attempt", ["first", "retry", "speculative"])
def test_a_tasks_chip_is_its_partition_mod_the_devices(attempt, on_devices,
                                                       tmp_path):
    """A pure function of (partition id, devices): whichever attempt of a
    task runs, it runs on the task's chip."""
    from blaze_tpu.bridge.runtime import NativeExecutionRuntime
    from blaze_tpu.bridge.tasks import run_tasks
    devices = on_devices(4)
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"k": pa.array(range(64), type=pa.int64())}),
                   path)
    scan = {"kind": "parquet_scan", "file_groups": [[path]] * 6,
            "schema": {"fields": [{"name": "k", "type": {"id": "int64"},
                                   "nullable": True}]}}
    ran = []   # (partition, device of the runtime's task, device seen
    #            inside the task's scope)
    lock = threading.Lock()

    def body(p):
        token = threading.Event() if attempt == "speculative" else None
        with attempt_scope(token):
            rt = NativeExecutionRuntime(
                {"stage_id": 0, "partition_id": p, "num_partitions": 6,
                 "task_attempt_id": int(attempt == "speculative"),
                 "plan": scan}).start()
            try:
                rows = sum(rb.num_rows for rb in rt.batches())
            finally:
                rt.finalize()
        with task_scope(rt.task):
            inside = next(iter(jnp.zeros(1).devices()))
        with lock:
            first = all(q != p for q, _d, _i in ran)
            ran.append((p, rt.task.device, inside))
        if attempt == "retry" and first:
            raise ConnectionError("transient, by this test")
        return rows

    assert run_tasks(body, 6, 60.0, "placement") == [64] * 6
    assert len(ran) == (12 if attempt == "retry" else 6)
    for p, dev, inside in ran:
        assert dev == inside == devices[p % 4] == task_device(p)


def test_one_device_pins_nothing(on_devices):
    """With one device in the mesh a task has no chip, and what it places
    is placed as JAX places it by default: nothing changes."""
    on_devices(1)
    assert task_device(0) is None and task_device(3) is None
    assert current_task().device is None and current_task().device_id == 0
    with task_scope(TaskContext(partition_id=3, device=task_device(3))):
        placed = to_device(np.arange(4))
        assert placed.devices() == {jax.devices()[0]}
        assert not placed.committed
        assert on_task_chip(placed) is placed


def test_a_prefetch_worker_places_on_its_tasks_chip(on_devices):
    from blaze_tpu.ops.base import PrefetchIterator
    dev = on_devices(4)[2]
    seen = []

    def place(a):
        seen.append(threading.current_thread().name)
        return to_device(a), jnp.zeros(4), current_task().device

    with task_scope(TaskContext(partition_id=2, device=dev)):
        got = list(PrefetchIterator(iter([np.arange(8), np.arange(8)]),
                                    depth=2, transform=place, name="chip"))
    assert seen == ["blaze-prefetch-chip"] * 2
    for placed, made, task_dev in got:
        assert task_dev == dev
        assert placed.devices() == made.devices() == {dev}
        assert placed.committed   # a committed input pins its programs
    # outside any task nothing is pinned
    assert to_device(np.arange(8)).devices() == {jax.devices()[0]}


def test_an_operand_found_on_another_chip_is_moved_and_counted(on_devices):
    devices = on_devices(4)
    stray = jax.device_put(np.arange(1000, dtype=np.int64), devices[0])
    home = jax.device_put(np.arange(1000, dtype=np.int64), devices[2])
    before = xla_stats.placement_stats()["cross_chip_bytes"]
    with task_scope(TaskContext(partition_id=2, device=devices[2])):
        a, b = on_task_chip((stray, home))
        assert b is home and a.devices() == {devices[2]}
        # a metered program does the same to its operands
        from blaze_tpu.bridge.xla_stats import meter_jit
        out = meter_jit(lambda x, y: x + y, name="test.add")(stray, home)
    assert out.devices() == {devices[2]}
    np.testing.assert_array_equal(np.asarray(out), 2 * np.arange(1000))
    assert xla_stats.placement_stats()["cross_chip_bytes"] - before \
        == 2 * 8000


# -- the layout does not change the answer ------------------------------------

@pytest.mark.parametrize("name", ["q06", "q01pair", "q93"])
def test_the_answer_is_the_same_on_one_two_and_four_devices(
        name, on_devices, cases, tmp_path):
    query, paths, tables, want = cases(name)
    answers = {}
    for n in (1, 2, 4):
        ids = [d.id for d in on_devices(n)]
        before = xla_stats.snapshot()
        got, _sched = _run(name, query, paths, tables, tmp_path)
        moved = xla_stats.delta(before)
        _holds(got, want, query)
        assert moved["cross_chip_bytes"] == 0
        assert {d for d in range(8) if moved.get(f"chip{d}_tasks")} \
            == set(ids)
        answers[n] = got
    assert answers[1].num_rows == want.num_rows > 0
    for n in (2, 4):
        _holds(answers[n], answers[1], query)


# -- the exchange ---------------------------------------------------------------

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
def test_exchange_from_four_devices_returns_the_one_device_partitions(
        n_tasks, on_devices):
    """Map outputs on the devices their tasks ran on: the partitions are
    the ones the concatenation on one device gives, row for row (tasks in
    the order the chips hold them)."""
    devices = on_devices(4)
    mesh = make_mesh(4)
    tasks = _task_columns(n_tasks)
    placed = [([jax.device_put(c, devices[t % 4]) for c in cols],
               [jax.device_put(v, devices[t % 4]) for v in vals], n)
              for t, (cols, vals, n) in enumerate(tasks)]
    by_chip = sorted(range(n_tasks), key=lambda t: (t % 4, t))
    cols = [np.concatenate([tasks[t][0][i] for t in by_chip])
            for i in range(2)]
    vals = [np.concatenate([tasks[t][1][i] for t in by_chip])
            for i in range(2)]
    before = xla_stats.snapshot()
    ex = DeviceExchange(mesh)
    with jax.transfer_guard_device_to_device("disallow"):
        got = ex.drain(ex.dispatch_placed(placed, [0], 3))
    moved = xla_stats.delta(before)
    want = DeviceExchange(make_mesh(1)).exchange(
        [jnp.asarray(c) for c in cols], [jnp.asarray(v) for v in vals],
        [0], 3)
    assert len(got) == len(want) == 3
    for (gd, gv), (wd, wv) in zip(got, want):
        for g, w in zip(gd + gv, wd + wv):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert sum(len(d[0]) for d, _v in got) == sum(t[2] for t in tasks)
    assert moved["shuffle_device_rows"] == sum(t[2] for t in tasks)
    assert moved["cross_chip_bytes"] == 0


def test_a_column_on_the_wrong_device_is_moved_by_the_exchange_and_counted(
        on_devices):
    devices = on_devices(4)
    (cols, vals, n), = _task_columns(1)
    placed = [([jax.device_put(cols[0], devices[1]),
                jax.device_put(cols[1], devices[3])],
               [jax.device_put(v, devices[1]) for v in vals], n)]
    before = xla_stats.placement_stats()["cross_chip_bytes"]
    ex = DeviceExchange(make_mesh(4))
    got = ex.drain(ex.dispatch_placed(placed, [0], 3))
    assert sum(len(d[0]) for d, _v in got) == n
    assert xla_stats.placement_stats()["cross_chip_bytes"] - before \
        == cols[1].nbytes


def test_collective_fault_on_four_devices_falls_back_to_the_file_shuffle(
        on_devices, cases, tmp_path):
    on_devices(4)
    query, paths, tables, want = cases("q06")
    before = xla_stats.snapshot()
    with faults.scoped(("device-collective", dict(at=(2,)))):
        got, sched = _run("q06", query, paths, tables, tmp_path)
    moved = xla_stats.delta(before)
    _holds(got, want, query)
    assert moved["shuffle_device_fallbacks"] == 1
    assert moved["shuffle_host_bytes"] > 0
    assert "file" in {p["exchange"] for p in sched.stage_placement.values()}
    assert all(v == [] for v in sched.leak_report().values())


# -- a broadcast build side, one copy a chip --------------------------------------

class _ScansInsideTheTask(MemoryScanExec):
    """Arrow in, placed when the task pulls it: on the task's chip, as a
    parquet scan's batches are."""

    def __init__(self, table: pa.Table, partitions: int):
        from blaze_tpu.schema import Schema
        super().__init__(Schema.from_arrow(table.schema),
                         [[] for _ in range(partitions)])
        self._slices = [table.slice(p * table.num_rows // partitions,
                                    table.num_rows // partitions)
                        for p in range(partitions)]

    def execute(self, partition: int):
        from blaze_tpu.batch import ColumnBatch
        for rb in self._slices[partition].to_batches(max_chunksize=1024):
            yield ColumnBatch.from_arrow(rb)


def test_a_broadcast_build_side_is_placed_once_a_chip(on_devices):
    """Eight tasks on four chips probe one shared `JoinMap` at once: each
    chip gets its own copy of the build side, placed by the first task
    that asks there and charged to that chip's budget, and no row changes
    chip on its way through the join."""
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.joins import BroadcastJoinExec, JoinType
    devices = on_devices(4)
    rng = np.random.default_rng(11)
    build = pa.table({"bk": pa.array(rng.permutation(3000)[:700]),
                      "bv": pa.array(rng.random(700))})
    probe = pa.table({"pk": pa.array(rng.integers(0, 3000, 16384)),
                      "pv": pa.array(np.arange(16384))})
    plan = BroadcastJoinExec(_ScansInsideTheTask(probe, 8),
                             MemoryScanExec.from_arrow(build),
                             [col(0)], [col(0)], JoinType.INNER)
    mgr = MemManager.get()
    start = threading.Barrier(8)
    got = [None] * 8

    def task(p):
        with task_scope(TaskContext(partition_id=p, num_partitions=8,
                                    device=task_device(p))):
            start.wait(timeout=60)
            got[p] = [b.to_arrow() for b in plan.execute(p)]

    before = xla_stats.snapshot()
    threads = [threading.Thread(target=task, args=(p,)) for p in range(8)]
    with jax.transfer_guard_device_to_device("disallow"):
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    moved = xla_stats.delta(before)
    joined = pa.Table.from_batches([rb for part in got for rb in part]) \
        .to_pandas().sort_values("pv").reset_index(drop=True)
    want = probe.to_pandas().merge(build.to_pandas(), left_on="pk",
                                   right_on="bk").sort_values("pv") \
        .reset_index(drop=True)
    assert joined.equals(want) and len(want) > 0
    assert moved["cross_chip_bytes"] == 0
    assert moved["join_probe_device_rows"] == 16384
    copies = plan._get_join_map(0)._on_device
    assert sorted(copies) == sorted(d.id for d in devices)
    charged = set()
    for d in devices:
        held = copies[d.id].held
        for a in (held.uh, held.urow, *held.keys, *held.direct,
                  *(x for dv in held.cols for x in dv)):
            assert a.devices() == {d}
        assert copies[d.id].chip == d.id
        assert moved[f"chip{d.id}_join_probe_device_rows"] == 4096
        charged.add(mgr.chip_used(d.id))
    # the same bytes on every chip, and they are the chip's to shed
    assert len(charged) == 1 and charged.pop() > 700 * 16
    with task_scope(TaskContext(partition_id=1, device=devices[1])):
        assert copies[devices[1].id].spill() > 0
        assert mgr.chip_used(devices[1].id) == 0
        again = plan._get_join_map(0).on_device()   # placed anew, there
        assert again.uh.devices() == {devices[1]}
        assert again.direct[0].devices() == {devices[1]}
    assert mgr.chip_used(devices[1].id) == mgr.chip_used(devices[0].id)


def test_the_direct_index_is_charged_to_its_chip_and_placed_again(
        on_devices, monkeypatch):
    """`drow` (4 B an entry of the key range, padded to a power of two)
    is part of what the build side's copy holds against its chip's
    budget; shed, the next probe there places it again."""
    from blaze_tpu.exprs import col
    from blaze_tpu.ops.joins.exec import JoinMap
    from blaze_tpu.schema import Schema
    devices = on_devices(4)
    rng = np.random.default_rng(12)
    build = pa.table({"bk": pa.array(5000 + rng.permutation(3000)[:700]),
                      "bv": pa.array(rng.random(700))})
    mgr = MemManager.get()

    def placed(chip):
        jmap = JoinMap(build, [col(0)], Schema.from_arrow(build.schema))
        with task_scope(TaskContext(partition_id=chip,
                                    device=devices[chip])):
            held = jmap.on_device()
        return jmap, held, mgr.chip_used(devices[chip].id)

    jmap, held, with_drow = placed(2)
    drow, kmin = held.direct
    assert drow.dtype == np.int32 and drow.shape == (4096,)
    assert int(kmin) == build["bk"].to_numpy().min() and kmin.shape == ()
    rows = np.asarray(drow)
    keys = build["bk"].to_numpy()
    assert np.array_equal(np.flatnonzero(rows >= 0), np.sort(keys) - kmin)
    assert np.array_equal(keys[rows[rows >= 0]], np.sort(keys))
    copy = jmap._on_device[devices[2].id]
    assert copy.chip == devices[2].id and copy.mem_used == with_drow
    with monkeypatch.context() as m:
        m.setattr(JoinMap, "direct_key", None)
        _jmap, searched, without = placed(3)
    assert searched.direct is None
    assert with_drow - without == 4 * 4096
    with task_scope(TaskContext(partition_id=2, device=devices[2])):
        assert copy.spill() == with_drow
        assert mgr.chip_used(devices[2].id) == 0 and copy.held is None
        again = jmap.on_device()
    assert again.direct[0].devices() == {devices[2]}
    assert np.array_equal(np.asarray(again.direct[0]), rows)
    assert mgr.chip_used(devices[2].id) == with_drow


# -- one budget a chip ----------------------------------------------------------

class _Spills(MemConsumer):
    def __init__(self, name):
        super().__init__(name)
        self.spilled = 0

    def spill(self) -> int:
        released, self._mem_used = self._mem_used, 0
        self.spilled += 1
        return released


def test_the_memory_budget_is_per_chip(on_devices):
    devices = on_devices(4)
    mgr = MemManager(1000)
    held = {}
    for chip in (0, 1):
        with task_scope(TaskContext(partition_id=chip,
                                    device=devices[chip])):
            held[chip] = _Spills(f"c{chip}")
            held[chip].set_spillable(mgr)
            held[chip].update_mem_used(900)
    # 1,800 bytes in all, 900 a chip: neither chip is over its budget
    assert [held[c].chip for c in (0, 1)] == [devices[0].id, devices[1].id]
    assert [held[c].spilled for c in (0, 1)] == [0, 0]
    assert mgr.chip_used(devices[0].id) == mgr.chip_used(devices[1].id) \
        == 900
    # pressure on chip 1 is chip 1's alone
    with task_scope(TaskContext(partition_id=5, device=devices[1])):
        more = _Spills("c1-more")
        more.set_spillable(mgr)
        more.update_mem_used(400)
    assert held[0].spilled == 0 and held[0].mem_used == 900
    assert held[1].spilled + more.spilled >= 1
    assert mgr.chip_used(devices[1].id) <= 1000

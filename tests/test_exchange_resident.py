"""The exchange's resident tier (shuffle/writer.py's resident lane,
shuffle/reader.py's `ResidentBlock`, plan/stages.py `_resident_tier`)
against the file tier, row for row and in order.

The file tier is the exchange as the CPU's default placement runs it:
every batch read to Arrow, counting-sorted by partition id on the host,
written as IPC frames and read back.  The resident tier is the same plan
with `placement.host_resident` patched to false and ONE device in the mesh,
as the scheduler sees one chip (tests/test_sort_device.py patches the
placement the same way): a map task's batches are laid partition-major
where they lie (`jit__partition_tile__exchange_partition`), committed as
device arrays, and a reduce task lays its runs into tiles
(`jit__lay_runs__exchange_lay`), all as jitted programs on the CPU backend
here.  Every table carries `rid`, a row's place on arrival, so equal `rid`
sequences say equal order.
"""

import contextlib
import os
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import blaze_tpu.bridge.placement as P
from blaze_tpu import config, faults
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.bridge import xla_stats
from blaze_tpu.bridge.resource import put_resource, remove_resource
from blaze_tpu.exprs import col
from blaze_tpu.faults import FetchFailedError
from blaze_tpu.memory import MemManager
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.plan.stages import DagScheduler
from blaze_tpu.shuffle import (HashPartitioning, RoundRobinPartitioning,
                               ShuffleWriterExec)
from blaze_tpu.shuffle.reader import (FileSegmentBlock, IpcReaderExec,
                                      ResidentBlock, read_block)
from blaze_tpu.shuffle.writer import RESIDENT_SINK, ResidentMapOutput

ROWS = 6000            # a file; two files, so two map tasks
BATCH = 512            # several batches a map task, several tiles a reader
PARTS = 3


@contextlib.contextmanager
def one_chip(**conf):
    """Batches on a device and ONE device in the mesh, the mesh tier
    declining as it does where one device is visible; every query staged."""
    keys = {config.MESH_DEVICES.key: 1, config.SHUFFLE_DEVICE.key: "off",
            config.DAG_SINGLE_TASK_BYTES.key: 0,
            config.BATCH_SIZE.key: BATCH, **conf}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(P, "host_resident", lambda: False)
        for k, v in keys.items():
            config.conf.set(k, v)
        try:
            yield
        finally:
            for k in keys:
                config.conf.unset(k)


@contextlib.contextmanager
def on_files(**conf):
    """The same settings on the CPU's own placement: the file tier."""
    keys = {config.DAG_SINGLE_TASK_BYTES.key: 0,
            config.BATCH_SIZE.key: BATCH, **conf}
    for k, v in keys.items():
        config.conf.set(k, v)
    try:
        yield
    finally:
        for k in keys:
            config.conf.unset(k)


def _nulls(values, rng, share=0.1):
    drop = rng.random(len(values)) < share
    return [None if d else v for v, d in zip(values.to_pylist(), drop)]


def _table(seed: int, first_rid: int, strings=None) -> pa.Table:
    rng = np.random.default_rng(seed)
    n = ROWS
    words = strings if strings is not None else \
        [f"w{i}" for i in rng.integers(0, 12, n)]
    return pa.table({
        "i32": pa.array(_nulls(pa.array(rng.integers(-50, 50, n)
                                        .astype(np.int32)), rng),
                        type=pa.int32()),
        "i64": pa.array(_nulls(pa.array(rng.integers(-2**40, 2**40, n)),
                               rng), type=pa.int64()),
        "f64": pa.array(_nulls(pa.array(rng.random(n)), rng),
                        type=pa.float64()),
        "d": pa.array(_nulls(pa.array(rng.integers(0, 20000, n)
                                      .astype(np.int32)), rng),
                      type=pa.int32()).cast(pa.date32()),
        "dec": pa.array([None if v is None else Decimal(v).scaleb(-2)
                         for v in _nulls(pa.array(
                             rng.integers(-99999, 99999, n)), rng)],
                        type=pa.decimal128(7, 2)),
        "s": pa.array(_nulls(pa.array(words), rng), type=pa.utf8()),
        "rid": pa.array(np.arange(first_rid, first_rid + n,
                                  dtype=np.int64))})


def _schema_dict(t: pa.Table) -> dict:
    from blaze_tpu.plan.types import schema_to_dict
    from blaze_tpu.schema import Schema
    return schema_to_dict(Schema.from_arrow(t.schema))


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """(paths, schema): two files of every carried type, a tenth NULL."""
    root = tmp_path_factory.mktemp("tables")
    tables = [_table(11, 0), _table(12, ROWS)]
    paths = []
    for i, t in enumerate(tables):
        paths.append(str(root / f"in-{i}.parquet"))
        pq.write_table(t, paths[-1], row_group_size=2048)
    return paths, _schema_dict(tables[0])


def c(name):
    return {"kind": "column", "name": name}


def _partitioning(kind: str, keys=("i64", "s")) -> dict:
    if kind == "hash":
        return {"kind": "hash", "exprs": [c(k) for k in keys],
                "num_partitions": PARTS}
    return {"kind": "round_robin", "num_partitions": PARTS}


def _plan(files, partitioning: dict, filtered: bool, through: str) -> dict:
    """scan [-> filter, which leaves a selection mask] -> exchange
    [-> project: a consumer that pulls `execute()`; the bare reader is
    pulled through `arrow_batches()`]."""
    paths, schema = files
    node = {"kind": "parquet_scan", "schema": schema,
            "file_groups": [[p] for p in paths]}
    if filtered:
        node = {"kind": "filter", "input": node, "predicates": [
            {"kind": "binary", "op": ">", "l": c("i32"),
             "r": {"kind": "literal", "value": -20,
                   "type": {"id": "int32"}}}]}
    node = {"kind": "local_exchange", "partitioning": partitioning,
            "input": node}
    if through == "execute":
        names = [f["name"] for f in schema["fields"]]
        node = {"kind": "project", "input": node,
                "exprs": [c(n) for n in names], "names": names}
    return node


def _run(plan, tmp_path, name):
    sched = DagScheduler(work_dir=str(tmp_path / name))
    before = xla_stats.snapshot()
    got = sched.run_collect(plan)
    return got, xla_stats.delta(before), sched


def _plain(t: pa.Table) -> pa.Table:
    return t.combine_chunks()


# -- (a) the same rows in the same order --------------------------------------

@pytest.mark.parametrize("through", ["execute", "arrow_batches"])
@pytest.mark.parametrize("filtered", [False, True],
                         ids=["whole", "selection"])
@pytest.mark.parametrize("kind", ["hash", "round_robin"])
def test_every_reduce_partition_gets_the_file_tiers_rows_in_its_order(
        files, tmp_path, kind, filtered, through):
    plan = _plan(files, _partitioning(kind), filtered, through)
    with on_files():
        want, moved, _ = _run(plan, tmp_path, "files")
    assert moved["shuffle_file_rows"] == want.num_rows
    assert moved["shuffle_resident_rows"] == 0
    with one_chip():
        got, moved, sched = _run(plan, tmp_path, "chip")
    # the result is partition 0's rows, then partition 1's, ...: one
    # sequence says every partition's rows and their order
    assert got.column("rid").to_pylist() == want.column("rid").to_pylist()
    assert _plain(got).equals(_plain(want))
    assert moved["shuffle_resident_rows"] == want.num_rows
    assert moved["shuffle_file_rows"] == moved["shuffle_host_bytes"] == 0
    assert sched.stage_placement[0]["exchange"] == "resident"
    assert all(v == [] for v in sched.leak_report().values())
    assert xla_stats.fallback_errors() == []


def test_a_hash_on_one_int_key_and_many_partitions(files, tmp_path):
    part = {"kind": "hash", "exprs": [c("i32")], "num_partitions": 37}
    plan = _plan(files, part, True, "execute")
    with on_files():
        want, _, _ = _run(plan, tmp_path, "files")
    with one_chip():
        got, moved, _ = _run(plan, tmp_path, "chip")
    assert got.column("rid").to_pylist() == want.column("rid").to_pylist()
    assert moved["shuffle_resident_rows"] == want.num_rows


# -- (b) a column the chip does not carry -------------------------------------

def test_a_map_task_with_plain_utf8_writes_files_among_resident_blocks(
        tmp_path):
    """File 1's strings outgrow the scan's dictionary after its first
    batches, so map task 1 meets a plain utf8 column mid-way: what it held
    on the chip is staged with the rest and its WHOLE output goes through
    the file lane; map task 0's stays resident, and the reader takes the
    mixed list."""
    tables = [_table(21, 0), _table(22, ROWS,
                                    [f"only-{i}" for i in range(ROWS)])]
    paths = []
    for i, t in enumerate(tables):
        paths.append(str(tmp_path / f"in-{i}.parquet"))
        pq.write_table(t, paths[-1], row_group_size=2048)
    files = paths, _schema_dict(tables[0])
    plan = _plan(files, _partitioning("hash", ("i64",)), False, "execute")
    cap = {config.ENCODING_DICT_MAX_ENTRIES.key: 2 * BATCH}
    with on_files(**cap):
        want, _, _ = _run(plan, tmp_path, "files")
    with one_chip(**cap):
        got, moved, sched = _run(plan, tmp_path, "chip")
    assert got.column("rid").to_pylist() == want.column("rid").to_pylist()
    assert _plain(got).equals(_plain(want))
    assert moved["shuffle_resident_rows"] == ROWS
    assert moved["shuffle_file_rows"] == ROWS
    assert moved["shuffle_host_bytes"] == moved["shuffle_file_bytes"] > 0
    assert sched.stage_placement[0]["exchange"] == "mixed"


# -- the writer and the reader alone ------------------------------------------

def _scan(table: pa.Table):
    return MemoryScanExec.from_arrow(table, num_partitions=1,
                                     batch_rows=BATCH)


def _write(table, partitioning, tmp_path, name, resident: bool):
    """One map task's output: (`ResidentMapOutput` or None, data, index)."""
    data, index = (str(tmp_path / f"{name}.data"),
                   str(tmp_path / f"{name}.index"))
    committed = []

    def sink(output):
        committed.append(output)
        return True

    if resident:
        put_resource(RESIDENT_SINK + data, sink)
    try:
        writer = ShuffleWriterExec(_scan(table), partitioning, data, index)
        list(writer.execute(0))
    finally:
        remove_resource(RESIDENT_SINK + data)
    return (committed[0] if committed else None), data, index


def _read(blocks_by_partition, schema, through: str):
    """Every partition's rows through an `IpcReaderExec`, as one table."""
    rid = f"test-exchange-resident-{id(blocks_by_partition)}"
    put_resource(rid, lambda p: blocks_by_partition[p])
    try:
        reader = IpcReaderExec(rid, schema, PARTS)
        out = []
        for p in range(PARTS):
            if through == "execute":
                out += [b.compact().to_arrow() for b in reader.execute(p)]
            else:
                out += list(reader.arrow_batches(p))
    finally:
        remove_resource(rid)
    return pa.Table.from_batches(out)


def _hash():
    return HashPartitioning([col(1), col(5)], PARTS)


def _segments(data: str, index: str, stage_id: int = 0, map_id: int = 0):
    """A committed `.data` / `.index` pair as one block a partition."""
    from blaze_tpu.shuffle.exchange import read_index_file
    offsets = read_index_file(index, expected_partitions=PARTS,
                              data_file=data)
    return [[FileSegmentBlock(data, offsets[p], offsets[p + 1] - offsets[p],
                              stage_id, map_id)] for p in range(PARTS)]


# -- (d) spill ----------------------------------------------------------------

@pytest.mark.parametrize("partitioning", [_hash,
                                          lambda: RoundRobinPartitioning(
                                              PARTS)],
                         ids=["hash", "round_robin"])
def test_a_spilled_output_is_the_file_lanes_pair_byte_for_byte(
        tmp_path, partitioning):
    table = _table(31, 0)
    with one_chip():
        _none, data, index = _write(table, partitioning(), tmp_path,
                                    "file", resident=False)
        output, rdata, rindex = _write(table, partitioning(), tmp_path,
                                       "resident", resident=True)
        assert _none is None and isinstance(output, ResidentMapOutput)
        assert not os.path.exists(rdata) and output.on_chip
        assert output.mem_used == output.nbytes > 0
        before = xla_stats.snapshot()
        released = output.spill()
        moved = xla_stats.delta(before)
        assert released == output.nbytes and output.mem_used == 0
        assert not output.on_chip and output.spill() == 0
        for ours, theirs in ((rdata, data), (rindex, index)):
            with open(ours, "rb") as a, open(theirs, "rb") as b:
                assert a.read() == b.read()
        assert moved["shuffle_spilled_rows"] == ROWS
        assert moved["shuffle_host_bytes"] == os.path.getsize(rdata) \
            == moved["shuffle_spilled_bytes"]
        # the reduce side reads what the spill wrote
        blocks = [[output.block(p, 0, 0)] for p in range(PARTS)]
        assert all(isinstance(b, FileSegmentBlock) for (b,) in blocks)
        got = _read(blocks, output.schema, "execute")
        output.release()
    assert sorted(got.column("rid").to_pylist()) == list(range(ROWS))


def test_memory_pressure_spills_the_lane_before_the_commit(tmp_path):
    """An injected pressure round at the map task's third batch: what the
    lane held joins the staged rows in a spill file and the task writes
    files from there on, the files of a task that never took the lane and
    was pressed at the same batch, byte for byte."""
    table = _table(32, 0)
    with one_chip():
        with faults.scoped(("mem-pressure", dict(at=(3,)))):
            _none, data, index = _write(table, _hash(), tmp_path, "file",
                                        resident=False)
        with faults.scoped(("mem-pressure", dict(at=(3,)))):
            output, rdata, rindex = _write(table, _hash(), tmp_path,
                                           "pressed", resident=True)
        assert output is None
        got = _read(_segments(rdata, rindex), _scan(table).schema,
                    "execute")
    for ours, theirs in ((rdata, data), (rindex, index)):
        with open(ours, "rb") as a, open(theirs, "rb") as b:
            assert a.read() == b.read()
    assert sorted(got.column("rid").to_pylist()) == list(range(ROWS))


@pytest.mark.parametrize("through", ["execute", "arrow_batches"])
def test_the_reader_takes_resident_and_file_blocks_in_one_list(
        tmp_path, through):
    """Map 0 resident, map 1 on files, map 2 resident: a reduce task's rows
    are map 0's, then map 1's, then map 2's, each in arrival order."""
    tables = [_table(41 + m, m * ROWS) for m in range(3)]
    with one_chip():
        outs = [_write(t, _hash(), tmp_path, f"m{m}", resident=m != 1)
                for m, t in enumerate(tables)]
        on_disk = _segments(outs[1][1], outs[1][2], 7, 1)
        blocks = [[outs[0][0].block(p, 7, 0), on_disk[p][0],
                   outs[2][0].block(p, 7, 2)] for p in range(PARTS)]
        assert isinstance(blocks[0][0], ResidentBlock)
        got = _read(blocks, outs[0][0].schema, through)
        for m in (0, 2):
            outs[m][0].release()
    with on_files():
        want = []
        for p in range(PARTS):
            for t in tables:
                pids = _hash().partition_ids(ColumnBatch.from_arrow(
                    MemoryScanExec.from_arrow(t).execute_collect()
                    .to_arrow()))
                want += [r for r, q in zip(t.column("rid").to_pylist(),
                                           pids) if q == p]
    assert got.column("rid").to_pylist() == want


# -- (c) a lost block names its map task --------------------------------------

def test_an_injected_read_fault_on_a_resident_block_names_its_map_task(
        tmp_path):
    with one_chip():
        output, _d, _i = _write(_table(51, 0), _hash(), tmp_path, "m",
                                resident=True)
        block = output.block(1, 4, 2)
        before = xla_stats.fault_stats()["fetch_failures"]
        with faults.scoped(("shuffle-read", dict(at=(1,)))):
            with pytest.raises(FetchFailedError) as e:
                list(read_block(block))
        assert (e.value.stage_id, e.value.map_id) == (4, 2)
        assert xla_stats.fault_stats()["fetch_failures"] == before + 1
        # a released output is a lost block too
        output.release()
        with pytest.raises(FetchFailedError) as e:
            list(read_block(output.block(1, 4, 2)))
        assert (e.value.stage_id, e.value.map_id) == (4, 2)


def test_the_query_recovers_through_the_file_tier_once(files, tmp_path):
    plan = _plan(files, _partitioning("hash"), True, "execute")
    config.conf.set(config.TASK_RETRY_BACKOFF_MS.key, 1)
    try:
        with one_chip():
            want, _, _ = _run(plan, tmp_path, "clean")
            xla_stats.reset()
            with faults.scoped(("shuffle-read", dict(at=(2,)))):
                got, moved, sched = _run(plan, tmp_path, "faulty")
    finally:
        config.conf.unset(config.TASK_RETRY_BACKOFF_MS.key)
    assert got.column("rid").to_pylist() == want.column("rid").to_pylist()
    fs = xla_stats.fault_stats()
    assert fs["stage_recoveries"] == fs["recovered_map_tasks"] == 1
    assert fs["fetch_failures"] == 1
    assert sorted(sched.task_runs.values()) == [1, 2]   # the map tasks
    # the re-run map task republished through the file tier
    assert 0 < moved["shuffle_file_rows"] < moved["shuffle_resident_rows"]
    assert all(v == [] for v in sched.leak_report().values())


# -- (e) who declines ---------------------------------------------------------

def _two_stage(files):
    return _plan(files, _partitioning("hash"), False, "execute")


def _under_broadcast(files):
    paths, schema = files
    scan = {"kind": "parquet_scan", "schema": schema,
            "file_groups": [[p] for p in paths]}
    build = {"kind": "local_exchange",
             "partitioning": _partitioning("hash", ("i64",)),
             "input": scan}
    return {"kind": "broadcast_join", "left": scan, "right": build,
            "left_keys": [c("i64")], "right_keys": [c("i64")],
            "join_type": "inner", "build_side": "right",
            "broadcast_id": "test-exchange-resident-bc"}


DECLINES = {
    "speculation": {config.SPECULATION_ENABLE.key: True},
    "worker_pool": {config.WORKERS_ENABLE.key: True},
    "shuffle_service": {config.SHUFFLE_SERVICE.key: "/tmp/blaze-rss-none"},
    "adaptive": {config.AQE_ENABLE.key: True},
}


@pytest.mark.parametrize("why", [None, *DECLINES, "pending_subplan",
                                 "broadcast_reader", "single_partition",
                                 "host_placement", "two_devices"])
def test_the_tier_is_taken_on_one_chip_and_declined_otherwise(
        files, tmp_path, why):
    from blaze_tpu.plan import adaptive
    plan = _under_broadcast(files) if why == "broadcast_reader" \
        else _two_stage(files)
    if why == "single_partition":
        plan["input"]["partitioning"] = {"kind": "single"}
    conf = dict(DECLINES.get(why, {}))
    if why == "two_devices":
        conf[config.MESH_DEVICES.key] = 2
    with (on_files if why == "host_placement" else one_chip)(**conf):
        adaptive.reset_conf_probe()
        try:
            sched = DagScheduler(work_dir=str(tmp_path / "dag"))
            stage = sched.split(plan)[0]
            if why == "pending_subplan":
                sched._pending_subplan[stage.sid] = ("fp", "snapshot")
            assert sched._resident_tier(stage) is (why is None)
        finally:
            sched.cleanup()
    adaptive.reset_conf_probe()


# -- (f) what the scheduler lets go -------------------------------------------

def test_cleanup_lets_the_resident_outputs_and_their_charge_go(
        files, tmp_path):
    plan = _two_stage(files)
    with one_chip():
        sched = DagScheduler(work_dir=str(tmp_path / "dag"))
        stage = sched.split(plan)[0]
        sched._run_producer(stage)
        held = [c for c in MemManager.get()._consumers
                if c.name == "shuffle_resident"]
        assert len(held) == 2 and all(c.mem_used > 0 for c in held)
        report = sched.leak_report()
        assert len(report["resident"]) == 2
        assert report["files"] == []     # nothing reached a disk
        sched.cleanup()
        assert all(v == [] for v in sched.leak_report().values())
        assert not [c for c in MemManager.get()._consumers
                    if c.name == "shuffle_resident"]
        assert all(c.mem_used == 0 and not c.on_chip for c in held)


# -- (g) counts ---------------------------------------------------------------

def test_a_map_task_reads_back_one_counts_array_and_no_row(files, tmp_path):
    """q93's map side: a bare scan under a hash exchange on two int64 keys.
    One partition program a batch, one readback a map task (its batches'
    partition counts, int32), no `compact` and no `take`."""
    paths, schema = files
    plan = _plan(files, _partitioning("hash", ("i64", "rid")), False,
                 "execute")
    with one_chip():
        sched = DagScheduler(work_dir=str(tmp_path / "dag"))
        stage = sched.split(plan)[0]
        sched._run_producer(stage)       # compiles
        sched.cleanup()
        sched = DagScheduler(work_dir=str(tmp_path / "dag2"))
        stage = sched.split(plan)[0]
        kernels = xla_stats.compile_report()["kernels"]
        calls = kernels["exchange.partition"]["calls"]
        before = xla_stats.snapshot()
        sched._run_producer(stage)
        moved = xla_stats.delta(before)
        batches = sum(-(-n // BATCH) for n in (2048, 2048, ROWS - 4096)) * 2
        kernels = xla_stats.compile_report()["kernels"]
        assert kernels["exchange.partition"]["calls"] - calls == batches
        assert kernels["exchange.partition"]["compiles"] <= 2
        assert moved["d2h_transfers"] == 2
        assert moved["d2h_bytes"] == batches * PARTS * 4
        assert moved["shuffle_resident_rows"] == 2 * ROWS
        sched.cleanup()


def test_the_explain_footer_and_the_span_say_which_tier(files, tmp_path):
    from blaze_tpu.bridge import tracing
    from blaze_tpu.plan.explain import QueryProfile
    plan = _two_stage(files)
    with one_chip():
        tracing.start_tracing()
        try:
            _got, moved, sched = _run(plan, tmp_path, "chip")
        finally:
            spans = tracing.stop_tracing()
    exchange, = [s for s in spans if s["name"] == "shuffle_exchange"]
    assert exchange["attrs"]["tier"] == "resident"
    text = QueryProfile(
        query_id="q-tier", wall_ns=1, tree=sched.collect_metrics(),
        partitions=PARTS, exec_mode="staged", xla=moved, kernels={},
        placement="device", output_rows=0).render_text()
    assert f"exchange tiers: resident={2 * ROWS} rows" in text
    assert "file=0 rows" in text and "spilled=0 rows" in text


def test_cell_with_ledger_prints_the_rows_by_tier():
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "cell_with_ledger", os.path.join(root, "tools",
                                         "cell_with_ledger.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    shuffle = dict(xla_stats.shuffle_stats(),
                   shuffle_resident_rows=300, shuffle_resident_bytes=7200,
                   shuffle_file_rows=100, shuffle_file_bytes=999,
                   shuffle_spilled_rows=0, shuffle_spilled_bytes=0)
    tiers = tool.exchange_tiers(shuffle)
    assert tiers["resident"] == {"rows": 300, "bytes": 7200}
    assert tiers["file"] == {"rows": 100, "bytes": 999}
    assert tiers["resident_rows_share"] == 75.0
    # a program without the counters (the parent) reports nothing
    assert tool.exchange_tiers({"shuffle_host_bytes": 5}) == {}


def test_readers_on_many_threads_see_whole_partitions_across_a_spill(
        tmp_path):
    """Eight readers ask for blocks over and over while another thread
    spills the output: every answer is a partition's whole rows, from the
    chip before the flip and from the files after it."""
    import sys
    import threading
    table = _table(61, 0)
    with one_chip():
        output, _d, _i = _write(table, _hash(), tmp_path, "m",
                                resident=True)
        want = [int(n) for n in output.partition_rows]
        errors, kinds = [], set()
        stop = threading.Event()

        def reader(p):
            try:
                while not stop.is_set():
                    block = output.block(p, 0, 0)
                    kinds.add(type(block).__name__)
                    rows = sum(r.rows if hasattr(r, "rows") else r.num_rows
                               for r in read_block(block))
                    assert rows == want[p], (rows, want[p])
            except BaseException as e:   # surfaces in the main thread
                errors.append(e)

        threads = [threading.Thread(target=reader, args=(i % PARTS,))
                   for i in range(8)]
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            assert output.spill() > 0
            for _ in range(200):      # a while on the files too
                output.block(0, 0, 0)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        output.release()
    assert errors == []
    assert "FileSegmentBlock" in kinds

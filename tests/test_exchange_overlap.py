"""Overlapped device exchange (ISSUE 18): the dispatch/drain split of
the cached shard_map collective, the staged scheduler's overlap path
(bit-identical blocks, wholesale fallback, clean cancellation, one
compile per ladder rung), the worker result frame's child CPU
accounting, and the compressed worker/RSS wire frames."""

import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu import config, faults
from blaze_tpu.bridge import xla_stats
from blaze_tpu.memory import MemManager
from blaze_tpu.parallel.stage import DeviceExchange
from blaze_tpu.plan.stages import DagScheduler

SENT = -(1 << 60)


@pytest.fixture(autouse=True)
def clean_slate():
    faults.clear()
    MemManager.init(4 << 30)
    try:
        yield
    finally:
        faults.clear()


@pytest.fixture
def staged_device():
    """Force the staged DAG path and the device shuffle lane."""
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    config.conf.set(config.SHUFFLE_DEVICE.key, "on")
    try:
        yield
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)
        config.conf.unset(config.SHUFFLE_DEVICE.key)


@pytest.fixture
def overlap_on(staged_device):
    config.conf.set(config.EXCHANGE_OVERLAP_ENABLE.key, True)
    try:
        yield
    finally:
        config.conf.unset(config.EXCHANGE_OVERLAP_ENABLE.key)


def _two_stage_plan(tmp_path, n=8000, n_reduce=3, n_files=4, wide=False):
    """hash_agg(final) <- hash exchange <- hash_agg(partial) <- scan,
    split over `n_files` map tasks so the overlap window sees several
    dispatches in flight."""
    rng = np.random.default_rng(7)
    # `wide`: the same keys spread over 2^48, so that the aggregation
    # takes the hash table (a compact key range takes the dense lane,
    # which the stage loop leaves to its own fold)
    keys = rng.integers(0, 200, n) << (40 if wide else 0)
    t = pa.table({"k": pa.array(keys, type=pa.int64()),
                  "v": pa.array(rng.random(n))})
    per = n // n_files
    paths = []
    for i in range(n_files):
        p = str(tmp_path / f"in-{i}.parquet")
        pq.write_table(t.slice(i * per, per), p)
        paths.append(p)
    schema = {"fields": [
        {"name": "k", "type": {"id": "int64"}, "nullable": True},
        {"name": "v", "type": {"id": "float64"}, "nullable": True}]}
    return {
        "kind": "hash_agg",
        "groupings": [{"expr": {"kind": "column", "index": 0},
                       "name": "k"}],
        "aggs": [{"fn": "sum", "mode": "final", "name": "s",
                  "args": [{"kind": "column", "index": 1}]}],
        "input": {
            "kind": "local_exchange",
            "partitioning": {"kind": "hash",
                             "exprs": [{"kind": "column", "index": 0}],
                             "num_partitions": n_reduce},
            "input": {
                "kind": "hash_agg",
                "groupings": [{"expr": {"kind": "column", "name": "k"},
                               "name": "k"}],
                "aggs": [{"fn": "sum", "mode": "partial", "name": "s",
                          "args": [{"kind": "column", "name": "v"}]}],
                "input": {"kind": "parquet_scan", "schema": schema,
                          "file_groups": [[p] for p in paths]}}}}


def _sorted_df(tbl):
    return tbl.to_pandas().sort_values("k").reset_index(drop=True)


# -- overlap scheduler: identity, fallback, cancellation, recompiles --------

def test_overlap_defaults_off():
    """Default-off acceptance: without the knob the synchronous path
    runs and nothing overlapped is recorded."""
    assert config.EXCHANGE_OVERLAP_ENABLE.get() is False


@pytest.mark.parametrize("resident,n_files", [("host", 4), ("devices", 4),
                                              ("devices", 12)])
def test_overlap_bit_identical_to_sync(tmp_path, device_mesh,
                                       staged_device, resident, n_files,
                                       monkeypatch):
    """Same plan, same seeds, same grow schedule: the overlapped
    exchange must publish byte-identical results (float sums are exact
    only if the per-partition concat order matches the sync merge).
    `devices`: the map tasks' columns lie on the devices they ran on,
    one ticket a task from its own device; with 12 tasks on 8 devices
    some devices hold two tasks' rows, and both paths keep them in
    (device, task) order."""
    if resident == "devices":
        import blaze_tpu.bridge.placement as P
        monkeypatch.setattr(P, "host_resident", lambda: False)
    plan = _two_stage_plan(tmp_path, n=8400, n_files=n_files,
                           wide=resident == "devices")
    sync = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-sync")).run_collect(plan))

    config.conf.set(config.EXCHANGE_OVERLAP_ENABLE.key, True)
    try:
        xla_stats.reset()
        sched = DagScheduler(work_dir=str(tmp_path / "dag-overlap"))
        got = _sorted_df(sched.run_collect(plan))
    finally:
        config.conf.unset(config.EXCHANGE_OVERLAP_ENABLE.key)

    assert got.equals(sync)
    ss = xla_stats.shuffle_stats()
    assert ss["shuffle_device_overlap_exchanges"] >= 1
    assert ss["shuffle_device_fallbacks"] == 0
    assert ss["shuffle_host_bytes"] == 0
    assert all(v == [] for v in sched.leak_report().values())
    if resident == "devices":
        producer = sched.stages[0].sid
        assert sched.stage_placement[producer]["compute"] == "device-loop"
        assert {chip for (sid, _m), chip in sched.task_chips.items()
                if sid == producer} == set(range(min(n_files, 8)))
        assert xla_stats.placement_stats()["cross_chip_bytes"] == 0


def test_overlap_fault_falls_back_wholesale(tmp_path, device_mesh,
                                            overlap_on):
    """A device-collective fault mid-overlap is deferred past the wave
    and downgrades the WHOLE stage to the file shuffle — never a
    per-task retry, never divergence."""
    plan = _two_stage_plan(tmp_path)
    config.conf.set(config.SHUFFLE_DEVICE.key, "off")
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-file")).run_collect(plan))
    config.conf.set(config.SHUFFLE_DEVICE.key, "on")

    xla_stats.reset()
    sched = DagScheduler(work_dir=str(tmp_path / "dag-fault"))
    with faults.scoped(("device-collective", dict(at=(1,)))):
        got = _sorted_df(sched.run_collect(plan))

    # two shuffle paths add a group's partial sums in different orders:
    # the keys are equal, the float sums to their last digits
    assert got.k.tolist() == clean.k.tolist()
    np.testing.assert_allclose(got.s.to_numpy(), clean.s.to_numpy(),
                               rtol=1e-12)
    assert xla_stats.shuffle_stats()["shuffle_device_fallbacks"] >= 1
    assert all(v == [] for v in sched.leak_report().values())


def test_overlap_cancellation_mid_chunk_leaves_no_leaks(
        tmp_path, device_mesh, overlap_on, monkeypatch):
    """Cancel the query BETWEEN a ticket's dispatch and its drain: the
    wave unwinds, the drainer thread is joined, and leak_report is
    clean — no shuffle files, resources or rss roots left behind."""
    from blaze_tpu.serving.context import QueryCancelled, QueryContext

    ctx = QueryContext("q-cancel-overlap")
    orig = DeviceExchange.dispatch

    def dispatch_then_cancel(self, *args, **kwargs):
        ticket = orig(self, *args, **kwargs)
        ctx.cancel("mid-chunk cancellation test")
        return ticket

    monkeypatch.setattr(DeviceExchange, "dispatch", dispatch_then_cancel)
    plan = _two_stage_plan(tmp_path)
    sched = DagScheduler(work_dir=str(tmp_path / "dag-cancel"),
                         query_ctx=ctx)
    with pytest.raises(QueryCancelled):
        sched.run_collect(plan)
    report = sched.leak_report()
    assert all(v == [] for v in report.values()), report
    assert not [t for t in threading.enumerate()
                if t.name.startswith("exchange-drain-")]


def _kv_columns(n=5000, seed=3):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 200, n, dtype=np.int64)
    kv = rng.random(n) > 0.1
    v = rng.random(n)
    return ([k, v], [kv, np.ones(n, dtype=bool)])


def _multiset(datas, valids):
    k, v = datas
    kval, _ = valids
    return sorted((int(k[i]) if kval[i] else SENT, float(v[i]))
                  for i in range(len(k)))


def _spread(mesh, cols, valids):
    """The rows as the output of one map task a device, on that device."""
    import jax
    devices = list(mesh.devices.reshape(-1))
    cuts = np.linspace(0, len(cols[0]), len(devices) + 1).astype(int)
    return [([jax.device_put(c[lo:hi], d) for c in cols],
             [jax.device_put(v[lo:hi], d) for v in valids], int(hi - lo))
            for d, lo, hi in zip(devices, cuts[:-1], cuts[1:])]


@pytest.mark.parametrize("layout", ["host", "spread"])
def test_dispatch_drain_compiles_once_per_rung(device_mesh, layout):
    """The async split must NOT cost extra traces: dispatch+drain of
    the same shape signature reuses the one cached shard_map program
    per ladder rung, and routes rows exactly like `exchange`.  So does
    the dispatch that takes map output where it lies on the mesh."""
    from blaze_tpu.parallel.stage import _exchange_program
    _exchange_program.cache_clear()  # order-independent: force a trace
    cols, valids = _kv_columns()
    ex = DeviceExchange(device_mesh)
    ref = ex.exchange(cols, valids, [0], 3)

    def compiles():
        kernels = xla_stats.compile_report()["kernels"]
        return kernels.get("mesh.exchange_rows", {}).get("compiles", 0)

    def once():
        if layout == "host":
            return ex.drain(ex.dispatch(cols, valids, [0], 3))
        return ex.drain(ex.dispatch_placed(
            _spread(device_mesh, cols, valids), [0], 3))

    once()   # the spread rows pad to a rung of their own: warm it
    c0 = compiles()
    assert c0 >= 1  # the warm exchanges above compiled the rungs
    for _ in range(2):
        parts = once()
        assert len(parts) == 3
        for r in range(3):
            assert _multiset(*parts[r]) == _multiset(*ref[r])
    assert compiles() == c0


def test_exchange_wire_cost_accounting():
    """Shared by the sync and overlapped paths: one collective per
    staged buffer (data + validity per column, plus the pid rider and
    the row mask), n_dev^2 x capacity slots moved."""
    from blaze_tpu.parallel.collective import exchange_wire_cost
    moved, colls = exchange_wire_cost(4, 128, ("int64", "float64"))
    assert colls == 2 * 2 + 2
    per_slot = 8 + 8 + 2 + 4 + 1  # data + valids + pid(int32) + mask
    assert moved == 4 * 4 * 128 * per_slot


# -- child CPU accounting ----------------------------------------------------

def test_worker_result_frame_accounts_child_cpu():
    """End to end through the CRC32C worker protocol: the result frame
    carries the child's cpu_ns (user+sys over the task), and it
    surfaces in pool.health() and xla_stats."""
    from blaze_tpu.parallel.workers import WorkerPool
    pool = None
    before = xla_stats.snapshot()
    try:
        pool = WorkerPool(count=1, liveness_ms=60000).start()
        # real work, so the child's os.times() moves by whole ticks
        key = pool.run({"fn": "hashlib:pbkdf2_hmac",
                        "args": ("sha256", b"k", b"salt", 400000)},
                       timeout_s=180)
        assert len(key) == 32
        health = pool.health()[0]
        assert health["tasks_done"] == 1
        assert health["cpu_s"] > 0
    finally:
        if pool is not None:
            pool.shutdown(wait=False)
    delta = xla_stats.delta(before)
    assert delta["worker_cpu_ns"] > 0


# -- compressed wire frames (worker protocol + RSS puts) --------------------

def _configured_codec():
    from blaze_tpu.shuffle.ipc import CODEC_RAW, _get_codec
    codec = _get_codec()
    if codec == CODEC_RAW:
        pytest.skip("no compression codec available in this build")
    return codec


def test_control_frame_codec_roundtrip():
    """The frame byte keys the decode, so old and new peers mix: a
    compressed frame round-trips, and a payload compression would GROW
    (or a raw request) stays a raw CRC frame."""
    from blaze_tpu.shuffle import rss
    from blaze_tpu.shuffle.ipc import (CODEC_RAW, _HEADER,
                                       pack_control_frame)
    codec = _configured_codec()
    payload = b"overlapped exchange " * 512
    frame = pack_control_frame(payload, codec)
    assert len(frame) < len(payload)
    assert (frame[0] & 0x7F) == codec
    assert rss._unpack_put(frame) == payload

    tiny = b"\x00\x01\x02"
    raw = pack_control_frame(tiny, codec)  # growth: falls back to raw
    assert (raw[0] & 0x7F) == CODEC_RAW
    assert rss._unpack_put(raw) == tiny
    assert raw[_HEADER.size + 4:] == tiny


def test_rss_pushz_roundtrip_and_accounting():
    from blaze_tpu.shuffle import rss
    _configured_codec()
    config.conf.set(config.IO_COMPRESSION_WORKER_FRAMES.key, True)
    before = xla_stats.snapshot()
    try:
        payload = b"rss partition put " * 512
        wire, suffix = rss._pack_put(payload)
        assert suffix == "pushz" and len(wire) < len(payload)
        assert rss._unpack_put(wire) == payload
        tiny_wire, tiny_suffix = rss._pack_put(b"xy")
        assert tiny_suffix == "push" and tiny_wire == b"xy"
    finally:
        config.conf.unset(config.IO_COMPRESSION_WORKER_FRAMES.key)
    assert xla_stats.delta(before)["rss_put_compressed_bytes_saved"] > 0
    # the read side keys the unwrap on the committed suffix
    assert rss._FRAME.match("m1-a0-s2.pushz").group(4) == "z"
    assert rss._FRAME.match("m1-a0-s2.push").group(4) == ""


def test_worker_frames_stay_raw_by_default():
    from blaze_tpu.parallel.workers import _frame_codec
    from blaze_tpu.shuffle.ipc import CODEC_RAW
    assert _frame_codec() == CODEC_RAW


# -- observability: explain footer, sentinel directions, statstore ----------

def test_explain_footer_reports_overlap_and_compression(
        tmp_path, device_mesh, overlap_on):
    from blaze_tpu.plan.explain import QueryProfile
    xla_stats.reset()
    before = xla_stats.snapshot()
    plan = _two_stage_plan(tmp_path)
    sched = DagScheduler(work_dir=str(tmp_path / "dag"))
    sched.run_collect(plan)
    xla_stats.note_frame_compression("worker", 1024)
    xla_stats.note_frame_compression("rss", 2048)
    profile = QueryProfile(
        query_id="q-overlap", wall_ns=1, tree=sched.collect_metrics(),
        partitions=3, exec_mode="staged", xla=xla_stats.delta(before),
        kernels={}, placement="device", output_rows=0)
    text = profile.render_text()
    assert "shuffle: device=" in text
    assert "overlap: exchanges=" in text
    assert "barrier_idle=" in text
    assert "frame compression: worker=" in text


def test_sentinel_directions_for_new_metrics():
    from blaze_tpu.tools.sentinel import metric_direction
    assert metric_direction("shuffle_barrier_idle_ns") == "lower"
    assert metric_direction("shuffle_device_overlap_exchanges") == "higher"
    assert metric_direction(
        "worker_frame_compressed_bytes_saved") == "higher"


def test_statstore_ingests_barrier_counters():
    from blaze_tpu.plan.statstore import INGEST_COUNTERS
    assert "shuffle_barrier_idle_ns" in INGEST_COUNTERS
    assert "shuffle_device_overlap_exchanges" in INGEST_COUNTERS

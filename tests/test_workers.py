"""Process-isolated worker runtime (ISSUE 11): crash fault domains with
supervised restart, heartbeats, liveness detection, blacklisting, and
lineage-recovery integration.  Every test leaves
`auron.tpu.workers.enable` OFF so the thread path stays the tier-1
seed-verified baseline."""

import io
import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu import config, faults
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.bridge.tasks import run_tasks
from blaze_tpu.faults import (FetchFailedError, WorkerCrashed,
                              classify_exception, parse_rules)
from blaze_tpu.memory import MemManager
from blaze_tpu.parallel import workers
from blaze_tpu.parallel.workers import (RemoteTaskError, WorkerPool,
                                        WorkerPoolUnavailable, _recv_msg,
                                        _send_msg)
from blaze_tpu.plan import statstore
from blaze_tpu.plan.stages import DagScheduler, Stage

ECHO = "blaze_tpu.parallel.workers:_task_echo"
SLEEP = "blaze_tpu.parallel.workers:_task_sleep"
RAISE = "blaze_tpu.parallel.workers:_task_raise"


@pytest.fixture(autouse=True)
def clean_slate():
    faults.clear()
    MemManager.init(4 << 30)
    try:
        yield
    finally:
        faults.clear()
        workers.shutdown_pool(wait=False)
        tracing.stop_tracing()
        with tracing._lock:
            tracing._spans.clear()
        for key in ("auron.tpu.stats.enable", "auron.tpu.stats.dir",
                    "auron.tpu.workers.enable", "auron.tpu.workers.count",
                    "auron.tpu.workers.heartbeatMs",
                    "auron.tpu.workers.livenessMs",
                    "auron.tpu.workers.crashBudget",
                    "auron.tpu.workers.restartBackoffMs",
                    "auron.tpu.dag.singleTaskBytes",
                    "auron.tpu.task.retryBackoffMs",
                    "auron.tpu.task.maxAttempts"):
            config.conf.unset(key)
        statstore.reset_conf_probe()


def _pool(count=2, **kw) -> WorkerPool:
    kw.setdefault("heartbeat_ms", 50)
    kw.setdefault("liveness_ms", 2000)
    kw.setdefault("restart_backoff_ms", 10)
    return WorkerPool(count=count, **kw).start()


# -- satellite: parse_rules site validation ---------------------------------

def test_parse_rules_rejects_unknown_site():
    with pytest.raises(ValueError, match="unknown fault site"):
        parse_rules("shufle-write=0.5")  # typo'd site fails LOUDLY
    with pytest.raises(ValueError, match="unknown fault site"):
        parse_rules("task-start=0.5,wroker-crash@1")


def test_parse_rules_accepts_worker_sites_and_registered():
    sites = [s for s, _ in parse_rules(
        "worker-crash=0.25,worker-hang@2,worker-slow=0.1*3")]
    assert sites == ["worker-crash", "worker-hang", "worker-slow"]
    with pytest.raises(ValueError):
        parse_rules("my-plugin-site@1")
    faults.register_site("my-plugin-site")  # escape hatch
    try:
        assert parse_rules("my-plugin-site@1")[0][0] == "my-plugin-site"
    finally:
        faults._extra_sites.discard("my-plugin-site")


# -- pipe framing -----------------------------------------------------------

def test_frame_roundtrip_and_truncation():
    buf = io.BytesIO()
    msgs = [{"kind": "task", "args": (1, "x", [2.5])},
            {"kind": "heartbeat"}]
    for m in msgs:
        _send_msg(buf, m)
    buf.seek(0)
    assert _recv_msg(buf) == msgs[0]
    assert _recv_msg(buf) == msgs[1]
    assert _recv_msg(buf) is None  # clean EOF
    # a torn frame (process killed mid-write) is EOFError — never a
    # partial unpickle
    whole = io.BytesIO()
    _send_msg(whole, msgs[0])
    for cut in (3, 7, len(whole.getvalue()) - 3):
        with pytest.raises(EOFError):
            _recv_msg(io.BytesIO(whole.getvalue()[:cut]))


def test_frame_crc_detects_corruption():
    from blaze_tpu.faults import ShuffleChecksumError
    buf = io.BytesIO()
    _send_msg(buf, {"k": "v"})
    raw = bytearray(buf.getvalue())
    raw[-1] ^= 0xFF  # flip a payload bit
    with pytest.raises(ShuffleChecksumError):
        _recv_msg(io.BytesIO(bytes(raw)))


# -- pool basics ------------------------------------------------------------

def test_pool_echo_and_health():
    pool = _pool(count=2)
    try:
        r = pool.run({"fn": ECHO, "args": (7, "ok")})
        assert r["echo"] == [7, "ok"]
        assert r["pid"] != os.getpid()  # really another process
        assert r["_worker_id"] in (0, 1)
        h = pool.health()
        assert len(h) == 2
        assert all(s["state"] in ("idle", "starting") for s in h)
        assert sum(s["tasks_done"] for s in h) == 1
    finally:
        pool.shutdown()


def test_remote_error_classification_crosses_boundary():
    pool = _pool(count=1)
    try:
        with pytest.raises(FetchFailedError) as ei:
            pool.run({"fn": RAISE, "args": ("fetch",)})
        assert (ei.value.stage_id, ei.value.map_id) == (7, 3)
        with pytest.raises(RemoteTaskError) as ei:
            pool.run({"fn": RAISE, "args": ("retryable",)})
        assert classify_exception(ei.value) == "retryable"
        with pytest.raises(RemoteTaskError) as ei:
            pool.run({"fn": RAISE, "args": ("fatal",)})
        assert classify_exception(ei.value) == "fatal"
        # the worker survived all three failures: errors are not crashes
        assert pool.health()[0]["crashes"] == 0
    finally:
        pool.shutdown()


def test_crash_classified_restarted_and_retry_lands_elsewhere():
    xla_stats.reset()
    pool = _pool(count=2)
    try:
        with faults.scoped(("worker-crash", dict(at=(1,)))):
            with pytest.raises(WorkerCrashed) as ei:
                pool.run({"fn": SLEEP, "args": (0.5, "v")})
        crashed = ei.value.worker_id
        assert crashed is not None
        assert ei.value.exit_code == -9  # really SIGKILLed
        # the retry contract: exclude the crashed worker, land elsewhere
        r = pool.run({"fn": ECHO, "args": ("after",)}, exclude={crashed})
        assert r["_worker_id"] != crashed
        # supervision respawns the crashed slot
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            st = pool.health()[crashed]
            if st["state"] in ("idle", "starting") \
                    and st["incarnation"] == 2:
                break
            time.sleep(0.05)
        assert pool.health()[crashed]["incarnation"] == 2
        ws = xla_stats.worker_stats()
        assert ws["worker_crashes"] == 1
        assert ws["worker_restarts"] >= 1
    finally:
        pool.shutdown()


def test_hang_detected_within_liveness_deadline():
    xla_stats.reset()
    pool = _pool(count=1, heartbeat_ms=25, liveness_ms=400)
    try:
        # start() returns before the child has imported and said hello
        # (2.3-3.1 s here); a task waits for that, so warm the pool
        # first and time the hung task alone
        assert pool.run({"fn": ECHO, "args": (0,)}) is not None
        with faults.scoped(("worker-hang", dict(at=(1,)))):
            t0 = time.monotonic()
            with pytest.raises(WorkerCrashed, match="heartbeat miss"):
                pool.run({"fn": ECHO, "args": (1,)})
            elapsed = time.monotonic() - t0
        # detected by the 0.4 s liveness deadline (0.41 s measured), not
        # by the 10x-liveness wedge sleep running out at 4 s
        assert elapsed < 2.0
        assert xla_stats.worker_stats()["worker_hangs"] == 1
    finally:
        pool.shutdown()


def test_slow_worker_not_mistaken_for_dead():
    pool = _pool(count=1, heartbeat_ms=25, liveness_ms=300)
    try:
        # worker-slow stalls the task well past the liveness deadline
        # but KEEPS heartbeating: the pool must wait, not kill
        with faults.scoped(("worker-slow", dict(at=(1,)))):
            r = pool.run({"fn": SLEEP, "args": (0.5, "done")})
        assert r["value"] == "done"
        assert pool.health()[0]["crashes"] == 0
    finally:
        pool.shutdown()


def test_blacklisted_worker_never_receives_tasks():
    xla_stats.reset()
    pool = _pool(count=2, crash_budget=1)
    try:
        victim = None
        with faults.scoped(("worker-crash", dict(at=(1, 2)))):
            for _ in range(2):
                with pytest.raises(WorkerCrashed) as ei:
                    # exclude the healthy worker so BOTH crashes hit the
                    # same slot and exhaust its budget of 1
                    pool.run({"fn": SLEEP, "args": (0.5,)},
                             exclude=set() if victim is None
                             else {1 - victim})
                victim = ei.value.worker_id if victim is None else victim
        assert pool.health()[victim]["state"] == "blacklisted"
        assert xla_stats.worker_stats()["worker_blacklisted"] == 1
        # a blacklisted slot never comes back or takes work
        for _ in range(6):
            r = pool.run({"fn": ECHO, "args": ("x",)})
            assert r["_worker_id"] != victim
        assert pool.health()[victim]["state"] == "blacklisted"
    finally:
        pool.shutdown()


def test_fully_blacklisted_pool_signals_unavailable():
    pool = _pool(count=1, crash_budget=0)
    try:
        with faults.scoped(("worker-crash", dict(at=(1,)))):
            with pytest.raises(WorkerCrashed):
                pool.run({"fn": SLEEP, "args": (0.5,)})
        with pytest.raises(WorkerPoolUnavailable):
            pool.run({"fn": ECHO, "args": (1,)})
    finally:
        pool.shutdown()


# -- satellite: run_tasks timeout regression --------------------------------

def test_run_tasks_timeout_nonblocking_thread_path():
    config.conf.set(config.TASK_RETRY_BACKOFF_MS.key, 1)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="still"):
        run_tasks(lambda i: time.sleep(8.0), 2, 0.5, "wedge-test",
                  max_workers=2)
    # the wave raises promptly and does NOT join the wedged threads
    assert time.monotonic() - t0 < 5.0


def test_run_tasks_timeout_under_worker_pool_kills_and_recovers():
    config.conf.set(config.WORKERS_ENABLE.key, "true")
    config.conf.set(config.WORKERS_COUNT.key, 1)
    config.conf.set(config.WORKERS_RESTART_BACKOFF_MS.key, 10)
    config.conf.set(config.TASK_RETRY_BACKOFF_MS.key, 1)
    config.conf.set(config.TASK_MAX_ATTEMPTS.key, 1)
    pool = workers.get_pool()
    assert pool is not None
    pool.run({"fn": ECHO, "args": ("warm",)}, timeout_s=60.0)
    xla_stats.reset()
    remote = lambda i: {"fn": SLEEP, "args": (30.0, i)}  # noqa: E731
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        run_tasks(lambda i: None, 1, 1.0, "pool-wedge", remote=remote)
    assert time.monotonic() - t0 < 10.0
    # the deadline escalates INTO the child (cancel -> SIGTERM ->
    # SIGKILL) from the task thread, which may land a poll tick after
    # the wave-level TimeoutError surfaced: no worker slot may be left
    # wedged busy
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline \
            and xla_stats.worker_stats()["worker_cancels"] < 1:
        time.sleep(0.05)
    assert xla_stats.worker_stats()["worker_cancels"] >= 1
    r = pool.run({"fn": ECHO, "args": ("alive",)}, timeout_s=60.0)
    assert r["echo"] == ["alive"]


# -- scheduler integration --------------------------------------------------

def _two_stage_plan(tmp_path, n=20_000, n_reduce=3):
    rng = np.random.default_rng(7)
    t = pa.table({"k": pa.array(rng.integers(0, 200, n), type=pa.int64()),
                  "v": pa.array(rng.random(n))})
    paths = []
    for i in range(2):
        p = str(tmp_path / f"in-{i}.parquet")
        pq.write_table(t.slice(i * (n // 2), n // 2), p)
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
                          "file_groups": [[paths[0]], [paths[1]]]}}}}


def _sorted_df(tbl):
    return tbl.to_pandas().sort_values("k").reset_index(drop=True)


def _enable_workers(count=2):
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    config.conf.set(config.TASK_RETRY_BACKOFF_MS.key, 5)
    config.conf.set(config.WORKERS_ENABLE.key, "true")
    config.conf.set(config.WORKERS_COUNT.key, count)
    config.conf.set(config.WORKERS_RESTART_BACKOFF_MS.key, 10)


@pytest.mark.parametrize("observed", ["plain", "traced", "stats"])
def test_staged_query_through_pool_bit_identical(tmp_path, observed):
    """The pool changes where a task runs, never the answer; and neither
    does watching it: with the span tracer on (child spans ride the
    frames home) or the statistics store on (the run merges into its
    fingerprint's record) the frame is the plain run's, bit for bit."""
    plan = _two_stage_plan(tmp_path)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag0")).run_collect(plan))
    _enable_workers()
    xla_stats.reset()
    if observed == "traced":
        tracing.start_tracing()
    elif observed == "stats":
        config.conf.set(config.STATS_ENABLE.key, "on")
        config.conf.set(config.STATS_DIR.key, str(tmp_path / "stats"))
        statstore.reset_conf_probe()
    sched = DagScheduler(work_dir=str(tmp_path / "dag1"))
    got = _sorted_df(sched.run_collect(plan))
    assert got.equals(clean)
    if observed == "stats":
        again = DagScheduler(work_dir=str(tmp_path / "dag2"))
        assert _sorted_df(again.run_collect(plan)).equals(clean)
        assert statstore.prior(again.stats_fingerprint)["run_count"] == 2
    ws = xla_stats.worker_stats()
    # both map tasks process-isolated (twice where the plan ran twice)
    assert ws["worker_tasks"] == (4 if observed == "stats" else 2)
    if observed == "traced":
        assert [r for r in tracing.spans() if r["name"] == "worker_task"]
        assert xla_stats.snapshot()["obs_spans_ingested"] > 0
    # per-task metric trees rode the result frames home
    assert sched.stage_metrics[0].to_dict()
    assert all(v == [] for v in sched.leak_report().values())


@pytest.mark.parametrize("entry", ["scheduler", "service"])
def test_sigkill_mid_map_task_recovers_via_retry(tmp_path, entry):
    """SIGKILL mid-shuffle-write: tmp+os.replace commit means NO
    committed partial output exists, the retry (on another worker)
    produces the whole output, and the query is bit-identical, handed
    to the scheduler directly or admitted through the query service
    (which must complete it and stay open for the next query)."""
    from blaze_tpu.serving import QueryService
    plan = _two_stage_plan(tmp_path)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag0")).run_collect(plan))
    _enable_workers()
    xla_stats.reset()
    with faults.scoped(("worker-crash", dict(at=(1,)))):
        if entry == "scheduler":
            sched = DagScheduler(work_dir=str(tmp_path / "dag1"))
            got = _sorted_df(sched.run_collect(plan))
            leaks = sched.leak_report()
        else:
            with QueryService(max_concurrent=2) as svc:
                victim = svc.submit(plan, tenant="a")
                other = svc.submit(plan, tenant="b")
                assert victim.exception(timeout=120) is None
                assert other.exception(timeout=120) is None
                assert (victim.status, other.status) == ("done", "done")
                got = _sorted_df(victim.result())
                assert _sorted_df(other.result()).equals(clean)
                leaks = victim.leak_report
    assert got.equals(clean)
    ws = xla_stats.worker_stats()
    assert ws["worker_crashes"] == 1
    # 2 map tasks a query + 1 crash retry
    assert ws["worker_tasks"] == (3 if entry == "scheduler" else 5)
    # leak_report clean after a crash-recovered query
    assert all(v == [] for v in leaks.values())
    # the wave retried in place (different worker) — no lineage round
    # was needed because nothing poisoned was ever committed
    assert xla_stats.fault_stats()["task_retries"] >= 1


def test_invalidate_worker_outputs_marks_torn_entries(tmp_path):
    """A crash wedged between the .data and .index commits leaves a
    torn pair: the crash listener re-validates the dead worker's
    entries and poisons exactly the torn one in the map-output table."""
    sched = DagScheduler(work_dir=str(tmp_path / "dag"))
    part = {"kind": "hash", "exprs": [], "num_partitions": 2}
    stage = Stage(sid=0, plan={}, partitioning=part, resource_id="r0",
                  num_tasks=2)
    sched.stages = [stage]
    # map 0: valid committed pair; map 1: .data without .index (torn)
    import struct
    good = sched._map_data_path(0, 0)
    with open(good, "wb") as f:
        f.write(b"\0" * 10)
    with open(good[:-5] + ".index", "wb") as f:
        f.write(struct.pack("<3q", 0, 4, 10))
    torn = sched._map_data_path(0, 1)
    with open(torn, "wb") as f:
        f.write(b"\0" * 10)
    sched._stage_outputs[0] = {0: (good, [0, 4, 10]),
                               1: (torn, [0, 5, 10])}
    sched._map_worker = {(0, 0): 3, (0, 1): 3}
    sched.invalidate_worker_outputs(3)
    assert sched._stage_outputs[0][0] is not None  # survived validation
    assert sched._stage_outputs[0][1] is None      # poisoned
    sched.invalidate_worker_outputs(None)  # no-op, never raises
    sched.cleanup()


def test_pool_disabled_is_default_and_thread_path_untouched(tmp_path):
    assert config.WORKERS_ENABLE.get() is False
    plan = _two_stage_plan(tmp_path, n=4_000)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    xla_stats.reset()
    DagScheduler(work_dir=str(tmp_path / "dag")).run_collect(plan)
    assert xla_stats.worker_stats()["worker_tasks"] == 0
    assert workers.active_pool() is None


# -- satellite: bounded crash soak (runs in tier-1) -------------------------

@pytest.mark.soak
def test_worker_crash_soak_bounded(tmp_path):
    """Seeded worker-crash/worker-hang chaos over repeated staged runs:
    every query bit-identical, no leaks, bounded wall time (<60s)."""
    plan = _two_stage_plan(tmp_path, n=8_000)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag0")).run_collect(plan))
    _enable_workers()
    config.conf.set(config.WORKERS_LIVENESS_MS.key, 500)
    config.conf.set(config.WORKERS_HEARTBEAT_MS.key, 50)
    xla_stats.reset()
    t0 = time.monotonic()
    faults.configure("worker-crash=0.3*2,worker-hang@5", seed=1234)
    try:
        for i in range(4):
            sched = DagScheduler(work_dir=str(tmp_path / f"dag{i + 1}"))
            got = _sorted_df(sched.run_collect(plan))
            assert got.equals(clean), f"divergence in soak round {i}"
            assert all(v == [] for v in sched.leak_report().values())
    finally:
        faults.clear()
    ws = xla_stats.worker_stats()
    assert ws["worker_crashes"] >= 1  # the chaos actually bit
    assert time.monotonic() - t0 < 60.0

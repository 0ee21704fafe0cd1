"""Distributed tracing, flight recorder, and per-query attribution.

Covers the tentpole of the observability PR: the conf-lazy enable knob
(zero hot-path cost when off), cross-process trace stitching over the
worker wire protocol (clock rebase, parent span linkage, worker tags),
speculation winner/loser linking, retry/backoff spans, streaming epoch
spans and recovery instants, the crash flight recorder (deadline,
quota-kill, stream-recovery-exhausted classifications, first-fatal
wins), the Chrome-trace timeline endpoint payload, per-query resource
attribution, and the profile-store LRU cap satellite.
"""

import json
import os
import threading
import time

import pytest

from blaze_tpu import config, faults
from blaze_tpu.bridge import context as bridge_context
from blaze_tpu.bridge import profiling, tracing, xla_stats
from blaze_tpu.bridge.context import TaskKilledError, current_attempt_token
from blaze_tpu.bridge.tasks import run_tasks
from blaze_tpu.memory import MemManager
from blaze_tpu.ops.kafka import KafkaRecord
from blaze_tpu.ops.window import EventTimeWindowSpec
from blaze_tpu.streaming import (MemoryStreamSource, StreamExecutor,
                                 StreamWindowConfig)

ECHO = "blaze_tpu.parallel.workers:_task_echo"
SLEEP = "blaze_tpu.parallel.workers:_task_sleep"

_KEYS = (config.TRACE_ENABLE, config.FLIGHT_RECORDER_ENABLE,
         config.FLIGHT_RECORDER_DIR, config.FLIGHT_RECORDER_SPANS,
         config.PROFILE_STORE_MAX,
         config.WORKERS_ENABLE, config.WORKERS_COUNT,
         config.WORKERS_HEARTBEAT_MS, config.WORKERS_RESTART_BACKOFF_MS,
         config.SPECULATION_ENABLE, config.SPECULATION_QUANTILE,
         config.SPECULATION_MULTIPLIER, config.SPECULATION_MIN_MS,
         config.TASK_RETRY_BACKOFF_MS, config.TASK_MAX_ATTEMPTS,
         config.STREAM_MAX_RECOVERIES)


def _drop_buffered_spans():
    # stop_tracing() deliberately KEEPS the buffer (the /trace/stop
    # contract); tests need a truly empty tracer, so drain it too.
    tracing.stop_tracing()
    with tracing._lock:
        tracing._spans.clear()


@pytest.fixture(autouse=True)
def clean_slate():
    faults.clear()
    MemManager.init(4 << 30)
    bridge_context.reset_flight_recorder()
    _drop_buffered_spans()
    tracing.reset_conf_probe()
    try:
        yield
    finally:
        from blaze_tpu.parallel import workers
        workers.shutdown_pool(wait=False)
        for opt in _KEYS:
            config.conf.unset(opt.key)
        faults.clear()
        bridge_context.reset_flight_recorder()
        _drop_buffered_spans()
        tracing.reset_conf_probe()
        MemManager.init(4 << 30)


def _names(records):
    return [r["name"] for r in records]


def _by_name(records, name):
    return [r for r in records if r["name"] == name]


# -- enable knob ------------------------------------------------------------

def test_tracing_default_off_and_wire_context_absent():
    """Default-off contract: no spans buffered, and wire_context() is
    None so the worker task message grows by NOTHING on the off path."""
    assert not tracing.enabled()
    with tracing.span("task", task=0):
        pass
    tracing.instant("task_retry", task=0)
    assert tracing.wire_context(worker=1) is None
    assert tracing.spans() == []


def test_conf_knob_enables_lazily_and_unset_disables():
    config.conf.set(config.TRACE_ENABLE.key, "on")
    tracing.reset_conf_probe()  # forget the probe: next emit re-reads conf
    with tracing.span("task", task=7):
        pass
    assert tracing.enabled()
    got = _by_name(tracing.spans(), "task")
    assert got and got[-1]["attrs"]["task"] == 7
    config.conf.unset(config.TRACE_ENABLE.key)
    tracing.reset_conf_probe()
    with tracing.span("task", task=8):
        pass
    assert not tracing.enabled()
    assert tracing.wire_context() is None


def test_unknown_span_name_rejected_when_enabled():
    tracing.start_tracing()
    with pytest.raises(ValueError, match="unregistered span"):
        with tracing.span("not-a-registered-span"):
            pass
    with pytest.raises(ValueError, match="unregistered span"):
        tracing.instant("also-not-registered")
    # wildcard names pass: produce spans carry the prefetcher's name
    with tracing.span("produce:parquet_scan", rows=1):
        pass
    assert _by_name(tracing.spans(), "produce:parquet_scan")


# -- thread ids, collector pauses, the buffer's bound ------------------------

def test_every_record_carries_the_emitting_threads_id():
    """`thread` is a name, which threads share; `tid` is the thread's
    own, so a reader nests spans by it."""
    tracing.start_tracing()
    seen = {}

    def emit(key):
        with tracing.span("task", task=key):
            tracing.instant("task_retry", task=key)
            tracing.emit_span("admission_wait", 10, query=key)
        seen[key] = threading.get_ident()

    # both alive at once: an identifier is unique among LIVE threads
    gate = threading.Barrier(2)
    threads = [threading.Thread(target=lambda k=k: (gate.wait(5), emit(k),
                                                    gate.wait(5)),
                                name="same-name") for k in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    emit(0)
    recs = [r for r in tracing.spans() if r["name"] != "gc_pause"]
    assert len(recs) == 9 and all(r["thread"] for r in recs)
    for r in recs:
        key = r["attrs"].get("task", r["attrs"].get("query"))
        assert r["tid"] == seen[key]
    assert len({seen[1], seen[2], seen[0]}) == 3
    assert {r["thread"] for r in recs if r["tid"] != seen[0]} == \
        {"same-name"}


def test_gc_pause_is_emitted_while_tracing_and_the_callback_goes():
    import gc
    before = list(gc.callbacks)
    tracing.start_tracing()
    assert len(gc.callbacks) == len(before) + 1
    tracing.start_tracing()                      # twice: still one
    assert len(gc.callbacks) == len(before) + 1
    with tracing.execution_context(query="q-gc"):
        with tracing.span("task", mode="sync"):
            gc.collect()
    spans = tracing.stop_tracing()
    assert gc.callbacks == before
    (task,) = _by_name(spans, "task")
    full = [s for s in _by_name(spans, "gc_pause")
            if s["attrs"]["generation"] == 2]
    assert len(full) == 1
    (pause,) = full
    assert pause["dur_ns"] > 0 and pause["attrs"]["collected"] >= 0
    assert pause["parent"] == task["sid"]
    assert pause["tid"] == threading.get_ident()
    assert pause["ctx"] == {"query": "q-gc"}
    assert task["t0_ns"] <= pause["t0_ns"] and \
        pause["t1_ns"] <= task["t1_ns"]
    # off: nothing is installed and a collection leaves no record
    n = len(tracing.spans())
    gc.collect()
    assert len(tracing.spans()) == n and gc.callbacks == before


def test_a_collection_inside_the_tracers_lock_does_not_deadlock():
    """The collector can start at any bytecode, also of a thread that
    holds the tracer's lock; its callback emits under the same lock."""
    import gc
    tracing.start_tracing()
    done = []

    def collect_under_the_lock():
        with tracing._lock:
            gc.collect()
        done.append(True)

    t = threading.Thread(target=collect_under_the_lock)
    t.start()
    t.join(10)
    assert done and not t.is_alive()
    assert _by_name(tracing.stop_tracing(), "gc_pause")


def test_trimming_the_buffer_is_counted(monkeypatch):
    monkeypatch.setattr(tracing, "_MAX_SPANS", 5)
    before = xla_stats.snapshot()
    tracing.start_tracing()
    assert tracing.dropped() == 0
    for i in range(8):
        tracing.instant("task_retry", task=i)
    kept = _by_name(tracing.spans(), "task_retry")
    total = len(tracing.spans()) + tracing.dropped()
    assert len(tracing.spans()) == 5
    assert tracing.dropped() == total - 5 >= 3
    assert [r["attrs"]["task"] for r in kept][-1] == 7   # the newest stay
    assert xla_stats.delta(before)["obs_spans_dropped"] == tracing.dropped()
    # what a child ships home is bounded by the same count
    tracing.ingest([{"name": "worker_task", "t0_ns": 1, "t1_ns": 2,
                     "dur_ns": 1}] * 4, worker=1)
    assert len(tracing.spans()) == 5
    assert xla_stats.delta(before)["obs_spans_dropped"] == \
        tracing.dropped() >= 7
    tracing.start_tracing()                      # a new window counts anew
    assert tracing.dropped() == 0


def test_register_span_is_gone():
    """It had no caller: a name is registered in SPAN_NAMES, where the
    conformance tests see it."""
    assert not hasattr(tracing, "register_span")


# -- wire roundtrip ---------------------------------------------------------

def test_wire_context_and_child_rebase_stitch_one_trace():
    """Parent packs a compact context; the child-side scope buffers spans
    on a skewed clock; ingest() rebases them onto the parent clock and
    parents them under the dispatching span."""
    tracing.start_tracing()
    with tracing.execution_context(query="q-wire", stage="s0"):
        with tracing.span("task_attempt", task=3, attempt=0,
                          what="wire-test"):
            wctx = tracing.wire_context(worker=5)
    assert wctx is not None
    assert wctx["query"] == "q-wire" and wctx["stage"] == "s0"
    assert wctx["worker"] == 5
    parent_sid = wctx["parent"]
    assert parent_sid == _by_name(tracing.spans(), "task_attempt")[0]["sid"]

    # child side: adopt the wire context; spans go to the child buffer
    with tracing.remote_task_scope(wctx):
        with tracing.span("worker_task", pid=123, fn=ECHO):
            time.sleep(0.01)
        tracing.instant("worker_heartbeat", pid=123)
    shipped = tracing.take_buffered()
    assert sorted(_names(shipped)) == ["worker_heartbeat", "worker_task"]
    assert all(r["ctx"]["query"] == "q-wire" for r in shipped)
    wt = _by_name(shipped, "worker_task")[0]
    assert wt["parent"] == parent_sid

    # simulate a child whose perf_counter origin is 5s behind ours
    skew_ns = 5_000_000_000
    for r in shipped:
        r["t0_ns"] -= skew_ns
        r["t1_ns"] -= skew_ns
    before = time.perf_counter_ns()
    n = tracing.ingest(shipped, worker=5,
                       clock_ns=time.perf_counter_ns() - skew_ns)
    assert n == 2
    stitched = _by_name(tracing.spans_for_query("q-wire"), "worker_task")
    assert stitched and stitched[0]["worker"] == 5
    # rebased back onto our clock: within transit slop of `before`
    assert abs(stitched[0]["t1_ns"] - before) < 1_000_000_000


def test_worker_pool_stitches_child_spans_into_one_query_trace():
    """End to end over the real wire: process-isolated worker tasks ship
    their spans home in heartbeat/result frames; the parent trace holds
    ONE query with task_attempt -> worker_task parent links and
    worker-tagged heartbeat instants."""
    config.conf.set(config.WORKERS_ENABLE.key, "true")
    config.conf.set(config.WORKERS_COUNT.key, 1)
    config.conf.set(config.WORKERS_HEARTBEAT_MS.key, 30)
    from blaze_tpu.parallel import workers
    pool = workers.get_pool()
    assert pool is not None
    pool.run({"fn": ECHO, "args": ("warm",)}, timeout_s=60.0)

    tracing.start_tracing()
    before = xla_stats.snapshot()
    with tracing.execution_context(query="q-pool"):
        out = run_tasks(lambda i: None, 2, 30.0, "pool-trace-wave",
                        max_workers=2,
                        remote=lambda i: {"fn": SLEEP, "args": (0.25, i)})
    assert [r["value"] for r in out] == [0, 1]
    recs = tracing.spans_for_query("q-pool")
    attempts = _by_name(recs, "task_attempt")
    wtasks = _by_name(recs, "worker_task")
    assert len(attempts) == 2 and len(wtasks) == 2
    attempt_sids = {r["sid"] for r in attempts}
    # every child span is stitched under its dispatching attempt and
    # tagged with the worker slot that ran it
    assert all(r.get("parent") in attempt_sids for r in wtasks)
    assert all("worker" in r for r in wtasks)
    assert all(r["ctx"]["query"] == "q-pool" for r in wtasks)
    # 0.25s of child work at 30ms heartbeats: liveness beats streamed
    beats = _by_name(tracing.spans(), "worker_heartbeat")
    assert beats and all("worker" in r for r in beats)
    assert xla_stats.delta(before).get("obs_spans_ingested", 0) >= 2


# -- speculation and retries ------------------------------------------------

def test_speculation_attempts_link_winner_and_loser():
    config.conf.set(config.SPECULATION_ENABLE.key, "on")
    config.conf.set(config.SPECULATION_QUANTILE.key, 0.25)
    config.conf.set(config.SPECULATION_MULTIPLIER.key, 1.0)
    config.conf.set(config.SPECULATION_MIN_MS.key, 10)
    tracing.start_tracing()
    lock = threading.Lock()
    calls = {}

    def fn(i):
        with lock:
            attempt = calls[i] = calls.get(i, -1) + 1
        if i == 3 and attempt == 0:
            tok = current_attempt_token()
            if not tok.wait(8.0):
                raise AssertionError("straggler was never cancelled")
            raise TaskKilledError("cooperative cancel observed")
        return i

    with tracing.execution_context(query="q-spec"):
        out = run_tasks(fn, 4, 10.0, "spec trace wave", max_workers=4)
    assert out == [0, 1, 2, 3]
    recs = tracing.spans_for_query("q-spec")
    launched = _by_name(recs, "speculation_attempt")
    wins = _by_name(recs, "speculation_win")
    losers = _by_name(recs, "speculation_loser")
    assert launched and wins and losers
    win = wins[0]["attrs"]
    assert win["task"] == 3
    # the winner names its losers and each loser points back at the
    # winner: one linked hedge pair on the query's own trace
    assert losers[0]["attrs"]["attempt"] in win["loser_attempts"]
    assert losers[0]["attrs"]["winner_attempt"] == win["winner_attempt"]
    spec_attempts = [r for r in _by_name(recs, "task_attempt")
                     if r["attrs"].get("speculative")]
    assert spec_attempts, "the hedged duplicate must carry speculative=True"


def test_retry_emits_instant_and_backoff_wait_span():
    config.conf.set(config.TASK_RETRY_BACKOFF_MS.key, 20)
    tracing.start_tracing()
    with faults.scoped(("task-start", dict(at=(1,))), seed=5):
        with tracing.execution_context(query="q-retry"):
            out = run_tasks(lambda i: i + 100, 1, 30.0, "retry trace",
                            max_workers=1)
    assert out == [100]
    recs = tracing.spans_for_query("q-retry")
    retries = _by_name(recs, "task_retry")
    waits = _by_name(recs, "backoff_wait")
    attempts = _by_name(recs, "task_attempt")
    assert retries and waits
    assert retries[0]["attrs"]["attempt"] == 1
    assert retries[0]["attrs"]["error"] == "InjectedFault"
    assert waits[0]["dur_ns"] >= 10_000_000  # the sleep really happened
    # the task-start fault fires BEFORE the attempt span opens, so only
    # the successful retry attempt has a task_attempt span
    assert [r["attrs"]["attempt"] for r in attempts] == [2]
    assert _by_name(recs, "fault_injected")


# -- streaming --------------------------------------------------------------

_SCHEMA = {"fields": [
    {"name": "k", "type": {"id": "utf8"}, "nullable": True},
    {"name": "v", "type": {"id": "int64"}, "nullable": True}]}

_WIN = StreamWindowConfig(spec=EventTimeWindowSpec(size_ms=1000),
                          keys=["k"], aggs=[("sum", "v"), ("count", None)])


def _stream_plan():
    return {"kind": "kafka_scan", "topic": "orders", "format": "json",
            "operator_id": "trace-stream", "num_partitions": 1,
            "schema": _SCHEMA}


def _stream_records(n):
    return [KafkaRecord(value=json.dumps({"k": f"k{i % 2}",
                                          "v": i}).encode("utf-8"),
                        offset=i, partition=0, timestamp_ms=i * 100)
            for i in range(n)]


def _stream_exec(tmp_path, tag="a"):
    return StreamExecutor(_stream_plan(),
                          MemoryStreamSource([_stream_records(24)]), _WIN,
                          sink_dir=str(tmp_path / f"sink-{tag}"),
                          checkpoint_dir=str(tmp_path / f"ckpt-{tag}"),
                          max_records_per_poll=6)


def test_stream_epochs_become_spans_and_recovery_an_instant(tmp_path):
    tracing.start_tracing()
    ex = _stream_exec(tmp_path)
    with faults.scoped(("stream-epoch", dict(at=(2,))), seed=9):
        summary = ex.run()
    assert summary["recoveries"] == 1
    epochs = _by_name(tracing.spans(), "stream_epoch")
    assert len(epochs) >= summary["epochs"]
    assert {r["attrs"]["epoch"] for r in epochs} >= set(
        range(summary["epochs"]))
    rec = _by_name(tracing.spans(), "stream_recovery")
    assert rec and rec[0]["attrs"]["resume_epoch"] >= 0


def test_stream_recovery_exhaustion_dumps_flight_record(tmp_path):
    config.conf.set(config.FLIGHT_RECORDER_DIR.key, str(tmp_path / "fd"))
    config.conf.set(config.STREAM_MAX_RECOVERIES.key, 0)
    tracing.start_tracing()
    ex = _stream_exec(tmp_path, tag="x")
    with faults.scoped(("stream-epoch", dict(at=(1,))), seed=2):
        with pytest.raises(faults.InjectedFault):
            ex.run()
    dumps = bridge_context.flight_dumps()
    assert len(dumps) == 1
    qid, path = next(iter(dumps.items()))
    rec = bridge_context.flight_dump(qid)
    assert rec["classification"] == "stream-recovery-exhausted"
    assert "recovery exhausted" in rec["reason"]
    assert path and os.path.exists(path)


# -- flight recorder --------------------------------------------------------

def _service_fatal(tmp_path, executor, **submit_kw):
    from blaze_tpu.serving.service import QueryService
    config.conf.set(config.FLIGHT_RECORDER_DIR.key, str(tmp_path / "fd"))
    svc = QueryService(max_concurrent=1, executor=executor)
    try:
        h = svc.submit({"kind": "noop"}, query_id="q-fatal", **submit_kw)
        with pytest.raises(Exception):
            h.result(10)
        return h
    finally:
        svc.shutdown()


def test_deadline_fatal_dumps_flight_record(tmp_path):
    tracing.start_tracing()

    def ex(plan, ctx, handle):
        time.sleep(0.2)
        ctx.check()

    _service_fatal(tmp_path, ex, deadline_ms=50)
    rec = bridge_context.flight_dump("q-fatal")
    assert rec is not None
    assert rec["classification"] == "deadline"
    assert rec["query_id"] == "q-fatal"
    # the dump is a self-contained post-mortem: recent spans, counter
    # deltas since query start, and the live config snapshot
    blob = json.load(open(rec["path"]))
    assert blob["classification"] == "deadline"
    assert "spans" in blob and "counters" in blob and "config" in blob
    assert any(s["name"] == "admission_wait" for s in blob["spans"])
    assert _by_name(tracing.spans(), "flight_dump")


def test_quota_kill_fatal_dumps_and_first_fatal_wins(tmp_path):
    def ex(plan, ctx, handle):
        ctx.cancel(reason="scan exceeded quota", kind="mem")
        ctx.check()

    _service_fatal(tmp_path, ex)
    rec = bridge_context.flight_dump("q-fatal")
    assert rec is not None and rec["classification"] == "quota-kill"
    # first-fatal-wins: a later classification cannot overwrite the dump
    assert bridge_context.record_fatal("q-fatal", "again", "deadline") is None
    assert bridge_context.flight_dump("q-fatal")["classification"] \
        == "quota-kill"


def test_flight_recorder_disabled_by_knob(tmp_path):
    config.conf.set(config.FLIGHT_RECORDER_ENABLE.key, "false")

    def ex(plan, ctx, handle):
        ctx.cancel(kind="mem")
        ctx.check()

    _service_fatal(tmp_path, ex)
    assert bridge_context.flight_dump("q-fatal") is None


# -- timeline + attribution -------------------------------------------------

def test_query_timeline_is_perfetto_loadable_with_attribution():
    tracing.start_tracing()
    with tracing.execution_context(query="q-tl"):
        with tracing.span("task_attempt", task=0, attempt=1,
                          what="tl-test"):
            time.sleep(0.005)
        tracing.emit_span("stream_epoch", 2_000_000, epoch=0, query="q-tl")
        tracing.instant("mem_spill", bytes=4096, consumer="agg",
                        cause="query-quota")
        tracing.instant("xla_compile", kernel="tl.kernel")
    wt = {"name": "worker_task", "t0_ns": 1, "t1_ns": 2_000_001,
          "dur_ns": 2_000_000, "sid": 999_999, "thread": 1,
          "ctx": {"query": "q-tl"}, "attrs": {}}
    tracing.ingest([wt], worker=3)

    tl = profiling.query_timeline("q-tl")
    assert tl["query_id"] == "q-tl"
    events = tl["traceEvents"]
    json.dumps(tl)  # the payload must be directly Perfetto-loadable
    assert all({"name", "ph", "pid", "tid"} <= set(e) for e in events)
    durs = [e for e in events if e["ph"] == "X"]
    assert durs and all("dur" in e and "ts" in e for e in durs)
    assert any(e["ph"] == "i" for e in events)
    meta = [e for e in events if e["ph"] == "M"]
    # track routing: worker spans land on their own worker process,
    # epochs and device dispatches on dedicated driver-side tracks
    procs = {e["args"]["name"] for e in meta if e["name"] == "process_name"}
    assert {"driver", "worker-3"} <= procs
    threads = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert {"epoch-0", "device"} <= threads

    attr = tl["attribution"]
    assert attr["task_cpu_seconds"] >= 0.005
    assert attr["worker_task_seconds"] == pytest.approx(0.002)
    assert attr["spill_bytes"] == 4096
    assert attr["span_count"] == len(tracing.spans_for_query("q-tl"))
    assert set(attr["shuffle_bytes_by_tier"]) == {"device", "file", "rss"}


def test_query_timeline_unknown_query_is_none():
    assert profiling.query_timeline("never-ran") is None


# -- satellites: profile store LRU, registry pin ----------------------------

def test_profile_store_lru_cap_counts_evictions():
    config.conf.set(config.PROFILE_STORE_MAX.key, 3)
    before = xla_stats.snapshot()
    for i in range(5):
        profiling.record_profile(f"lru-{i}", {"wall_ns": 100})
    kept = [p["query_id"] for p in profiling.list_profiles()]
    # the cap bounds the WHOLE store: exactly the 3 newest survive
    assert len(kept) == 3
    assert kept[-3:] == ["lru-2", "lru-3", "lru-4"]
    assert xla_stats.delta(before).get("obs_profile_evictions", 0) >= 2
    # get_profile is an LRU touch: re-reading the oldest survivor
    # protects it from the next eviction
    assert profiling.get_profile("lru-2") is not None
    profiling.record_profile("lru-5", {"wall_ns": 100})
    kept = [p["query_id"] for p in profiling.list_profiles()]
    assert "lru-2" in kept and "lru-3" not in kept


def test_span_registry_pin():
    """The full span vocabulary, pinned: adding a span name means
    registering it AND updating docs/observability.md AND exercising it
    in a test (test_span_names.py enforces the latter two)."""
    assert set(tracing.SPAN_NAMES) == {
        "task", "task_attempt", "backoff_wait", "admission_wait",
        "worker_task", "device_exchange", "exchange_stage",
        "exchange_unstage", "rss_exchange", "shuffle_exchange",
        "stage_recovery", "stage_loop_chunk",
        "stream_epoch", "explain_analyze",
        "d2h", "h2d", "prefetch_wait", "produce:*", "join_build",
        "join_probe", "agg_drain", "sort_device", "smj_merge",
        "partial_passthrough", "table_rehash",
        "op:*", "coalesce", "loop_window", "table_init", "gc_pause",
        "decimal_host_eval", "window_device", "dict_decode", "dict_remap",
        "task_retry", "fault_injected", "xla_compile",
        "device_shuffle_fallback", "rss_shuffle_fallback",
        "stage_loop_fallback", "quota_breach", "mem_spill",
        "worker_heartbeat", "worker_cancel_escalation",
        "speculation_attempt", "speculation_win", "speculation_loser",
        "stream_recovery", "flight_dump",
        "aqe_rewrite", "aqe_history_seed",
        "result_cache_hit", "subplan_cache_hit",
        "fleet_replica_down", "fleet_replica_up",
    }
    assert all(doc.strip() for doc in tracing.SPAN_NAMES.values())

"""Lightweight span tracer for query execution.

The reference exposes pprof flamegraphs over its HTTP service; the
TPU-port equivalent is a structured span log: every task, shuffle
exchange, operator stream, and fused-kernel dispatch can emit a span
carrying the (query, stage, partition) execution context.  Spans are
buffered in memory and optionally streamed to a JSONL file (one JSON
object per line: name, t0/t1 ns, thread, context, attrs) that loads
directly into Perfetto-style tooling or pandas.

Since the worker pool (PR 11) the runtime spans process boundaries, so
the tracer does too: `wire_context()` packs the current (query, stage,
task, attempt, parent-span) context into the task message riding the
CRC32C-framed worker protocol, the child adopts it under
`remote_task_scope()` and buffers its spans locally, heartbeat/result
frames carry the buffered spans back (`take_buffered()`), and the
parent stitches them into the one per-query trace via `ingest()` with
a monotonic-clock rebase — child `perf_counter_ns` origins differ per
process, so the frame carries the child clock at send time and the
parent shifts every span by the observed offset.

Tracing can be enabled programmatically (`start_tracing()`) or from
conf (`auron.tpu.trace.enable`, probed once lazily, same one-shot
pattern as faults._current).  Disabled tracing is a near-free boolean
check — operators call `span(...)` unconditionally.

Every span name the runtime can emit is registered in SPAN_NAMES
(enforced by tests/test_span_names.py: undocumented or dead names fail
conformance).  Names with a trailing `*` are prefix families — the
suffix is dynamic (prefetcher names).

While tracing is on, `span()` also enters a `jax.profiler`
`TraceAnnotation` of the same name, so a profiler trace taken through
`/trace/start` shows the host spans on the timeline of the device
programs.  Without a profiler session the annotation records nothing.
"""

from __future__ import annotations

import gc
import itertools
import json
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

_enabled = False
_conf_probed = False  # lazy one-shot auron.tpu.trace.enable probe
# re-entrant: a collection can start at any bytecode of a thread that
# holds it, and the collector's callback emits a span (`_on_gc`)
_lock = threading.RLock()
_spans: List[dict] = []
# With one `op:*` span an operator a pull a query of the benchmark holds
# 800 to 8,000 spans (PERF.md has each cell's count), a traced window a
# few of them.  What is trimmed is counted (`dropped()`), never lost in
# silence.
_MAX_SPANS = 100_000
_dropped = 0
_unreported = 0  # of `_dropped`, not yet in xla_stats' obs_spans_dropped
_gc_t0: Dict[int, int] = {}  # thread id -> clock at its collection's start
_sink = None  # open JSONL file, when exporting
_tls = threading.local()
_ids = itertools.count(1)
_annotation = None  # jax.profiler.TraceAnnotation, imported on first use

# Worker-child mode: spans are buffered locally and shipped back to the
# parent in heartbeat/result frames instead of accumulating here.
_child_mode = False
_child_buf: List[dict] = []
_CHILD_BUF_CAP = 10_000
_child_dropped = 0  # trimmed from `_child_buf`, not yet sent home

#: Registry of every span/instant name the runtime emits, with the
#: one-line doc rendered into docs/observability.md.  A trailing `*`
#: marks a prefix family (dynamic suffix).
SPAN_NAMES: Dict[str, str] = {
    # -- spans (dur_ns > 0) -------------------------------------------
    "task": "per-partition runtime stream covering one task's operator "
            "chain (bridge/runtime.py, mode=sync|producer; plan/stages.py "
            "for a map task through the stage loop, mode=loop; attrs "
            "device: the id of the chip the task runs on)",
    "task_attempt": "one scheduled attempt of a task in the wave loop, "
                    "local or routed to a pool worker (bridge/tasks.py; "
                    "attrs task/attempt/what/speculative/remote)",
    "backoff_wait": "retry backoff sleep between task attempts "
                    "(bridge/tasks.py; interruptible by cancel/deadline)",
    "admission_wait": "queue wait from QueryService.submit() to the "
                      "worker pop that starts running the query "
                      "(serving/service.py; attrs query/tenant)",
    "worker_task": "child-process execution of a remote task inside a "
                   "pool worker (parallel/workers.py child_main)",
    "device_exchange": "on-device collective shuffle dispatch for one "
                       "stage (plan/stages.py -> DeviceExchange; attrs "
                       "device: the chip of the thread that drives it, "
                       "an overlapped one its map task's; chips; rows; "
                       "staged: the wave's rows were gathered on the "
                       "host and cut over the mesh)",
    "exchange_stage": "first part of a synchronous device_exchange, on "
                      "the scheduler's thread: from the span's opening "
                      "to the collective's dispatch returning (the host "
                      "concat of a staged wave, padding, the cut over "
                      "the mesh; a placed wave's per-chip assembly) "
                      "(plan/stages.py _exchange_sync; attrs stage, "
                      "rows, bytes, tasks, staged_tasks, device)",
    "exchange_unstage": "last part of a synchronous device_exchange: "
                        "from the overflow scalar's arrival to the last "
                        "IPC block (the readback of the receive "
                        "buffers, padding included, the host split by "
                        "partition, the Arrow batch and its IPC bytes); "
                        "between exchange_stage and this lies the wait "
                        "for the collective (plan/stages.py; attrs "
                        "rows, bytes_read, partitions)",
    "rss_exchange": "remote-shuffle-service exchange tier for one stage "
                    "(plan/stages.py)",
    "shuffle_exchange": "file-tier shuffle exchange for one stage "
                        "(plan/stages.py)",
    "stage_recovery": "lineage re-run of a poisoned producer map task "
                      "after FetchFailedError (plan/stages.py)",
    "stage_loop_chunk": "one fused device-loop chunk dispatch folding a "
                        "window of batches in a single XLA call "
                        "(runtime/loop.py; overlap vs device_exchange "
                        "is the ROADMAP item-4 signal; attrs device)",
    "stream_epoch": "one streaming micro-batch epoch: poll -> plan -> "
                    "window/watermark -> sink attempt -> checkpoint "
                    "commit (streaming/executor.py; attrs epoch/rows)",
    "explain_analyze": "whole-query profiled execution (plan/explain.py)",
    "d2h": "one blocking device-to-host readback: the caller waits for "
           "the value and the programs that produce it (xputil.to_host; "
           "attrs bytes, device: the reading task's chip)",
    "h2d": "one host-to-device placement; device_put returns before the "
           "copy lands, so this is host staging and dispatch time "
           "(xputil.to_device; attrs bytes, device: the task's chip, "
           "which the buffers are committed to; a staged device "
           "exchange's columns, cut over the mesh, under the driving "
           "thread's chip: parallel/stage.py DeviceExchange.dispatch)",
    "prefetch_wait": "the consumer blocked on a prefetch queue: the "
                     "producer thread is behind (ops/base.py "
                     "PrefetchIterator.__next__; attrs source)",
    "op:*": "one pull of one operator, a real interval: next() of the "
            "operator's stream, or its eager execute()/arrow_batches() "
            "call (phase=open); suffix is the operator's class name.  "
            "Operators pull, so the spans nest child inside parent on a "
            "thread and the innermost open one is the operator whose own "
            "code runs; none on an operator's re-entrant self-call "
            "(ops/base.py _MeteredIter, _meter_stream; attrs rows)",
    "produce:*": "one item produced on a prefetch worker thread, "
                 "next(source) plus transform; suffix is the prefetcher "
                 "name, e.g. produce:parquet_scan = decode, dictionary "
                 "encoding, from_arrow and the nested h2d (ops/base.py "
                 "PrefetchIterator._work; attrs rows, and for a parquet "
                 "scan row_groups and pruned: the row groups of the files "
                 "the pull opened and those left undecoded on their "
                 "statistics)",
    "join_build": "a join's build side collected (step=collect) or "
                  "hash-indexed (step=index) (ops/joins/exec.py)",
    "join_probe": "one probe batch from hashed keys to joined batch, or "
                  "one Arrow-lane join over the collected probe side "
                  "(ops/joins/exec.py; attrs rows, lane, and for "
                  "lane=device index: direct or search)",
    "sort_device": "a sort's permutation taken on the device, one pass "
                   "per 32-bit digit of the order keys, under a merge join "
                   "or not (ops/sort.py; attrs rows, passes)",
    "smj_merge": "one partition's sort-merge join as device programs: "
                 "bounds, expansion, gather (ops/joins/merge.py; attrs "
                 "rows of both sides, pairs)",
    "window_device": "one sorted run's window functions: flags and every "
                     "function's scan, in ONE device program where the run "
                     "stayed on the chip (lane=resident; no d2h inside), "
                     "in numpy over Arrow where it did not (lane=host, a "
                     "partition-aligned chunk a span) (ops/window.py; "
                     "attrs lane, rows, functions, partitions: the "
                     "sorted runs covered, 1, not SQL partitions)",
    "agg_drain": "an aggregation table read back and turned into an "
                 "Arrow batch (plan/fused.py _emit_*; attrs table), or a "
                 "`mode=loop` map task's table drained into the device "
                 "exchange's columns where it lies (plan/stages.py, "
                 "table=loop)",
    "partial_passthrough": "one chunk of a partial aggregation that "
                           "stopped grouping: the chain as one program, "
                           "the live rows read back in accumulator form "
                           "(runtime/loop.py; attrs stage, partition, "
                           "chunk, batches)",
    "table_rehash": "the stage loop moves its hash table into a larger "
                    "one: the exchange fences, the rehash program and "
                    "the readback of its overflow scalar "
                    "(runtime/loop.py; attrs stage, partition, chunk, "
                    "from_slots, to_slots, groups, device)",
    "coalesce": "small batches concatenated into one by the coalescing "
                "stream (ops/base.py CoalesceStream; attrs batches, rows)",
    "loop_window": "a chunk's source batches assembled for the stage "
                   "loop's fold by ONE device program (stacked, widened "
                   "to the chunk, selected lanes counted), after a pad "
                   "of each batch of another capacity; the pulls of the "
                   "source outside it (plan/fused.py _assemble_window; "
                   "attrs batches, padded: batches padded first)",
    "decimal_host_eval": "decimal work outside a device program, a real "
                         "interval: an expression batch with a decimal "
                         "operand through Arrow or numpy "
                         "(exprs/program.py FusedExprsEvaluator), an "
                         "eager aggregation's batch over a decimal "
                         "argument, a decimal average's final quotient "
                         "(ops/agg/exec.py; attrs op, rows, precision, "
                         "scale)",
    "dict_decode": "dictionary codes decoded to strings on the host "
                   "(batch.py DictColumn.to_arrow), an instant under the "
                   "operator's span: where rows are shown, or where an "
                   "operator has no code lane (attrs rows)",
    "dict_remap": "a batch's codes moved under its stream's dictionary: "
                  "the dictionaries unified on the host, the codes "
                  "gathered through the remap lane where they lie "
                  "(shuffle/reader.py, batch.py concat; attrs rows, "
                  "entries)",
    "table_init": "the stage loop allocates an empty hash table "
                  "(runtime/loop.py _fold_partition; attrs slots, device)",
    "gc_pause": "one run of Python's cyclic garbage collector, on the "
                "thread whose allocation set it off; every other thread "
                "waits for the interpreter lock meanwhile (bridge/"
                "tracing.py, a gc.callbacks entry while tracing is on; "
                "attrs generation, collected)",
    "xla_compile": "one phase of one program JAX was asked for, a real "
                   "interval on the dispatching thread: phase=trace (to a "
                   "jaxpr), lower (to a module) or backend (compiled, or "
                   "looked up and loaded from the persistent cache: "
                   "cache_hit).  Emitted from JAX's own monitoring events, "
                   "so the eager one-operation programs are here too; a "
                   "`jit` traced inside a `jit` nests inside its parent's "
                   "trace (bridge/xla_stats.py _on_program_phase, the "
                   "program ledger's record as a span; attrs program: the "
                   "name the device trace prints, phase, site: "
                   "module:function:line of the caller in blaze_tpu/, ns, "
                   "source=backend, cache_hit on a backend phase, kernel "
                   "where the program is a metered one)",
    # -- instants (dur_ns == 0) ---------------------------------------
    "task_retry": "a failed attempt was classified retryable and will "
                  "back off and retry (bridge/tasks.py)",
    "fault_injected": "a seeded chaos fault fired at a registered site "
                      "(faults.py)",
    "device_shuffle_fallback": "device collective exchange declined or "
                               "failed; stage fell back a tier "
                               "(plan/stages.py)",
    "rss_shuffle_fallback": "RSS exchange tier failed; stage fell back "
                            "to the file tier (plan/stages.py)",
    "stage_loop_fallback": "fused device loop bailed; stage re-ran "
                           "staged per-batch (plan/stages.py)",
    "quota_breach": "per-query memory quota breach climbed one degrade "
                    "rung (memory/manager.py; attrs query/used/quota/"
                    "rung)",
    "mem_spill": "a memory consumer spilled under pressure or quota "
                 "shed (memory/manager.py; attrs consumer/bytes/query)",
    "worker_heartbeat": "pool-worker child liveness beat observed while "
                        "a task runs (parallel/workers.py)",
    "worker_cancel_escalation": "cancel/abandon escalated on a worker "
                                "slot: cancel msg, SIGTERM or SIGKILL "
                                "(parallel/workers.py; attrs action)",
    "speculation_attempt": "a duplicate attempt was hedged against a "
                           "straggler (bridge/tasks.py; attrs task/"
                           "attempt)",
    "speculation_win": "an attempt committed first; links the "
                       "winner/loser attempt pair (bridge/tasks.py; "
                       "attrs task/winner_attempt/loser_attempts)",
    "speculation_loser": "a losing attempt was cancelled or abandoned "
                         "after the sibling committed (bridge/tasks.py)",
    "aqe_rewrite": "an adaptive-execution rule rewrote a not-yet-"
                   "dispatched consumer stage at the boundary "
                   "(plan/adaptive.py; attrs stage/rule)",
    "aqe_history_seed": "bind-time planning applied statstore-derived "
                        "seeds to the plan (plan/adaptive.py; attrs "
                        "seeds)",
    "stream_recovery": "streaming epoch restored from the latest "
                       "checkpoint manifest after a retryable failure "
                       "(streaming/executor.py)",
    "flight_dump": "the flight recorder wrote a post-mortem artifact "
                   "for a fatally-classified query (bridge/context.py)",
    "result_cache_hit": "a whole-query result was served from the "
                        "work-sharing cache, skipping execution "
                        "(serving/service.py; attrs query/fingerprint/"
                        "nbytes)",
    "subplan_cache_hit": "a leaf map stage replayed cached "
                         "exchange-boundary blocks instead of running "
                         "its tasks (plan/stages.py; attrs stage/"
                         "fingerprint)",
    "fleet_replica_down": "the fleet router marked a replica down "
                          "after a transport error, a missed liveness "
                          "deadline, or drain (fleet/router.py; attrs "
                          "replica/reason)",
    "fleet_replica_up": "a down replica answered a backoff probe and "
                        "rejoined the routable set (fleet/router.py; "
                        "attrs replica)",
}


def _check_name(name: str) -> None:
    """Emitting an unregistered span name is a bug, not telemetry: the
    registry is the conformance contract (tests/test_span_names.py).
    Only reached when tracing is ON — the disabled path never gets here."""
    if name in SPAN_NAMES:
        return
    i = name.find(":")
    if i > 0 and name[:i + 1] + "*" in SPAN_NAMES:
        return
    raise ValueError(
        f"unregistered span name {name!r}: add it to tracing.SPAN_NAMES "
        "and document it in docs/observability.md")


def _probe_conf() -> None:
    global _conf_probed, _enabled
    with _lock:
        if _conf_probed:
            return
        _conf_probed = True
    try:
        from blaze_tpu import config
        if config.TRACE_ENABLE.get():
            _watch_gc(True)
            _enabled = True
    except Exception:
        pass


def enabled() -> bool:
    if not _conf_probed:
        _probe_conf()
    return _enabled


def _ctx_stack() -> List[Dict[str, Any]]:
    stack = getattr(_tls, "ctx", None)
    if stack is None:
        stack = _tls.ctx = []
    return stack


def _span_stack() -> List[int]:
    stack = getattr(_tls, "span_stack", None)
    if stack is None:
        stack = _tls.span_stack = []
    return stack


def current_context() -> Dict[str, Any]:
    """Innermost query/stage/partition context on this thread."""
    out: Dict[str, Any] = {}
    for frame in _ctx_stack():
        out.update(frame)
    return out


@contextmanager
def execution_context(**fields):
    """Push query_id/stage/partition (any subset) for spans emitted on
    this thread; nests — inner frames override outer keys."""
    stack = _ctx_stack()
    stack.append({k: v for k, v in fields.items() if v is not None})
    try:
        yield
    finally:
        stack.pop()


def capture() -> tuple:
    """(execution context, enclosing span id) of this thread, for a
    helper thread to `adopt()`: thread-locals do not follow the work."""
    stack = getattr(_tls, "span_stack", None)
    return current_context(), (stack[-1] if stack else None)


@contextmanager
def adopt(captured: tuple):
    """Helper-thread side of `capture()`: spans emitted in the body carry
    the capturing thread's query/stage/partition and parent under its
    enclosing span (what remote_task_scope does across processes)."""
    ctx, parent = captured
    stack = _span_stack()
    if parent is not None:
        stack.append(parent)
    try:
        with execution_context(**ctx):
            yield
    finally:
        if parent is not None:
            stack.pop()


def _profiler_annotation(name: str):
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name)


@contextmanager
def span(name: str, **attrs):
    """Emit one span covering the `with` body.  No-op when disabled."""
    if not _enabled:
        if _conf_probed or not enabled():
            yield attrs
            return
    _check_name(name)
    sid = next(_ids)
    stack = _span_stack()
    parent = stack[-1] if stack else None
    stack.append(sid)
    t0 = time.perf_counter_ns()
    try:
        with _profiler_annotation(name):
            yield attrs  # the body may add what it learns (rows, bytes)
    finally:
        t1 = time.perf_counter_ns()
        stack.pop()
        record = {"name": name, "t0_ns": t0, "t1_ns": t1,
                  "dur_ns": t1 - t0, "sid": sid,
                  "thread": threading.current_thread().name,
                  "tid": threading.get_ident()}
        if parent is not None:
            record["parent"] = parent
        ctx = current_context()
        if ctx:
            record["ctx"] = ctx
        if attrs:
            record["attrs"] = attrs
        _emit(record)


def emit_span(name: str, dur_ns: int, **attrs) -> None:
    """Record a span whose duration was measured externally (the operator
    stream meter accumulates time across many next() calls)."""
    if not _enabled:
        if _conf_probed or not enabled():
            return
    _check_name(name)
    t1 = time.perf_counter_ns()
    record = {"name": name, "t0_ns": t1 - int(dur_ns), "t1_ns": t1,
              "dur_ns": int(dur_ns), "sid": next(_ids),
              "thread": threading.current_thread().name,
              "tid": threading.get_ident()}
    stack = _span_stack()
    if stack:
        record["parent"] = stack[-1]
    ctx = current_context()
    if ctx:
        record["ctx"] = ctx
    if attrs:
        record["attrs"] = attrs
    _emit(record)


def instant(name: str, **attrs) -> None:
    """Zero-duration event (e.g. an XLA compile)."""
    if not _enabled:
        if _conf_probed or not enabled():
            return
    _check_name(name)
    t = time.perf_counter_ns()
    record = {"name": name, "t0_ns": t, "t1_ns": t, "dur_ns": 0,
              "sid": next(_ids),
              "thread": threading.current_thread().name,
              "tid": threading.get_ident()}
    stack = _span_stack()
    if stack:
        record["parent"] = stack[-1]
    ctx = current_context()
    if ctx:
        record["ctx"] = ctx
    if attrs:
        record["attrs"] = attrs
    _emit(record)


def _trim() -> None:
    """Drop the oldest spans over `_MAX_SPANS`, counted (caller holds
    `_lock`)."""
    global _dropped, _unreported
    over = len(_spans) - _MAX_SPANS
    if over > 0:
        del _spans[:over]
        _dropped += over
        _unreported += over


def _report_dropped() -> None:
    """Hand what `_trim` counted to `obs_spans_dropped`.  Not from the
    collector's callback: the collecting thread may hold xla_stats' lock,
    and the next span emitted reports for it."""
    global _unreported
    if not _unreported or threading.get_ident() in _gc_t0:
        return
    with _lock:
        n, _unreported = _unreported, 0
    from blaze_tpu.bridge import xla_stats
    xla_stats.note_obs(spans_dropped=n)


def _emit(record: dict) -> None:
    global _child_dropped
    with _lock:
        if _child_mode:
            _child_buf.append(record)
            over = len(_child_buf) - _CHILD_BUF_CAP
            if over > 0:
                del _child_buf[:over]
                _child_dropped += over
            return
        _spans.append(record)
        _trim()
        if _sink is not None:
            _sink.write(json.dumps(record, default=str) + "\n")
            _sink.flush()
    _report_dropped()


def dropped() -> int:
    """Spans trimmed from the buffer since `start_tracing()`: a reader of
    `spans()` that finds this above 0 holds a window's end, not all of
    it (counter `obs_spans_dropped` sums it over the process's life)."""
    with _lock:
        return _dropped


def _watch_gc(on: bool) -> None:
    """Install or take out the `gc.callbacks` entry (`gc_pause` spans):
    in while tracing is on, however it was switched on."""
    with _lock:
        if on and _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        elif not on:
            if _on_gc in gc.callbacks:
                gc.callbacks.remove(_on_gc)
            _gc_t0.clear()


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` entry while tracing is on: one `gc_pause` span a
    collection.  `start` and `stop` come on the collecting thread, and
    collections do not nest, so the start's clock is kept by thread."""
    tid = threading.get_ident()
    if phase == "start":
        _gc_t0[tid] = time.perf_counter_ns()
        return
    t0 = _gc_t0.get(tid)
    if t0 is not None:
        emit_span("gc_pause", time.perf_counter_ns() - t0,
                  generation=info.get("generation"),
                  collected=info.get("collected"))
        del _gc_t0[tid]


# -- cross-process propagation ---------------------------------------------

_WIRE_KEYS = ("query", "stage", "task", "attempt", "what", "partition")


def wire_context(**extra) -> Optional[dict]:
    """Compact trace context to ride the worker wire protocol: the
    current (query, stage, task, attempt) plus the enclosing span id as
    `parent`.  Returns None when tracing is off, so the task message
    grows by nothing on the disabled path."""
    if not enabled():
        return None
    ctx = current_context()
    out = {k: ctx[k] for k in _WIRE_KEYS if k in ctx}
    stack = getattr(_tls, "span_stack", None)
    if stack:
        out["parent"] = stack[-1]
    for k, v in extra.items():
        if v is not None:
            out[k] = v
    return out


@contextmanager
def remote_task_scope(wire_ctx: Optional[dict]):
    """Child-process side: adopt a parent trace context for the duration
    of one task.  Enables span collection in child-buffer mode (spans go
    to a local buffer drained by take_buffered() into heartbeat/result
    frames) and parents every child span under the dispatching span."""
    if not wire_ctx:
        yield
        return
    global _enabled, _conf_probed, _child_mode
    with _lock:
        saved = (_enabled, _conf_probed, _child_mode)
        _enabled = True
        _conf_probed = True
        _child_mode = True
    parent = wire_ctx.get("parent")
    fields = {k: v for k, v in wire_ctx.items() if k != "parent"}
    stack = _span_stack()
    if parent is not None:
        stack.append(parent)
    try:
        with execution_context(**fields):
            yield
    finally:
        if parent is not None:
            stack.pop()
        with _lock:
            _enabled, _conf_probed, _child_mode = saved


def take_buffered() -> List[dict]:
    """Drain the child-mode span buffer (heartbeat/result frame payload)."""
    with _lock:
        out = list(_child_buf)
        del _child_buf[:]
    return out


def take_child_dropped() -> int:
    """Spans the child-mode buffer's cap trimmed since the last frame:
    rides the frame beside `take_buffered()`'s spans, and the parent's
    `ingest()` adds it to `obs_spans_dropped`."""
    global _child_dropped
    with _lock:
        n, _child_dropped = _child_dropped, 0
    return n


def ingest(records: Optional[List[dict]], worker=None,
           clock_ns: Optional[int] = None, dropped: int = 0) -> int:
    """Parent side: stitch spans shipped back from a worker child into
    the process trace.  `worker` tags the originating slot; `clock_ns`
    is the child's perf_counter_ns at frame-send time, used to rebase
    the child's clock origin onto ours (transit latency is absorbed
    into the offset — fine at heartbeat granularity); `dropped`: spans
    the child's buffer trimmed before the frame left, counted as the
    parent's own trims are."""
    if dropped:
        from blaze_tpu.bridge import xla_stats
        xla_stats.note_obs(spans_dropped=int(dropped))
    if not records or not _enabled:
        return 0
    offset = 0
    if clock_ns is not None:
        offset = time.perf_counter_ns() - int(clock_ns)
    with _lock:
        for r in records:
            if not isinstance(r, dict):
                continue
            if worker is not None:
                r.setdefault("worker", worker)
            if offset:
                r["t0_ns"] = r.get("t0_ns", 0) + offset
                r["t1_ns"] = r.get("t1_ns", 0) + offset
            _spans.append(r)
            if _sink is not None:
                _sink.write(json.dumps(r, default=str) + "\n")
        _trim()
        if _sink is not None:
            _sink.flush()
    try:
        from blaze_tpu.bridge import xla_stats
        xla_stats.note_obs(spans_ingested=len(records))
    except Exception:
        pass
    _report_dropped()
    return len(records)


def spans_for_query(query_id) -> List[dict]:
    """All buffered spans whose context names this query (the timeline
    endpoint and the flight recorder read this)."""
    with _lock:
        return [r for r in _spans
                if r.get("ctx", {}).get("query") == query_id]


# -- lifecycle --------------------------------------------------------------

def start_tracing(path: Optional[str] = None) -> None:
    """Enable span collection; `path` additionally streams JSONL there."""
    global _enabled, _sink, _conf_probed, _dropped
    with _lock:
        _spans.clear()
        _dropped = 0
        if _sink is not None:
            _sink.close()
            _sink = None
        if path:
            _sink = open(path, "w")
        _conf_probed = True
        _watch_gc(True)
    _enabled = True


def stop_tracing() -> List[dict]:
    """Disable collection; returns (and keeps) the buffered spans."""
    global _enabled, _sink
    _enabled = False
    with _lock:
        _watch_gc(False)
        if _sink is not None:
            _sink.close()
            _sink = None
        return list(_spans)


def reset_conf_probe() -> None:
    """Forget the lazy auron.tpu.trace.enable probe (tests)."""
    global _conf_probed, _enabled, _child_mode, _child_dropped
    with _lock:
        _conf_probed = False
        _enabled = False
        _child_mode = False
        del _child_buf[:]
        _child_dropped = 0
        _watch_gc(False)


def spans() -> List[dict]:
    with _lock:
        return list(_spans)

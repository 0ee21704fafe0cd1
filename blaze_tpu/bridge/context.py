"""Per-task execution context.

Parity: the reference's TaskDefinition proto (task_id/stage_id/partition_id,
ref auron-planner/proto/auron.proto:814 TaskDefinition) and the thread-local
stage/partition ids the native runtime injects into every worker thread
(ref native-engine/auron/src/rt.rs:133-135, logging.rs).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Optional


@dataclass
class TaskContext:
    stage_id: int = 0
    partition_id: int = 0
    num_partitions: int = 1
    attempt_num: int = 0
    task_attempt_id: int = 0
    # cooperative-cancel probe (ref JniBridge.isTaskRunning,
    # AuronAdaptor.java:76-80; polled in long loops)
    is_running: Callable[[], bool] = lambda: True
    # owning serving.QueryContext, if this task runs inside the query
    # service; carried on the TaskContext so PrefetchIterator workers
    # re-entering via task_scope() inherit the cancellation token.
    query: Optional[Any] = None
    # device-resident stage loop progress (runtime/loop.py): chunks this
    # task has folded so far.  The cancellation token is checked at each
    # chunk boundary, so teardown tests can assert the loop stopped
    # within one chunk of the cancel by reading this counter.
    loop_chunks: int = 0
    # the chip this task runs on (parallel/mesh.task_device: partition p
    # on device p mod n of the dp mesh), or None: the process's one
    # device, or compute pinned to the host's XLA backend, where nothing
    # is pinned and JAX's default placement holds.  `task_scope` makes
    # it the thread's default device, so every array the task creates is
    # made there; `xputil.to_device` commits the task's inputs to it; a
    # PrefetchIterator worker re-enters the scope, so the chip rides
    # with the context.
    device: Optional[Any] = None

    @property
    def device_id(self) -> int:
        """The chip's id for spans and counters (0 where nothing is
        pinned: the one device there is)."""
        return 0 if self.device is None else int(self.device.id)

    def check_running(self):
        if not self.is_running():
            raise TaskKilledError(
                f"task stage={self.stage_id} partition={self.partition_id} killed")
        probe = _host_task_probe
        if probe is not None and not probe(self.stage_id,
                                           self.partition_id):
            raise TaskKilledError(
                f"task stage={self.stage_id} "
                f"partition={self.partition_id} killed by host")
        q = self.query if self.query is not None else current_query()
        if q is not None:
            q.check()


class TaskKilledError(RuntimeError):
    pass


_local = threading.local()


def current_task() -> TaskContext:
    ctx = getattr(_local, "ctx", None)
    if ctx is None:
        ctx = TaskContext()
        _local.ctx = ctx
    return ctx


def set_current_task(ctx: Optional[TaskContext]) -> None:
    _local.ctx = ctx


class task_scope:
    """`with task_scope(TaskContext(...)):` — restores the previous
    context.  A task that has a chip runs inside `jax.default_device` of
    that chip (thread-local, like the context itself)."""

    def __init__(self, ctx: TaskContext):
        self._ctx = ctx
        self._prev: Optional[TaskContext] = None
        self._on_chip = None

    def __enter__(self) -> TaskContext:
        self._prev = getattr(_local, "ctx", None)
        _local.ctx = self._ctx
        if self._ctx.device is not None:
            import jax
            self._on_chip = jax.default_device(self._ctx.device)
            self._on_chip.__enter__()
        return self._ctx

    def __exit__(self, *exc):
        if self._on_chip is not None:
            self._on_chip.__exit__(*exc)
            self._on_chip = None
        _local.ctx = self._prev
        return False


_attempt_local = threading.local()


def current_attempt_token():
    """The speculative-attempt cancel token (threading.Event) bound to
    this thread, or None.  NativeExecutionRuntime reads it at TaskContext
    creation (like current_query) so a losing attempt's check_running()
    raises TaskKilledError as soon as the sibling commits."""
    return getattr(_attempt_local, "token", None)


class attempt_scope:
    """`with attempt_scope(event):` — binds a per-attempt cancel token
    to this thread.  Accepts None (no-op binding); restores the previous
    binding on exit."""

    def __init__(self, token):
        self._token = token
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_attempt_local, "token", None)
        _attempt_local.token = self._token
        return self._token

    def __exit__(self, *exc):
        _attempt_local.token = self._prev
        return False


_query_local = threading.local()


def current_query():
    """The serving.QueryContext bound to this thread, or None."""
    return getattr(_query_local, "query", None)


def active_query():
    """The query governing the current execution, or None.

    Prefers the query attached to the current TaskContext (survives
    hand-off to prefetch workers via task_scope) and falls back to the
    thread-local set by query_scope.
    """
    ctx = getattr(_local, "ctx", None)
    if ctx is not None and ctx.query is not None:
        return ctx.query
    return current_query()


class query_scope:
    """`with query_scope(qctx):` — binds a query to this thread.

    Accepts None (no-op binding) so call sites can thread an optional
    query without branching.  Restores the previous binding on exit.
    """

    def __init__(self, query):
        self._query = query
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_query_local, "query", None)
        _query_local.query = self._query
        return self._query

    def __exit__(self, *exc):
        _query_local.query = self._prev
        return False


#: Host-engine task-liveness probe installed via the C-ABI callback
#: surface (ref JniBridge.isTaskRunning)
_host_task_probe = None


def set_host_task_probe(fn) -> None:
    global _host_task_probe
    _host_task_probe = fn


# -- flight recorder --------------------------------------------------------
#
# A bounded per-query black box: the counter plane is snapshotted at
# query start, and when the query dies with a fatal classification
# (quota kill, deadline, pool-unavailable, stream recovery exhaustion)
# the recorder dumps the last N spans + counter deltas + config
# snapshot to a post-mortem JSON artifact.  First fatal per query wins;
# DagScheduler.leak_report() references the artifact path.

_flight_lock = threading.Lock()
_flight_dumps: dict = {}      # query_id -> dump dict (incl. "path")
_flight_baselines: dict = {}  # query_id -> xla_stats.snapshot() at start
_FLIGHT_BASELINE_CAP = 256


def note_query_start(query_id) -> None:
    """Snapshot the counter plane at query start so a later fatal dump
    carries deltas attributable to this query's lifetime."""
    if query_id is None:
        return
    try:
        from blaze_tpu.bridge import xla_stats
        snap = xla_stats.snapshot()
    except Exception:
        return
    with _flight_lock:
        _flight_baselines[query_id] = snap
        while len(_flight_baselines) > _FLIGHT_BASELINE_CAP:
            _flight_baselines.pop(next(iter(_flight_baselines)))


def record_fatal(query_id, reason: str, classification: str = "fatal"):
    """Write the post-mortem artifact for a fatally-classified query.

    Returns the dump dict (also retrievable via flight_dump), or None
    when the recorder is disabled or this query already dumped."""
    import json
    import os
    import tempfile
    import time as _time
    try:
        from blaze_tpu import config
        from blaze_tpu.bridge import tracing, xla_stats
        if not config.FLIGHT_RECORDER_ENABLE.get():
            return None
        max_spans = max(1, config.FLIGHT_RECORDER_SPANS.get())
        out_dir = config.FLIGHT_RECORDER_DIR.get() or os.path.join(
            tempfile.gettempdir(), "blaze_flight")
    except Exception:
        return None
    with _flight_lock:
        if query_id in _flight_dumps:
            return None  # first fatal wins
        baseline = _flight_baselines.pop(query_id, None)
        _flight_dumps[query_id] = {}  # claim before the slow I/O below
    spans = tracing.spans_for_query(query_id)
    if not spans:  # query ran without span context (or tracing off)
        spans = tracing.spans()
    spans = spans[-max_spans:]
    counters = (xla_stats.delta(baseline) if baseline is not None
                else xla_stats.snapshot())
    dump = {
        "query_id": str(query_id),
        "reason": str(reason),
        "classification": str(classification),
        "wall_time": _time.time(),
        "spans": spans,
        "counters": counters,
        "config": config.conf.snapshot(),
    }
    path = None
    try:
        os.makedirs(out_dir, exist_ok=True)
        safe = "".join(c if c.isalnum() or c in "-._" else "_"
                       for c in str(query_id))
        path = os.path.join(out_dir,
                            f"flight-{safe}-{os.getpid()}.json")
        with open(path, "w") as f:
            json.dump(dump, f, indent=1, default=str)
    except OSError:
        path = None  # keep the in-memory dump even if the disk write failed
    dump["path"] = path
    with _flight_lock:
        _flight_dumps[query_id] = dump
    xla_stats.note_obs(flight_dumps=1)
    tracing.instant("flight_dump", query=query_id, reason=reason,
                    classification=classification, path=path)
    return dump


def flight_dump(query_id):
    """The post-mortem dump recorded for this query, or None."""
    with _flight_lock:
        d = _flight_dumps.get(query_id)
        return d if d else None


def flight_dumps() -> dict:
    """query_id -> artifact path for every recorded dump."""
    with _flight_lock:
        return {q: d.get("path") for q, d in _flight_dumps.items() if d}


def reset_flight_recorder() -> None:
    """Test helper: forget dumps and baselines (files are left on disk)."""
    with _flight_lock:
        _flight_dumps.clear()
        _flight_baselines.clear()

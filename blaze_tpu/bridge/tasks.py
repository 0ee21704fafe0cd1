"""Bounded task-pool helper of the stage scheduler.

A task thread wedged inside backend init/compile must convert to a
TimeoutError for the caller instead of hanging ThreadPoolExecutor
forever (a thread stuck in `jax.devices()` otherwise ends its process
at the caller's time limit, rc=124).  shutdown(wait=False) leaves any
stuck thread behind; a caller that must exit promptly despite one
reports and then uses os._exit.

Fault tolerance: each task gets bounded retries with exponential
backoff + jitter for RETRYABLE failures (transient IO, injected faults —
faults.classify_exception), the spark.task.maxFailures analog.  Fatal
errors (plan/serde/logic) and FetchFailedError reach the caller after
ONE attempt: retrying a bad plan wastes budget, and a fetch failure
needs the DAG scheduler's lineage recovery, not a local re-read of the
same poisoned block.  The pool waits with FIRST_EXCEPTION semantics so
a task that fails in the first millisecond surfaces immediately instead
of sitting out the full timeout behind healthy siblings.

Speculative execution (`auron.tpu.speculation.enable`, the
spark.speculation analog): the wave loop is attempt-SET-aware — each
task owns a list of attempts rather than one future.  Once the quantile
share of a wave's tasks has finished, a task running longer than
multiplier x the wave's median successful duration gets ONE duplicate
attempt with a fresh attempt id, dispatched to a different pool worker
(the crash-exclude set seeds from the original's worker) or a spare
thread slot otherwise.  The first attempt to commit wins; the loser is
cancelled through the cooperative token (context.attempt_scope ->
TaskContext.is_running) and its output is rejected by the shuffle
tier's first-wins commit arbitration even if it runs to completion (the
speculation-loser-commit-race fault site forces exactly that).  With
speculation off every task has exactly one attempt and the loop
degenerates to the historical single-future-per-task behavior.
"""

from __future__ import annotations

import logging
import math
import random
import statistics
import threading
import time
import zlib
from concurrent.futures import FIRST_EXCEPTION, ThreadPoolExecutor, wait
from typing import Any, Callable, Dict, List, Optional

from blaze_tpu import faults
from blaze_tpu.faults import FetchFailedError, TaskDeadlineExpired, \
    WorkerCrashed, classify_exception

log = logging.getLogger("blaze_tpu.tasks")

_BACKOFF_CAP_S = 10.0


def default_task_parallelism(n: int) -> int:
    """Concurrent task slots.  Device placement overlaps host round trips,
    so one slot per task; host placement runs tasks serially by default —
    the per-task work is Python-orchestrated (GIL) around C++ kernels that
    already use every core intra-op, and measured 4-task concurrency on a
    2-core host was 2.5x SLOWER than serial (GIL contention + thread
    thrash).  `auron.tpu.host.taskParallelism` overrides."""
    from blaze_tpu.bridge.placement import host_resident
    if not host_resident():
        return max(1, n)
    from blaze_tpu import config
    return max(1, min(n, config.HOST_TASK_PARALLELISM.get()))


class _Attempt:
    """One attempt of one task in a wave: the unit the attempt-set-aware
    loop schedules, cancels and arbitrates.  `cancel` is the cooperative
    token — set when a sibling attempt committed first; the running
    attempt observes it at its next check point (TaskContext.is_running
    in-process, the pool's poll loop for a worker-dispatched attempt)."""

    __slots__ = ("task", "speculative", "future", "cancel", "exclude",
                 "started", "duration", "worker_id")

    def __init__(self, task: int, speculative: bool = False):
        self.task = task
        self.speculative = speculative
        self.future = None
        self.cancel = threading.Event()
        # worker-pool ids this attempt must avoid: crashed workers
        # accumulate here, and a speculative duplicate seeds it with the
        # original attempt's worker so the hedge lands elsewhere
        self.exclude: set = set()
        self.started: Optional[float] = None   # monotonic, on-thread
        self.duration: Optional[float] = None  # successful elapsed (s)
        self.worker_id: Optional[int] = None   # current pool assignment


def _backoff_jitter(what: str, task: int, attempt: int) -> float:
    """Deterministic jitter in [0, 1): a pure function of the faults
    seed + (what, task, attempt), the same crc32-keyed construction as
    faults.FaultInjector — seeded chaos soaks (--chaos/--workers/
    --speculate) replay with identical retry timing, while distinct
    tasks still decorrelate their retry herds."""
    from blaze_tpu import config
    seed = config.FAULTS_SEED.get()
    key = f"{seed}|backoff|{what}|{task}|{attempt}".encode()
    return random.Random(zlib.crc32(key)).random()


def _run_with_retries(fn: Callable[[int], Any], i: int, what: str,
                      query=None, remote=None, deadline=None,
                      state: Optional[_Attempt] = None) -> Any:
    """One task slot: bounded attempts around `fn(i)` (runs ON the pool
    thread, so retries never hold a second slot).  `query` (an optional
    serving.QueryContext) is bound to the pool thread for the duration
    and makes backoff sleeps interruptible: a cancelled query raises
    from inside the sleep instead of sitting out the full backoff.

    `remote` optionally maps `i` to a worker-pool task spec
    ({"fn": "module:qualname", "args": tuple}); when the pool is enabled
    the attempt runs process-isolated there instead of via `fn(i)`, a
    crash comes back as retryable WorkerCrashed, and the retry EXCLUDES
    the crashed worker so it lands on a different one.  `deadline`
    (monotonic) bounds each remote attempt so a wedged worker is killed
    instead of holding its slot past the wave timeout.

    `state` (an _Attempt) carries the cooperative cancel token and the
    worker-exclude set across the retry loop; its cancel event aborts
    the slot — including mid-backoff — when a sibling attempt won."""
    from blaze_tpu import config
    from blaze_tpu.bridge import tracing, xla_stats
    from blaze_tpu.bridge.context import TaskKilledError, attempt_scope, \
        query_scope
    max_attempts = max(1, config.TASK_MAX_ATTEMPTS.get())
    base_s = max(0, config.TASK_RETRY_BACKOFF_MS.get()) / 1e3
    wait_ns = 0
    attempt = 1
    cancel = state.cancel if state is not None else None
    exclude: set = state.exclude if state is not None else set()
    speculative = bool(state is not None and state.speculative)
    t0 = time.monotonic()
    if state is not None:
        state.started = t0
    with query_scope(query), attempt_scope(cancel), \
            tracing.execution_context(
                query=getattr(query, "query_id", None), task=i, what=what):
        while True:
            try:
                if cancel is not None and cancel.is_set():
                    raise TaskKilledError(
                        f"{what}: task {i} attempt cancelled — a sibling "
                        f"attempt committed first")
                if query is not None:
                    query.check()
                faults.maybe_fail("task-start", task=i, attempt=attempt,
                                  what=what)
                out = _POOL_MISS
                with tracing.span("task_attempt", task=i, attempt=attempt,
                                  what=what, speculative=speculative):
                    if remote is not None:
                        # resolved per ATTEMPT: shuffle-input locations
                        # may have moved after a lineage recovery round,
                        # and an invalidated input must surface as
                        # FetchFailedError now, not ship a stale block
                        # list
                        spec = remote(i)
                        if spec is not None:
                            out = _run_remote(spec, exclude, deadline,
                                              query, what, state)
                    if out is _POOL_MISS:
                        if attempt == 1:
                            out = fn(i)
                        else:
                            # retries take the most conservative path:
                            # decline the device-resident stage loop (an
                            # optimization that was live during the
                            # attempt that failed)
                            from blaze_tpu.plan.stage_compiler import \
                                decline_loop_scope
                            with decline_loop_scope():
                                out = fn(i)
                xla_stats.note_task_attempts(attempt, wait_ns)
                dur = time.monotonic() - t0
                if state is not None:
                    state.duration = dur
                xla_stats.note_task_duration(int(dur * 1e9))
                return out
            except BaseException as e:
                if cancel is not None and cancel.is_set():
                    # cancelled loser unwinding, not a task failure: the
                    # sibling attempt already committed — don't count it
                    # against fault-tolerance stats or retry budget
                    raise
                if isinstance(e, WorkerCrashed) \
                        and e.worker_id is not None:
                    exclude.add(e.worker_id)
                kind = classify_exception(e)
                if kind != "retryable" or attempt >= max_attempts:
                    xla_stats.note_task_attempts(attempt, wait_ns,
                                                 failed=True)
                    raise
                delay = min(base_s * (2 ** (attempt - 1)), _BACKOFF_CAP_S)
                # decorrelate herds — deterministically, so seeded soaks
                # replay with identical retry timing
                delay *= 1.0 + 0.25 * _backoff_jitter(what, i, attempt)
                log.warning("%s: task %d attempt %d/%d failed (%s: %s); "
                            "retrying in %.2fs", what, i, attempt,
                            max_attempts, type(e).__name__, e, delay)
                tracing.instant("task_retry", task=i, attempt=attempt,
                                error=type(e).__name__, what=what)
                with tracing.span("backoff_wait", task=i, attempt=attempt,
                                  what=what, delay_s=round(delay, 4)):
                    if query is not None:
                        if query.wait_cancelled(delay):
                            query.check()
                    elif cancel is not None:
                        # interruptible by a sibling's win: the loser
                        # must not sit out a capped backoff before
                        # noticing
                        cancel.wait(delay)
                    else:
                        time.sleep(delay)
                wait_ns += int(delay * 1e9)
                attempt += 1


_POOL_MISS = object()


def _run_remote(spec, exclude: set, deadline, query, what: str,
                state: Optional[_Attempt] = None) -> Any:
    """One process-isolated attempt on the worker pool.  Returns
    _POOL_MISS when the pool can't take it (disabled / spawn failed /
    fully blacklisted) so the caller falls back to in-process."""
    from blaze_tpu import config
    if not config.WORKERS_ENABLE.get() and not (
            query is not None and config.SERVING_USE_WORKERS.get()):
        return _POOL_MISS
    from blaze_tpu.parallel import workers
    pool = workers.get_pool()
    if pool is None:
        return _POOL_MISS
    timeout_s = None
    if deadline is not None:
        timeout_s = deadline - time.monotonic()
        if timeout_s <= 0:
            # FATAL, not retryable: an expired wave deadline cannot
            # un-expire, so burning maxAttempts backoff sleeps here only
            # delays the wave-level TimeoutError
            raise TaskDeadlineExpired(
                "worker task deadline already expired")
    on_assign = None
    cancel_event = None
    if state is not None:
        cancel_event = state.cancel

        def on_assign(worker_id: int) -> None:
            # remembered so a speculative duplicate can exclude the
            # worker the original attempt is (still) running on
            state.worker_id = worker_id
    try:
        return pool.run(spec, exclude=exclude, timeout_s=timeout_s,
                        query=query, what=what,
                        cancel_event=cancel_event, on_assign=on_assign)
    except workers.WorkerPoolUnavailable:
        return _POOL_MISS


def run_tasks(fn: Callable[[int], Any], n: int, timeout_s: float,
              what: str, max_workers: Optional[int] = None,
              query=None, remote=None) -> List[Any]:
    from blaze_tpu import config
    from blaze_tpu.bridge import tracing, xla_stats
    deadline = time.monotonic() + timeout_s
    if remote is not None:
        # process-isolated tasks don't contend on the GIL: give every
        # map task its own slot-waiter thread and let the worker pool's
        # slot count be the real concurrency limit
        if max_workers is None and (
                config.WORKERS_ENABLE.get()
                or (query is not None
                    and config.SERVING_USE_WORKERS.get())):
            max_workers = max(1, n)
    spec_conf = None
    if n >= 2 and config.SPECULATION_ENABLE.get():
        spec_conf = (min(1.0, max(0.0, config.SPECULATION_QUANTILE.get())),
                     max(1.0, config.SPECULATION_MULTIPLIER.get()),
                     max(0, config.SPECULATION_MIN_MS.get()) / 1e3)
    pool = ThreadPoolExecutor(max_workers=max_workers or
                              default_task_parallelism(n))
    # speculative duplicates run on their own small executor: the
    # primary pool's slots may all be held by the very stragglers being
    # hedged, and a duplicate queued behind its original would be
    # useless ("a spare thread slot otherwise")
    spec_pool: Optional[ThreadPoolExecutor] = None
    by_future: Dict[Any, _Attempt] = {}
    attempts: Dict[int, List[_Attempt]] = {}
    results: Dict[int, Any] = {}
    deferred: Dict[int, BaseException] = {}  # failed, sibling still live
    durations: List[float] = []              # successful task durations
    speculated = False
    wave_t0 = time.monotonic()

    # attempt threads don't inherit the caller's thread-local trace
    # context (the scheduler's query id); re-apply it around each attempt
    caller_ctx = tracing.current_context()

    def submit(executor, att: _Attempt) -> None:
        def call():
            with tracing.execution_context(**caller_ctx):
                return _run_with_retries(fn, att.task, what, query,
                                         remote, deadline, att)
        att.future = executor.submit(call)
        by_future[att.future] = att

    for i in range(n):
        att = _Attempt(i)
        attempts[i] = [att]
        submit(pool, att)
    pending = set(by_future)

    def shutdown_all(cancel_futures: bool) -> None:
        pool.shutdown(wait=False, cancel_futures=cancel_futures)
        if spec_pool is not None:
            spec_pool.shutdown(wait=False, cancel_futures=cancel_futures)

    def settle_losers(winner: _Attempt) -> None:
        """First-wins: cancel the losing attempts of the winner's task —
        unless the loser-commit-race site fires, in which case BOTH run
        to the commit point and the shuffle tier must reject the late
        one (that rejection is the property under test)."""
        losers = [a for a in attempts[winner.task]
                  if a is not winner and not a.future.done()]
        if not losers:
            return
        if faults.fires("speculation-loser-commit-race",
                        task=winner.task, what=what):
            xla_stats.note_speculation(commit_races=1)
            log.info("%s: task %d loser-commit-race forced; letting %d "
                     "attempt(s) race the commit", what, winner.task,
                     len(losers))
            return
        atts = attempts[winner.task]
        tracing.instant("speculation_win", task=winner.task, what=what,
                        query=getattr(query, "query_id", None),
                        winner_attempt=atts.index(winner),
                        winner_speculative=winner.speculative,
                        loser_attempts=[atts.index(a) for a in losers])
        for a in losers:
            a.cancel.set()
            tracing.instant("speculation_loser", task=winner.task,
                            what=what,
                            query=getattr(query, "query_id", None),
                            attempt=atts.index(a),
                            winner_attempt=atts.index(winner))
        xla_stats.note_speculation(losers_cancelled=len(losers))

    while len(results) < n:
        if query is not None and query.cancelled:
            shutdown_all(cancel_futures=True)
            query.check()
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            shutdown_all(cancel_futures=True)
            # surface a completed task's REAL failure over the phantom
            # hang: a sibling wedged in backend init must not mask the
            # root cause.  Cancelled losers' teardown errors don't count.
            for atts in attempts.values():
                for att in atts:
                    f = att.future
                    if f.done() and not f.cancelled() \
                            and not att.cancel.is_set() \
                            and f.exception() is not None:
                        raise f.exception()
            raise TimeoutError(f"{what}: {len(pending)}/{n} tasks still "
                               f"running after {timeout_s:g}s")
        # FIRST_EXCEPTION: a task that failed terminally (retries
        # exhausted / fatal / fetch-failed) wakes the caller NOW, not
        # after the slowest sibling or the full timeout.  With a query
        # bound, poll in short rounds so an external cancel() is
        # noticed without waiting for a task to hit a check point; with
        # speculation on, poll faster still so straggler hedges launch
        # within one cutoff granule of the trigger condition.
        if spec_conf is not None:
            poll = min(remaining, 0.05)
        else:
            poll = remaining if query is None else min(remaining, 0.25)
        done, pending = wait(pending, timeout=poll,
                             return_when=FIRST_EXCEPTION)
        first_err = fetch_err = None
        for f in done:
            att = by_future[f]
            i = att.task
            if f.cancelled():
                continue
            exc = f.exception()
            if exc is None:
                if i in results:
                    # the losing attempt ran to completion anyway (the
                    # commit-race leg): its output was already rejected
                    # by the tier's first-wins arbitration — drop it
                    continue
                results[i] = f.result()
                deferred.pop(i, None)
                if att.duration is not None:
                    durations.append(att.duration)
                if att.speculative:
                    xla_stats.note_speculation(wins=1)
                settle_losers(att)
                continue
            if att.cancel.is_set() or i in results:
                continue  # cancelled loser raising out of its teardown
            live = [a for a in attempts[i]
                    if a is not att and not a.future.done()]
            if live:
                # a sibling attempt is still running: defer — if it
                # commits, this failure never mattered; if it fails too,
                # the terminal error surfaces then (fetch-failed kept in
                # preference, it carries lineage)
                prev = deferred.get(i)
                if not isinstance(prev, FetchFailedError):
                    deferred[i] = exc
                continue
            prev = deferred.pop(i, None)
            if isinstance(prev, FetchFailedError) \
                    and not isinstance(exc, FetchFailedError):
                exc = prev
            if isinstance(exc, FetchFailedError) and fetch_err is None:
                fetch_err = exc
            elif first_err is None:
                first_err = exc
        if fetch_err is not None or first_err is not None:
            shutdown_all(cancel_futures=True)
            # a FetchFailedError outranks sibling errors: it carries the
            # lineage the scheduler needs to recover the whole stage
            raise fetch_err if fetch_err is not None else first_err
        if spec_conf is not None and durations:
            quantile, multiplier, min_s = spec_conf
            finished = len(results)
            if finished < n and finished >= max(1, math.ceil(quantile * n)):
                cutoff = max(multiplier * statistics.median(durations),
                             min_s)
                now = time.monotonic()
                for i in range(n):
                    # re-hedge a straggling attempt SET: if the newest
                    # attempt is itself past the cutoff (its dispatch
                    # may have landed on another slow worker), launch
                    # one more, up to 3 duplicates per task — each
                    # steered away from every live attempt's worker
                    atts = attempts[i]
                    if i in results or i in deferred or len(atts) >= 4:
                        continue
                    newest = atts[-1]
                    if newest.started is None \
                            or now - newest.started <= cutoff:
                        continue
                    if newest.speculative and remote is not None \
                            and newest.worker_id is None:
                        # the newest duplicate is still queued for a
                        # worker slot — it isn't running slow, there's
                        # no capacity; another dup would queue behind
                        # it and clog the pool for sibling stages
                        continue
                    dup = _Attempt(i, speculative=True)
                    for a in atts:
                        if a.worker_id is not None \
                                and not a.future.done():
                            dup.exclude.add(a.worker_id)
                    if spec_pool is None:
                        spec_pool = ThreadPoolExecutor(
                            max_workers=max(1, n))
                    submit(spec_pool, dup)
                    atts.append(dup)
                    pending.add(dup.future)
                    tracing.instant(
                        "speculation_attempt", task=i, what=what,
                        query=getattr(query, "query_id", None),
                        attempt=len(atts) - 1,
                        running_s=round(now - newest.started, 4),
                        cutoff_s=round(cutoff, 4))
                    xla_stats.note_speculation(
                        attempts=1, waves=0 if speculated else 1)
                    speculated = True
                    log.info("%s: task %d attempt %d running %.3fs > "
                             "cutoff %.3fs (median %.3fs x %.2f); "
                             "launched speculative duplicate", what, i,
                             len(atts) - 1, now - newest.started,
                             cutoff, statistics.median(durations),
                             multiplier)
    shutdown_all(cancel_futures=False)
    xla_stats.note_wave_wall(int((time.monotonic() - wave_t0) * 1e9))
    return [results[i] for i in range(n)]

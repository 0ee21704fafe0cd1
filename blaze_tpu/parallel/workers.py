"""Supervised worker-process pool: crash fault domains for map tasks.

Every task in the engine used to run on a ThreadPoolExecutor inside ONE
Python process, so a native XLA segfault, kernel OOM-kill, or hung
dispatch took down the whole query service.  The reference engine gives
each task a native runtime inside a JVM executor process that Spark
supervises and restarts; this module is that executor runtime for
blaze_tpu:

- `WorkerPool` spawns N long-lived child processes
  (`python -m blaze_tpu.parallel.workers --child`) and ships tasks to
  them over a length-prefixed pipe protocol reusing the CRC-framed wire
  format from shuffle/ipc.py (same header/CRC structs, so a torn or
  bit-rotted frame is detected, not deserialized).
- Children heartbeat while running a task; a busy worker silent past
  `auron.tpu.workers.livenessMs` is declared hung, SIGKILLed, and its
  task re-dispatched (the executor-heartbeat analog).
- A dead child's exit status is classified into `WorkerCrashed`
  (negative rc = signal), which faults.classify_exception treats as
  RETRYABLE; the crashed worker's id rides along so the retry can land
  on a DIFFERENT worker (bridge/tasks.py excludes it).
- Crashed slots restart with exponential backoff; a slot that exceeds
  `auron.tpu.workers.crashBudget` is blacklisted and never receives
  tasks again (the excludeOnFailure analog).
- Cancellation / per-call deadlines propagate as a cancel message, then
  escalate SIGTERM -> SIGKILL; cancel kills do NOT count against the
  crash budget (the worker was healthy, the query was not).
- Crash listeners let the DAG scheduler invalidate the dead worker's
  entries in the map-output table so FetchFailedError lineage recovery
  re-runs only the poisoned producers (plan/stages.py).

Fallback matrix: the pool is opt-in (`auron.tpu.workers.enable`); when
it is off, cannot spawn, or is fully blacklisted, callers fall back to
the in-process thread path (`WorkerPoolUnavailable`), which stays the
seed-verified baseline.
"""

from __future__ import annotations

import importlib
import io
import logging
import os
import pickle
import queue
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Set

from blaze_tpu import faults
from blaze_tpu.bridge import tracing
from blaze_tpu.faults import FetchFailedError, WorkerCrashed, \
    classify_exception
from blaze_tpu.shuffle.ipc import CODEC_RAW, FLAG_CRC, _check_frame_byte, \
    _CRC, _decompress, _HEADER, _verify_crc, pack_control_frame

log = logging.getLogger("blaze_tpu.workers")


class WorkerPoolUnavailable(RuntimeError):
    """The pool cannot take this task (disabled, spawn failed, closed,
    or every slot blacklisted).  Callers fall back to running the task
    in-process on the thread path."""


class RemoteTaskError(RuntimeError):
    """A task raised inside a worker and the exception type could not be
    (or should not be) reconstructed parent-side.  Carries the child's
    verdict in `remote_classify` so faults.classify_exception preserves
    retryable/fatal semantics across the process boundary."""

    def __init__(self, message: str, remote_classify: str = "fatal"):
        super().__init__(message)
        self.remote_classify = remote_classify


# ---------------------------------------------------------------------------
# Pipe framing: pickled control/result messages ride the shuffle IPC
# frame format ([codec|FLAG_CRC][u32 len][u32 crc32c][payload]) so a
# truncated or corrupted frame surfaces as a checksum/EOF error the
# retry machinery already classifies, never as a bad unpickle.

def _frame_codec() -> int:
    """The wire codec for OUTGOING control frames: io.compression.codec
    when io.compression.workerFrames opts the worker protocol in, raw
    otherwise.  Each frame self-describes its codec in the header byte,
    so mixed parent/child settings (the conf snapshot lands only with
    the first task) interoperate frame-by-frame."""
    from blaze_tpu import config
    if not config.IO_COMPRESSION_WORKER_FRAMES.get():
        return CODEC_RAW
    from blaze_tpu.shuffle.ipc import _get_codec
    return _get_codec()


def _send_msg(fp, obj: Any, lock: Optional[threading.Lock] = None) -> None:
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    codec = _frame_codec()
    frame = pack_control_frame(payload, codec)
    if codec != CODEC_RAW:
        saved = (_HEADER.size + _CRC.size + len(payload)) - len(frame)
        if saved > 0:
            from blaze_tpu.bridge import xla_stats
            xla_stats.note_frame_compression("worker", saved)
    if lock is not None:
        with lock:
            fp.write(frame)
            fp.flush()
    else:
        fp.write(frame)
        fp.flush()


def _read_exact(fp, n: int) -> Optional[bytes]:
    buf = io.BytesIO()
    got = 0
    while got < n:
        chunk = fp.read(n - got)
        if not chunk:
            return None if got == 0 else b""
        buf.write(chunk)
        got += len(chunk)
    return buf.getvalue()


def _recv_msg(fp) -> Optional[Any]:
    """Read one framed message; None on clean EOF, EOFError on a torn
    frame, ShuffleChecksumError on CRC mismatch."""
    header = _read_exact(fp, _HEADER.size)
    if header is None:
        return None
    if header == b"":
        raise EOFError("truncated worker-pipe frame header")
    raw_codec, length = _HEADER.unpack(header)
    codec = _check_frame_byte(raw_codec)
    crc = None
    if raw_codec & FLAG_CRC:
        crc_bytes = _read_exact(fp, _CRC.size)
        if not crc_bytes:
            raise EOFError("truncated worker-pipe frame checksum")
        (crc,) = _CRC.unpack(crc_bytes)
    payload = _read_exact(fp, length)
    if payload is None or len(payload) != length:
        raise EOFError("truncated worker-pipe frame payload")
    if crc is not None:
        _verify_crc(crc, payload)
    if codec != CODEC_RAW:
        # CRC covers the wire bytes (corruption detection happens before
        # any codec touches them); the codec byte keys the decode
        payload = _decompress(codec, payload)
    return pickle.loads(payload)


# ---------------------------------------------------------------------------
# Parent side

_STARTING = "starting"
_IDLE = "idle"
_BUSY = "busy"
_DEAD = "dead"
_BLACKLISTED = "blacklisted"


class _Slot:
    """One supervised worker slot: a process incarnation plus its crash
    history.  The slot survives its processes — crashes accumulate on
    the slot, which is what the crash budget blacklists."""

    def __init__(self, slot_id: int):
        self.id = slot_id
        self.proc: Optional[subprocess.Popen] = None
        self.state = _DEAD
        self.incarnation = 0
        self.crashes = 0
        self.tasks_done = 0
        self.last_heartbeat = 0.0
        self.restart_at = 0.0      # monotonic time gating respawn
        self.hang_kill = False     # liveness SIGKILL in flight
        self.cancel_kill = False   # cancel/deadline kill: not a crash
        self.inbox: "queue.Queue" = queue.Queue()
        self.write_lock = threading.Lock()
        self.platform: Optional[str] = None  # hello frame: JAX_PLATFORMS
        self.cpu_ns = 0            # child CPU (user+sys) across tasks

    def pid(self) -> Optional[int]:
        return self.proc.pid if self.proc is not None else None


class WorkerPool:
    """N supervised long-lived worker processes executing one task each
    at a time.  Thread-safe: run() may be called concurrently from many
    task threads; each call owns one slot for the duration."""

    def __init__(self, count: int = 2, heartbeat_ms: int = 100,
                 liveness_ms: int = 2000, crash_budget: int = 3,
                 restart_backoff_ms: int = 50, drain_ms: int = 1000):
        self.count = max(1, int(count))
        self.heartbeat_ms = max(10, int(heartbeat_ms))
        self.liveness_ms = max(self.heartbeat_ms * 2, int(liveness_ms))
        self.crash_budget = int(crash_budget)  # crashes a slot SURVIVES
        # (0 = blacklist on first crash, negative = never blacklist)
        self.restart_backoff_ms = max(0, int(restart_backoff_ms))
        self.drain_ms = max(0, int(drain_ms))
        self.closed = False
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._slots = [_Slot(i) for i in range(self.count)]
        self._crash_listeners: List[Callable[[int], None]] = []

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "WorkerPool":
        with self._lock:
            for slot in self._slots:
                self._spawn(slot, restart=False)
        return self

    def _spawn(self, slot: _Slot, restart: bool) -> None:
        """Fork a fresh child into `slot` (caller holds the lock).  A
        fresh inbox per incarnation keeps stale sentinels/results from a
        previous process out of the next task's wait loop."""
        from blaze_tpu.bridge import xla_stats
        slot.inbox = queue.Queue()
        slot.incarnation += 1
        slot.hang_kill = False
        slot.cancel_kill = False
        try:
            slot.proc = subprocess.Popen(
                [sys.executable, "-m", "blaze_tpu.parallel.workers",
                 "--child"],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                bufsize=0, env=self._child_env(slot))
        except OSError as e:
            slot.proc = None
            slot.state = _DEAD
            slot.restart_at = time.monotonic() + 1.0
            log.error("worker %d spawn failed: %s", slot.id, e)
            raise
        slot.state = _STARTING
        slot.last_heartbeat = time.monotonic()
        xla_stats.note_worker_spawn(restart=restart)
        t = threading.Thread(
            target=self._reader, args=(slot, slot.proc, slot.inbox),
            name=f"blaze-worker-reader-{slot.id}", daemon=True)
        t.start()

    @staticmethod
    def _child_env(slot: _Slot) -> Dict[str, str]:
        """Spawn env for one child.  Its platform is always decided here
        and stated: a child inherits the parent's JAX_PLATFORMS, and a
        child that would open the accelerator its parent holds is
        refused (bridge/placement.py refuse_chip_contention) — the pool then
        fails to start and tasks run in-process."""
        from blaze_tpu.bridge.placement import refuse_chip_contention
        env = dict(os.environ)
        refuse_chip_contention(env, f"worker {slot.id}")
        return env

    def _reader(self, slot: _Slot, proc: subprocess.Popen,
                inbox: "queue.Queue") -> None:
        """Per-incarnation reader: hello promotes the slot to idle,
        heartbeats stamp liveness, results go to the inbox, EOF/torn
        frames become the crash sentinel (None)."""
        try:
            while True:
                msg = _recv_msg(proc.stdout)
                if msg is None:
                    break
                kind = msg.get("kind")
                if kind == "hello":
                    log.info("worker %d (pid %s) up on platform %s",
                             slot.id, msg.get("pid"), msg.get("platform"))
                    with self._cond:
                        if slot.proc is proc and slot.state == _STARTING:
                            slot.state = _IDLE
                            slot.platform = msg.get("platform")
                            slot.last_heartbeat = time.monotonic()
                            self._cond.notify_all()
                elif kind == "heartbeat":
                    slot.last_heartbeat = time.monotonic()
                    if msg.get("spans"):
                        # mid-task child spans stream back in heartbeat
                        # frames; rebase the child clock onto ours
                        tracing.ingest(msg["spans"], worker=slot.id,
                                       clock_ns=msg.get("mono_ns"),
                                       dropped=msg.get("spans_dropped", 0))
                else:
                    slot.last_heartbeat = time.monotonic()
                    inbox.put(msg)
        except Exception:
            pass  # torn frame / CRC mismatch == the process is gone
        inbox.put(None)
        with self._cond:
            self._cond.notify_all()

    def add_crash_listener(self, fn: Callable[[int], None]) -> None:
        """`fn(worker_id)` runs (outside the pool lock) after a worker
        death is recorded — the scheduler's map-output invalidation
        hook."""
        with self._lock:
            self._crash_listeners.append(fn)

    def remove_crash_listener(self, fn: Callable[[int], None]) -> None:
        with self._lock:
            try:
                self._crash_listeners.remove(fn)
            except ValueError:
                pass

    def _fire_crash_listeners(self, worker_id: int) -> None:
        with self._lock:
            listeners = list(self._crash_listeners)
        for fn in listeners:
            try:
                fn(worker_id)
            except Exception:
                log.exception("worker crash listener failed")

    # -- supervision -------------------------------------------------------

    def _record_crash(self, slot: _Slot, hang: bool) -> None:
        """Caller holds the lock.  Counts the crash against the slot's
        budget and either schedules a backoff restart or blacklists."""
        from blaze_tpu.bridge import xla_stats
        slot.crashes += 1
        xla_stats.note_worker_crash(hang=hang)
        if self.crash_budget >= 0 and slot.crashes > self.crash_budget:
            slot.state = _BLACKLISTED
            xla_stats.note_worker_blacklisted()
            log.warning("worker %d blacklisted after %d crashes",
                        slot.id, slot.crashes)
        else:
            slot.state = _DEAD
            backoff = (self.restart_backoff_ms / 1e3
                       * (2 ** max(0, slot.crashes - 1)))
            slot.restart_at = time.monotonic() + min(backoff, 10.0)
        slot.proc = None
        self._cond.notify_all()

    def _maintain(self) -> None:
        """Caller holds the lock: reap idle deaths, respawn dead slots
        whose backoff has elapsed."""
        now = time.monotonic()
        for slot in self._slots:
            if slot.state in (_IDLE, _STARTING) and slot.proc is not None \
                    and slot.proc.poll() is not None:
                # died while not running a task (import error, OOM-kill
                # at rest): still a crash for budget purposes
                log.warning("worker %d exited idle (rc=%s)", slot.id,
                            slot.proc.returncode)
                self._record_crash(slot, hang=False)
            if slot.state == _DEAD and not self.closed \
                    and now >= slot.restart_at:
                try:
                    self._spawn(slot, restart=True)
                except OSError:
                    pass

    def _kill(self, slot: _Slot, sig: int) -> None:
        proc = slot.proc
        if proc is not None and proc.poll() is None:
            try:
                proc.send_signal(sig)
            except (ProcessLookupError, OSError):
                pass

    def _escalate_stop(self, slot: _Slot, task_id: int,
                       grace_s: float = 0.2) -> None:
        """Cancel-message -> SIGTERM -> SIGKILL ladder for a busy slot
        whose task must stop NOW (query cancelled / deadline)."""
        proc = slot.proc
        try:
            if proc is not None and proc.stdin is not None:
                _send_msg(proc.stdin, {"kind": "cancel", "task_id": task_id},
                          slot.write_lock)
        except (OSError, ValueError):
            pass
        deadline = time.monotonic() + grace_s
        while proc is not None and proc.poll() is None \
                and time.monotonic() < deadline:
            # the child may finish the task and go idle within grace; a
            # result frame means we can keep the (healthy) process
            try:
                item = slot.inbox.get(timeout=0.02)
            except queue.Empty:
                continue
            if isinstance(item, dict) and item.get("task_id") == task_id:
                with self._lock:
                    if slot.state == _BUSY:
                        slot.state = _IDLE
                        self._cond.notify_all()
                return
            if item is None:
                break
        self._kill(slot, signal.SIGTERM)
        if proc is not None:
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self._kill(slot, signal.SIGKILL)
                try:
                    proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    pass

    # -- dispatch ----------------------------------------------------------

    def _acquire(self, exclude: Set[int], deadline: Optional[float],
                 query=None) -> _Slot:
        with self._cond:
            dropped_exclude = False
            while True:
                if self.closed:
                    raise WorkerPoolUnavailable("worker pool is shut down")
                if query is not None and query.cancelled:
                    query.check()
                self._maintain()
                viable = [s for s in self._slots
                          if s.state != _BLACKLISTED]
                if not viable:
                    raise WorkerPoolUnavailable(
                        "all workers blacklisted by the crash budget")
                eligible = [s for s in viable if s.state == _IDLE
                            and s.id not in exclude]
                if not eligible and not dropped_exclude \
                        and all(s.id in exclude for s in viable):
                    # the retry excluded every surviving worker; running
                    # SOMEWHERE beats not running at all
                    dropped_exclude = True
                    exclude = set()
                    continue
                if eligible:
                    slot = eligible[0]
                    slot.state = _BUSY
                    slot.last_heartbeat = time.monotonic()
                    return slot
                if deadline is not None \
                        and time.monotonic() >= deadline:
                    raise TimeoutError(
                        "worker pool: no idle worker before deadline")
                self._cond.wait(timeout=0.05)

    def _release(self, slot: _Slot) -> None:
        with self._cond:
            if slot.state == _BUSY:
                slot.state = _IDLE
            self._cond.notify_all()

    def _directive(self, what: str) -> Dict[str, int]:
        """Evaluate worker-* fault sites PARENT-side at dispatch so
        chaos decisions stay deterministic in (seed, site, occurrence)
        regardless of child process identity, then ship the directive
        for the child to act out."""
        d: Dict[str, int] = {}
        if faults.fires("worker-crash", what=what):
            d["kill_after_ms"] = 15
        if faults.fires("worker-hang", what=what):
            d["hang_ms"] = self.liveness_ms * 10
        if faults.fires("worker-slow", what=what):
            from blaze_tpu import config
            d["delay_ms"] = max(0, config.FAULTS_WORKER_SLOW_MS.get())
        return d

    def run(self, spec: Dict[str, Any], exclude: Optional[Set[int]] = None,
            timeout_s: Optional[float] = None, query=None,
            what: str = "task", cancel_event=None,
            on_assign=None) -> Any:
        """Execute `spec` ({"fn": "module:qualname", "args": tuple}) on
        one worker and return its result.  Raises WorkerCrashed (with
        the dead worker's id) on crash/hang, TimeoutError past
        `timeout_s`, the reconstructed task error otherwise.

        `cancel_event` is the speculative-attempt token: when set (a
        sibling attempt committed first) the in-flight task is cancelled
        like a deadline — stop escalation, no crash-budget charge — and
        TaskKilledError is raised so the caller's retry loop treats the
        attempt as dead rather than retryable.  `on_assign(worker_id)`
        fires once the task is dispatched, letting the wave loop steer a
        later duplicate attempt away from this worker."""
        from blaze_tpu import config
        from blaze_tpu.bridge import xla_stats
        deadline = (time.monotonic() + timeout_s
                    if timeout_s is not None else None)
        slot = self._acquire(set(exclude or ()), deadline, query)
        if cancel_event is not None and cancel_event.is_set():
            # the sibling won while this attempt queued for a slot:
            # hand the slot straight back instead of dispatching a
            # task whose output is already dead
            self._release(slot)
            from blaze_tpu.bridge.context import TaskKilledError
            raise TaskKilledError(
                f"{what}: attempt cancelled before dispatch — a "
                f"sibling attempt committed first")
        if on_assign is not None:
            on_assign(slot.id)
        incarnation = slot.incarnation
        inbox = slot.inbox
        proc = slot.proc
        task_id = slot.tasks_done + slot.crashes + incarnation * 100003
        msg = {"kind": "task", "task_id": task_id,
               "fn": spec["fn"], "args": tuple(spec.get("args") or ()),
               "conf": config.conf.snapshot(),
               "directive": self._directive(what),
               "heartbeat_ms": self.heartbeat_ms}
        trace = tracing.wire_context(worker=slot.id)
        if trace is not None:
            # trace context rides the framed wire protocol; absent
            # entirely when tracing is off (zero disabled-path bytes)
            msg["trace"] = trace
        try:
            _send_msg(proc.stdin, msg, slot.write_lock)
        except (OSError, ValueError) as e:
            return self._handle_crash(slot, incarnation, hang=False,
                                      reason=f"dispatch failed: {e}")
        xla_stats.note_worker_task()
        slot.last_heartbeat = time.monotonic()
        liveness_s = self.liveness_ms / 1e3
        while True:
            try:
                item = inbox.get(timeout=0.05)
            except queue.Empty:
                item = _PENDING
            if item is None:
                hang = slot.hang_kill
                return self._handle_crash(slot, incarnation, hang=hang,
                                          reason="heartbeat miss: liveness "
                                                 "deadline exceeded"
                                          if hang else "")
            if item is not _PENDING and isinstance(item, dict):
                if item.get("task_id") != task_id:
                    continue  # stale result from a cancelled attempt
                return self._finish(slot, item)
            now = time.monotonic()
            if query is not None and query.cancelled:
                self._cancel_slot(slot, task_id)
                query.check()
            if cancel_event is not None and cancel_event.is_set():
                # sibling attempt won the first-wins commit: ABANDON the
                # attempt rather than killing the child.  The loser runs
                # to completion in the worker (its late commit is
                # rejected by the attempt arbitration on every shuffle
                # tier) and the process keeps its warm backend + compile
                # caches — killing it would make the next task on this
                # slot pay a cold re-init costlier than the straggle
                # being hedged.  No crash-budget charge.
                self._abandon_slot(slot, task_id, incarnation)
                from blaze_tpu.bridge.context import TaskKilledError
                raise TaskKilledError(
                    f"{what}: worker {slot.id} attempt cancelled — a "
                    f"sibling attempt committed first")
            if deadline is not None and now >= deadline:
                self._cancel_slot(slot, task_id)
                raise TimeoutError(
                    f"{what}: worker {slot.id} task exceeded "
                    f"{timeout_s:g}s deadline")
            if now - slot.last_heartbeat > liveness_s:
                # busy and silent past the liveness deadline: hung.
                # SIGKILL; the reader's EOF sentinel completes the story.
                with self._lock:
                    slot.hang_kill = True
                log.warning("worker %d (pid %s) missed heartbeats for "
                            "%.2fs; killing", slot.id, slot.pid(),
                            now - slot.last_heartbeat)
                self._kill(slot, signal.SIGKILL)

    def _abandon_slot(self, slot: _Slot, task_id: int,
                      incarnation: int) -> None:
        """Detach from a speculative loser WITHOUT stopping the child:
        a drainer thread babysits the slot until the task's result
        frame arrives (discarded — first-wins already settled), then
        releases it.  The slot stays _BUSY meanwhile so `_acquire`
        cannot double-book the worker.  Liveness is still enforced: a
        child that stops heartbeating mid-abandon is killed and takes
        the normal crash path (with budget charge — it really died)."""
        from blaze_tpu.bridge import xla_stats
        xla_stats.note_worker_cancel()
        tracing.instant("worker_cancel_escalation", worker=slot.id,
                        action="abandon")
        liveness_s = self.liveness_ms / 1e3

        def drain() -> None:
            while True:
                if self.closed:
                    return
                try:
                    item = slot.inbox.get(timeout=0.05)
                except queue.Empty:
                    item = _PENDING
                if item is None:
                    try:
                        self._handle_crash(slot, incarnation, hang=slot.
                                           hang_kill)
                    except BaseException:
                        pass
                    return
                if item is not _PENDING and isinstance(item, dict):
                    if item.get("task_id") != task_id:
                        continue
                    try:
                        self._finish(slot, item)
                    except BaseException:
                        pass  # the loser's result (or error) is dead
                    return
                if time.monotonic() - slot.last_heartbeat > liveness_s:
                    with self._lock:
                        slot.hang_kill = True
                    log.warning("worker %d (pid %s) missed heartbeats "
                                "while draining an abandoned attempt; "
                                "killing", slot.id, slot.pid())
                    self._kill(slot, signal.SIGKILL)

        threading.Thread(target=drain, daemon=True,
                         name=f"blaze-worker-{slot.id}-abandon").start()

    def _cancel_slot(self, slot: _Slot, task_id: int) -> None:
        """Deadline/cancel escalation.  If the process survived (it
        finished the task inside the grace window) it stays; otherwise
        it restarts WITHOUT a crash-budget charge."""
        from blaze_tpu.bridge import xla_stats
        with self._lock:
            slot.cancel_kill = True
        xla_stats.note_worker_cancel()
        tracing.instant("worker_cancel_escalation", worker=slot.id,
                        action="cancel")
        self._escalate_stop(slot, task_id)
        with self._cond:
            proc = slot.proc
            if proc is not None and proc.poll() is not None:
                slot.state = _DEAD
                slot.proc = None
                slot.restart_at = time.monotonic()
            elif slot.state == _BUSY:
                slot.state = _IDLE
            slot.cancel_kill = False
            self._cond.notify_all()

    def _handle_crash(self, slot: _Slot, incarnation: int, hang: bool,
                      reason: str = "") -> Any:
        rc = None
        with self._cond:
            proc = slot.proc
            if proc is not None:
                try:
                    rc = proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    rc = None
            if slot.incarnation == incarnation \
                    and slot.state not in (_DEAD, _BLACKLISTED):
                self._record_crash(slot, hang=hang)
        self._fire_crash_listeners(slot.id)
        if rc is not None and rc < 0 and not reason:
            reason = f"killed by signal {-rc}"
        raise WorkerCrashed(worker_id=slot.id, exit_code=rc, reason=reason)

    def _finish(self, slot: _Slot, res: Dict[str, Any]) -> Any:
        if res.get("spans"):
            # final child spans ride the result frame — including an
            # abandoned speculation loser's (the drainer lands here too)
            tracing.ingest(res["spans"], worker=slot.id,
                           clock_ns=res.get("mono_ns"),
                           dropped=res.get("spans_dropped", 0))
        cpu_ns = res.get("cpu_ns")
        if cpu_ns:
            # actual worker-process CPU (user+sys from os.times in the
            # child): the SUM of these over a wave's wall is the cores
            # the children kept busy
            from blaze_tpu.bridge import xla_stats
            xla_stats.note_worker_cpu(int(cpu_ns))
        with self._cond:
            slot.tasks_done += 1
            if cpu_ns:
                slot.cpu_ns += int(cpu_ns)
            if slot.state == _BUSY:
                slot.state = _IDLE
            self._cond.notify_all()
        if res.get("ok"):
            value = res.get("value")
            if isinstance(value, dict):
                value["_worker_id"] = slot.id
            return value
        fetch = res.get("fetch")
        if fetch:
            raise FetchFailedError(fetch[0], fetch[1],
                                   res.get("error_msg", ""))
        raise RemoteTaskError(
            f"worker {slot.id}: {res.get('error_type', 'Exception')}: "
            f"{res.get('error_msg', '')}",
            remote_classify=res.get("classify", "fatal"))

    # -- shutdown / health -------------------------------------------------

    def shutdown(self, wait: bool = True) -> None:
        with self._cond:
            self.closed = True
            slots = list(self._slots)
            self._cond.notify_all()
        if wait:
            for slot in slots:
                proc = slot.proc
                if proc is None or proc.poll() is not None:
                    continue
                try:
                    _send_msg(proc.stdin, {"kind": "shutdown"},
                              slot.write_lock)
                except (OSError, ValueError):
                    pass
            deadline = time.monotonic() + self.drain_ms / 1e3
            for slot in slots:
                proc = slot.proc
                if proc is None:
                    continue
                remaining = max(0.0, deadline - time.monotonic())
                try:
                    proc.wait(timeout=remaining)
                except subprocess.TimeoutExpired:
                    pass
        for slot in slots:
            proc = slot.proc
            if proc is not None and proc.poll() is None:
                self._kill(slot, signal.SIGTERM)
        for slot in slots:
            proc = slot.proc
            if proc is None:
                continue
            try:
                proc.wait(timeout=0.2)
            except subprocess.TimeoutExpired:
                self._kill(slot, signal.SIGKILL)
                try:
                    proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    pass
            slot.proc = None
            slot.state = _DEAD

    def health(self) -> List[Dict[str, Any]]:
        with self._lock:
            self._maintain()
            now = time.monotonic()
            return [{"worker": s.id, "pid": s.pid(), "state": s.state,
                     "crashes": s.crashes, "tasks_done": s.tasks_done,
                     "incarnation": s.incarnation,
                     "platform": s.platform,
                     "cpu_s": s.cpu_ns / 1e9,
                     "heartbeat_age_ms": int((now - s.last_heartbeat) * 1e3)
                     if s.state == _BUSY else None}
                    for s in self._slots]


_PENDING = object()


# ---------------------------------------------------------------------------
# Module-level pool registry (config-driven singleton)

_pool: Optional[WorkerPool] = None
_pool_lock = threading.Lock()
_pool_failed = False


def get_pool() -> Optional[WorkerPool]:
    """The config-driven pool singleton: created lazily from the
    `auron.tpu.workers.*` knobs at first use, None when disabled or
    unspawnable (callers then take the in-process thread path)."""
    global _pool, _pool_failed
    from blaze_tpu import config
    if not (config.WORKERS_ENABLE.get()
            or config.SERVING_USE_WORKERS.get()):
        return None
    with _pool_lock:
        if _pool is not None and not _pool.closed:
            return _pool
        if _pool_failed:
            return None
        try:
            _pool = WorkerPool(
                count=config.WORKERS_COUNT.get(),
                heartbeat_ms=config.WORKERS_HEARTBEAT_MS.get(),
                liveness_ms=config.WORKERS_LIVENESS_MS.get(),
                crash_budget=config.WORKERS_CRASH_BUDGET.get(),
                restart_backoff_ms=config.WORKERS_RESTART_BACKOFF_MS.get(),
                drain_ms=config.WORKERS_DRAIN_MS.get()).start()
        except Exception:
            log.exception("worker pool spawn failed; falling back to "
                          "in-process threads")
            _pool = None
            _pool_failed = True
            return None
        return _pool


def active_pool() -> Optional[WorkerPool]:
    """The live pool if one exists — never creates."""
    with _pool_lock:
        if _pool is not None and not _pool.closed:
            return _pool
        return None


def shutdown_pool(wait: bool = True) -> None:
    """Close and forget the singleton (tests re-knob between
    legs; serving shutdown)."""
    global _pool, _pool_failed
    with _pool_lock:
        pool, _pool = _pool, None
        _pool_failed = False
    if pool is not None:
        pool.shutdown(wait=wait)


def pool_health() -> Dict[str, Any]:
    """JSON-ready pool health for the /serving endpoint."""
    from blaze_tpu import config
    from blaze_tpu.bridge import xla_stats
    pool = active_pool()
    out: Dict[str, Any] = {"enabled": bool(config.WORKERS_ENABLE.get()),
                           "active": pool is not None}
    if pool is not None:
        out["slots"] = pool.health()
    out["counters"] = xla_stats.worker_stats()
    return out


# ---------------------------------------------------------------------------
# Task entry points (must be module-level: specs cross the process
# boundary as "module:qualname" strings, not closures)

def run_shuffle_map_task(task: dict) -> dict:
    """Execute one shuffle-writer TaskDefinition inside a worker: the
    native runtime writes the map output files (tmp + os.replace commit,
    so a SIGKILL mid-write leaves NOTHING committed) and the metric tree
    rides the result frame home for the parent scheduler to absorb.

    `task["shuffle_inputs"]` is the shipped map-output table: on-disk
    segment lists for every upstream stage:// resource the per-task
    plan reads (resolved by the parent at dispatch).  They're
    registered in THIS process's resource map for the duration of the
    task and removed after — the worker is long-lived and must not
    accumulate stale block lists across tasks."""
    from blaze_tpu.bridge.resource import get_resource, put_resource
    from blaze_tpu.bridge.runtime import NativeExecutionRuntime
    from blaze_tpu.plan.proto_serde import task_definition_to_bytes
    from blaze_tpu.shuffle.reader import FileSegmentBlock
    task = dict(task)
    shuffle_inputs = task.pop("shuffle_inputs", None) or {}
    rids = []
    try:
        for rid, parts in shuffle_inputs.items():
            blocks = [[FileSegmentBlock(data, off, length,
                                        stage_id=sid, map_id=mid)
                       for (data, off, length, sid, mid) in segs]
                      for segs in parts]

            def blocks_for(p, _b=blocks):
                return iter(_b[p]) if 0 <= p < len(_b) else iter(())
            put_resource(rid, blocks_for)
            rids.append(rid)
        td = task_definition_to_bytes(task)
        rt = NativeExecutionRuntime(td).start()
        try:
            for _ in rt.batches():
                pass
        finally:
            tree = rt.finalize()
        return {"metrics": tree.to_dict()}
    finally:
        for rid in rids:
            get_resource(rid, remove=True)


def _task_echo(*args) -> dict:
    """Test helper: round-trips its args."""
    return {"echo": list(args), "pid": os.getpid()}


def _task_sleep(seconds: float, value: Any = None) -> dict:
    """Test helper: hold a worker busy (heartbeating) then echo."""
    time.sleep(float(seconds))
    return {"value": value, "pid": os.getpid()}


def _task_raise(kind: str = "runtime") -> None:
    """Test helper: raise a classified error inside the worker."""
    if kind == "fetch":
        raise FetchFailedError(7, 3, "injected remote fetch failure")
    if kind == "retryable":
        raise ConnectionError("injected transient failure")
    raise RuntimeError("injected fatal failure")


# ---------------------------------------------------------------------------
# Child side

def _resolve_fn(spec: str) -> Callable:
    mod_name, _, qual = spec.partition(":")
    fn: Any = importlib.import_module(mod_name)
    for part in qual.split("."):
        fn = getattr(fn, part)
    return fn


def _ship_spans(frame: Dict[str, Any]) -> None:
    """The child's buffered spans onto a heartbeat or result frame, with
    the child's clock at send time and what the buffer's cap trimmed."""
    frame["spans"] = tracing.take_buffered()
    frame["mono_ns"] = time.perf_counter_ns()
    dropped = tracing.take_child_dropped()
    if dropped:
        frame["spans_dropped"] = dropped


def _run_child_task(msg: Dict[str, Any], out, out_lock) -> Dict[str, Any]:
    from blaze_tpu import config
    config.conf.replace(msg.get("conf") or {})
    directive = msg.get("directive") or {}
    hb_s = max(10, int(msg.get("heartbeat_ms") or 100)) / 1e3
    kill_timer = None
    if directive.get("kill_after_ms") is not None:
        # worker-crash: really die, mid-task, the hard way
        kill_timer = threading.Timer(
            directive["kill_after_ms"] / 1e3,
            lambda: os.kill(os.getpid(), signal.SIGKILL))
        kill_timer.daemon = True
        kill_timer.start()
    hang_ms = directive.get("hang_ms")
    if hang_ms is not None:
        # worker-hang: wedge WITHOUT heartbeats so the parent's liveness
        # deadline — not this sleep expiring — is what ends us
        time.sleep(hang_ms / 1e3)
    stop_beat = threading.Event()
    trace = msg.get("trace")

    def _beat() -> None:
        while not stop_beat.wait(hb_s):
            beat: Dict[str, Any] = {"kind": "heartbeat"}
            if trace:
                tracing.instant("worker_heartbeat", pid=os.getpid())
                _ship_spans(beat)
            try:
                _send_msg(out, beat, out_lock)
            except Exception:
                return

    beater = None
    if hang_ms is None:
        beater = threading.Thread(target=_beat, name="blaze-worker-beat",
                                  daemon=True)
        beater.start()
    cpu0 = os.times()

    def _cpu_ns() -> int:
        t = os.times()
        return int(((t.user - cpu0.user) +
                    (t.system - cpu0.system)) * 1e9)

    try:
        if directive.get("delay_ms"):
            # worker-slow: stall but KEEP heartbeating — slow must never
            # be mistaken for dead
            time.sleep(directive["delay_ms"] / 1e3)
        fn = _resolve_fn(msg["fn"])
        if trace:
            # adopt the parent trace context: spans emitted while the
            # task runs buffer locally and ship home in heartbeat
            # frames (above) and in this result frame
            with tracing.remote_task_scope(trace), \
                    tracing.span("worker_task", pid=os.getpid(),
                                 fn=msg["fn"]):
                value = fn(*msg.get("args", ()))
        else:
            value = fn(*msg.get("args", ()))
        if kill_timer is not None:
            # the task won the race with the kill timer: worker-crash
            # means this process DIES.  Committed output files may
            # exist but the result frame is lost — the exact
            # lost-executor shape the parent's map-output re-validation
            # and retry-on-another-worker handle
            os.kill(os.getpid(), signal.SIGKILL)
        reply = {"kind": "result", "task_id": msg["task_id"], "ok": True,
                 "value": value, "cpu_ns": _cpu_ns()}
        if trace:
            _ship_spans(reply)
        return reply
    except BaseException as e:
        if kill_timer is not None:
            os.kill(os.getpid(), signal.SIGKILL)
        fetch = None
        if isinstance(e, FetchFailedError):
            fetch = (e.stage_id, e.map_id)
        reply = {"kind": "result", "task_id": msg["task_id"], "ok": False,
                 "error_type": type(e).__name__, "error_msg": str(e),
                 "classify": classify_exception(e), "fetch": fetch,
                 "cpu_ns": _cpu_ns()}
        if trace:
            _ship_spans(reply)
        return reply
    finally:
        stop_beat.set()
        if beater is not None:
            beater.join(timeout=1.0)


def child_main() -> int:
    """Worker process entry (`--child`): frame loop over binary stdio.
    stdout is reserved for protocol frames — anything the task prints is
    rerouted to stderr so it cannot corrupt the stream."""
    inp = sys.stdin.buffer
    out = sys.stdout.buffer
    sys.stdout = sys.stderr
    out_lock = threading.Lock()
    signal.signal(signal.SIGTERM, lambda *_: os._exit(143))
    # the platform is stated from the spawn env alone: importing jax in
    # the frame loop would initialize a backend before the first task's
    # conf snapshot lands
    hello: Dict[str, Any] = {
        "kind": "hello", "pid": os.getpid(),
        "platform": os.environ.get("JAX_PLATFORMS") or "default"}
    _send_msg(out, hello, out_lock)
    while True:
        try:
            msg = _recv_msg(inp)
        except Exception:
            return 1
        if msg is None or msg.get("kind") == "shutdown":
            return 0
        if msg.get("kind") != "task":
            continue  # stray cancel for a task we already finished
        reply = _run_child_task(msg, out, out_lock)
        try:
            _send_msg(out, reply, out_lock)
        except Exception:
            return 1


if __name__ == "__main__":
    if "--child" in sys.argv:
        sys.exit(child_main())
    print("usage: python -m blaze_tpu.parallel.workers --child",
          file=sys.stderr)
    sys.exit(2)

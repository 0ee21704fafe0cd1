"""Deterministic fault injection + the fault-tolerance exception taxonomy.

Production pillar (PAPER.md; Flare arXiv:1703.08219 makes the same
point): a native engine only displaces the reference engine if it keeps
the host's fault-tolerance contract — tasks die, disks flip bits,
shuffle fetches fail, and the query must still finish with the same
rows.  This module is the *test* side of that contract: a process-wide
injection registry with named sites threaded through the scheduler,
task pool, shuffle writer/reader and memory manager, so chaos runs
(tests/test_fault_tolerance.py, tests/test_serving_soak.py) can script
failures deterministically and assert bit-identical recovery.

Sites (the code points that call in here):
    task-start     bridge/tasks.py, before each task attempt
    shuffle-write  shuffle/ipc.py, per flushed frame (supports `corrupt`)
    shuffle-read   shuffle/reader.py, per block fetch
    ipc-decode     shuffle/ipc.py, per frame decode
    mem-pressure   memory/manager.py, per mem_used update (forces spill)
    device-collective  parallel/stage.py DeviceExchange, per shard per
                   collective dispatch (kills the device-resident
                   exchange; the scheduler falls back to file shuffle)
    device-loop    runtime/loop.py, per chunk boundary of the
                   device-resident stage loop (kills the loop mid-fold;
                   the task falls back wholesale to the staged
                   per-batch executor)
    admit          serving/service.py, per admission decision (sheds the
                   query with QueryRejected kind="injected")
    cancel-race    serving/service.py QueryService.cancel, widens the
                   cancel-vs-completion race window
    quota-breach   memory/manager.py, per quota evaluation (forces a
                   per-query quota breach → degradation rung)
    stream-epoch   streaming/executor.py, at each micro-batch epoch
                   boundary (kills the epoch mid-flight; the stream
                   replays from the last committed checkpoint)
    checkpoint-commit  streaming/checkpoint.py, before the first-wins
                   manifest create (a crash between sink attempt and
                   commit; replay must not double-emit)
    worker-crash   parallel/workers.py, per task dispatch (the child
                   really SIGKILLs itself mid-task; the pool classifies
                   the exit as WorkerCrashed and the retry lands on a
                   different worker)
    worker-hang    parallel/workers.py, per task dispatch (the child
                   suppresses heartbeats and wedges; the pool's liveness
                   deadline detects the miss and kills the process)
    worker-slow    parallel/workers.py, per task dispatch (the child
                   stalls but keeps heartbeating: slow must never be
                   mistaken for dead)
    speculation-loser-commit-race  bridge/tasks.py, when a winning
                   attempt would cancel its speculative sibling
                   (suppresses the cancel so BOTH attempts race the
                   commit; every shuffle tier must reject the late
                   loser)
    replica-crash  fleet/replica.py, per query request (the replica
                   process really SIGKILLs itself mid-query — the host
                   death the router must survive: connection reset →
                   mark the replica down, re-route the query to the
                   next replica in rendezvous order, retry end-to-end)
    replica-hang   fleet/replica.py, per heartbeat (the replica wedges —
                   stops answering pings while its socket stays open;
                   the router's liveness deadline must classify the
                   miss as down and stop routing to it)
    socket-torn-frame  shuffle/ipc.py sock_send_frame, per frame (the
                   sender dies mid-send: the peer sees a length prefix
                   it can never satisfy; readers must classify the tear
                   as retryable FrameTransportClosed loss, never as a
                   ShuffleChecksumError)

Determinism: every decision is a pure function of (seed, site,
occurrence-index) — the k-th evaluation of a site fires or not
regardless of thread interleaving, so a fixed seed gives a fixed fire
*set* even when the task pool races.  Rules either fire on explicit
occurrence indices (`at`) or with probability `p` drawn from a
per-occurrence `random.Random(crc32(seed|site|k))`.

Config (`auron.tpu.faults.*`): `enable` activates the injector from
`rules` + `seed` on first use; tests usually call `install()` /
`scoped()` directly.  Rule-string grammar, comma-separated:

    site=0.25            fire with p=0.25 per occurrence
    site=0.25*3          ... at most 3 times
    site@2+7             fire exactly on occurrences 2 and 7
    site=0.1:corrupt     action `corrupt` (flip a payload byte) instead
                         of raising InjectedFault
"""

from __future__ import annotations

import random
import threading
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, Optional, Tuple

SITES = ("task-start", "shuffle-write", "shuffle-read", "ipc-decode",
         "mem-pressure", "device-collective", "device-loop", "admit",
         "cancel-race", "quota-breach", "stream-epoch", "checkpoint-commit",
         "worker-crash", "worker-hang", "worker-slow",
         "speculation-loser-commit-race", "replica-crash", "replica-hang",
         "socket-torn-frame")

#: dynamically registered sites (register_site): rule validation accepts
#: them alongside the static SITES tuple
_extra_sites: set = set()


def register_site(site: str) -> None:
    """Escape hatch for sites created at runtime (plugins, tests):
    parse_rules validates rule site names against SITES, and a
    dynamically registered site must opt in here or its rules are
    rejected as typos."""
    _extra_sites.add(site)


class InjectedFault(RuntimeError):
    """A scripted transient failure; classified retryable by the task
    pool (the moral equivalent of a lost executor heartbeat)."""


class ShuffleChecksumError(IOError):
    """A shuffle/spill IPC frame failed its CRC32C verification."""


class WorkerCrashed(RuntimeError):
    """A pool worker process died (or missed its liveness deadline) while
    running a task — the lost-executor analog.  Retryable: the task pool
    re-dispatches the attempt, and the crashed worker's id rides along so
    the retry can land on a DIFFERENT worker."""

    def __init__(self, worker_id: Optional[int] = None,
                 exit_code: Optional[int] = None, reason: str = ""):
        self.worker_id = worker_id
        self.exit_code = exit_code
        self.reason = reason
        detail = []
        if worker_id is not None:
            detail.append(f"worker={worker_id}")
        if exit_code is not None:
            detail.append(f"exit={exit_code}")
        if reason:
            detail.append(reason)
        super().__init__("worker crashed"
                         + (f" ({', '.join(detail)})" if detail else ""))


class TaskDeadlineExpired(TimeoutError):
    """The wave deadline passed before (or while) an attempt could run.
    Classified FATAL, not retryable: TimeoutError is an OSError subclass
    and would otherwise look like transient IO, burning maxAttempts
    backoff sleeps an already-expired task can never use."""


class FetchFailedError(RuntimeError):
    """A shuffle block could not be read back intact (Spark's
    FetchFailedException analog).  Carries the lineage the scheduler
    needs to re-run ONLY the poisoned producer map task: the producer
    stage id and map task id that wrote the block."""

    def __init__(self, stage_id: int = -1, map_id: int = -1,
                 reason: str = ""):
        self.stage_id = int(stage_id)
        self.map_id = int(map_id)
        self.reason = reason
        super().__init__(
            f"shuffle fetch failed (stage={stage_id} map={map_id})"
            + (f": {reason}" if reason else ""))


def classify_exception(e: BaseException) -> str:
    """'retryable' | 'fetch-failed' | 'fatal'.

    Retryable = transient IO and injected faults (a fresh attempt can
    succeed); fetch-failed propagates to the DAG scheduler for lineage
    recovery (re-running THIS task would just re-read the same poisoned
    block); everything else — plan/serde/logic errors — is fatal and
    must fail fast without burning retry budget."""
    if isinstance(e, FetchFailedError):
        return "fetch-failed"
    if isinstance(e, (InjectedFault, ShuffleChecksumError, WorkerCrashed,
                      EOFError, ConnectionError, BrokenPipeError,
                      InterruptedError)):
        return "retryable"
    # a worker-side failure arrives re-raised in the parent as a proxy
    # exception carrying the CHILD's classification verdict: honor it
    # (the child saw the real type; the proxy is just a RuntimeError)
    remote = getattr(e, "remote_classify", None)
    if remote in ("retryable", "fetch-failed", "fatal"):
        return remote
    if isinstance(e, (MemoryError, KeyboardInterrupt, SystemExit,
                      TaskDeadlineExpired)):
        return "fatal"
    if isinstance(e, OSError):
        return "retryable"  # transient filesystem/socket trouble
    if type(e).__name__ == "StageLoopFallback":
        # containment escape hatch: every stage-loop caller handles the
        # fallback in place, but if one leaks, the retry runs with the
        # loop declined (bridge/tasks.py) — by name to keep faults.py a
        # leaf module below blaze_tpu.runtime
        return "retryable"
    return "fatal"


@dataclass
class FaultRule:
    site: str
    p: float = 0.0
    at: Tuple[int, ...] = ()       # explicit 1-based occurrence indices
    times: Optional[int] = None    # cap on total fires
    action: str = "raise"          # "raise" | "corrupt"
    fires: int = 0                 # mutated under the injector lock


@dataclass
class _SiteStats:
    evals: int = 0
    fires: int = 0


class FaultInjector:
    """Seeded, counter-deterministic fault decision engine."""

    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self._lock = threading.Lock()
        self._rules: Dict[str, list] = {}
        self._counters: Dict[str, int] = {}
        self._stats: Dict[str, _SiteStats] = {}

    def install(self, site: str, p: float = 0.0,
                at: Iterable[int] = (), times: Optional[int] = None,
                action: str = "raise") -> None:
        if action not in ("raise", "corrupt"):
            raise ValueError(f"unknown fault action {action!r}")
        rule = FaultRule(site=site, p=float(p), at=tuple(at),
                         times=times, action=action)
        with self._lock:
            self._rules.setdefault(site, []).append(rule)

    # -- decisions ---------------------------------------------------------
    def decide(self, site: str) -> Optional[FaultRule]:
        """Consume one occurrence of `site`; return the firing rule (or
        None).  Deterministic in the occurrence index, not in which
        thread happened to claim it."""
        with self._lock:
            rules = self._rules.get(site)
            stats = self._stats.setdefault(site, _SiteStats())
            stats.evals += 1
            if not rules:
                return None
            k = self._counters.get(site, 0) + 1
            self._counters[site] = k
            for rule in rules:
                if rule.times is not None and rule.fires >= rule.times:
                    continue
                if rule.at:
                    hit = k in rule.at
                elif rule.p > 0.0:
                    # crc32-keyed seed: stable across processes (str
                    # hash() is salted) and legal Random() input
                    rng = random.Random(
                        zlib.crc32(f"{self.seed}|{site}|{k}".encode()))
                    hit = rng.random() < rule.p
                else:
                    hit = False
                if hit:
                    rule.fires += 1
                    stats.fires += 1
                    return rule
        return None

    def stats(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {s: {"evals": st.evals, "fires": st.fires}
                    for s, st in self._stats.items()}

    def reset_counters(self) -> None:
        with self._lock:
            self._counters.clear()
            self._stats.clear()
            for rules in self._rules.values():
                for r in rules:
                    r.fires = 0


def _check_site(site: str) -> str:
    """A typo'd site name would silently never fire — the worst possible
    chaos-rule failure mode (the soak 'passes' having injected nothing).
    Fail loudly at parse time; register_site() is the escape hatch for
    sites created at runtime."""
    if site not in SITES and site not in _extra_sites:
        raise ValueError(
            f"unknown fault site {site!r}; known sites: "
            f"{', '.join(SITES)}"
            + (f"; registered: {', '.join(sorted(_extra_sites))}"
               if _extra_sites else "")
            + " (faults.register_site() declares dynamic sites)")
    return site


def parse_rules(spec: str) -> list:
    """Parse the `auron.tpu.faults.rules` grammar into (site, kwargs).
    Site names are validated against SITES (+ register_site entries)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        action = "raise"
        if ":" in part:
            part, action = part.rsplit(":", 1)
        times = None
        if "*" in part:
            part, times_s = part.rsplit("*", 1)
            times = int(times_s)
        if "@" in part:
            site, at_s = part.split("@", 1)
            at = tuple(int(x) for x in at_s.split("+"))
            out.append((_check_site(site.strip()),
                        dict(at=at, times=times, action=action)))
        elif "=" in part:
            site, p_s = part.split("=", 1)
            out.append((_check_site(site.strip()),
                        dict(p=float(p_s), times=times, action=action)))
        else:
            raise ValueError(f"bad fault rule {part!r} "
                             f"(want site=p or site@k)")
    return out


# -- process-wide registry --------------------------------------------------

_lock = threading.Lock()
_injector: Optional[FaultInjector] = None
_conf_probed = False  # lazy one-shot auron.tpu.faults.enable probe


def install(site: str, **kw: Any) -> FaultInjector:
    """Programmatic rule install (tests); activates the injector."""
    global _injector
    with _lock:
        if _injector is None:
            from blaze_tpu import config
            _injector = FaultInjector(seed=config.FAULTS_SEED.get())
        inj = _injector
    inj.install(site, **kw)
    return inj


def configure(rules: str, seed: int = 0) -> FaultInjector:
    """Replace the active injector with one built from a rule string
    (`site=p*cap,site@k:action`, parse_rules' grammar): a whole seeded
    chaos script in one call."""
    global _injector, _conf_probed
    inj = FaultInjector(seed=seed)
    for site, kw in parse_rules(rules):
        inj.install(site, **kw)
    with _lock:
        _injector = inj
        _conf_probed = True
    return inj


def activate_from_conf() -> Optional[FaultInjector]:
    """Build the injector from `auron.tpu.faults.*` when enabled."""
    global _injector, _conf_probed
    from blaze_tpu import config
    with _lock:
        _conf_probed = True
        if not config.FAULTS_ENABLE.get():
            _injector = None
            return None
        inj = FaultInjector(seed=config.FAULTS_SEED.get())
        for site, kw in parse_rules(config.FAULTS_RULES.get()):
            inj.install(site, **kw)
        _injector = inj
        return inj


def clear() -> None:
    """Deactivate injection entirely (tests teardown)."""
    global _injector, _conf_probed
    with _lock:
        _injector = None
        _conf_probed = False


def _current() -> Optional[FaultInjector]:
    global _conf_probed
    inj = _injector
    if inj is not None:
        return inj
    if _conf_probed:
        return None
    # first call since clear(): honor a conf-enabled injector.  The
    # probe result is cached — per-frame hot paths must not pay a
    # config lookup when injection is off.
    with _lock:
        if _injector is not None:
            return _injector
        _conf_probed = True
    from blaze_tpu import config
    if config.FAULTS_ENABLE.get():
        return activate_from_conf()
    return None


def _note_fire(site: str) -> None:
    from blaze_tpu.bridge import xla_stats
    xla_stats.note_fault_injected()
    from blaze_tpu.bridge import tracing
    tracing.instant("fault_injected", site=site)


def maybe_fail(site: str, **ctx: Any) -> None:
    """Raise InjectedFault if a raise-action rule fires for `site`."""
    inj = _current()
    if inj is None:
        return
    rule = inj.decide(site)
    if rule is not None and rule.action == "raise":
        _note_fire(site)
        raise InjectedFault(
            f"injected fault at {site}"
            + (f" ({', '.join(f'{k}={v}' for k, v in ctx.items())})"
               if ctx else ""))


def corrupt(site: str, payload: bytes, **ctx: Any) -> bytes:
    """Return `payload`, bit-flipped if a corrupt-action rule fires for
    `site`; a raise-action rule on the same site raises instead."""
    inj = _current()
    if inj is None or not payload:
        return payload
    rule = inj.decide(site)
    if rule is None:
        return payload
    _note_fire(site)
    if rule.action == "raise":
        raise InjectedFault(f"injected fault at {site}")
    buf = bytearray(payload)
    pos = (inj.seed + rule.fires) % len(buf)
    buf[pos] ^= 0xFF
    return bytes(buf)


def fires(site: str, **ctx: Any) -> bool:
    """Non-raising decision (the mem-pressure site: injection forces a
    spill round rather than throwing inside an operator)."""
    inj = _current()
    if inj is None:
        return False
    if inj.decide(site) is None:
        return False
    _note_fire(site)
    return True


def stats() -> Dict[str, Dict[str, int]]:
    inj = _injector
    return inj.stats() if inj is not None else {}


def reset_counters() -> None:
    inj = _injector
    if inj is not None:
        inj.reset_counters()


class scoped:
    """`with faults.scoped(("task-start", dict(at=(1,)))): ...` —
    install rules for a block, restore the previous injector on exit."""

    def __init__(self, *rules: Tuple[str, Dict[str, Any]], seed: int = 0):
        self._rules = rules
        self._seed = seed
        self._saved: Optional[FaultInjector] = None
        self._saved_probed = False

    def __enter__(self) -> FaultInjector:
        global _injector, _conf_probed
        with _lock:
            self._saved, self._saved_probed = _injector, _conf_probed
            inj = FaultInjector(seed=self._seed)
            _injector, _conf_probed = inj, True
        for site, kw in self._rules:
            inj.install(site, **kw)
        return inj

    def __exit__(self, *exc) -> bool:
        global _injector, _conf_probed
        with _lock:
            _injector, _conf_probed = self._saved, self._saved_probed
        return False

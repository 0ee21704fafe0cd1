"""Unified memory manager: fixed budget, fair consumer caps, spill-on-pressure.

Parity: auron-memmgr (ref: auron-memmgr/src/lib.rs:38 `MemManager`, `:46`
init, `:82` register_consumer, `:202` `MemConsumer` trait — update_mem_used
triggers spill() of the biggest consumer when the pool overflows).

TPU mapping: the budget models DEVICE HBM held by operator state (sort runs,
agg tables, join build sides, shuffle staging), and is a budget PER CHIP:
a consumer is charged to the chip its task runs on (bridge/context
TaskContext.device), and pressure, the fair cap and the choice of what
to shed are all taken among the consumers of that chip.  With one chip
that is every consumer, as before.  Spill tiers mirror the
reference's Spill abstraction (ref auron-memmgr/src/spill.rs:89
try_new_spill: JVM on-heap if available else disk): here tier 1 is host RAM
(the "on-heap" analog — device arrays become numpy/Arrow buffers), tier 2 is
a zstd-compressed disk file.  Synchronous (no condvar): one task runtime
drives one operator chain, so update_mem_used spills inline, matching the
per-task budget discipline rather than the cross-task waiting.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from blaze_tpu import config
from blaze_tpu.memory.spill import SpillMetrics

MEM_SPILL_FACTOR = 0.8  # consumer must shrink below cap*factor after spill


def _trace_spill(consumer, released: int, cause: str) -> None:
    """mem_spill trace instant: which consumer shed how much, why, and
    for which query — the attribution surface's spill-bytes source."""
    try:
        from blaze_tpu.bridge import tracing
        tracing.instant(
            "mem_spill", consumer=consumer.name, bytes=released,
            cause=cause,
            query=getattr(getattr(consumer, "query", None),
                          "query_id", None))
    except Exception:
        pass


class MemConsumer:
    """Spillable operator state (ref MemConsumer trait, lib.rs:202).

    Subclasses implement `spill()` to move their largest retained structure
    down a tier and return the bytes released.
    """

    name: str = "consumer"

    def __init__(self, name: str):
        self.name = name
        self._mem_used = 0
        #: set (under the manager lock) by cross-query arbitration; the
        #: consumer sheds itself on its OWN thread at its next
        #: update_mem_used — a foreign thread must never mutate another
        #: query's operator state mid-batch
        self._release_requested = False
        self._manager: Optional[MemManager] = None
        #: owning serving.QueryContext (captured at set_spillable time);
        #: None for standalone consumers.  Lets the manager arbitrate
        #: ACROSS queries and enforce per-query quotas.
        self.query = None
        #: id of the chip whose budget this consumer's state is charged
        #: to: its task's (captured at set_spillable time)
        self.chip = 0
        self.spill_metrics = SpillMetrics()
        # owning operator's MetricNode; when set, retained-byte peaks are
        # recorded there as `mem_used` (baseline metric vocabulary).  A
        # class may be both ExecutionPlan and MemConsumer — keep the
        # operator MetricNode if one is already attached.
        self.metrics = getattr(self, "metrics", None)

    @property
    def mem_used(self) -> int:
        return self._mem_used

    def set_spillable(self, manager: "MemManager") -> None:
        from blaze_tpu.bridge.context import active_query, current_task
        if self.query is None:
            self.query = active_query()
        self.chip = current_task().device_id
        self._manager = manager
        manager.register_consumer(self)

    def update_mem_used(self, nbytes: int) -> None:
        """Declare current retained bytes; may trigger spills (incl. self)."""
        self._mem_used = max(0, int(nbytes))
        if self.metrics is not None:
            self.metrics.set_max("mem_used", self._mem_used)
        if self._manager is not None:
            self._manager.on_mem_updated(self)

    def add_mem_used(self, delta: int) -> None:
        self.update_mem_used(self._mem_used + delta)

    def spill(self) -> int:
        """Release memory down a tier; returns bytes released."""
        raise NotImplementedError

    def try_release_pressure(self) -> int:
        """Cheaper-than-spill release under pressure, if the consumer has
        one; returns bytes released (0 = nothing cheap, spill() follows).

        The one current implementor is the partial-agg state: with
        auron.tpu.partialAgg.skipping.onSpill it hands its buffered
        partials downstream un-merged (mode switch to pass-through)
        instead of paying spill IO the final stage must re-read anyway."""
        return 0

    def unregister(self) -> None:
        if self._manager is not None:
            self._manager.unregister_consumer(self)
            self._manager = None


class MemManager:
    """Process-wide manager of one budget a chip over registered
    consumers (ref lib.rs:38).  `total` is what ONE chip may hold."""

    _instance: Optional["MemManager"] = None
    _instance_lock = threading.Lock()

    def __init__(self, total_bytes: int):
        self.total = int(total_bytes)
        self._lock = threading.RLock()
        self._consumers: List[MemConsumer] = []
        self.total_spill_count = 0
        self.total_spilled_bytes = 0
        self.total_pressure_releases = 0
        self.total_quota_breaches = 0
        self.peak_used = 0
        #: per-query shed attribution: query_id (or "<solo>") -> bytes
        #: released on its consumers by pressure/quota arbitration
        self.shed_bytes_by_query: Dict[str, int] = {}
        #: query_id of the first consumer shed under GLOBAL pressure —
        #: the observable form of "the heaviest query pays first"
        self.first_shed_query: Optional[str] = None

    # -- singleton wiring (ref MemManager::init, lib.rs:46) ---------------
    @classmethod
    def init(cls, total_bytes: Optional[int] = None) -> "MemManager":
        with cls._instance_lock:
            if cls._instance is None or total_bytes is not None:
                if total_bytes is None:
                    total_bytes = default_budget_bytes()
                cls._instance = cls(total_bytes)
            return cls._instance

    @classmethod
    def get(cls) -> "MemManager":
        return cls.init()

    # -- consumer registry -------------------------------------------------
    def register_consumer(self, c: MemConsumer) -> None:
        with self._lock:
            if c not in self._consumers:
                self._consumers.append(c)

    def unregister_consumer(self, c: MemConsumer) -> None:
        with self._lock:
            if c in self._consumers:
                self._consumers.remove(c)

    @property
    def mem_used(self) -> int:
        with self._lock:
            return sum(c.mem_used for c in self._consumers)

    def _on_chip(self, chip: int) -> List[MemConsumer]:
        return [c for c in self._consumers if c.chip == chip]

    def chip_used(self, chip: int) -> int:
        """Bytes the consumers charged to `chip` retain."""
        with self._lock:
            return sum(c.mem_used for c in self._on_chip(chip))

    def consumer_cap(self, chip: int = 0) -> int:
        """Fair per-consumer cap on one chip: total / max(1, N there)
        (ref lib.rs fair share)."""
        with self._lock:
            return self.total // max(1, len(self._on_chip(chip)))

    # -- pressure handling -------------------------------------------------
    def on_mem_updated(self, updated: MemConsumer) -> None:
        with self._lock:
            # a pending cross-query release request is honored first, on
            # the consumer's own thread (the only thread that may touch
            # its state)
            if updated._release_requested and updated.mem_used > 0:
                updated._release_requested = False
                released = updated.try_release_pressure()
                if released > 0:
                    self.total_pressure_releases += 1
                else:
                    released = updated.spill()
                    self.total_spill_count += 1
                    self.total_spilled_bytes += released
                    _trace_spill(updated, released, "cross-query-release")
                self._attribute_shed(updated, released,
                                     global_pressure=True)
            used = self.mem_used
            if used > self.peak_used:
                self.peak_used = used
            # the budget is the chip's: only what is charged to the
            # updating consumer's chip can press on it or be shed for it
            chip = updated.chip
            overflow = self.chip_used(chip) - self.total
            cap = self.consumer_cap(chip)
            # chaos hook: a scripted mem-pressure fault spills the
            # updating consumer as if the pool had overflowed (exercises
            # the spill / re-read path without a real over-budget
            # workload)
            from blaze_tpu import faults
            if faults.fires("mem-pressure") and updated.mem_used > 0:
                released = updated.spill()
                self.total_spill_count += 1
                self.total_spilled_bytes += released
                _trace_spill(updated, released, "injected-pressure")
            # per-query quota first: a query over ITS budget sheds its
            # own state (and climbs the degradation ladder) before its
            # pressure is socialized across the pool
            self._enforce_query_quota(updated)
            # a consumer far over its fair share spills even without global
            # overflow, so one giant sort cannot starve later operators
            if overflow <= 0 and updated.mem_used <= cap * 2:
                return
            # spill biggest consumers until under budget (ref lib.rs: spill
            # of the biggest consumer on pressure).  Across queries the
            # heaviest QUERY pays first (its largest consumer leading), so
            # a light query sharing the pool with a hog is untouched.  A
            # consumer offering a cheaper-than-spill release (partial-agg
            # pass-through switch) is taken at its word first — the
            # released partials stream downstream instead of hitting
            # spill IO.  Consumers of a DIFFERENT query are never shed
            # from this thread (their owner may be mid-mutation): they
            # get a release request they honor at their next update,
            # and because the order is heaviest-first, this thread stops
            # rather than shed its lighter self while the hog's release
            # is pending.
            upd_q = getattr(updated, "query", None)
            for c in self._arbitration_order(chip):
                if self.chip_used(chip) <= self.total * MEM_SPILL_FACTOR:
                    break
                if c.mem_used == 0:
                    continue
                c_q = getattr(c, "query", None)
                if c_q is not None and c_q is not upd_q:
                    c._release_requested = True
                    break
                released = c.try_release_pressure()
                if released > 0:
                    self.total_pressure_releases += 1
                    self._attribute_shed(c, released, global_pressure=True)
                    continue
                released = c.spill()
                self.total_spill_count += 1
                self.total_spilled_bytes += released
                _trace_spill(c, released, "pool-pressure")
                self._attribute_shed(c, released, global_pressure=True)

    def _attribute_shed(self, c: MemConsumer, released: int,
                        global_pressure: bool = False) -> None:
        if released <= 0:
            return
        qid = str(getattr(getattr(c, "query", None), "query_id", None)
                  or "<solo>")
        if global_pressure and self.first_shed_query is None:
            self.first_shed_query = qid
        self.shed_bytes_by_query[qid] = (
            self.shed_bytes_by_query.get(qid, 0) + released)

    def _arbitration_order(self, chip: Optional[int] = None
                           ) -> List[MemConsumer]:
        """Consumers (of one chip, where given) ordered heaviest-query-
        first, then biggest-first.

        Standalone consumers (no query) form singleton groups, which
        preserves the single-query behaviour: biggest consumer first.
        """
        consumers = (self._consumers if chip is None
                     else self._on_chip(chip))
        totals: Dict[object, int] = {}
        for c in consumers:
            q = getattr(c, "query", None)
            key = id(q) if q is not None else ("solo", id(c))
            totals[key] = totals.get(key, 0) + c.mem_used

        def order(c: MemConsumer):
            q = getattr(c, "query", None)
            key = id(q) if q is not None else ("solo", id(c))
            return (-totals[key], -c.mem_used)

        return sorted(consumers, key=order)

    def _enforce_query_quota(self, updated: MemConsumer) -> None:
        """Per-query quota: shed the breaching query's own state largest-
        first, and advance its degradation ladder one rung per breaching
        update (pass-through → shrink-capacity → kill)."""
        from blaze_tpu import faults
        q = getattr(updated, "query", None)
        if q is None:
            return
        quota = int(getattr(q, "mem_quota", 0) or 0)
        mine = [c for c in self._consumers if getattr(c, "query", None) is q]
        used = sum(c.mem_used for c in mine)
        forced = faults.fires("quota-breach")
        if not forced and (quota <= 0 or used <= quota):
            return
        self.total_quota_breaches += 1
        rung = q.degrade()
        try:
            from blaze_tpu.bridge import tracing
            tracing.instant("quota_breach", query=q.query_id, used=used,
                            quota=quota, rung=rung)
        except Exception:
            pass
        target = int((quota if quota > 0 else used) * MEM_SPILL_FACTOR)
        for c in sorted(mine, key=lambda c: -c.mem_used):
            if sum(x.mem_used for x in mine) <= target:
                break
            if c.mem_used == 0:
                continue
            released = c.try_release_pressure()
            if released > 0:
                self.total_pressure_releases += 1
                self._attribute_shed(c, released)
                continue
            released = c.spill()
            self.total_spill_count += 1
            self.total_spilled_bytes += released
            _trace_spill(c, released, "query-quota")
            self._attribute_shed(c, released)

    # -- diagnostics (ref lib.rs:143 dump_status) -------------------------
    def dump_status(self) -> str:
        with self._lock:
            lines = [f"MemManager total={self.total} used={self.mem_used} "
                     f"spills={self.total_spill_count} "
                     f"spilled_bytes={self.total_spilled_bytes} "
                     f"pressure_releases={self.total_pressure_releases}"]
            if self.shed_bytes_by_query:
                shed = " ".join(f"{q}={b}" for q, b in
                                sorted(self.shed_bytes_by_query.items()))
                lines.append(f"  shed_by_query: {shed}")
            for c in self._consumers:
                lines.append(f"  {c.name}: chip={c.chip} "
                             f"used={c.mem_used}")
            return "\n".join(lines)


def default_budget_bytes() -> int:
    """HBM budget of one chip: device memory * memory fraction (the
    executor-overhead × fraction formula of the reference,
    NativeHelper.scala:51-73).  Every device that tasks are placed on is
    asked (parallel/mesh.current_mesh); the smallest answer holds for
    each."""
    from blaze_tpu.parallel.mesh import current_mesh
    frac = config.MEMORY_FRACTION.get()
    # memory_stats() is None on the CPU backend and a dict with
    # bytes_limit on a TPU (16.9e9 on a v5e); an accelerator that cannot
    # report it is an error, not a 4 GiB host budget in silence
    limits = [(d.memory_stats() or {}).get("bytes_limit")
              for d in current_mesh().devices.reshape(-1)]
    if all(limits):
        return int(min(limits) * frac)
    # CPU backend: host memory bounded by the process-RSS fraction
    # (ref auron.process.vmrss.memoryFraction), nominally capped at 4 GiB
    try:
        phys = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        phys = 4 << 30
    vmrss = config.PROCESS_VMRSS_MEMORY_FRACTION.get()
    return int(min(phys * vmrss, 4 << 30) * frac)

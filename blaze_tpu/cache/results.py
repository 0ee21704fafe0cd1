"""Bounded, byte-budgeted result + subplan cache (the work-sharing
tentpole's ring (a)).

One process-wide `ResultCache` holds two entry kinds under one LRU and
one byte budget (`auron.tpu.cache.maxBytes`):

* ``result`` — the final Arrow table of a whole query, keyed by the
  plan fingerprint (plan/fingerprint.py);
* ``subplan`` — the exchange-boundary shuffle blocks of one leaf map
  stage (``{reduce_id: [bytes, ...]}``), keyed by the subplan
  fingerprint, so a later query with the same producing subtree skips
  the whole map stage and replays the blocks.

Every entry stores the `source_snapshot` observed when it was built.
Lookups re-validate: a snapshot mismatch (file mtime/size changed,
connector snapshot_id advanced) actively evicts the stale entry and
counts `result_cache_invalidations` — the cache can serve stale bytes
only if the source is bit-identical to when they were produced.

The cache is a `MemConsumer` with `query = None` (it outlives every
query), so its footprint rides the existing memory-pressure ladder:
under global pressure the manager calls `spill()`, which evicts LRU
entries — cached convenience always yields to live query state.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from blaze_tpu import config
from blaze_tpu.bridge import xla_stats
from blaze_tpu.memory.manager import MemConsumer, MemManager


def _entry_nbytes(kind: str, value: Any) -> Optional[int]:
    """Retained footprint of a candidate value; None = unmeasurable
    (never cached)."""
    if kind == "subplan":
        return sum(len(b) for blocks in value.values() for b in blocks)
    nbytes = getattr(value, "nbytes", None)
    return int(nbytes) if isinstance(nbytes, int) else None


class _Entry:
    __slots__ = ("kind", "snapshot", "value", "nbytes", "hits")

    def __init__(self, kind: str, snapshot: Dict[str, Any], value: Any,
                 nbytes: int):
        self.kind = kind
        self.snapshot = snapshot
        self.value = value
        self.nbytes = nbytes
        self.hits = 0


class ResultCache(MemConsumer):
    """LRU over (fingerprint -> _Entry); thread-safe, MemManager-
    accounted, evicting on its own byte budget and under pool
    pressure."""

    def __init__(self, max_bytes: int):
        super().__init__("result_cache")
        self.max_bytes = max(0, int(max_bytes))
        self._cache_lock = threading.RLock()
        self._entries: "OrderedDict[str, _Entry]" = OrderedDict()
        self._used = 0

    # -- lookup ---------------------------------------------------------
    def _get(self, kind: str, fp: str,
             snapshot: Dict[str, Any]) -> Optional[Any]:
        counter = ("result_cache" if kind == "result"
                   else "subplan_cache")
        with self._cache_lock:
            e = self._entries.get(fp)
            if e is not None and e.kind == kind:
                if e.snapshot == snapshot:
                    self._entries.move_to_end(fp)
                    e.hits += 1
                    xla_stats.note_cache(**{f"{counter}_hits": 1})
                    return e.value
                # source moved under the entry: stale, actively evict
                self._evict_locked(fp)
                xla_stats.note_cache(result_cache_invalidations=1)
            xla_stats.note_cache(**{f"{counter}_misses": 1})
            return None

    def get_result(self, fp: str, snapshot: Dict[str, Any]
                   ) -> Optional[Any]:
        return self._get("result", fp, snapshot)

    def get_subplan(self, fp: str, snapshot: Dict[str, Any]
                    ) -> Optional[Dict[int, List[bytes]]]:
        return self._get("subplan", fp, snapshot)

    def peek_result_nbytes(self, fp: str, snapshot: Dict[str, Any]
                           ) -> Optional[int]:
        """Entry size if a lookup WOULD hit; no counters, no LRU touch —
        the serving admission gate's cheap probe."""
        with self._cache_lock:
            e = self._entries.get(fp)
            if (e is not None and e.kind == "result"
                    and e.snapshot == snapshot):
                return e.nbytes
            return None

    # -- insert ---------------------------------------------------------
    def _put(self, kind: str, fp: str, snapshot: Dict[str, Any],
             value: Any) -> bool:
        nbytes = _entry_nbytes(kind, value)
        if nbytes is None or nbytes > self.max_bytes:
            return False
        counter = ("result_cache" if kind == "result"
                   else "subplan_cache")
        with self._cache_lock:
            if fp in self._entries:
                self._evict_locked(fp, count=False)
            self._entries[fp] = _Entry(kind, snapshot, value, nbytes)
            self._used += nbytes
            while self._used > self.max_bytes and len(self._entries) > 1:
                self._evict_locked(next(iter(self._entries)))
            xla_stats.note_cache(**{f"{counter}_puts": 1,
                                    "cache_used_bytes_last": self._used})
        # outside the cache lock: may arbitrate (and call spill() back)
        self.update_mem_used(self._used)
        return True

    def put_result(self, fp: str, snapshot: Dict[str, Any],
                   value: Any) -> bool:
        return self._put("result", fp, snapshot, value)

    def put_subplan(self, fp: str, snapshot: Dict[str, Any],
                    blocks: Dict[int, List[bytes]]) -> bool:
        return self._put("subplan", fp, snapshot, blocks)

    def invalidate(self, fp: str) -> None:
        with self._cache_lock:
            if fp in self._entries:
                self._evict_locked(fp)
                xla_stats.note_cache(result_cache_invalidations=1)
        self.update_mem_used(self._used)

    # -- eviction -------------------------------------------------------
    def _evict_locked(self, fp: str, count: bool = True) -> int:
        e = self._entries.pop(fp)
        self._used -= e.nbytes
        if count:
            xla_stats.note_cache(result_cache_evictions=1,
                                 cache_used_bytes_last=self._used)
        return e.nbytes

    def spill(self) -> int:
        """Memory-pressure hook: shed LRU entries until half the
        footprint is gone (or the cache is empty)."""
        with self._cache_lock:
            target = self._used // 2
            released = 0
            while self._entries and self._used > target:
                released += self._evict_locked(next(iter(self._entries)))
            self._mem_used = self._used  # manager reads it post-spill
            return released

    def clear(self) -> None:
        with self._cache_lock:
            self._entries.clear()
            self._used = 0
            xla_stats.note_cache(cache_used_bytes_last=0)
        self._mem_used = 0

    def stats(self) -> Dict[str, int]:
        with self._cache_lock:
            return {"entries": len(self._entries),
                    "used_bytes": self._used,
                    "max_bytes": self.max_bytes}


# -- process-wide singleton ----------------------------------------------

_singleton: Optional[ResultCache] = None
_singleton_lock = threading.Lock()


def get_cache() -> Optional[ResultCache]:
    """The process cache, created lazily — and only when
    `auron.tpu.cache.enable` is on (None otherwise, so the disabled
    path allocates nothing)."""
    if not config.CACHE_ENABLE.get():
        return None
    global _singleton
    with _singleton_lock:
        manager = MemManager.get()
        if _singleton is None:
            c = ResultCache(config.CACHE_MAX_BYTES.get())
            c.set_spillable(manager)
            # cross-query state: never owned by whichever query happened
            # to touch it first (set_spillable captures active_query())
            c.query = None
            _singleton = c
        elif _singleton._manager is not manager:
            # MemManager.init() swapped the pool (tests):
            # re-home the accounting
            _singleton._manager = None
            _singleton.set_spillable(manager)
            _singleton.query = None
        return _singleton


def reset_cache() -> None:
    """Drop the singleton (tests teardown): clears entries and
    unregisters the consumer so leak checks see an empty pool."""
    global _singleton
    with _singleton_lock:
        c, _singleton = _singleton, None
    if c is not None:
        c.clear()
        c.update_mem_used(0)
        c.unregister()

"""Persistent query history: event log, replay store, fleet rollups.

The reference ships a dedicated `ui` module whose whole job is
reporting native-engine metrics back into the host engine's history UI;
per-query introspection is useless for operating a fleet unless it
survives the process and aggregates over time.  The PR 13 tracing /
flight-recorder plane is strictly in-memory and per-query — this module
is the longitudinal layer on top of it:

* **event log** — an append-only, schema-versioned JSONL file per query
  (`query-<qid>.jsonl` under `auron.tpu.history.dir`), written at
  admission, stage completion, recovery/speculation-relevant events and
  final metric-tree + attribution.  Emitters live in serving/service.py
  (admission + final), plan/stages.py (stage completion, lineage
  recovery) and streaming/executor.py (epochs, recovery).  Like
  `auron.tpu.trace.enable`, the knob is probed once lazily and disabled
  history costs one boolean check per site — no I/O, no allocation.
  Size is bounded two ways: per-query events beyond
  `auron.tpu.history.maxEventsPerQuery` are dropped (the terminal event
  always lands, carrying the drop count) and retention keeps at most
  `auron.tpu.history.maxQueries` query logs (oldest deleted first).

* **history store** — `HistoryStore` replays event logs from disk into
  queryable per-query summaries and a fleet `rollup()`.  Replay is
  deterministic: the same log bytes produce the same summary in any
  process, which is what makes `/history/<qid>` survive a restart and
  stay bit-stable across replays.  `compact()` rewrites terminal query
  logs down to their summary-bearing events.

* **device-utilization ledger** — `device_ledger(spans)` derives, per
  stage, device-busy vs wall seconds, dispatch-gap idle inside the
  device activity window, and map→exchange barrier idle from the PR 13
  span trace.  It rides in the terminal event (when tracing was on), so
  ROADMAP item 4's "overlap visible in span traces" claim is falsifiable
  from the history surface alone.

The HTTP surface (`/history`, `/history/<qid>`, `/history/rollup`)
lives in bridge/profiling.py; the regression sentinel that diffs two
saved rollups is blaze_tpu/tools/sentinel.py.

This module deliberately imports nothing heavy at module scope (no jax,
no pyarrow): a fresh process can replay history without touching the
engine.
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

#: bump when the event shape changes; every event line carries it
HISTORY_SCHEMA_VERSION = 1

#: bump when the summary/rollup shape changes; both payloads carry it
ROLLUP_SCHEMA_VERSION = 2

#: every event type the emitters may write (docs/observability.md keeps
#: a row per entry; tests/test_history_conformance.py enforces it)
EVENT_TYPES = frozenset({
    "admitted",         # serving/service.py submit(): query accepted
    "started",          # serving/service.py _run(): popped off the queue
    "stage_complete",   # plan/stages.py: one stage's placement + metrics
    "stage_recovery",   # plan/stages.py: lineage re-run of a map task
    "stream_epoch",     # streaming/executor.py: one micro-batch epoch
    "stream_recovery",  # streaming/executor.py: checkpoint restore
    "finished",         # serving/service.py: terminal status + metric
                        # tree + attribution (+ device ledger)
})

#: terminal event types compact() preserves verbatim
_KEEP_ON_COMPACT = ("admitted", "started", "stage_complete",
                    "stage_recovery", "finished")

_lock = threading.Lock()
_enabled = False
_conf_probed = False  # lazy one-shot auron.tpu.history.enable probe
#: per-query event counts / drop counts / counter baselines, bounded
_counts: Dict[str, int] = {}
_dropped: Dict[str, int] = {}
_baselines: Dict[str, Dict[str, int]] = {}
_STATE_CAP = 1024


def _probe_conf() -> None:
    global _conf_probed, _enabled
    with _lock:
        if _conf_probed:
            return
        _conf_probed = True
    try:
        from blaze_tpu import config
        if config.HISTORY_ENABLE.get():
            _enabled = True
    except Exception:
        pass


def enabled() -> bool:
    """One near-free boolean at every emit site once probed (the
    auron.tpu.trace.enable pattern)."""
    if not _conf_probed:
        _probe_conf()
    return _enabled


def reset_conf_probe() -> None:
    """Test helper: forget the probe and per-query bookkeeping so the
    next emit re-reads `auron.tpu.history.enable`."""
    global _conf_probed, _enabled
    with _lock:
        _conf_probed = False
        _enabled = False
        _counts.clear()
        _dropped.clear()
        _baselines.clear()


def history_dir() -> str:
    """Resolved log directory (auron.tpu.history.dir; empty uses
    <system tempdir>/blaze_history)."""
    try:
        from blaze_tpu import config
        d = config.HISTORY_DIR.get()
    except Exception:
        d = ""
    return d or os.path.join(tempfile.gettempdir(), "blaze_history")


def _max_events() -> int:
    try:
        from blaze_tpu import config
        return max(1, config.HISTORY_MAX_EVENTS.get())
    except Exception:
        return 512


def _max_queries() -> int:
    try:
        from blaze_tpu import config
        return max(1, config.HISTORY_MAX_QUERIES.get())
    except Exception:
        return 256


def _safe_qid(query_id: Any) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]", "_", str(query_id))[:128]


def _log_path(query_id: Any, root: Optional[str] = None) -> str:
    return os.path.join(root or history_dir(),
                        f"query-{_safe_qid(query_id)}.jsonl")


def _trim_state() -> None:
    # bound the in-memory per-query maps (caller holds _lock)
    for m in (_counts, _dropped, _baselines):
        while len(m) > _STATE_CAP:
            m.pop(next(iter(m)))


def _append(query_id: Any, event: str, fields: Dict[str, Any],
            terminal: bool = False) -> None:
    """Write one event line; bounded per query.  Failures are swallowed —
    history must never take a query down."""
    if not enabled() or query_id is None:
        return
    assert event in EVENT_TYPES, event
    qid = str(query_id)
    with _lock:
        n = _counts.get(qid, 0)
        if not terminal and n >= _max_events():
            _dropped[qid] = _dropped.get(qid, 0) + 1
            return
        _counts[qid] = n + 1
        dropped = _dropped.get(qid, 0)
        _trim_state()
    rec = {"v": HISTORY_SCHEMA_VERSION, "event": event, "ts": time.time(),
           "query": qid}
    rec.update(fields)
    if terminal and dropped:
        rec["events_dropped"] = dropped
    try:
        root = history_dir()
        os.makedirs(root, exist_ok=True)
        with open(_log_path(qid, root), "a") as f:
            f.write(json.dumps(rec, sort_keys=True, default=str) + "\n")
    except OSError:
        pass


def prune(root: Optional[str] = None) -> int:
    """Retention: delete the oldest query logs beyond
    auron.tpu.history.maxQueries; returns how many were removed."""
    root = root or history_dir()
    try:
        names = [n for n in os.listdir(root)
                 if n.startswith("query-") and n.endswith(".jsonl")]
    except OSError:
        return 0
    cap = _max_queries()
    if len(names) <= cap:
        return 0
    paths = [os.path.join(root, n) for n in names]
    paths.sort(key=lambda p: (os.path.getmtime(p), p))
    removed = 0
    for p in paths[:len(paths) - cap]:
        try:
            os.remove(p)
            removed += 1
        except OSError:
            pass
    return removed


# -- emitters (called from serving/stages/streaming) ---------------------

def note_admitted(query_id: Any, *, tenant: str, deadline_ms: float = 0,
                  mem_quota: int = 0) -> None:
    """Query accepted by admission control; snapshots the counter plane
    so the terminal event can attribute deltas to this query."""
    if not enabled():
        return
    from blaze_tpu.bridge import xla_stats
    with _lock:
        _baselines[str(query_id)] = xla_stats.snapshot()
        _trim_state()
    _append(query_id, "admitted",
            {"tenant": tenant, "deadline_ms": deadline_ms,
             "mem_quota": int(mem_quota)})
    prune()


def note_started(query_id: Any, queued_s: float = 0.0) -> None:
    if not enabled():
        return
    _append(query_id, "started", {"queued_s": round(float(queued_s), 6)})


def note_stage(query_id: Any, *, sid: int, exchange: str, compute: str,
               tasks: Optional[int] = None,
               metrics: Optional[Dict[str, Any]] = None) -> None:
    """One stage completed: observed placement + merged metric summary."""
    if not enabled():
        return
    _append(query_id, "stage_complete",
            {"stage": int(sid), "exchange": exchange, "compute": compute,
             "tasks": tasks, "metrics": dict(metrics or {})})


def note_stage_recovery(query_id: Any, *, sid: int, map_task: int) -> None:
    if not enabled():
        return
    _append(query_id, "stage_recovery",
            {"stage": int(sid), "map_task": int(map_task)})


def note_stream_epoch(query_id: Any, *, epoch: int, rows: int,
                      records: int, wall_ns: int,
                      committed: bool) -> None:
    if not enabled():
        return
    _append(query_id, "stream_epoch",
            {"epoch": int(epoch), "rows": int(rows),
             "records": int(records), "wall_ns": int(wall_ns),
             "committed": bool(committed)})


def note_stream_recovery(query_id: Any, *, resume_epoch: int,
                         replayed: int) -> None:
    if not enabled():
        return
    _append(query_id, "stream_recovery",
            {"resume_epoch": int(resume_epoch),
             "replayed": int(replayed)})


def note_finished(query_id: Any, *, status: str, tenant: str,
                  wall_s: Optional[float] = None,
                  error: Optional[str] = None,
                  metric_tree: Optional[dict] = None,
                  fingerprint: Optional[str] = None) -> None:
    """Terminal event: final status, metric tree, counter-delta
    attribution, (when tracing ran) the device-utilization ledger plus
    the critical-path bottleneck report, and (when the stats plane is
    on) the plan fingerprint and advisor findings."""
    if not enabled():
        return
    from blaze_tpu.bridge import xla_stats
    with _lock:
        base = _baselines.pop(str(query_id), None)
    counters = xla_stats.delta(base) if base else {}
    # attribution is the per-query slice of the process counter plane —
    # best-effort under concurrent queries, same caveat as the flight
    # recorder's counter deltas
    try:
        from blaze_tpu.bridge import tracing
        spans = tracing.spans_for_query(str(query_id))
    except Exception:
        spans = []
    spill = sum(int((r.get("attrs") or {}).get("bytes", 0) or 0)
                for r in spans if r.get("name") == "mem_spill")
    rss = sum(int((r.get("attrs") or {}).get("nbytes", 0) or 0)
              for r in spans if r.get("name") == "rss_exchange")
    attribution = {
        "counters": {k: v for k, v in sorted(counters.items())
                     if isinstance(v, (int, float))},
        "spill_bytes": spill,
        "shuffle_bytes_by_tier": {
            "device": int(counters.get("shuffle_device_bytes", 0)),
            "rss": rss,
            "file": int(counters.get("shuffle_host_bytes", 0))},
        "approximate": True,
    }
    fields: Dict[str, Any] = {
        "status": status, "tenant": tenant,
        "wall_s": round(float(wall_s), 6) if wall_s is not None else None,
        "metric_tree": metric_tree, "attribution": attribution,
    }
    if error:
        fields["error"] = str(error)[:512]
    if spans:
        fields["device_ledger"] = device_ledger(spans)
        try:
            from blaze_tpu.bridge import critical_path
            report = critical_path.bottleneck_report(spans, wall_s)
            if report is not None:
                fields["bottleneck"] = report
        except Exception:
            pass
    if fingerprint:
        fields["fingerprint"] = str(fingerprint)
    # fleet: stamp which replica served the query, so per-replica
    # rollups across a shared history dir account for every submitted
    # query (the kill-replica soak sums these against the total)
    try:
        from blaze_tpu import config
        replica = config.FLEET_REPLICA_ID.get()
        if replica:
            fields["replica"] = str(replica)
    except Exception:
        pass
    try:
        from blaze_tpu.plan import statstore
        if statstore.enabled():
            from blaze_tpu.plan import advisor as advisor_mod
            findings = advisor_mod.findings(
                statstore.prior(fingerprint), fields.get("bottleneck"))
            fields["advisor"] = findings
            if findings:
                xla_stats.note_stats(advisor_findings=len(findings))
    except Exception:
        pass
    _append(query_id, "finished", fields, terminal=True)


# -- device-utilization ledger -------------------------------------------

#: span names that represent the device actually doing work
_DEVICE_SPANS = ("device_exchange", "stage_loop_chunk", "xla_compile")
#: exchange-tier spans that end a stage's map side (the barrier)
_EXCHANGE_SPANS = ("device_exchange", "rss_exchange", "shuffle_exchange")


def _merged_busy_ns(intervals: List[tuple]) -> int:
    """Union length of [t0, t1) intervals — overlapping device dispatches
    must not double-count busy time."""
    total = 0
    end = None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


def device_ledger(spans: List[dict]) -> Dict[str, Any]:
    """Per-stage device-busy vs wall seconds from one query's span trace.

    For each stage (spans grouped by ctx/attr `stage`; stage-less spans
    land under stage -1 as query overhead):

    * ``wall_s``   — extent of ALL the stage's spans;
    * ``device_busy_s`` — union of device-span intervals
      (device_exchange / stage_loop_chunk; xla_compile instants count
      their `ns` attr);
    * ``dispatch_gap_s`` — idle inside the device activity window
      (first device dispatch → last device completion, minus busy): the
      host-orchestration cost between dispatches;
    * ``barrier_idle_s`` — gap between the last pre-exchange span end
      and the exchange-tier span start: the map→exchange→reduce barrier
      ROADMAP item 4 wants overlapped away.

    Totals aggregate the per-stage rows; ``device_utilization`` is
    busy/wall over stages that dispatched to the device at all.

    Edge contract: an empty or all-malformed trace yields an empty
    ledger; a stage with zero exchange-tier spans (single-stage plans,
    streaming epoch traces) reports ``barrier_idle_s`` of 0 — never a
    crash, never negative.  Malformed records (non-dict, non-numeric
    timestamps) are skipped, matching HistoryStore.events()."""

    def _ns(v: Any) -> Optional[int]:
        try:
            return int(v)
        except (TypeError, ValueError):
            return None

    by_stage: Dict[int, List[dict]] = {}
    for r in spans:
        if not isinstance(r, dict) or _ns(r.get("t0_ns", 0)) is None:
            continue
        ctx = r.get("ctx") if isinstance(r.get("ctx"), dict) else {}
        attrs = r.get("attrs") if isinstance(r.get("attrs"), dict) else {}
        stage = ctx.get("stage", attrs.get("stage"))
        try:
            stage = int(stage)
        except (TypeError, ValueError):
            stage = -1
        by_stage.setdefault(stage, []).append(r)

    def _t0(r: dict) -> int:
        return _ns(r.get("t0_ns", 0)) or 0

    def _t1(r: dict) -> int:
        v = _ns(r.get("t1_ns"))
        return v if v is not None else _t0(r)

    stages: Dict[str, Dict[str, Any]] = {}
    tot_busy = tot_wall = tot_gap = tot_barrier = 0
    for stage in sorted(by_stage):
        rs = by_stage[stage]
        t0 = min(_t0(r) for r in rs)
        t1 = max(_t1(r) for r in rs)
        device: List[tuple] = []
        for r in rs:
            name = r.get("name")
            if name not in _DEVICE_SPANS:
                continue
            s0 = _t0(r)
            dur = _ns(r.get("dur_ns", 0)) or 0
            if name == "xla_compile" and not dur:  # an instant of old:
                # its wall rides attrs["ns"]
                attrs = (r.get("attrs")
                         if isinstance(r.get("attrs"), dict) else {})
                dur = _ns(attrs.get("ns", 0)) or 0
            device.append((s0, s0 + max(0, dur)))
        busy = _merged_busy_ns(device)
        gap = 0
        if device:
            d0 = min(i[0] for i in device)
            d1 = max(i[1] for i in device)
            gap = max(0, (d1 - d0) - busy)
        barrier = 0
        exchanges = [r for r in rs if r.get("name") in _EXCHANGE_SPANS]
        if exchanges:
            ex0 = min(_t0(r) for r in exchanges)
            pre = [_t1(r) for r in rs
                   if r.get("name") not in _EXCHANGE_SPANS
                   and _t1(r) <= ex0]
            if pre:
                barrier = max(0, ex0 - max(pre))
        wall = max(0, t1 - t0)
        stages[str(stage)] = {
            "wall_s": round(wall / 1e9, 6),
            "device_busy_s": round(busy / 1e9, 6),
            "dispatch_gap_s": round(gap / 1e9, 6),
            "barrier_idle_s": round(barrier / 1e9, 6),
            "device_spans": len(device),
            "spans": len(rs),
        }
        tot_busy += busy
        tot_gap += gap
        tot_barrier += barrier
        if device:
            tot_wall += wall
    return {
        "stages": stages,
        "device_busy_s": round(tot_busy / 1e9, 6),
        "device_wall_s": round(tot_wall / 1e9, 6),
        "dispatch_gap_s": round(tot_gap / 1e9, 6),
        "barrier_idle_s": round(tot_barrier / 1e9, 6),
        "device_utilization": round(tot_busy / tot_wall, 4)
        if tot_wall else 0.0,
    }


# -- replay store ---------------------------------------------------------

def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


def rollup_counter_keys() -> List[str]:
    """Every flat xla_stats counter key the rollup aggregates (the
    `_last` entries are point-in-time gauges, not delta-able counters).
    tests/test_history_conformance.py holds this and prometheus_text()
    to the same family list."""
    from blaze_tpu.bridge import xla_stats
    keys: List[str] = []
    for fam in sorted(xla_stats.counter_families()):
        for k in sorted(xla_stats.counter_families()[fam]):
            if not k.endswith("_last"):
                keys.append(k)
    return keys


class HistoryStore:
    """Replays event logs under `root` (default the live history dir)
    into per-query summaries and fleet rollups.  Pure stdlib + file
    reads: a fresh process (or another host with the directory mounted)
    serves the same answers."""

    def __init__(self, root: Optional[str] = None):
        self.root = root or history_dir()

    # -- raw access ----------------------------------------------------
    def query_ids(self) -> List[str]:
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return []
        return [n[len("query-"):-len(".jsonl")] for n in names
                if n.startswith("query-") and n.endswith(".jsonl")]

    def events(self, query_id: Any) -> List[dict]:
        """Parsed event lines, in file order; torn trailing lines (a
        crash mid-append) are skipped, not fatal."""
        out: List[dict] = []
        try:
            with open(_log_path(query_id, self.root)) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        continue
        except OSError:
            return []
        return out

    # -- replay --------------------------------------------------------
    def summary(self, query_id: Any) -> Optional[dict]:
        """One query's replayed summary (the /history/<qid> payload);
        None when no log exists.  Deterministic over the log bytes."""
        events = self.events(query_id)
        if not events:
            return None
        s: Dict[str, Any] = {
            "schema_version": ROLLUP_SCHEMA_VERSION,
            "query_id": _safe_qid(query_id),
            "tenant": None, "status": "unknown",
            "submitted_ts": None, "finished_ts": None,
            "wall_s": None, "queued_s": None,
            "deadline_ms": None, "mem_quota": None,
            "stages": [], "stage_recoveries": 0,
            "stream": {"epochs": 0, "rows": 0, "records": 0,
                       "replays": 0, "recoveries": 0,
                       "replayed_epochs": 0},
            "metric_tree": None, "attribution": None,
            "device_ledger": None, "bottleneck": None,
            "advisor": None, "fingerprint": None, "error": None,
            "replica": None,
            "events": len(events), "events_dropped": 0,
        }
        for e in events:
            kind = e.get("event")
            if kind == "admitted":
                s["tenant"] = e.get("tenant")
                s["status"] = "queued"
                s["submitted_ts"] = e.get("ts")
                s["deadline_ms"] = e.get("deadline_ms")
                s["mem_quota"] = e.get("mem_quota")
            elif kind == "started":
                s["status"] = "running"
                s["queued_s"] = e.get("queued_s")
            elif kind == "stage_complete":
                s["stages"].append({
                    "stage": e.get("stage"),
                    "exchange": e.get("exchange"),
                    "compute": e.get("compute"),
                    "tasks": e.get("tasks"),
                    "metrics": e.get("metrics") or {}})
            elif kind == "stage_recovery":
                s["stage_recoveries"] += 1
            elif kind == "stream_epoch":
                st = s["stream"]
                st["epochs"] += 1
                st["rows"] += int(e.get("rows", 0))
                st["records"] += int(e.get("records", 0))
                if not e.get("committed", True):
                    st["replays"] += 1
            elif kind == "stream_recovery":
                st = s["stream"]
                st["recoveries"] += 1
                st["replayed_epochs"] += int(e.get("replayed", 0))
            elif kind == "finished":
                s["status"] = e.get("status", "unknown")
                s["tenant"] = e.get("tenant", s["tenant"])
                s["finished_ts"] = e.get("ts")
                s["wall_s"] = e.get("wall_s")
                s["metric_tree"] = e.get("metric_tree")
                s["attribution"] = e.get("attribution")
                s["device_ledger"] = e.get("device_ledger")
                s["bottleneck"] = e.get("bottleneck")
                s["advisor"] = e.get("advisor")
                s["fingerprint"] = e.get("fingerprint")
                s["replica"] = e.get("replica")
                s["error"] = e.get("error")
                s["events_dropped"] = int(e.get("events_dropped", 0))
        return s

    def summaries(self) -> List[dict]:
        """Light listing for /history: terminal fields only, no trees."""
        out = []
        for qid in self.query_ids():
            s = self.summary(qid)
            if s is None:
                continue
            out.append({k: s[k] for k in
                        ("query_id", "tenant", "status", "wall_s",
                         "queued_s", "events", "stage_recoveries")})
        return out

    # -- fleet rollup ----------------------------------------------------
    def rollup(self) -> dict:
        """Fleet aggregate over every replayed query, keyed by tenant
        and stage type (the /history/rollup payload).

        Per tenant: query counts by status, qps over the observed
        submit→finish window, wall p50/p99 ms, device-vs-host lane
        fractions (expression batches through the fused device lane vs
        the eager host evaluator), expr/StageProgram cache-hit rates,
        spill bytes and shuffle bytes by tier.  `counters` sums the
        per-query attribution deltas over every flat xla_stats counter
        key, so each family the engine exposes is represented here."""
        tenants: Dict[str, Dict[str, Any]] = {}
        replicas: Dict[str, Dict[str, Any]] = {}
        by_exchange: Dict[str, Dict[str, int]] = {}
        by_compute: Dict[str, Dict[str, int]] = {}
        counters: Dict[str, float] = {k: 0 for k in rollup_counter_keys()}
        walls: Dict[str, List[float]] = {}
        t_lo: Dict[str, float] = {}
        t_hi: Dict[str, float] = {}
        n_queries = 0
        for qid in self.query_ids():
            s = self.summary(qid)
            if s is None:
                continue
            n_queries += 1
            tenant = s["tenant"] or "unknown"
            t = tenants.setdefault(tenant, {
                "queries": 0, "completed": 0, "failed": 0,
                "cancelled": 0, "qps": 0.0,
                "wall_ms_p50": 0.0, "wall_ms_p99": 0.0,
                "device_lane_fraction": 0.0, "host_lane_fraction": 0.0,
                "expr_cache_hit_rate": 0.0,
                "stage_program_cache_hit_rate": 0.0,
                "result_cache_hit_rate": 0.0,
                "subplan_cache_hit_rate": 0.0,
                "scan_share_hit_rate": 0.0,
                "spill_bytes": 0,
                "shuffle_bytes_by_tier": {"device": 0, "rss": 0,
                                          "file": 0},
                "_fused": 0, "_eager": 0, "_expr_hits": 0,
                "_expr_built": 0, "_sp_hits": 0, "_sp_built": 0,
                "_rc_hits": 0, "_rc_miss": 0, "_spl_hits": 0,
                "_spl_miss": 0, "_ss_hits": 0, "_ss_miss": 0,
            })
            t["queries"] += 1
            status = s["status"]
            if status == "done":
                t["completed"] += 1
            elif status == "failed":
                t["failed"] += 1
            elif status == "cancelled":
                t["cancelled"] += 1
            if s["wall_s"] is not None:
                walls.setdefault(tenant, []).append(float(s["wall_s"]))
            # fleet: per-replica attribution from the stamped terminal
            # events — across a shared history dir these counts sum to
            # the fleet's total submitted queries (the soak's invariant)
            if s.get("replica"):
                r = replicas.setdefault(str(s["replica"]), {
                    "queries": 0, "completed": 0, "failed": 0,
                    "cancelled": 0, "wall_s_total": 0.0})
                r["queries"] += 1
                if status == "done":
                    r["completed"] += 1
                elif status == "failed":
                    r["failed"] += 1
                elif status == "cancelled":
                    r["cancelled"] += 1
                if s["wall_s"] is not None:
                    r["wall_s_total"] = round(
                        r["wall_s_total"] + float(s["wall_s"]), 6)
            for ts_key in ("submitted_ts", "finished_ts"):
                ts = s.get(ts_key)
                if ts is not None:
                    t_lo[tenant] = min(t_lo.get(tenant, ts), ts)
                    t_hi[tenant] = max(t_hi.get(tenant, ts), ts)
            delta = ((s.get("attribution") or {}).get("counters")) or {}
            for k, v in delta.items():
                if k in counters and isinstance(v, (int, float)):
                    counters[k] += v
            t["_fused"] += int(delta.get("expr_fused_batches", 0))
            t["_eager"] += int(delta.get("expr_eager_batches", 0))
            t["_expr_hits"] += int(delta.get("expr_program_cache_hits", 0))
            t["_expr_built"] += int(delta.get("expr_programs_built", 0))
            t["_sp_hits"] += int(
                delta.get("stage_loop_program_cache_hits", 0))
            t["_sp_built"] += int(delta.get("stage_loop_programs_built", 0))
            t["_rc_hits"] += int(delta.get("result_cache_hits", 0))
            t["_rc_miss"] += int(delta.get("result_cache_misses", 0))
            t["_spl_hits"] += int(delta.get("subplan_cache_hits", 0))
            t["_spl_miss"] += int(delta.get("subplan_cache_misses", 0))
            t["_ss_hits"] += int(delta.get("scan_share_hits", 0))
            t["_ss_miss"] += int(delta.get("scan_share_misses", 0))
            attrib = s.get("attribution") or {}
            t["spill_bytes"] += int(attrib.get("spill_bytes", 0) or 0)
            tiers = t["shuffle_bytes_by_tier"]
            by_tier = attrib.get("shuffle_bytes_by_tier")
            if isinstance(by_tier, dict):
                for tier in tiers:
                    tiers[tier] += int(by_tier.get(tier, 0) or 0)
            else:
                tiers["device"] += int(
                    delta.get("shuffle_device_bytes", 0))
                tiers["file"] += int(delta.get("shuffle_host_bytes", 0))
            for st in s["stages"]:
                ex = by_exchange.setdefault(
                    str(st.get("exchange") or "unknown"),
                    {"stages": 0, "tasks": 0, "output_rows": 0})
                ex["stages"] += 1
                ex["tasks"] += int(st.get("tasks") or 0)
                ex["output_rows"] += int(
                    (st.get("metrics") or {}).get("output_rows", 0) or 0)
                cp = by_compute.setdefault(
                    str(st.get("compute") or "unknown"),
                    {"stages": 0, "tasks": 0, "output_rows": 0})
                cp["stages"] += 1
                cp["tasks"] += int(st.get("tasks") or 0)
                cp["output_rows"] += int(
                    (st.get("metrics") or {}).get("output_rows", 0) or 0)
        for tenant, t in tenants.items():
            vals = sorted(walls.get(tenant, []))
            t["wall_ms_p50"] = round(_percentile(vals, 0.50) * 1e3, 3)
            t["wall_ms_p99"] = round(_percentile(vals, 0.99) * 1e3, 3)
            span = t_hi.get(tenant, 0.0) - t_lo.get(tenant, 0.0)
            t["qps"] = round(t["completed"] / span, 4) if span > 0 else 0.0
            fused, eager = t.pop("_fused"), t.pop("_eager")
            if fused + eager:
                t["device_lane_fraction"] = round(
                    fused / (fused + eager), 4)
                t["host_lane_fraction"] = round(
                    eager / (fused + eager), 4)
            eh, eb = t.pop("_expr_hits"), t.pop("_expr_built")
            if eh + eb:
                t["expr_cache_hit_rate"] = round(eh / (eh + eb), 4)
            sh, sb = t.pop("_sp_hits"), t.pop("_sp_built")
            if sh + sb:
                t["stage_program_cache_hit_rate"] = round(
                    sh / (sh + sb), 4)
            for rate_key, hk, mk in (
                    ("result_cache_hit_rate", "_rc_hits", "_rc_miss"),
                    ("subplan_cache_hit_rate", "_spl_hits", "_spl_miss"),
                    ("scan_share_hit_rate", "_ss_hits", "_ss_miss")):
                h, m = t.pop(hk), t.pop(mk)
                if h + m:
                    t[rate_key] = round(h / (h + m), 4)
        return {
            "schema_version": ROLLUP_SCHEMA_VERSION,
            "queries": n_queries,
            "tenants": tenants,
            "replicas": replicas,
            "stages_by_exchange": by_exchange,
            "stages_by_compute": by_compute,
            "counters": counters,
        }

    # -- compaction ------------------------------------------------------
    def compact(self, query_id: Optional[Any] = None) -> int:
        """Rewrite terminal query logs down to their summary-bearing
        events (admission, stage rows, recoveries, the terminal event) —
        streaming epochs dominate long-lived logs and are already folded
        into the terminal counters.  Returns events removed.  Logs
        without a `finished` event are left alone (still being
        written)."""
        qids = [query_id] if query_id is not None else self.query_ids()
        removed = 0
        for qid in qids:
            events = self.events(qid)
            if not events or not any(
                    e.get("event") == "finished" for e in events):
                continue
            kept = [e for e in events
                    if e.get("event") in _KEEP_ON_COMPACT]
            if len(kept) == len(events):
                continue
            path = _log_path(qid, self.root)
            tmp = path + ".compact"
            try:
                with open(tmp, "w") as f:
                    for e in kept:
                        f.write(json.dumps(e, sort_keys=True,
                                           default=str) + "\n")
                os.replace(tmp, path)
                removed += len(events) - len(kept)
            except OSError:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
        return removed

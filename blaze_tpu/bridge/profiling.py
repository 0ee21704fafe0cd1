"""Profiling / observability service.

Parity: the reference's optional native HTTP service (feature
`http-service`, ref auron/src/exec.rs:53-60; poem routes for CPU pprof
flamegraphs auron/src/http/pprof.rs:71 and jemalloc heap profiles
http/memory_profiling.rs:49).

TPU-native equivalents served over a stdlib HTTP endpoint:
  /status         — engine status: memory manager dump, device memory stats
  /metrics        — last collected metric trees (JSON)
  /metrics.prom   — Prometheus text exposition: XLA compile/cache-hit
                    counters per kernel, transfer volume, memory-manager
                    totals, per-operator aggregates
  /profile        — list of recorded query profiles (id + summary)
  /profile/<qid>  — full explain-analyze profile for one query (JSON)
  /query/<qid>/timeline — Chrome-trace-event JSON (Perfetto-loadable)
                    of the query's stitched span trace: one track per
                    worker / device / stream epoch, plus a per-query
                    resource-attribution block
  /trace/start?dir=<path>, /trace/stop — JAX profiler trace (XLA's own
                    profiler is the pprof analog: device + host timelines
                    viewable in TensorBoard/Perfetto)
  /history        — replayed per-query summaries from the persistent
                    event log (bridge/history.py); /history/<qid> is one
                    query's full summary (final status, metric tree,
                    attribution, device ledger), /history/rollup the
                    fleet aggregate keyed by tenant and stage type

The query-profile store is a bounded LRU (auron.tpu.profile.maxEntries;
get_profile touches) so long-lived serving processes don't grow it
without limit; evictions count as obs_profile_evictions.
"""

from __future__ import annotations

import json
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional

_lock = threading.Lock()
_recent_metrics: List[dict] = []
_MAX_METRICS = 64
_profiles: Dict[str, dict] = {}
_profile_order: List[str] = []
_MAX_PROFILES = 64


def record_metrics(tree: dict) -> None:
    """Runtimes push finalize()-time metric trees here (metrics.rs:22)."""
    with _lock:
        _recent_metrics.append(tree)
        del _recent_metrics[:-_MAX_METRICS]


def recent_metrics() -> List[dict]:
    with _lock:
        return list(_recent_metrics)


def _profile_cap() -> int:
    try:
        from blaze_tpu import config
        return max(1, config.PROFILE_STORE_MAX.get())
    except Exception:
        return _MAX_PROFILES


def record_profile(query_id: str, profile: dict) -> None:
    """explain_analyze pushes finished query profiles here, keyed by the
    ui-store query id; served on /profile/<qid>.  The store is an LRU
    bounded by auron.tpu.profile.maxEntries — record and get_profile
    both refresh recency; evictions are counted in xla_stats."""
    cap = _profile_cap()
    evicted = 0
    with _lock:
        if query_id in _profiles:
            _profile_order.remove(query_id)
        _profile_order.append(query_id)
        _profiles[query_id] = profile
        while len(_profile_order) > cap:
            _profiles.pop(_profile_order.pop(0), None)
            evicted += 1
    if evicted:
        from blaze_tpu.bridge import xla_stats
        xla_stats.note_obs(profile_evictions=evicted)


def get_profile(query_id: str) -> Optional[dict]:
    with _lock:
        p = _profiles.get(query_id)
        if p is not None:  # LRU touch
            _profile_order.remove(query_id)
            _profile_order.append(query_id)
        return p


def list_profiles() -> List[dict]:
    with _lock:
        return [{"query_id": q,
                 "wall_ns": _profiles[q].get("wall_ns"),
                 "output_rows": (_profiles[q].get("tree") or {})
                 .get("values", {}).get("output_rows")}
                for q in _profile_order]


def _prom_escape(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"')


def prometheus_text() -> str:
    """Prometheus text exposition (version 0.0.4) of the engine gauges:
    XLA compile accounting, host<->device transfer volume, memory-manager
    spill totals, and per-operator aggregates over the recent trees."""
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.memory import MemManager
    lines: List[str] = []
    # per-SCRAPE header dedup — a default-arg set here persisted across
    # calls, so every scrape after the first silently dropped all
    # HELP/TYPE headers (tests/test_metric_conformance.py pins this)
    seen: set = set()

    def emit(name, value, help_=None, labels=None):
        if help_ and name not in seen:
            seen.add(name)
            # *_total families are monotone counters, everything else a
            # point-in-time gauge — Prometheus rate() needs the former
            kind = "counter" if name.endswith("_total") else "gauge"
            lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} {kind}")
        lab = ""
        if labels:
            lab = "{" + ",".join(
                f'{k}="{_prom_escape(str(v))}"'
                for k, v in sorted(labels.items())) + "}"
        lines.append(f"{name}{lab} {int(value)}")

    rep = xla_stats.compile_report()
    for kname, e in rep["kernels"].items():
        lab = {"kernel": kname}
        emit("blaze_xla_compiles_total", e["compiles"],
             "XLA compilations per kernel signature", lab)
        emit("blaze_xla_cache_hits_total", e["cache_hits"],
             "jit dispatches served from the compile cache", lab)
        emit("blaze_xla_compile_ns_total", e["compile_ns"],
             "nanoseconds spent compiling", lab)
        emit("blaze_xla_distinct_signatures", e["distinct_signatures"],
             "distinct arg signatures seen (churn when high)", lab)
    # every flat counter plane, from the one shared family registry (the
    # history rollup iterates the same source, so the two surfaces
    # cannot drift apart); *_last keys are point-in-time gauges
    fam_help = {
        "transfers": "host<->device transfer",
        "pipeline": "batch-shaping / IO-pipeline",
        "exprs": "whole-stage expression program",
        "faults": "fault-tolerance (retries, lineage recovery)",
        "shuffle": "exchange transport",
        "stage_loop": "device-resident stage loop",
        "agg": "adaptive partial aggregation",
        "stream": "streaming runtime",
        "workers": "worker pool supervision",
        "speculation": "speculative execution",
        "obs": "observability plane",
        "cache": "cross-query work sharing",
        "stats": "statistics feedback plane",
        "fleet": "replicated serving fleet",
        "backend": "jax backend compile",
    }
    families = xla_stats.counter_families()
    for fam in sorted(families):
        label = fam_help.get(fam, fam)
        for k in sorted(families[fam]):
            v = families[fam][k]
            if k.endswith("_last"):
                emit(f"blaze_{k[:-5]}", v, f"{label} gauge")
            else:
                emit(f"blaze_{k}_total", v, f"{label} counter")

    def emit_histogram(name, hist, help_, labels=None):
        # real Prometheus histogram exposition (cumulative le buckets +
        # _sum/_count), not the gauge families above
        lab_items = sorted((labels or {}).items())

        def fmt(extra):
            items = lab_items + sorted(extra.items())
            if not items:
                return ""
            return "{" + ",".join(
                f'{k}="{_prom_escape(str(v))}"' for k, v in items) + "}"

        if name not in seen:
            seen.add(name)
            lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} histogram")
        for le, count in hist["buckets"]:
            lines.append(f"{name}_bucket{fmt({'le': le})} {count}")
        lines.append(f"{name}_bucket{fmt({'le': '+Inf'})} {hist['count']}")
        lines.append(f"{name}_sum{fmt({})} {hist['sum']:.6f}")
        lines.append(f"{name}_count{fmt({})} {hist['count']}")

    hists = xla_stats.latency_histograms()
    emit_histogram("blaze_task_duration_seconds",
                   hists["task_duration_seconds"],
                   "successful task-attempt wall time")
    emit_histogram("blaze_wave_wall_seconds", hists["wave_wall_seconds"],
                   "run_tasks wave wall, submit to last result")
    try:
        from blaze_tpu.serving.service import tenant_wall_samples
        for tenant, samples in sorted(tenant_wall_samples().items()):
            emit_histogram(
                "blaze_tenant_query_wall_seconds",
                xla_stats._histogram([int(s * 1e9) for s in samples]),
                "per-tenant completed-query wall time (attribution)",
                {"tenant": tenant})
    except Exception:
        pass  # serving layer not in use
    mm = MemManager.get()
    emit("blaze_mem_spill_count_total", mm.total_spill_count,
         "memory-manager spills")
    emit("blaze_mem_spilled_bytes_total", mm.total_spilled_bytes,
         "bytes released by spills")
    emit("blaze_mem_peak_used_bytes", mm.peak_used,
         "peak retained bytes across consumers")

    per_op: Dict[str, Dict[str, int]] = {}

    def fold(node):
        op = node.get("name") or "unknown"
        agg = per_op.setdefault(op, {})
        for k, v in node.get("values", {}).items():
            agg[k] = agg.get(k, 0) + int(v)
        for c in node.get("children", ()):
            fold(c)

    with _lock:
        for tree in _recent_metrics:
            fold(tree)
    for op, vals in sorted(per_op.items()):
        for metric in ("output_rows", "output_batches",
                       "elapsed_compute_ns", "spilled_bytes", "io_bytes"):
            if metric in vals:
                emit(f"blaze_operator_{metric}_total", vals[metric],
                     f"per-operator {metric} over recent metric trees",
                     {"operator": op})
    return "\n".join(lines) + "\n"


def query_timeline(query_id: str) -> Optional[dict]:
    """Chrome-trace-event JSON for one query's stitched span trace.

    Loads directly in Perfetto / chrome://tracing: a top-level object
    with `traceEvents` (complete "X" events for spans, instant "i"
    events for markers), one process track per origin (driver, each
    worker slot) and dedicated tracks for device dispatches and each
    stream epoch.  A per-query resource-attribution block (task CPU
    seconds, shuffle bytes by tier, device dispatches, spill bytes,
    speculation hedge cost) rides as a top-level key — extra keys are
    legal in the trace-event object format.  Returns None when no spans
    name the query."""
    from blaze_tpu.bridge import tracing
    spans = tracing.spans_for_query(query_id)
    if not spans:
        return None

    _DRIVER_PID, _WORKER_PID0 = 1, 100
    events: List[dict] = []
    tids: Dict[tuple, int] = {}
    procs: Dict[int, str] = {_DRIVER_PID: "driver"}

    def tid_for(pid, key, label):
        t = tids.get((pid, key))
        if t is None:
            t = tids[(pid, key)] = len(tids) + 1
            events.append({"ph": "M", "pid": pid, "tid": t,
                           "name": "thread_name",
                           "args": {"name": label}})
        return t

    attribution = {"task_cpu_seconds": 0.0, "worker_task_seconds": 0.0,
                   "device_dispatches": 0,
                   "spill_bytes": 0, "speculation_attempts": 0,
                   "speculation_hedge_seconds": 0.0,
                   "shuffle_bytes_by_tier": {"device": 0, "rss": 0,
                                             "file": 0}, "span_count": 0}
    profile = get_profile(query_id)
    if profile:
        x = profile.get("xla") or {}
        attribution["shuffle_bytes_by_tier"]["device"] = int(
            x.get("shuffle_device_bytes", 0))
        attribution["shuffle_bytes_by_tier"]["file"] = int(
            x.get("shuffle_host_bytes", 0))

    for r in spans:
        name = r.get("name", "?")
        attrs = r.get("attrs") or {}
        ctx = r.get("ctx") or {}
        worker = r.get("worker")
        if worker is not None:
            try:
                pid = _WORKER_PID0 + int(worker)
            except (TypeError, ValueError):
                pid = _WORKER_PID0 + (hash(str(worker)) % 97)
            procs.setdefault(pid, f"worker-{worker}")
            tid = tid_for(pid, r.get("thread", "main"),
                          str(r.get("thread", "main")))
        elif name in ("device_exchange", "stage_loop_chunk",
                      "xla_compile"):
            pid = _DRIVER_PID
            tid = tid_for(pid, "device", "device")
        elif name in ("stream_epoch", "stream_recovery"):
            pid = _DRIVER_PID
            ep = attrs.get("epoch", ctx.get("epoch", 0)) or 0
            tid = tid_for(pid, ("epoch", ep), f"epoch-{ep}")
        else:
            pid = _DRIVER_PID
            tid = tid_for(pid, r.get("thread", "main"),
                          str(r.get("thread", "main")))
        args = dict(ctx)
        args.update(attrs)
        if "sid" in r:
            args["sid"] = r["sid"]
        if "parent" in r:
            args["parent"] = r["parent"]
        ev = {"name": name, "pid": pid, "tid": tid,
              "ts": r.get("t0_ns", 0) / 1e3, "args": args}
        if r.get("dur_ns", 0) > 0:
            ev["ph"] = "X"
            ev["dur"] = r["dur_ns"] / 1e3
        else:
            ev["ph"] = "i"
            ev["s"] = "t"
        events.append(ev)

        attribution["span_count"] += 1
        dur_s = r.get("dur_ns", 0) / 1e9
        if name == "task_attempt":
            # driver-side attempt wall; child-process execution is the
            # separate worker_task_seconds (summing both double-counts)
            attribution["task_cpu_seconds"] += dur_s
            if attrs.get("speculative"):
                attribution["speculation_hedge_seconds"] += dur_s
        elif name == "worker_task":
            attribution["worker_task_seconds"] += dur_s
        elif name in ("device_exchange", "stage_loop_chunk"):
            attribution["device_dispatches"] += 1
        elif name == "mem_spill":
            attribution["spill_bytes"] += int(attrs.get("bytes", 0) or 0)
        elif name == "speculation_attempt":
            attribution["speculation_attempts"] += 1
        elif name == "rss_exchange":
            attribution["shuffle_bytes_by_tier"]["rss"] += int(
                attrs.get("nbytes", 0) or 0)

    for pid, pname in procs.items():
        events.append({"ph": "M", "pid": pid, "tid": 0,
                       "name": "process_name", "args": {"name": pname}})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "query_id": str(query_id), "attribution": attribution}


def engine_status() -> dict:
    from blaze_tpu.memory import MemManager
    import jax
    status = {"mem_manager": MemManager.get().dump_status()}
    try:
        stats = jax.devices()[0].memory_stats() or {}
        status["device_memory"] = {k: v for k, v in stats.items()
                                   if isinstance(v, (int, float))}
    except Exception:
        status["device_memory"] = {}
    return status


#: every GET route the service answers, placeholders included; the 404
#: payload and the HTTP conformance sweep
#: (tests/test_http_conformance.py) both read this — a handler branch
#: without a row here, or vice versa, fails the sweep.
ROUTES = (
    "/status", "/metrics", "/metrics.prom",
    "/profile", "/profile/<qid>",
    "/query/<qid>/timeline", "/query/<qid>/bottleneck",
    "/query/<qid>/progress",
    "/auron", "/auron.html",
    "/trace/start", "/trace/stop",
    "/history", "/history/<qid>", "/history/rollup",
    "/stats", "/stats/<fingerprint>",
    "/progress",
    "/serving", "/serving/cancel",
    "/fleet",
)


class _Handler(BaseHTTPRequestHandler):
    _tracing = False

    def log_message(self, *args):
        pass

    def _send(self, code: int, body: str,
              ctype: str = "application/json"):
        data = body.encode()
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        parsed = urllib.parse.urlsplit(self.path)
        route = parsed.path
        if route == "/auron":
            from blaze_tpu.bridge import ui
            self._send(200, json.dumps(
                {"executions": ui.executions(),
                 "fallback_summary": ui.fallback_summary()}))
        elif route == "/auron.html":
            from blaze_tpu.bridge import ui
            self._send(200, ui.executions_html(), ctype="text/html")
        elif route == "/status":
            self._send(200, json.dumps(engine_status()))
        elif route == "/metrics":
            with _lock:
                self._send(200, json.dumps(_recent_metrics))
        elif route == "/metrics.prom":
            self._send(200, prometheus_text(),
                       ctype="text/plain; version=0.0.4")
        elif route == "/profile":
            self._send(200, json.dumps(list_profiles()))
        elif route.startswith("/profile/"):
            qid = urllib.parse.unquote(route[len("/profile/"):])
            profile = get_profile(qid)
            if profile is None:
                self._send(404, json.dumps(
                    {"error": f"no profile for {qid!r}",
                     "known": [p["query_id"] for p in list_profiles()]}))
            else:
                self._send(200, json.dumps(profile))
        elif route.startswith("/query/") and route.endswith("/timeline"):
            qid = urllib.parse.unquote(
                route[len("/query/"):-len("/timeline")])
            timeline = query_timeline(qid)
            if timeline is None:
                self._send(404, json.dumps(
                    {"error": f"no spans recorded for query {qid!r} "
                              f"(is tracing enabled?)"}))
            else:
                self._send(200, json.dumps(timeline, default=str))
        elif route.startswith("/query/") and route.endswith("/bottleneck"):
            from blaze_tpu.bridge import critical_path, tracing
            qid = urllib.parse.unquote(
                route[len("/query/"):-len("/bottleneck")])
            report = None
            spans = tracing.spans_for_query(qid)
            if spans:
                report = critical_path.bottleneck_report(spans)
            if report is None:
                # the live buffer may have rotated; the history finished
                # event keeps the report alongside the device ledger
                from blaze_tpu.bridge.history import HistoryStore
                summary = HistoryStore().summary(qid)
                if summary:
                    report = summary.get("bottleneck")
            if report is None:
                self._send(404, json.dumps(
                    {"error": f"no bottleneck report for query {qid!r} "
                              f"(is tracing or history enabled?)"}))
            else:
                self._send(200, json.dumps(report, sort_keys=True))
        elif route.startswith("/query/") and route.endswith("/progress"):
            from blaze_tpu.serving import progress as progress_mod
            qid = urllib.parse.unquote(
                route[len("/query/"):-len("/progress")])
            p = progress_mod.progress(qid)
            if p is None:
                self._send(404, json.dumps(
                    {"error": f"no progress for query {qid!r} "
                              f"(is auron.tpu.stats.enable on?)",
                     "live": progress_mod.live()}))
            else:
                self._send(200, json.dumps(p, sort_keys=True))
        elif route == "/progress":
            from blaze_tpu.serving import progress as progress_mod
            self._send(200, json.dumps(progress_mod.snapshot_all(),
                                       sort_keys=True))
        elif route == "/stats":
            from blaze_tpu.plan.statstore import StatStore
            self._send(200, json.dumps(StatStore().summary(),
                                       sort_keys=True))
        elif route.startswith("/stats/"):
            from blaze_tpu.plan.statstore import StatStore
            fp = urllib.parse.unquote(route[len("/stats/"):])
            store = StatStore()
            rec = store.record(fp)
            if rec is None:
                self._send(404, json.dumps(
                    {"error": f"no statistics for fingerprint {fp!r}",
                     "known": store.fingerprints()}))
            else:
                self._send(200, json.dumps(rec, sort_keys=True))
        elif route == "/trace/start":
            import jax
            # the trace dir arrives as ?dir=<path> (query STRING, not the
            # raw text after '?' — that produced directories literally
            # named "dir=/tmp/x")
            # keep_blank_values so a stray "?/tmp/x" (no '=') surfaces as
            # an unknown key instead of silently starting a default trace
            params = urllib.parse.parse_qs(parsed.query,
                                           keep_blank_values=True)
            out = params.get("dir", ["/tmp/blaze-tpu-trace"])[0]
            bad_keys = set(params) - {"dir"}
            if bad_keys:
                self._send(400, json.dumps(
                    {"error": f"unknown query params {sorted(bad_keys)}; "
                              f"expected ?dir=<path>"}))
                return
            if not out or "\x00" in out or not out.startswith("/"):
                self._send(400, json.dumps(
                    {"error": "trace dir must be an absolute path",
                     "dir": out}))
                return
            try:
                jax.profiler.start_trace(out)
                _Handler._tracing = True
                self._send(200, json.dumps({"tracing": True, "dir": out}))
            except Exception as e:
                self._send(500, json.dumps({"error": str(e)}))
        elif route == "/trace/stop":
            import jax
            try:
                jax.profiler.stop_trace()
                _Handler._tracing = False
                self._send(200, json.dumps({"tracing": False}))
            except Exception as e:
                self._send(500, json.dumps({"error": str(e)}))
        elif route == "/history":
            from blaze_tpu.bridge.history import HistoryStore
            self._send(200, json.dumps(HistoryStore().summaries(),
                                       sort_keys=True))
        elif route == "/history/rollup":
            from blaze_tpu.bridge.history import HistoryStore
            self._send(200, json.dumps(HistoryStore().rollup(),
                                       sort_keys=True))
        elif route.startswith("/history/"):
            from blaze_tpu.bridge.history import HistoryStore
            qid = urllib.parse.unquote(route[len("/history/"):])
            store = HistoryStore()
            summary = store.summary(qid)
            if summary is None:
                self._send(404, json.dumps(
                    {"error": f"no history for query {qid!r} "
                              f"(is auron.tpu.history.enable on?)",
                     "known": store.query_ids()}))
            else:
                self._send(200, json.dumps(summary, sort_keys=True))
        elif route == "/serving":
            from blaze_tpu.parallel.workers import pool_health
            from blaze_tpu.serving import serving_stats
            self._send(200, json.dumps({"services": serving_stats(),
                                        "workers": pool_health()}))
        elif route == "/fleet":
            # fleet health: every live router's replica table (state,
            # heartbeat age, affinity hit-rate) + the fleet counter
            # family.  Empty-but-200 when no fleet is running, so the
            # conformance sweep and dashboards can always scrape it.
            from blaze_tpu.fleet.router import fleet_health
            self._send(200, json.dumps(fleet_health(), sort_keys=True,
                                       default=str))
        elif route == "/serving/cancel":
            from blaze_tpu.serving import cancel_query
            params = urllib.parse.parse_qs(parsed.query,
                                           keep_blank_values=True)
            qid = params.get("qid", [""])[0]
            if not qid:
                self._send(400, json.dumps(
                    {"error": "expected ?qid=<query id>"}))
                return
            self._send(200, json.dumps({"query_id": qid,
                                        "cancelled": cancel_query(qid)}))
        else:
            self._send(404, json.dumps({"error": "unknown path",
                                        "paths": list(ROUTES)}))


_server: Optional[ThreadingHTTPServer] = None


def start_http_service(port: int = 0) -> int:
    """Start the service; returns the bound port (0 picks a free one)."""
    global _server
    if _server is not None:
        return _server.server_address[1]
    _server = ThreadingHTTPServer(("127.0.0.1", port), _Handler)
    t = threading.Thread(target=_server.serve_forever, daemon=True,
                         name="blaze-http-service")
    t.start()
    return _server.server_address[1]


def stop_http_service() -> None:
    global _server
    if _server is not None:
        _server.shutdown()
        _server = None

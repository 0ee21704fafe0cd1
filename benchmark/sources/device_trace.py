"""Source `device_trace`: the reduction from a `jax.profiler` trace to
metrics.

`extract()` turns the profiler's `.xplane.pb` into a small dict (the
recorded trace under `tests/data/` is one): per device the merged busy
intervals and the program executions by name, and the harness's own
annotations from the host plane.  `reduce()` needs nothing but that dict
and the program's spans, so it is tested without a chip.

All times in the dict are nanoseconds on the profiler's clock.  The
program's spans are on `time.perf_counter_ns`; every `bench_query`
annotation was opened at a recorded perf_counter reading, and the median
difference brings the spans over.

spec["read"]:
  {"stat": "idle_share"}                        100 * (1 - busy / window)
  {"stat": "idle_share", "less": [categories]}  the same with those idle
                                                gaps out of both terms
  {"stat": "program_seconds", "pattern": regex, "den": "queries" | null}
  {"stat": "gap_seconds", "categories": [...], "den": "queries" | null}
  {"stat": "fold_roofline", "pattern": regex}   least bytes / time / peak:
      kernel_costs.fold_min_bytes of the query file's `fold_work(tables)`
      (ctx["fold_work"], computed by the harness beside the oracle: the
      SQL's aggregations, never a counter of the program) times the
      queries of the window, over the matching programs' device time
      SUMMED over the chips, over one chip's HBM peak
"""

from __future__ import annotations

import json
import os
import re
import statistics
from typing import Dict, List, Tuple

from benchmark import kernel_costs

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION = re.compile(r"^bench_")
_HERE = os.path.dirname(os.path.abspath(__file__))


def merge(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def program_name(event_name: str) -> str:
    """`jit_fold_impl(1234567)` -> `jit_fold_impl`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def extract(xplane_path: str) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices: Dict[str, dict] = {}
    annotations = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            mods = [[program_name(e.name), e.start_ns, e.duration_ns]
                    for e in (lines[MODULES_LINE].events
                              if MODULES_LINE in lines else [])]
            busy_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
            busy = merge([(e.start_ns, e.start_ns + e.duration_ns)
                          for e in (busy_line.events if busy_line else [])])
            devices[plane.name] = {"lines": sorted(lines),
                                   "busy": busy, "programs": mods}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if ANNOTATION.match(e.name):
                        annotations.append(
                            [e.name, e.start_ns, e.duration_ns])
    return {"devices": devices, "annotations": sorted(
        annotations, key=lambda a: a[1])}


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def _covers(sorted_spans, t) -> bool:
    return any(s <= t < e for s, e in sorted_spans)


def reduce(events: dict, spans: List[dict], query_starts_ns: List[int],
           categories: dict = None) -> dict:
    """busy_s / window_s averaged over devices, program seconds by name,
    idle-gap seconds by category.  The window runs from the first
    `bench_query` annotation's start to the last one's end."""
    if categories is None:
        with open(os.path.join(_HERE, "gap_categories.json")) as f:
            categories = json.load(f)
    queries = [a for a in events["annotations"] if a[0] == "bench_query"]
    if not queries or not events["devices"]:
        return {}
    lo = min(a[1] for a in queries)
    hi = max(a[1] + a[2] for a in queries)
    window_s = (hi - lo) / 1e9
    # perf_counter_ns -> profiler clock
    offsets = [a[1] - t0 for a, t0 in zip(queries, query_starts_ns)]
    offset = statistics.median(offsets) if offsets else 0
    by_name: Dict[str, list] = {"bench_query": [[a[1], a[1] + a[2]]
                                                for a in queries]}
    for s in spans:
        if s["dur_ns"] > 0:
            by_name.setdefault(s["name"], []).append(
                [s["t0_ns"] + offset, s["t1_ns"] + offset])
    cats = [(c["category"],
             merge([iv for n in c["spans"] for iv in by_name.get(n, [])]))
            for c in categories["categories"]]

    busy_s, programs, gaps = [], {}, {}
    for dev in events["devices"].values():
        busy = _clip(dev["busy"], lo, hi)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        for name, start, dur in dev["programs"]:
            if start + dur > lo and start < hi:
                programs[name] = programs.get(name, 0.0) + dur / 1e9
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            cat = next((c for c, ivs in cats if _covers(ivs, mid)),
                       categories["otherwise"])
            gaps[cat] = gaps.get(cat, 0.0) + (g1 - g0) / 1e9
    n = len(busy_s)
    return {"window_s": window_s, "busy_s": sum(busy_s) / n,
            "programs": {k: v / n for k, v in programs.items()},
            "gaps": {k: v / n for k, v in gaps.items()},
            "devices": n}


def breakdown(summary: dict, top: int = 10) -> dict:
    def rank(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(summary.get("programs", {})),
            "idle_gaps": rank(summary.get("gaps", {}))}


def read(spec: dict, ctx: dict):
    t = ctx.get("trace") or {}
    if not t:
        return None
    r = spec["read"]
    per = ctx["queries"] if r.get("den") == "queries" else 1
    if r["stat"] == "idle_share":
        less = sum(t["gaps"].get(c, 0.0) for c in r.get("less", []))
        return 100.0 * (1.0 - t["busy_s"] / (t["window_s"] - less))
    if r["stat"] == "gap_seconds":
        return sum(t["gaps"].get(c, 0.0) for c in r["categories"]) / per
    pat = re.compile(r["pattern"])
    secs = sum(v for k, v in t["programs"].items() if pat.search(k))
    if not secs:
        return None
    if r["stat"] == "program_seconds":
        return secs / per
    if r["stat"] == "fold_roofline":
        q, work = ctx["query"], ctx.get("fold_work")
        if not work:
            return None
        least = ctx["queries"] * kernel_costs.fold_min_bytes(
            work, q.FOLD_ROW_BYTES, q.FOLD_SLOT_BYTES)
        # `programs` holds the mean over the chips: the sum is what the
        # chips spent, each on its share of the bytes
        return 100.0 * least / (secs * t["devices"]) \
            / ctx["peaks"]["hbm_bytes_per_s"]
    raise ValueError(f"unknown device_trace stat {r['stat']!r}")

"""Source `chips`: what each chip of a cell did, where `device_trace`
keeps only the mean over the chips.

It reads what `run.py` wrote beside the trace
(`<root>/.bench_work/<cell>.trace/trace_events.json`, found as `span_gap`
finds it): per device plane the merged busy intervals and the program
executions, and the harness's `bench_query` annotations, which bound the
window and bring the program's spans onto the profiler's clock.  Without
a device plane (the CPU) there is nothing to read.

spec["read"]:
  {"stat": "busy_s", "pick": "min" | "max", "den": "queries" | null}
      busy seconds inside the window of the least or the most busy chip
  {"stat": "all_idle_s", "spans": [names], "den": ...}
      seconds in which EVERY chip is idle while one of the program's
      spans of these names is open
  {"stat": "busy_balance"}
      busy seconds of the least busy chip over the most busy chip's, in %
  {"stat": "exchange_roofline", "pattern": regex}
      kernel_costs_x4.exchange_min_bytes (of the program's counter
      `shuffle_device_row_bytes`) over the matching programs' device time
      summed over the chips, over one chip's HBM peak: every chip's share
      of the bytes against its own time, so never more than one chip's
      peak
A program without the spans, counters or programs named has nothing to
read, and the result line leaves the metric out.
"""

from __future__ import annotations

import os
import re
import statistics

from benchmark import kernel_costs_x4
from benchmark.sources import device_trace, span_gap

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(_HERE))
_KEY = "chips_record"   # the trace's record, kept in ctx: one per run


def _record(ctx: dict, root: str):
    if _KEY not in ctx:
        rec = span_gap.newest_trace_events(root)
        stale = rec is None or \
            len(rec["query_starts_ns"]) != ctx["queries"]
        ctx[_KEY] = None if stale else rec
    return ctx[_KEY]


def window(rec: dict):
    """(lo, hi, offset): the traced window on the profiler's clock and
    what brings a perf_counter_ns reading onto it; None without queries."""
    queries = [a for a in rec["events"]["annotations"]
               if a[0] == "bench_query"]
    if not queries or not rec["events"]["devices"]:
        return None
    offsets = [a[1] - t0 for a, t0 in zip(queries, rec["query_starts_ns"])]
    return (min(a[1] for a in queries), max(a[1] + a[2] for a in queries),
            statistics.median(offsets) if offsets else 0)


def busy_by_chip(rec: dict) -> dict:
    """device plane -> busy seconds inside the window."""
    lo, hi, _ = window(rec)
    return {name: sum(e - s for s, e in
                      device_trace._clip(dev["busy"], lo, hi)) / 1e9
            for name, dev in sorted(rec["events"]["devices"].items())}


def all_idle_seconds(rec: dict, spans, names) -> float:
    """Seconds of the window in which no chip runs anything and a span
    of `names` is open."""
    lo, hi, offset = window(rec)
    busy = device_trace.merge(
        [tuple(iv) for dev in rec["events"]["devices"].values()
         for iv in device_trace._clip(dev["busy"], lo, hi)])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    idle = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]
    held = device_trace.merge(
        [(s["t0_ns"] + offset, s["t1_ns"] + offset) for s in spans
         if s["name"] in names and s["dur_ns"] > 0])
    return sum(max(0, min(g1, h1) - max(g0, h0))
               for g0, g1 in idle for h0, h1 in held) / 1e9


def read(spec: dict, ctx: dict, root: str = ROOT):
    rec = _record(ctx, root)
    if not rec or window(rec) is None:
        return None
    r = spec["read"]
    per = ctx["queries"] if r.get("den") == "queries" else 1
    if r["stat"] == "busy_s":
        return {"min": min, "max": max}[r["pick"]](
            busy_by_chip(rec).values()) / per
    if r["stat"] == "busy_balance":
        busy = busy_by_chip(rec).values()
        return 100.0 * min(busy) / max(busy) if max(busy) else None
    if r["stat"] == "all_idle_s":
        if not any(s["name"] in r["spans"] for s in ctx["spans"]):
            return None
        return all_idle_seconds(rec, ctx["spans"], r["spans"]) / per
    if r["stat"] == "exchange_roofline":
        lo, hi, _ = window(rec)
        pat = re.compile(r["pattern"])
        secs = sum(dur for dev in rec["events"]["devices"].values()
                   for name, start, dur in dev["programs"]
                   if pat.search(name) and start + dur > lo
                   and start < hi) / 1e9
        row_bytes = ctx["counters"].get("shuffle_device_row_bytes", 0)
        if not secs or not row_bytes:
            return None
        least = kernel_costs_x4.exchange_min_bytes(row_bytes)
        return 100.0 * least / secs / ctx["peaks"]["hbm_bytes_per_s"]
    raise ValueError(f"unknown chips stat {r['stat']!r}")

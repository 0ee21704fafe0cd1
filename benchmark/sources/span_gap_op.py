"""Source `span_gap_op`: the device's idle seconds inside tasks by the
operator that held the thread, and by single spans.

`span_gap` cuts these seconds by the kind of boundary (`d2h`, `h2d`,
`prefetch_wait`, ...); this is the other cut, by who asked.  The program
emits one `op:<operator class>` span a pull of every operator, real
intervals that nest child inside parent on a thread (`tid`), so at any
instant the innermost open one is the operator whose own code runs.  The
trace file, what makes it stale and the spans dropped before the reduction
are `span_gap`'s (`newest_trace_events`, `task_thread_spans`).

For each device plane, each idle gap whose midpoint lies in a `task` (or
`stage_loop_chunk`) span, i.e. what `breakdown` calls `in_task` +
`stage_loop_chunk`:

  * every thread is asked for its innermost open span at the midpoint
    (spans nest by `tid`) and walks outward by `parent`; a thread speaks
    for a task if the walk reaches a `task` span open at the midpoint: its
    own, or the one a `PrefetchIterator` worker adopted;
  * a thread inside a `prefetch_wait` for a pipeline stage is silent, the
    stage's thread speaks for it; where a task has no other speaker the
    silent thread speaks itself (the stage thread is between two items);
  * a speaker's family is that of the first span of its walk that
    `op_families.json` gives one;
  * the gap's seconds are split equally over the speakers, so the
    families sum to the gaps.
Then the mean over the device planes, as `device_trace.reduce` takes it.

spec["read"]: {"stat": "family", "families": [...]}     summed
              {"stat": "span_idle", "spans": [...]}      the same shares,
                  by the first span of the walk that is no boundary span
              {"stat": "gc_pause"}    whole gaps whose midpoint lies in a
                  `gc_pause` span of any thread, inside a task or not
              "den": "queries" | null
A program that emitted no `op:*` span (the parent) reads nothing; one that
did reads 0.0 for a family without a gap.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics

from benchmark.sources import device_trace, span_gap

_HERE = os.path.dirname(os.path.abspath(__file__))
_KEY = "span_gap_op_summary"   # the reduction, kept in ctx: one per run
_WALK_MAX = 64                 # a walk by `parent` ends, whatever it reads
TASK, OP_PREFIX = "task", "op:"
OTHER, NO_OP = "other", "no_op"   # the two families the table cannot list


def load_table() -> dict:
    with open(os.path.join(_HERE, "op_families.json")) as f:
        return json.load(f)


def _thread(s):
    return s.get("tid", s.get("thread"))


class _Timeline:
    """The innermost open span of one thread at any instant."""

    def __init__(self, spans):
        self.times, self.inner = [], []
        stack = []

        def mark(t):
            top = stack[-1] if stack else None
            if self.times and self.times[-1] == t:
                self.inner[-1] = top
            else:
                self.times.append(t)
                self.inner.append(top)

        def expire(upto):
            while stack:
                first = min(stack, key=lambda s: s["t1_ns"])
                if first["t1_ns"] > upto:
                    return
                stack.remove(first)
                mark(first["t1_ns"])

        for s in sorted(spans, key=lambda s: (s["t0_ns"], -s["t1_ns"])):
            expire(s["t0_ns"])
            stack.append(s)
            mark(s["t0_ns"])
        expire(float("inf"))

    def at(self, t):
        i = bisect.bisect_right(self.times, t) - 1
        return self.inner[i] if i >= 0 else None


def _timelines(spans):
    by_thread = {}
    for s in spans:
        by_thread.setdefault(_thread(s), []).append(s)
    return {k: _Timeline(v) for k, v in by_thread.items()}


def _walk(span, by_sid):
    for _ in range(_WALK_MAX):
        if span is None:
            return
        yield span
        span = by_sid.get(span.get("parent"))


def _covered(merged, t) -> bool:
    i = bisect.bisect_right(merged, [t, float("inf")]) - 1
    return i >= 0 and merged[i][0] <= t < merged[i][1]


def summarize(rec: dict, spans, table: dict) -> dict:
    events = rec["events"]
    queries = [a for a in events["annotations"] if a[0] == "bench_query"]
    if not queries or not events["devices"]:
        return {}
    lo = min(a[1] for a in queries)
    hi = max(a[1] + a[2] for a in queries)
    # perf_counter_ns -> profiler clock, as device_trace.reduce takes it
    offset = statistics.median(
        a[1] - t0 for a, t0 in zip(queries, rec["query_starts_ns"]))

    real = [s for s in spans if s["dur_ns"] > 0]
    kept = span_gap.task_thread_spans(real)
    kept_ids = {id(s) for s in kept}
    # dropped and a prefetch_wait: a wait for a pipeline stage
    waits = [s for s in real if s["name"] == "prefetch_wait"
             and id(s) not in kept_ids]
    by_sid = {s["sid"]: s for s in real if "sid" in s}
    open_spans, silences = _timelines(kept), _timelines(waits)
    boundary = set(table["boundary"])
    family_of = {n: f for f, names in table["families"].items()
                 for n in names}

    def merged(names):
        return device_trace.merge([(s["t0_ns"], s["t1_ns"]) for s in real
                                  if s["name"] in names])
    under_task = merged({TASK, "stage_loop_chunk"})
    in_gc = merged({"gc_pause"})

    def speakers(t):
        """[(family, first span that is no boundary)] of the threads that
        speak at `t`."""
        by_task = {}
        for key, line in open_spans.items():
            inner = line.at(t)
            if inner is None:
                continue
            family = idle = owner = None
            for s in _walk(inner, by_sid):
                name = s["name"]
                if name == TASK:
                    if s["t0_ns"] <= t < s["t1_ns"]:
                        owner = s
                    break
                if idle is None and name not in boundary:
                    idle = name
                if family is None:
                    family = family_of.get(name) or (
                        OTHER if name.startswith(OP_PREFIX) else None)
            if owner is None:
                continue
            silent = key in silences and silences[key].at(t) is not None
            by_task.setdefault(id(owner), []).append(
                (silent, family or NO_OP, idle or TASK))
        out = []
        for said in by_task.values():
            loud = [x for x in said if not x[0]]
            out.extend(x[1:] for x in (loud or said))
        return out

    families = dict.fromkeys(list(table["families"]) + [OTHER, NO_OP], 0.0)
    span_idle, gc_s, total = {}, 0.0, 0.0
    for dev in events["devices"].values():
        busy = [[max(s, lo), min(e, hi)] for s, e in dev["busy"]
                if e > lo and s < hi]
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            t = (g0 + g1) / 2 - offset
            secs = (g1 - g0) / 1e9
            if _covered(in_gc, t):
                gc_s += secs
            if not _covered(under_task, t):
                continue
            total += secs
            said = speakers(t) or [(NO_OP, TASK)]
            for family, idle in said:
                families[family] += secs / len(said)
                span_idle[idle] = span_idle.get(idle, 0.0) + secs / len(said)
    n = len(events["devices"])
    return {"families": {k: v / n for k, v in families.items()},
            "span_idle": {k: v / n for k, v in span_idle.items()},
            "gc_pause_s": gc_s / n, "under_task_s": total / n, "devices": n}


def read(spec: dict, ctx: dict, root: str = span_gap.ROOT):
    if not any(s["name"].startswith(OP_PREFIX) for s in ctx["spans"]):
        return None
    if _KEY not in ctx:
        rec = span_gap.newest_trace_events(root)
        stale = rec is None or \
            len(rec["query_starts_ns"]) != ctx["queries"]
        ctx[_KEY] = {} if stale else summarize(rec, ctx["spans"],
                                               load_table())
    summary = ctx[_KEY]
    if not summary:
        return None
    r = spec["read"]
    per = ctx["queries"] if r.get("den") == "queries" else 1
    if r["stat"] == "family":
        return sum(summary["families"][f] for f in r["families"]) / per
    if r["stat"] == "span_idle":
        return sum(summary["span_idle"].get(n, 0.0)
                   for n in r["spans"]) / per
    if r["stat"] == "gc_pause":
        return summary["gc_pause_s"] / per
    raise ValueError(f"unknown span_gap_op stat {r['stat']!r}")

"""Source `span_gap`: the device's idle seconds by what the task threads
were doing, finer than `gap_categories.json` draws it.

It is `device_trace.reduce` again, with the category table of
`gap_categories_task.json`: the program's spans at the host/device
boundary (`d2h`, `h2d`, `prefetch_wait`, `agg_drain`, `join_probe`,
`join_build`) stand ahead of `task`, so what the old table calls
`in_task` falls into them, and what is left under `task` is Python glue
between dispatches.

`reduce` needs the device's busy intervals.  `run.py` hands readers the
reduced summary only, but writes what `reduce` was given beside the trace
(`<root>/.bench_work/<cell>.trace/trace_events.json`): the newest such
file under this checkout is this run's, if it holds as many query starts
as the run completed queries.  On the CPU there is no device plane and
nothing to read.

A task's work is spread over threads.  The task thread holds the `task`
span; a `PrefetchIterator` worker (`blaze-prefetch-<name>`) may be either
  - a leaf that reads beside the work (`parquet_scan`, `ipc_reader`: it
    emits `produce:*` and `h2d` only), or
  - a stage of the task's pipeline: the shuffle writer prefetches its
    child's whole operator chain, so scan waits, joins, folds and drains
    of a map task run on `blaze-prefetch-shuffle_map` while the task
    thread waits for it in a `prefetch_wait`.
A prefetch thread that emitted a `d2h`, a `stage_loop_chunk` or a
`prefetch_wait` of its own is taken for a pipeline stage: it blocks on the
device or on another producer, which a leaf never does.  Before the
reduction the leaves' spans are dropped (they do not decide when the
device gets work), and so are `prefetch_wait` spans whose `source` is a
pipeline stage: that wait is the stage's own spans, seen from outside.
What is left under `prefetch_wait` is waiting for a leaf: input not ready.

spec["read"]: {"categories": [category names, summed],
               "needs": [span names: nothing is read unless the program
                         emitted one of them, as the parent does not],
               "den": "queries" | null}
"""

from __future__ import annotations

import glob
import json
import os

from benchmark.sources import device_trace

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(_HERE))
PREFETCH_THREAD = "blaze-prefetch-"
_KEY = "span_gap_summary"   # the reduction, kept in ctx: one per run


PIPELINE_MARKS = ("d2h", "stage_loop_chunk", "prefetch_wait")


def task_thread_spans(spans):
    """`spans` less the leaf prefetch threads' and less the waits for a
    pipeline stage (module docstring)."""
    def thread(s):
        return str(s.get("thread") or "")

    stages = {thread(s) for s in spans
              if s["name"] in PIPELINE_MARKS
              and thread(s).startswith(PREFETCH_THREAD)}
    out = []
    for s in spans:
        t = thread(s)
        if t.startswith(PREFETCH_THREAD) and t not in stages:
            continue
        if s["name"] == "prefetch_wait" and PREFETCH_THREAD + str(
                (s.get("attrs") or {}).get("source")) in stages:
            continue
        out.append(s)
    return out


def newest_trace_events(root: str = ROOT):
    """The newest `trace_events.json` under `root`, or None."""
    found = glob.glob(os.path.join(root, ".bench_work", "*.trace",
                                   "trace_events.json"))
    if not found:
        return None
    with open(max(found, key=os.path.getmtime)) as f:
        return json.load(f)


def summarize(rec: dict, spans) -> dict:
    with open(os.path.join(_HERE, "gap_categories_task.json")) as f:
        categories = json.load(f)
    return device_trace.reduce(rec["events"], task_thread_spans(spans),
                               rec["query_starts_ns"],
                               categories=categories)


def read(spec: dict, ctx: dict, root: str = ROOT):
    if _KEY not in ctx:
        rec = newest_trace_events(root)
        stale = rec is None or \
            len(rec["query_starts_ns"]) != ctx["queries"]
        ctx[_KEY] = {} if stale else summarize(rec, ctx["spans"])
    summary = ctx[_KEY]
    r = spec["read"]
    if not summary or not any(s["name"] in r["needs"]
                              for s in ctx["spans"]):
        return None
    per = ctx["queries"] if r.get("den") == "queries" else 1
    return sum(summary["gaps"].get(c, 0.0) for c in r["categories"]) / per

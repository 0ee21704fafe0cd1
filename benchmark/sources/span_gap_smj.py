"""Source `span_gap_smj`: `span_gap`'s reduction under the category table
the metric file names, for tables that came after `gap_categories_task.json`.

`gap_categories_smj.json` is that table with the sort's and the merge
join's own spans (`sort_device`, `smj_merge`: ops/sort.py,
ops/joins/merge.py) ahead of the boundary spans: the device's idle time
while a task thread is inside one of the two operators, whatever it does
there (placing digits, waiting for the pairs' total, gathering).  The
spans dropped before the reduction, the trace file read and what makes it
stale are `span_gap`'s.

spec["read"]: `span_gap`'s, and "table": the category file's name in this
directory.
"""

from __future__ import annotations

import json
import os

from benchmark.sources import device_trace, span_gap

_HERE = os.path.dirname(os.path.abspath(__file__))


def summarize(rec: dict, spans, table: str) -> dict:
    with open(os.path.join(_HERE, table)) as f:
        categories = json.load(f)
    return device_trace.reduce(rec["events"],
                               span_gap.task_thread_spans(spans),
                               rec["query_starts_ns"], categories=categories)


def read(spec: dict, ctx: dict, root: str = span_gap.ROOT):
    r = spec["read"]
    key = "span_gap_summary:" + r["table"]
    if key not in ctx:
        rec = span_gap.newest_trace_events(root)
        stale = rec is None or \
            len(rec["query_starts_ns"]) != ctx["queries"]
        ctx[key] = {} if stale else summarize(rec, ctx["spans"], r["table"])
    summary = ctx[key]
    if not summary or not any(s["name"] in r["needs"]
                              for s in ctx["spans"]):
        return None
    per = ctx["queries"] if r.get("den") == "queries" else 1
    return sum(summary["gaps"].get(c, 0.0) for c in r["categories"]) / per

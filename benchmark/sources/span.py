"""Source `span`: the program's span tracer (`bridge/tracing.py`), switched
on for the traced window only.

spec["read"]: {"span": name, "stat": "count" | "seconds",
               "den": "queries" | null}
"""

from __future__ import annotations


def read(spec: dict, ctx: dict):
    r = spec["read"]
    hits = [s for s in ctx["spans"] if s["name"] == r["span"]]
    if not hits:
        return None
    value = (len(hits) if r["stat"] == "count"
             else sum(s["dur_ns"] for s in hits) / 1e9)
    if r.get("den") == "queries":
        value /= ctx["queries"]
    return value

"""Source `counter`: deltas of the program's `xla_stats` counters over the
traced window.  They are counts and byte totals, never times.

spec["read"]: {"num": [counter keys, summed],
               "den": "queries" | "fact_rows_scanned" | [counter keys] | null,
               "scale": factor (100 for a share in %, 1e-6 for MB)}
A share whose denominator counted nothing has nothing to read.
"""

from __future__ import annotations


def read(spec: dict, ctx: dict):
    r = spec["read"]
    counters = ctx["counters"]
    if any(k not in counters for k in r["num"]):
        return None
    num = sum(counters[k] for k in r["num"])
    den = r.get("den")
    if den is None:
        denom = 1
    elif den == "queries":
        denom = ctx["queries"]
    elif den == "fact_rows_scanned":
        denom = ctx["fact_rows"] * ctx["queries"]
    else:
        if any(k not in counters for k in den):
            return None
        denom = sum(counters[k] for k in den)
    if not denom:
        return None
    return r.get("scale", 1.0) * num / denom

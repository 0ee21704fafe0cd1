"""Source `rollup_roofline`: the expanded fold's share of its memory
roofline.

least bytes (`kernel_costs_rollup.expand_fold_min_bytes` of the rows the
program's counter `expand_rows_out` states, at the lanes and the value
width the query file states) over the device time of the programs whose
name matches spec["read"]["pattern"], over the HBM peak in peaks.json.
Memory-bound: a probe has no matrix work.

A program without the counter or without such programs (the parent of the
PR that folds an Expand inside the stage loop), a window in which no such
fold ran, a query file without the lanes, or a run without a device trace
has nothing to read.
"""

from __future__ import annotations

import re

from benchmark import kernel_costs_rollup


def read(spec: dict, ctx: dict):
    t = ctx.get("trace") or {}
    q = ctx["query"]
    rows = ctx["counters"].get("expand_rows_out", 0)
    if not t or not rows or not hasattr(q, "FOLD_KEY_LANES"):
        return None
    pat = re.compile(spec["read"]["pattern"])
    secs = sum(v for k, v in t["programs"].items() if pat.search(k))
    if not secs:
        return None
    least = kernel_costs_rollup.expand_fold_min_bytes(
        rows, q.FOLD_KEY_LANES, q.FOLD_VALUE_BYTES)
    return 100.0 * least / secs / ctx["peaks"]["hbm_bytes_per_s"]

"""Source `smj_roofline`: the merge join's share of its memory roofline.

least bytes (`kernel_costs_smj.merge_join_min_bytes`: the rows both sides
sent down the device path and the pairs it wrote, by the program's counters
`smj_device_rows` and `smj_device_pairs`, times the widths the query file
states) over the device time of the programs whose name matches
spec["read"]["pattern"], over the HBM peak in peaks.json.

A program without the counters or without such programs (the parent of the
PR that brought the device merge join), a query file without the widths, or
a run without a device trace has nothing to read.
"""

from __future__ import annotations

import re

from benchmark import kernel_costs_smj


def read(spec: dict, ctx: dict):
    t = ctx.get("trace") or {}
    q, c = ctx["query"], ctx["counters"]
    rows, pairs = c.get("smj_device_rows", 0), c.get("smj_device_pairs", 0)
    if not t or not rows or not hasattr(q, "SMJ_KEY_BYTES"):
        return None
    pat = re.compile(spec["read"]["pattern"])
    secs = sum(v for k, v in t["programs"].items() if pat.search(k))
    if not secs:
        return None
    least = kernel_costs_smj.merge_join_min_bytes(
        rows, q.SMJ_KEY_BYTES, pairs, q.SMJ_PAIR_BYTES)
    return 100.0 * least / secs / ctx["peaks"]["hbm_bytes_per_s"]

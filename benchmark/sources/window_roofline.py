"""Source `window_roofline`: the window scans' share of their memory
roofline.

least bytes (`kernel_costs_window.window_min_bytes` a run a window node
scanned on the chip: the rows and functions its `window_device` span of
lane `resident` states, times the key, argument and result widths the
query file states) over the device time of the programs whose name matches
spec["read"]["pattern"], over the HBM peak in peaks.json.  Memory-bound: a
scan has no matrix work.

A program without the span or without such programs (the parent of the PR
that brought the resident lane), a window in which no window node ran on
the chip, a query file without the widths, or a run without a device trace
has nothing to read.
"""

from __future__ import annotations

import re

from benchmark import kernel_costs_window


def read(spec: dict, ctx: dict):
    t = ctx.get("trace") or {}
    q = ctx["query"]
    runs = [s["attrs"] for s in ctx["spans"] if s["name"] == "window_device"
            and s["attrs"].get("lane") == "resident"]
    if not t or not runs or not hasattr(q, "WINDOW_KEY_BYTES"):
        return None
    pat = re.compile(spec["read"]["pattern"])
    secs = sum(v for k, v in t["programs"].items() if pat.search(k))
    if not secs:
        return None
    least = sum(kernel_costs_window.window_min_bytes(
        a["rows"], q.WINDOW_KEY_BYTES, a["functions"] * q.WINDOW_ARG_BYTES,
        a["functions"] * q.WINDOW_OUT_BYTES) for a in runs)
    return 100.0 * least / secs / ctx["peaks"]["hbm_bytes_per_s"]

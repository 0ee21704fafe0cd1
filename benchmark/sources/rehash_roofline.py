"""Source `rehash_roofline`: the table rehash's share of its memory
roofline.

least bytes (`kernel_costs_rehash.rehash_min_bytes`: the groups each
rehash moved and the slots of the table it moved them into, by the
program's counters `stage_loop_rehash_groups` and
`stage_loop_rehash_new_slots`, times the slot width the query file states)
over the device time of the programs whose name matches
spec["read"]["pattern"], over the HBM peak in peaks.json.

A program without the counters (the parent of the PR that brought them), a
window in which no rehash ran, a query file without the width, or a run
without a device trace has nothing to read.
"""

from __future__ import annotations

import re

from benchmark import kernel_costs_rehash


def read(spec: dict, ctx: dict):
    t = ctx.get("trace") or {}
    q, c = ctx["query"], ctx["counters"]
    groups = c.get("stage_loop_rehash_groups", 0)
    new_slots = c.get("stage_loop_rehash_new_slots", 0)
    if not t or not new_slots or not hasattr(q, "FOLD_SLOT_BYTES"):
        return None
    pat = re.compile(spec["read"]["pattern"])
    secs = sum(v for k, v in t["programs"].items() if pat.search(k))
    if not secs:
        return None
    least = kernel_costs_rehash.rehash_min_bytes(groups, new_slots,
                                                 q.FOLD_SLOT_BYTES)
    return 100.0 * least / secs / ctx["peaks"]["hbm_bytes_per_s"]

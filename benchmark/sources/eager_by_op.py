"""Source `eager_by_op`: the device time of the eager one-operation
programs, by the operator family that asked for them.

A program of the device trace is eager glue when its name has no `__`
after `jit_` (`jit__take`: the function is `_take`; a metered program is
`jit_<function>__<kernel>`).  The trace's record (`chips._record`) holds,
per device plane, every program execution (name, start, duration) on the
profiler's clock.  Every execution of an eager program that overlaps the
window goes, whole, to a family of `op_families.json` BY TIME: the
execution's start is brought onto the spans' clock (`chips.window`'s
offset) and split equally over the families of the threads that speak for
a task then (`speakers.Speakers`: `span_gap_op`'s rule); where nobody
speaks, to `no_op`.  The device may start a program after the host has
left the span that dispatched it.  The drains' and the shuffle writer's
gathers are each followed by a blocking `d2h` inside the same span, which
bounds the error for the programs that matter (`jit__take` is 98-99% of
these seconds in every cell: PERF.md section 6, PR 51).

Over several chips a plane's executions are given to the speakers whose
`task` span says that chip (`/device:TPU:<n>` against the span's
`device`), and the result is the mean over the planes, as
`device_trace.reduce` takes it.  The trace and the spans alone decide: a
second reading of one trace gives the same families.  A program without
`op:*` spans, or a run without a device plane, has nothing to read.

spec["read"]: {"families": [...] | null (every eager program: exact, by
               name alone), "den": "queries" | null}
"""

from __future__ import annotations

import re

from benchmark.sources import chips
from benchmark.sources.span_gap_op import NO_OP, OP_PREFIX, load_table
from benchmark.sources.speakers import Speakers

_KEY = "eager_by_op_summary"   # the reduction, kept in ctx: one per run
_PLANE = re.compile(r"(\d+)$")


def is_eager(program: str) -> bool:
    return program.partition("_")[2].find("__", 1) <= 0


def summarize(rec: dict, spans, table: dict) -> dict:
    """{"total_s", "families": {family: s}}: device seconds of the eager
    programs inside the window, mean over the planes."""
    lo, hi, offset = chips.window(rec)
    who = Speakers(spans, table)
    speakers_at = who.lookup()
    families = dict.fromkeys(who.families, 0.0)
    total = 0.0
    planes = rec["events"]["devices"]
    for plane, dev in planes.items():
        m = _PLANE.search(plane)
        chip = int(m.group(1)) if m and len(planes) > 1 else None
        for name, start, dur in dev["programs"]:
            if not is_eager(name) or start + dur <= lo or start >= hi:
                continue
            secs = dur / 1e9
            total += secs
            said = speakers_at(start - offset, chip) or [(NO_OP,)]
            for speaker in said:
                families[speaker[0]] += secs / len(said)
    n = len(planes)
    return {"total_s": total / n,
            "families": {k: v / n for k, v in families.items()}}


def read(spec: dict, ctx: dict, root: str = chips.ROOT):
    if not any(s["name"].startswith(OP_PREFIX) for s in ctx["spans"]):
        return None
    if _KEY not in ctx:
        rec = chips._record(ctx, root)
        if not rec or chips.window(rec) is None:
            ctx[_KEY] = {}
        else:
            ctx[_KEY] = summarize(rec, ctx["spans"], load_table())
    summary = ctx[_KEY]
    if not summary:
        return None
    r = spec["read"]
    per = ctx["queries"] if r.get("den") == "queries" else 1
    if r.get("families") is None:
        return summary["total_s"] / per
    return sum(summary["families"][f] for f in r["families"]) / per

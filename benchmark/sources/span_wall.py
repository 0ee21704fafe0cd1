"""Source `span_wall`: a wait cut to the wall.  The program's spans alone.

`d2h_wait_s` and `prefetch_wait_s` are counters summed over threads, and
exceed a query's wall where several threads wait at once.  This is the
same wait on the wall's clock: the integral, over the traced window, of

    (threads that speak for a task and are inside a span of the name)
    ------------------------------------------------------------------
                   (threads that speak for a task)

so a wait that two of four speaking threads are in for a second reads
half a second, and the whole never exceeds the time some task was open.
Who speaks is `span_gap_op`'s rule (`speakers.Speakers`): the leaf
prefetch threads' spans are dropped, and a thread in a `prefetch_wait` for
a pipeline stage is silent while the stage's thread speaks, so
`prefetch_wait` here is waiting for a leaf (input not ready), as
`span_gap` reads it.  "Inside": the span is on the speaker's walk from its
innermost open span out to its `task`.

spec["read"]: {"span": name, "den": "queries" | null}
A program whose spans carry no `tid` or no span of the name (the parent
of PR 35) has nothing to read.
"""

from __future__ import annotations

from benchmark.sources.span_gap_op import load_table
from benchmark.sources.speakers import Speakers

_KEY = "span_wall_speakers"   # the timelines, kept in ctx: one per run


def wall_seconds(who: Speakers, name: str) -> float:
    total = 0.0
    for t0, t1, said in who.segments():
        inside = sum(name in names for _family, _idle, names in said)
        total += (t1 - t0) * inside / len(said)
    return total / 1e9


def read(spec: dict, ctx: dict):
    r = spec["read"]
    if not any(s["name"] == r["span"] and "tid" in s
               for s in ctx["spans"]):
        return None
    if _KEY not in ctx:
        ctx[_KEY] = Speakers(ctx["spans"], load_table())
    per = ctx["queries"] if r.get("den") == "queries" else 1
    return wall_seconds(ctx[_KEY], r["span"]) / per

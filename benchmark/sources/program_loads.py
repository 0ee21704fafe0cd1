"""Source `program_loads`: what set-up spent getting programs from JAX,
asked of the program's own ledger at read time.

The span tracer is off during set-up and `ctx["counters"]` is the traced
window's delta, in which no program may be loaded; so the program keeps a
ledger of every program it asks JAX for, for the life of the process
(`blaze_tpu.bridge.xla_stats.program_loads`: one record a phase of a
request, stamped on `time.perf_counter_ns` from JAX's monitoring events),
and this reader takes `xla_stats.program_load_summary(until_ns=<the first
query's start>)`.  The first query's start is the first of
`query_starts_ns` in the trace's record (`chips._record`: read once a
run, stale where it holds another count of queries or a start outside
this window's spans); without a record (no device plane was
traced under this checkout: a rehearsal on the CPU) it is the start of the
window's earliest span.  A program without the accessor (the parent) has
nothing to read, and the result line leaves the metric out; so does a
process whose ledger has dropped records (the summary's `trimmed`, the
counter `program_loads_trimmed`, above 0: the cap trims the OLDEST, which
are set-up's, and a sum that lacks them would read low).

spec["read"]: {"key": a key of the summary: "wall_s", "trace_s",
               "lower_s", "backend_s", "cache_retrieval_s",
               "requests_eager", "requests_metered", "cache_hits"}
"""

from __future__ import annotations

from benchmark.sources import chips

_KEY = "program_loads_summary"   # the summary, kept in ctx: one per run


def _until_ns(ctx: dict, root: str):
    spans = ctx.get("spans") or []
    first_span = min((s["t0_ns"] for s in spans), default=None)
    rec = chips._record(ctx, root)
    if rec and rec["query_starts_ns"]:
        start = rec["query_starts_ns"][0]
        # another process's record (a rehearsal's checkout is not where
        # its trace went) lies on another clock: outside this window's spans
        if first_span is None or first_span <= start <= max(
                s["t1_ns"] for s in spans):
            return start
    return first_span


def summary(ctx: dict, root: str = chips.ROOT):
    if _KEY not in ctx:
        from blaze_tpu.bridge import xla_stats
        ask = getattr(xla_stats, "program_load_summary", None)
        ctx[_KEY] = ask(until_ns=_until_ns(ctx, root)) if ask else None
    return ctx[_KEY]


def read(spec: dict, ctx: dict, root: str = chips.ROOT):
    s = summary(ctx, root)
    return None if s is None or s["trimmed"] else s.get(spec["read"]["key"])

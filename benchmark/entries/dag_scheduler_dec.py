"""Entry point `dag_scheduler_dec`: `dag_scheduler` for a plan whose
amounts are decimals and whose aggregations have to fold them on the chip.

A program that cannot fuse a sum over a decimal (`plan/fused.py` before the
PR that gave the stage loop an exact scaled-integer value lane) leaves both
of q01's aggregation stages to the eager per-batch aggregation, two dozen of
them over 573K rows a query, and then raises on the plan's `decimal(2,1)`
literal.  So this entry plans the query's first aggregation stage through
the program's own planner in its constructor and refuses a program in which
the decimal sum does not come out a fused aggregation: a run on such a
program fails at once, before any query.

After each query it says if the run was not the path the cell times:
  - no stage-loop task folded a decimal row;
  - an aggregation outside the stage loop took a fact-sized count of rows
    with a decimal argument (the 12-row average by store is not that);
  - a decimal sum or average overflowed (at this scale none can);
  - a task left the stage loop.
After every warm-up query it runs the query file's `plan_full`, off every
query's clock, and holds the answer to `check.py`'s limits AND to the
oracle's schema: a float column is not a decimal column, and decimal(18,2)
is not decimal(17,2).

Counters are read with `.get`: a program without one reads 0.
"""

from __future__ import annotations

from benchmark import check
from benchmark.entries.dag_scheduler import Entry as DagSchedulerEntry

WATCHED = ("stage_loop_decimal_rows", "agg_decimal_rows_host",
           "decimal_overflow_groups", "stage_loop_fallbacks")
# rows an eager aggregation may take with a decimal argument in one query:
# the partial averages of 12 stores over 4 partitions, with room
HOST_ROWS_ALLOWED = 1024


def first_partial_agg(node):
    """The deepest `hash_agg` of a plan dict whose functions are all
    partial: the stage that reads the fact table."""
    found = None
    if isinstance(node, dict):
        for child in node.values():
            found = first_partial_agg(child) or found
        if found is None and node.get("kind") == "hash_agg" and all(
                a.get("mode") == "partial" for a in node["aggs"]):
            found = node
    elif isinstance(node, list):
        for child in node:
            found = first_partial_agg(child) or found
    return found


class Entry(DagSchedulerEntry):
    def __init__(self, query, paths, tables, cfg, work_dir: str):
        super().__init__(query, paths, tables, cfg, work_dir)
        self._refuse_unfused(first_partial_agg(self.plan))
        self.query = query
        self.full = (query.plan_full(paths, tables, cfg["partitions"]),
                     query.full_oracle(tables))
        self._moved = {}

    @staticmethod
    def _refuse_unfused(stage: dict) -> None:
        from blaze_tpu.plan.fused import FusedPartialAggExec, fuse_plan
        from blaze_tpu.plan.planner import create_plan
        node = fuse_plan(create_plan(stage))
        if not isinstance(node, FusedPartialAggExec):
            raise RuntimeError(
                f"this program plans a sum over a decimal as "
                f"{type(node).__name__}, not as a fused aggregation "
                f"(plan/fused.py): both aggregation stages of this cell "
                f"would run eagerly, a batch at a time, outside the stage "
                f"loop the cell is there to time")

    def _watched(self) -> dict:
        from blaze_tpu.bridge import xla_stats
        now = xla_stats.snapshot()
        return {k: now.get(k, 0) for k in WATCHED}

    def begin(self) -> None:
        self._before = self._watched()

    def end(self) -> None:
        now = self._watched()
        self._moved = {k: now[k] - self._before[k] for k in WATCHED}

    def problem(self):
        why = super().problem()
        if why:
            return why
        m = self._moved
        if not m["stage_loop_decimal_rows"]:
            return "no stage-loop task folded a decimal row"
        if m["agg_decimal_rows_host"] > HOST_ROWS_ALLOWED:
            return (f"an aggregation outside the stage loop took "
                    f"{m['agg_decimal_rows_host']} rows with a decimal "
                    f"argument")
        if m["decimal_overflow_groups"]:
            return f"{m['decimal_overflow_groups']} decimal sums overflowed"
        if m["stage_loop_fallbacks"]:
            return f"{m['stage_loop_fallbacks']} tasks left the stage loop"
        return self._full_answer_differs()

    def _full_answer_differs(self):
        from blaze_tpu.plan.stages import DagScheduler
        plan, want = self.full
        with DagScheduler() as sched:
            got = sched.run_collect(plan)
        if got.schema.types != want.schema.types:
            return (f"the full answer's types are {got.schema.types}, the "
                    f"oracle's {want.schema.types}")
        ok, line = check.verdict(check.compare(
            got, want, self.query.FULL_KEYS, False))
        print(f"full answer, {got.num_rows} rows: {line}", flush=True)
        return None if ok else f"the full answer differs: {line}"

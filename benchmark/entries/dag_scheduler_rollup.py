"""Entry point `dag_scheduler_rollup`: `dag_scheduler` for a plan whose
Expand has to fold inside the stage loop and whose strings have to stay
int32 codes on the chip.

A program whose fused stages do not know `ExpandExec` projects every input
batch nine times, eagerly, and hands the 5.2M expanded rows of q67 to the
unfused `AggExec` over host strings (the path on which a string-keyed q79
took 10.5 s at SF1, `plan/fused.py`): a minute or more a query, none inside
the window.  A program whose broadcast join wants every build column
fixed-width sends the probes of `store` and `item` through the host.
Neither is the path this cell is there to time, so this entry plans the
query through the program's own planner in its constructor and refuses,
before any query, a program in which

  - no fused aggregation absorbed the Expand, or the one that did is no
    stage-loop task;
  - a broadcast join whose build side carries a utf8 column does not plan
    a device probe.

A run on such a program fails at once and cleanly instead of slowly.

After each query it says if the run was not the path the cell times: a task
left the stage loop; an aggregation outside it took more than 1,024 rows;
more rows x columns were decoded to strings than the rows that pass
`rk <= 100` hold (plus the dictionaries' own entries: a dimension table is
decoded where it is collected, once a process); a join probed on the host;
a sort or a window of 1,024 rows or more left the chip; compute was placed
on the host.

After every warm-up query it runs the query file's `plan_full` (every
rolled-up row with its sum and its rank), off every query's clock, and
holds the answer to `check.py`'s limits: keys and NULLs exactly, sums
within its tolerance, `rk` exactly wherever the oracle's neighbouring sums
differ by more than that tolerance; a row inside a cluster of nearer sums
has to hold a rank of the cluster's span (`queries/q67.py` `near_ties`),
and the line says how many rows lie in such clusters.

Counters are read with `.get`: a program without one reads 0.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from benchmark import check
from benchmark.entries.dag_scheduler import Entry as DagSchedulerEntry

WATCHED = ("stage_loop_fallbacks", "stage_loop_tasks", "agg_eager_rows",
           "dict_rows_decoded", "dict_rows_coded", "expand_rows_out",
           "join_probe_host_rows", "join_probe_device_rows", "window_rows",
           "window_resident_rows", "sort_device_rows", "sort_resident_rows")
# rows an aggregation outside the stage loop may take in one query, and
# the rows a sort or a window may leave to the host: the resident lanes'
# own floor (the grand total is alone in its partition)
HOST_ROWS_ALLOWED = 1024


def walk(plan):
    stack = [plan]
    while stack:
        node = stack.pop()
        yield node
        stack.extend(getattr(node, "children", None) or [])


class Entry(DagSchedulerEntry):
    def __init__(self, query, paths, tables, cfg, work_dir: str):
        super().__init__(query, paths, tables, cfg, work_dir)
        self._refuse_other_paths(self.plan)
        self.query = query
        want = query.full_oracle(tables)
        self.full = (query.plan_full(paths, tables, cfg["partitions"]),
                     self._by_keys(want), query.near_ties(
                         self._by_keys(want), check.REL_TOL))
        # the dictionaries' own entries: a distinct value of a string
        # column of a dimension table
        self.entries = sum(
            len(pc.unique(t.column(n))) for t in tables.values()
            for n in t.column_names if n in query.STRINGS)
        self._moved = {}

    @staticmethod
    def _refuse_other_paths(plan: dict) -> None:
        from blaze_tpu.plan import stage_compiler
        from blaze_tpu.plan.fused import fuse_plan
        from blaze_tpu.plan.planner import create_plan
        nodes = list(walk(fuse_plan(create_plan(plan))))
        folds = [n for n in nodes if getattr(n, "_expand", 0)]
        if not folds:
            raise RuntimeError(
                "this program fuses no aggregation over the plan's Expand "
                "(plan/fused.py knows no ExpandExec): nine eager "
                "projections a batch and the unfused aggregation over "
                "host strings would take the 5.2M expanded rows, which is "
                "not what this cell times")
        for node in folds:
            if stage_compiler.try_compile(node) is None:
                raise RuntimeError(
                    "the Expand's stage is no stage-loop task "
                    "(plan/stage_compiler.py declines it): the expanded "
                    "rows would fold outside the loop")
        joins = [n for n in nodes if type(n).__name__ == "BroadcastJoinExec"
                 and any(f.data_type.id.value == "utf8"
                         for f in n.children[1].schema)]
        if not joins:
            raise RuntimeError("the plan has no broadcast join with a "
                               "string payload: this entry is for plans "
                               "that have")
        for node in joins:
            planned = getattr(node, "device_probe_planned", None)
            if planned is None or not planned():
                raise RuntimeError(
                    f"this program plans the join to "
                    f"{[f.name for f in node.children[1].schema]} with no "
                    f"device probe (ops/joins/exec.py wants every build "
                    f"column fixed-width): its probe batches would go "
                    f"through the host")

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
        from blaze_tpu.bridge.placement import host_resident
        why = super().problem()
        if why:
            return why
        m = self._moved
        if host_resident():
            return "compute is placed on the host"
        if m["stage_loop_fallbacks"]:
            return f"{m['stage_loop_fallbacks']} tasks left the stage loop"
        if not m["expand_rows_out"]:
            return "no stage-loop task folded an Expand"
        if m["agg_eager_rows"] > HOST_ROWS_ALLOWED:
            return (f"an aggregation outside the stage loop took "
                    f"{m['agg_eager_rows']} rows")
        allowed = self.shown_rows() * len(self.query.STRINGS) + self.entries
        if m["dict_rows_decoded"] > allowed:
            return (f"{m['dict_rows_decoded']} rows x columns were decoded "
                    f"to strings, more than the {allowed} that the rows "
                    f"past the rank filter and the dictionaries hold")
        if m["join_probe_host_rows"]:
            return (f"{m['join_probe_host_rows']} probe rows went through "
                    f"the host")
        for what in ("window", "sort"):
            rows = m["window_rows" if what == "window"
                     else "sort_device_rows"]
            left = rows - m[f"{what}_resident_rows"]
            if left >= HOST_ROWS_ALLOWED:
                return f"{left} of {rows} {what} rows left the chip"
        return self._full_answer_differs()

    def shown_rows(self) -> int:
        """Rows that pass `rk <= 100`, by the oracle's full answer."""
        rk = self.full[1].column("rk").to_numpy()
        return int((rk <= self.query.TOP).sum())

    def _by_keys(self, t: pa.Table) -> pa.Table:
        return t.take(pc.sort_indices(
            t, sort_keys=[(k, "ascending", "at_start")
                          for k in self.query.KEYS]))

    def _full_answer_differs(self):
        from blaze_tpu.plan.stages import DagScheduler
        plan, want, (near, low, high) = self.full
        with DagScheduler() as sched:
            got = sched.run_collect(plan)
        if got.num_rows != want.num_rows:
            return (f"the full answer has {got.num_rows} rows, the "
                    f"oracle's {want.num_rows}")
        got = self._by_keys(got.rename_columns(want.column_names))
        # a rank inside a cluster of sums nearer than the tolerance may be
        # any of the cluster's span; such rows are compared by that span
        # and their `rk` then taken out of the exact comparison
        rk = got.column("rk").to_numpy()
        outside = int((near & ((rk < low) | (rk > high))).sum())
        masked = pa.array(np.where(near, 0, rk).astype(np.int32))
        at = got.column_names.index("rk")
        got = got.set_column(at, "rk", masked)
        want = want.set_column(at, "rk", pa.array(np.where(
            near, 0, want.column("rk").to_numpy()).astype(np.int32)))
        nums = check.compare(got, want, self.query.KEYS, True)
        nums["exact_value_mismatches"] += outside
        ok, line = check.verdict(nums)
        print(f"full answer, {got.num_rows} rows, {int(near.sum())} in "
              f"clusters of sums within {check.REL_TOL:g} (rk held to the "
              f"cluster's span, {outside} outside it): {line}", flush=True)
        return None if ok else f"the full answer differs: {line}"

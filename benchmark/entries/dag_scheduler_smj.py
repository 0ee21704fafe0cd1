"""Entry point `dag_scheduler_smj`: `dag_scheduler` for a plan whose
sort-merge join has to run as device programs.

A program without the device merge join (`blaze_tpu/ops/joins/merge.py`)
walks q93's 3.2 million one-row key runs in a Python cursor: on the chip the
first warm-up query did not end in ten minutes and was cut by the program's
own 600 s task timeout (PERF.md, PR 26).  So this entry refuses such a
program in its constructor, before any query, and a run on it fails at once
instead of slowly.  After each query it also says if any partition of the
join streamed through the run cursor: that is not the path the cell times.

The answer of such a plan may say little about its arithmetic (q93's first
100 sums are 0.0).  Where the query file declares `plan_full` and
`full_oracle`, the same plan less its last step, this entry runs that after
every warm-up query, off every query's clock, and holds its answer to
`check.py`'s limits like any other: a run whose sums are off is not
correct before its window starts.
"""

from __future__ import annotations

from benchmark import check
from benchmark.entries.dag_scheduler import Entry as DagSchedulerEntry


class Entry(DagSchedulerEntry):
    def __init__(self, query, paths, tables, cfg, work_dir: str):
        try:
            from blaze_tpu.ops.joins import merge  # noqa: F401
        except ImportError as e:
            raise RuntimeError(
                "this program has no device merge join "
                "(blaze_tpu/ops/joins/merge.py); its run cursor does not "
                "end one query of this cell in ten minutes") from e
        super().__init__(query, paths, tables, cfg, work_dir)
        self._streamed = 0
        self.query = query
        self.full = None
        if hasattr(query, "plan_full"):
            self.full = (query.plan_full(paths, tables, cfg["partitions"]),
                         query.full_oracle(tables))

    def begin(self) -> None:
        from blaze_tpu.bridge import xla_stats
        self._before = xla_stats.sortmerge_stats()["smj_streamed_runs"]

    def end(self) -> None:
        from blaze_tpu.bridge import xla_stats
        self._streamed = xla_stats.sortmerge_stats()["smj_streamed_runs"] \
            - self._before

    def problem(self):
        if self._streamed:
            return (f"the merge join streamed {self._streamed} key runs "
                    f"through the Python run cursor")
        return super().problem() or self._full_answer_differs()

    def _full_answer_differs(self):
        if self.full is None:
            return None
        from blaze_tpu.plan.stages import DagScheduler
        plan, want = self.full
        with DagScheduler() as sched:
            got = sched.run_collect(plan)
        ok, line = check.verdict(check.compare(got, want, self.query.KEYS,
                                               False))
        print(f"full answer, {got.num_rows} rows: {line}", flush=True)
        return None if ok else f"the full answer differs: {line}"

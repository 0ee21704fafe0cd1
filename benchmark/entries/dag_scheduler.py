"""Entry point `dag_scheduler`: the whole plan through
`DagScheduler.run_collect`, as a Spark driver would hand it over."""

from __future__ import annotations

import pyarrow as pa


class Entry:
    def __init__(self, query, paths, tables, cfg, work_dir: str):
        self.plan = query.plan(paths, tables, cfg["partitions"])
        self.last = {}

    def begin(self) -> None:
        pass

    def run(self) -> pa.Table:
        from blaze_tpu.plan.stages import DagScheduler
        with DagScheduler() as sched:
            got = sched.run_collect(self.plan)
            self.last = {"exec_mode": sched.exec_mode,
                         "stages": len(sched.stages)}
        return got

    def end(self) -> None:
        pass

    def problem(self):
        """Why the last run is not the path the cell is there to time."""
        if self.last.get("exec_mode") != "staged" \
                or self.last.get("stages", 0) < 2:
            return f"took the single-task shortcut: {self.last}"
        return None

"""Entry point `dag_scheduler_smj_x4`: `dag_scheduler_smj` for a cell whose
executor owns several chips, and whose two fact tables have to cross the
mesh before they are sorted and merge-joined a partition a chip.

It holds a run to BOTH entries it draws on.  Like `dag_scheduler_smj` it
refuses, in its constructor, a program without the device merge join, and
runs the query file's `plan_full` against `full_oracle` after every
warm-up query, off the clock.  Like `dag_scheduler_x4` it refuses a
program whose tasks have no chip (`TaskContext` without `device`,
`xla_stats` without `chip_stats`).  A run on either fails before its
first query.

After each query it says if the run was not the deployment the
configuration states:
  - a key run streamed through the Python run cursor
    (`smj_streamed_runs`);
  - a producer stage with at least as many tasks as the cell has chips
    left one of the chips without a task (`DagScheduler.task_chips`);
  - bytes changed chip outside the exchange's collective
    (`cross_chip_bytes`);
  - an exchange fell back to files (`shuffle_device_fallbacks`), or map
    output went to shuffle files at all (`shuffle_host_bytes`);
  - fewer rows went over the mesh than the query's two largest tables
    hold (`shuffle_device_rows`): the fact tables went another way and
    a small exchange alone crossed the mesh.
It reads counters every program with task placement has, so the parent
commit with this file laid over it runs the cell.
"""

from __future__ import annotations

from benchmark.entries.dag_scheduler_smj import Entry as SmjEntry
from benchmark.entries.dag_scheduler_x4 import Entry as X4Entry

WATCHED = ("smj_streamed_runs", "cross_chip_bytes",
           "shuffle_device_fallbacks", "shuffle_device_rows",
           "shuffle_host_bytes")


class Entry(SmjEntry):
    def __init__(self, query, paths, tables, cfg, work_dir: str):
        from blaze_tpu.bridge import xla_stats
        from blaze_tpu.bridge.context import TaskContext
        if "device" not in getattr(TaskContext, "__dataclass_fields__", {}) \
                or not hasattr(xla_stats, "chip_stats"):
            raise RuntimeError(
                "this program cannot say which chip a task ran on "
                "(bridge/context.TaskContext has no device): it runs every "
                "task on chip 0, and this cell times an executor that "
                "places its tasks on all of its chips")
        super().__init__(query, paths, tables, cfg, work_dir)
        self.chips = int(cfg["chips"])
        # both sides of the merge join: the query's two largest tables
        self.fact_rows = sum(sorted(t.num_rows for t in tables.values())[-2:])
        self._moved = {}

    def _watched(self) -> dict:
        from blaze_tpu.bridge import xla_stats
        now = dict(xla_stats.placement_stats(), **xla_stats.shuffle_stats(),
                   **xla_stats.sortmerge_stats())
        return {k: now.get(k, 0) for k in WATCHED}

    def begin(self) -> None:
        self._before = self._watched()

    # the stages' task counts and chips kept beside the answer
    run = X4Entry.run

    def end(self) -> None:
        now = self._watched()
        self._moved = {k: now[k] - self._before[k] for k in WATCHED}
        self._streamed = self._moved["smj_streamed_runs"]

    def _not_the_deployment(self):
        for sid, n_tasks in self.last.get("tasks", {}).items():
            used = {chip for (s, _p), chip in self.last["task_chips"].items()
                    if s == sid}
            if n_tasks >= self.chips and len(used) < self.chips:
                return (f"stage {sid} ran its {n_tasks} tasks on chips "
                        f"{sorted(used)} of {self.chips}")
        moved = self._moved
        if moved["cross_chip_bytes"]:
            return (f"{moved['cross_chip_bytes']} bytes changed chip "
                    f"outside the exchange")
        if moved["shuffle_device_fallbacks"]:
            return (f"{moved['shuffle_device_fallbacks']} exchanges fell "
                    f"back to files")
        if moved["shuffle_host_bytes"]:
            return (f"{moved['shuffle_host_bytes']} bytes of map output "
                    f"went to shuffle files")
        if moved["shuffle_device_rows"] < self.fact_rows:
            return (f"{moved['shuffle_device_rows']} rows went over the "
                    f"mesh, the two fact tables hold {self.fact_rows}")
        return None

    def problem(self):
        # the placement, then the streamed runs and the single-task
        # shortcut, then (a query of its own) the full answer
        return self._not_the_deployment() or super().problem()

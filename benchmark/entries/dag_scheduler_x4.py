"""Entry point `dag_scheduler_x4`: `dag_scheduler` for a cell whose
executor owns several chips and has to use them all.

A program whose tasks have no chip (`TaskContext` without `device`,
`xla_stats` without `chip_stats`: every program before the PR that placed
tasks on chips) runs every task on `jax.devices()[0]` and only the
collective on the others: under this cell's name it would time chip 0
alone.  So this entry refuses such a program in its constructor, before
any query, and a run on it fails at once.

After each query it says if the run was not the deployment the
configuration states:
  - a producer stage with at least as many tasks as the cell has chips
    left one of the chips without a task (`DagScheduler.task_chips`);
  - bytes changed chip outside the exchange's collective
    (`cross_chip_bytes`);
  - an exchange fell back to files (`shuffle_device_fallbacks`), or no
    exchange went over the mesh at all.
"""

from __future__ import annotations

import pyarrow as pa

from benchmark.entries.dag_scheduler import Entry as DagSchedulerEntry

WATCHED = ("cross_chip_bytes", "shuffle_device_fallbacks",
           "shuffle_device_exchanges")


class Entry(DagSchedulerEntry):
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
        self._moved = {}

    def _watched(self) -> dict:
        from blaze_tpu.bridge import xla_stats
        now = dict(xla_stats.placement_stats(), **xla_stats.shuffle_stats())
        return {k: now[k] for k in WATCHED}

    def begin(self) -> None:
        self._before = self._watched()

    def run(self) -> pa.Table:
        from blaze_tpu.plan.stages import DagScheduler
        with DagScheduler() as sched:
            got = sched.run_collect(self.plan)
            self.last = {"exec_mode": sched.exec_mode,
                         "stages": len(sched.stages),
                         "tasks": {s.sid: s.num_tasks
                                   for s in sched.stages[:-1]},
                         "task_chips": dict(sched.task_chips)}
        return got

    def end(self) -> None:
        now = self._watched()
        self._moved = {k: now[k] - self._before[k] for k in WATCHED}

    def problem(self):
        why = super().problem()
        if why:
            return why
        for sid, n_tasks in self.last["tasks"].items():
            used = {chip for (s, _p), chip in self.last["task_chips"].items()
                    if s == sid}
            if n_tasks >= self.chips and len(used) < self.chips:
                return (f"stage {sid} ran its {n_tasks} tasks on chips "
                        f"{sorted(used)} of {self.chips}")
        if self._moved["cross_chip_bytes"]:
            return (f"{self._moved['cross_chip_bytes']} bytes changed chip "
                    f"outside the exchange")
        if self._moved["shuffle_device_fallbacks"]:
            return (f"{self._moved['shuffle_device_fallbacks']} exchanges "
                    f"fell back to files")
        if not self._moved["shuffle_device_exchanges"]:
            return "no exchange went over the mesh"
        return None

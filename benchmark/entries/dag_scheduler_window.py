"""Entry point `dag_scheduler_window`: `dag_scheduler_smj` for a plan whose
window functions have to run on the chip.

A program whose `WindowExec` has no resident lane reads every sorted row
back, scans it on the host and places it again, three times a query in
q51, and a program without the device merge join walks a full outer join
of 690K rows in a Python cursor.  Neither is the path this cell is there
to time, so this entry plans the query through the program's planner in
its constructor and refuses, before any query, a program that has no
device merge join (`dag_scheduler_smj`'s refusal) or that plans one of the
query's window nodes with no device form: a run on it fails at once and
cleanly instead of slowly.

After each query it says if a window's rows left the chip
(`window_resident_rows` < `window_rows`), if a key run streamed through
the run cursor, if a sort left its resident lane, if a task left the stage
loop, or if compute was placed on the host.  The full answer
(`plan_full` against `full_oracle`) is compared after every warm-up query,
as `dag_scheduler_smj` does.
"""

from __future__ import annotations

from benchmark.entries.dag_scheduler_smj import Entry as SmjEntry

WATCHED = ("window_rows", "window_resident_rows", "sort_device_rows",
           "sort_resident_rows", "stage_loop_fallbacks")


def window_nodes(plan):
    """Every `WindowExec` of a planned tree."""
    found, stack = [], [plan]
    while stack:
        node = stack.pop()
        if type(node).__name__ == "WindowExec":
            found.append(node)
        stack.extend(getattr(node, "children", None) or [])
    return found


class Entry(SmjEntry):
    def __init__(self, query, paths, tables, cfg, work_dir: str):
        super().__init__(query, paths, tables, cfg, work_dir)
        self._refuse_host_windows(self.plan)
        self._moved = {}

    @staticmethod
    def _refuse_host_windows(plan: dict) -> None:
        from blaze_tpu.plan.planner import create_plan
        nodes = window_nodes(create_plan(plan))
        if not nodes:
            raise RuntimeError("the plan has no window node: this entry "
                               "is for plans that have")
        for node in nodes:
            if getattr(node, "_scan_funcs", None) is None:
                raise RuntimeError(
                    f"this program plans "
                    f"{[f.name for f in node.funcs]} as a WindowExec "
                    f"with no resident lane (blaze_tpu/ops/window.py, "
                    f"blaze_tpu/kernels/window.py): every sorted row "
                    f"would be read back, scanned on the host and placed "
                    f"again, which is not what this cell times")

    def _watched(self) -> dict:
        from blaze_tpu.bridge import xla_stats
        now = xla_stats.snapshot()
        return {k: now.get(k, 0) for k in WATCHED}

    def begin(self) -> None:
        super().begin()
        self._start = self._watched()

    def end(self) -> None:
        super().end()
        now = self._watched()
        self._moved = {k: now[k] - self._start[k] for k in WATCHED}

    def problem(self):
        from blaze_tpu.bridge.placement import host_resident
        m = self._moved
        if host_resident():
            return "compute is placed on the host"
        if not m["window_rows"]:
            return "no window node ran"
        if m["window_resident_rows"] < m["window_rows"]:
            return (f"{m['window_rows'] - m['window_resident_rows']} of "
                    f"{m['window_rows']} window rows left the chip (the "
                    f"host lane)")
        if m["sort_resident_rows"] < m["sort_device_rows"]:
            return (f"{m['sort_device_rows'] - m['sort_resident_rows']} "
                    f"sorted rows left the chip (the sort's host lane)")
        if m["stage_loop_fallbacks"]:
            return f"{m['stage_loop_fallbacks']} tasks left the stage loop"
        return super().problem()

"""Source `metric_tree`: the per-task operator metric trees the program
records at `finalize()` (`bridge/profiling.recent_metrics()`).

An operator's `elapsed_compute_ns` includes the time it waited on its
children, so its own time is that less its children's.  Tasks of a stage
run concurrently, so own times are summed over tasks and a share is of
summed task time, not of the query's wall.

spec["read"]: {"operators": [ExecutionPlan class names]}
value: 100 * own time of those operators / own time of all operators.
A cell whose plan has none of the operators has nothing to read.
"""

from __future__ import annotations

FIELD = "elapsed_compute_ns"


def own_times(trees) -> dict:
    """{operator name: summed own ns} over every node of every tree."""
    out: dict = {}
    stack = list(trees)
    while stack:
        node = stack.pop()
        kids = node.get("children") or []
        total = (node.get("values") or {}).get(FIELD, 0)
        own = max(0, total - sum((k.get("values") or {}).get(FIELD, 0)
                                 for k in kids))
        name = node.get("name") or "?"
        out[name] = out.get(name, 0) + own
        stack.extend(kids)
    return out


def read(spec: dict, ctx: dict):
    own = own_times(ctx["trees"])
    total = sum(own.values())
    wanted = [op for op in spec["read"]["operators"] if op in own]
    if not wanted or not total:
        return None
    return 100.0 * sum(own[op] for op in wanted) / total

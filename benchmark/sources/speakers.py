"""Which threads speak for a task at an instant, and what each is inside:
`span_gap_op`'s rule as a thing two more readers can ask (`eager_by_op`,
`span_wall`).  No source of its own: no metric file names it.

`span_gap_op.summarize` keeps the rule nested inside its reduction; here
it is restated over the same module-level helpers (`_timelines`, `_walk`,
`span_gap.task_thread_spans`), and `tests/test_bench_ledger.py` holds the
two to the same idle seconds by family on the recorded traces.

  * every thread is asked for its innermost open span at the instant and
    walks outward by `parent`; it speaks for a task if the walk reaches a
    `task` span open then (its own, or the one a prefetch worker adopted);
  * a thread inside a `prefetch_wait` for a pipeline stage is silent, the
    stage's thread speaks for it; where a task has no other speaker the
    silent thread speaks itself;
  * leaf prefetch threads never speak (their spans are dropped first).
"""

from __future__ import annotations

import bisect

from benchmark.sources import span_gap
from benchmark.sources.span_gap_op import (NO_OP, OP_PREFIX, OTHER, TASK,
                                           _timelines, _walk)


class Speakers:
    def __init__(self, spans, table: dict):
        real = [s for s in spans if s["dur_ns"] > 0]
        kept = span_gap.task_thread_spans(real)
        kept_ids = {id(s) for s in kept}
        # dropped and a prefetch_wait: a wait for a pipeline stage
        waits = [s for s in real if s["name"] == "prefetch_wait"
                 and id(s) not in kept_ids]
        self.real = real
        self.by_sid = {s["sid"]: s for s in real if "sid" in s}
        self.open, self.silences = _timelines(kept), _timelines(waits)
        self.boundary = set(table["boundary"])
        self.family_of = {n: f for f, names in table["families"].items()
                          for n in names}
        self.families = list(table["families"]) + [OTHER, NO_OP]

    def _said(self, key, t):
        """(task span, silent, family, first span that is no boundary
        span, names of the walk below the task) if thread `key` speaks at
        `t`, else None."""
        inner = self.open[key].at(t)
        if inner is None:
            return None
        family = idle = owner = None
        names = []
        for s in _walk(inner, self.by_sid):
            name = s["name"]
            if name == TASK:
                if s["t0_ns"] <= t < s["t1_ns"]:
                    owner = s
                break
            names.append(name)
            if idle is None and name not in self.boundary:
                idle = name
            if family is None:
                family = self.family_of.get(name) or (
                    OTHER if name.startswith(OP_PREFIX) else None)
        if owner is None:
            return None
        silent = key in self.silences and \
            self.silences[key].at(t) is not None
        return owner, silent, family or NO_OP, idle or TASK, names

    @staticmethod
    def _loudest(said, device=None):
        """Of [(task span, silent, ...)], the loud threads a task (its
        silent ones where it has no other), less the leading two fields."""
        by_task = {}
        for x in said:
            if device is None or \
                    (x[0].get("attrs") or {}).get("device") == device:
                by_task.setdefault(id(x[0]), []).append(x)
        out = []
        for of_task in by_task.values():
            loud = [x for x in of_task if not x[1]]
            out.extend(x[2:] for x in (loud or of_task))
        return out

    def at(self, t, device=None):
        """[(family, first span of the walk that is no boundary span,
        names of the walk below the task)] of the threads that speak at
        `t` (on the spans' clock); with `device`, only for tasks whose
        `task` span says that chip."""
        said = [x for x in (self._said(k, t) for k in self.open) if x]
        return self._loudest(said, device)

    def _sweep(self):
        """(t0, t1, [`_said` of every thread that speaks in [t0, t1)]) over
        the whole trace, in order: one pass over the threads' change
        points."""
        events = []
        for key, line in self.open.items():
            times = set(line.times)
            if key in self.silences:
                times.update(self.silences[key].times)
            times = sorted(times)
            for t, nxt in zip(times, times[1:] + [None]):
                said = self._said(key, t)
                events.append((t, key, said))
                if said and nxt is not None and said[0]["t1_ns"] < nxt:
                    events.append((said[0]["t1_ns"], key, None))
        events.sort(key=lambda e: e[0])
        speaking = {}
        for i, (t, key, said) in enumerate(events):
            if said is None:
                speaking.pop(key, None)
            else:
                speaking[key] = said
            if i + 1 < len(events) and events[i + 1][0] > t and speaking:
                yield t, events[i + 1][0], list(speaking.values())

    def segments(self):
        """(t0, t1, what `at` gives anywhere in [t0, t1)) for the stretches
        in which somebody speaks."""
        for t0, t1, said in self._sweep():
            yield t0, t1, self._loudest(said)

    def lookup(self):
        """`at`, for many instants: the sweep once, then a bisection an
        instant."""
        swept = list(self._sweep())
        starts = [s[0] for s in swept]

        def at(t, device=None):
            i = bisect.bisect_right(starts, t) - 1
            if i < 0 or t >= swept[i][1]:
                return []
            return self._loudest(swept[i][2], device)
        return at

"""A plain reference for `WindowExec`: every function it supports, a row at
a time over Python lists, with no numpy scan in it.

Rows arrive sorted by (partition keys, order keys), as the operator's do.
A partition is a stretch of rows with equal partition keys, an order run a
stretch of a partition with equal order keys; None equals None.  A running
frame is Spark's RANGE frame, UNBOUNDED PRECEDING to CURRENT ROW: it ends
at the last row of the current row's order run, so ties share a value.
Without order keys every frame is the whole partition and every row its
own order run.  Aggregates skip None; sum, min, max and avg of no value are
None, a count is 0.  Sums add in row order, first row to last.
"""

from __future__ import annotations

RANKS = ("row_number", "rank", "dense_rank", "percent_rank", "cume_dist")
AGGS = ("sum", "count", "min", "max", "avg")


def partitions(part_keys):
    """[(first row, one past the last)] of each partition."""
    out, first = [], 0
    for i in range(1, len(part_keys) + 1):
        if i == len(part_keys) or part_keys[i] != part_keys[first]:
            out.append((first, i))
            first = i
    return out


def _runs(order_keys, lo, hi, ordered):
    """[(first row, one past the last)] of each order run of [lo, hi)."""
    if not ordered:
        return [(i, i + 1) for i in range(lo, hi)]
    out, first = [], lo
    for i in range(lo + 1, hi + 1):
        if i == hi or order_keys[i] != order_keys[first]:
            out.append((first, i))
            first = i
    return out


class _Acc:
    """One aggregate's state, fed a row at a time; None is skipped."""

    def __init__(self, kind):
        self.kind, self.acc, self.n = kind, None, 0

    def add(self, v):
        if v is None:
            return
        self.n += 1
        if self.acc is None:
            self.acc = v
        elif self.kind in ("sum", "avg"):
            self.acc = self.acc + v
        elif self.kind == "min":
            self.acc = v if v < self.acc else self.acc
        elif self.kind == "max":
            self.acc = v if v > self.acc else self.acc

    def value(self):
        if self.kind == "count":
            return self.n
        if self.kind == "avg" and self.acc is not None:
            return self.acc / self.n
        return self.acc


def window(part_keys, order_keys, func, values=None, running=True,
           ordered=True):
    """One function's column.  `part_keys` / `order_keys`: a tuple a row;
    `func`: a name of RANKS or AGGS; `values`: the argument a row (None
    for count(*) and the rank family)."""
    n = len(part_keys)
    out = [None] * n
    for lo, hi in partitions(part_keys):
        size = hi - lo
        acc = _Acc(func)
        if func in AGGS and not (running and ordered):
            for i in range(lo, hi):     # the whole partition's frame
                acc.add(1 if values is None else values[i])
        for dense, (a, b) in enumerate(_runs(order_keys, lo, hi, ordered),
                                       start=1):
            if func in AGGS and running and ordered:
                for i in range(a, b):   # the frame ends with the run
                    acc.add(1 if values is None else values[i])
            for i in range(a, b):
                if func == "row_number":
                    out[i] = i - lo + 1
                elif func == "rank":
                    out[i] = a - lo + 1
                elif func == "dense_rank":
                    out[i] = dense
                elif func == "percent_rank":
                    out[i] = 0.0 if size == 1 else (a - lo) / (size - 1)
                elif func == "cume_dist":
                    out[i] = (b - lo) / size
                else:
                    out[i] = acc.value()
    return out

"""The least bytes a kernel has to move, from shapes; kept with the
benchmark so that no later PR can change what a roofline share divides."""

from __future__ import annotations


def fold_min_bytes(work, row_bytes: int, slot_bytes: int) -> int:
    """Hash aggregation, one query: `work` is the query file's
    `fold_work(tables)`, (input rows, groups) of every aggregation of the
    SQL.  Every input row's keys, value and selection byte are read once
    and every group's slot is written once.  A partial stage before the
    final one, rows handed over that a filter then drops, an Expand's
    copies, empty slots and further probe rounds cost nothing here: a
    program that moves them does more than it has to, and the share says
    so.  The same work whatever implements it."""
    return sum(rows * row_bytes + groups * slot_bytes
               for rows, groups in work)

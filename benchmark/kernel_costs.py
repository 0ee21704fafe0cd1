"""The least bytes a kernel has to move, from shapes; kept with the
benchmark so that no later PR can change what a roofline share divides."""

from __future__ import annotations


def fold_min_bytes(rows_folded: int, row_bytes: int, table_slots: int,
                   slot_bytes: int, tasks: int) -> int:
    """Hash-aggregation fold: every folded row's keys, value and selection
    byte are read once, and each task's table is written once."""
    return rows_folded * row_bytes + tasks * table_slots * slot_bytes

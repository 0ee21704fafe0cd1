"""The least bytes the merge join's device programs have to move, from
shapes; kept with the benchmark so that no later PR can change what
`smj_roofline` divides."""

from __future__ import annotations


def merge_join_min_bytes(rows: int, key_bytes: int, pairs: int,
                         pair_bytes: int) -> int:
    """Sort-merge join of two sorted sides: every row's join keys are read
    once (`rows` counts both sides), and every pair written has its columns
    of both sides read once and written once.  A row that finds no partner
    costs its keys only."""
    return rows * key_bytes + pairs * 2 * pair_bytes

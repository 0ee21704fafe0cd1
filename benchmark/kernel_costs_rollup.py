"""The least bytes the fold over an Expand's rows has to move, from shapes;
kept with the benchmark so that no later PR can change what
`q67_fold_roofline` divides."""

from __future__ import annotations


def expand_fold_min_bytes(expanded_rows: int, key_lanes: int,
                          value_bytes: int) -> int:
    """A hash-aggregation fold over `expanded_rows` rows (K projection
    lists x the input rows: what the Expand hands the aggregation, whether
    or not the rows ever exist as batches).  Every expanded row finds its
    slot by the table's 32-bit lanes, so it reads each of the `key_lanes`
    key lanes and the owner lane once (4 bytes a lane), reads its value
    (`value_bytes`) and reads and writes the slot's sum (`value_bytes`
    each way).  Further probe rounds, the claim and its undo, the batch's
    own key lanes and the table's empty slots cost nothing here: a program
    that moves them does more than it has to, and the share says so.  The
    same work whatever implements it."""
    return expanded_rows * (4 * (key_lanes + 1) + 3 * value_bytes)

"""The least bytes a rehash of the aggregation's hash table has to move,
from what the program counted; kept with the benchmark so that no later
PR can change what `rehash_roofline` divides."""

from __future__ import annotations


def rehash_min_bytes(groups_moved: int, new_slots: int,
                     slot_bytes: int) -> int:
    """Moving a table into a larger one: every group it holds is read
    once from the old table and written once into the new, and every slot
    of the new table is written once (it has to start out empty).  The
    old table's empty slots cost nothing: a rehash that reads them all
    does more than it has to, and the share says so.  `slot_bytes` is the
    query file's FOLD_SLOT_BYTES (keys, one accumulator, the used flag).
    The same work whatever implements it."""
    return groups_moved * 2 * slot_bytes + new_slots * slot_bytes

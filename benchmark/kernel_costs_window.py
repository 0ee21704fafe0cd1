"""The least bytes a window node's scans have to move, from shapes; kept
with the benchmark so that no later PR can change what `window_roofline`
divides."""

from __future__ import annotations


def window_min_bytes(rows: int, key_bytes: int, value_bytes: int,
                     out_bytes: int) -> int:
    """Window functions over `rows` sorted rows: every row's partition and
    order keys (`key_bytes`) and argument columns (`value_bytes`) are read
    once, every result column (`out_bytes`) is written once; each width
    counts a column's validity byte with its value.  Flags, positions and
    the scan's intermediate levels cost nothing here: a program that moves
    them through memory does more than it has to, and the share says so.
    `sources/window_roofline.py` prices each run a window node scanned on
    the chip (its span's rows and functions) with the widths the query file
    states, so a cell with several window nodes reads their total; the
    program's own count of the same sum is its counter `window_scan_bytes`,
    and `tests/test_bench_q51.py` holds the two equal."""
    return rows * (key_bytes + value_bytes + out_bytes)

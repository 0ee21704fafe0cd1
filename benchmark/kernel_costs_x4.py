"""The least bytes the mesh exchange has to move, from what the program
counted; kept with the benchmark so that no later PR can change what
`exchange_roofline` divides."""

from __future__ import annotations


def exchange_min_bytes(row_bytes: int) -> int:
    """Hash exchange over the mesh collective: on the sending side every
    row is read once and written once (into its destination's bucket),
    and on the receiving side once more each (out of the bucket, across
    the interconnect, into the received columns).  `row_bytes` are the
    exchanged rows as they ride (counter `shuffle_device_row_bytes`: the
    exchanged schema's columns, a validity byte a column, the 4-byte
    partition id and the row's own mask byte, times the rows).  Padding
    is not counted: the buckets' capacity is the program's choice."""
    return 4 * row_bytes

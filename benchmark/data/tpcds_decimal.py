"""`tpcds_data.py`'s tables with the specification's money type.

TPC-DS declares every amount `decimal(7,2)`; `tpcds_data.py` draws them as
float64 and its docstring asks for this file: a generator of its own, named
by a configuration of its own, so that no cell that runs on float money
moves.  Every table, key, row count, NULL and date order is
`tpcds_data.py`'s for the same `data_seed`; every float64 column (each one
is an amount of money, rounded to the cent where it was drawn) becomes
`decimal128(7,2)` holding the SAME number of cents: unscaled =
round(x * 100).  So the float twin's cardinalities, groups and batch shapes
are this generator's, and only the type of the amounts differs.

The files are written as Spark writes a `decimal(7,2)` column (parquet
INT32 with the DECIMAL annotation), not as pyarrow's default fixed-length
byte array.  The interface and the meaning of the two seeds are
`tpcds_data.py`'s: `data_seed` draws every value, `--seed` reorders rows
inside 1,024-row blocks of each file.
"""

from __future__ import annotations

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.data.tpcds_data import (  # noqa: F401  the same interface
    GENERATORS, SEED_BLOCK_ROWS, SF1_ROWS, _n_files, reorder, rows)

MONEY = pa.decimal128(7, 2)


def _decimal_from_cents(cents: np.ndarray, valid: np.ndarray) -> pa.Array:
    """int64 cents -> decimal128(7,2) without a cast (a cast would
    rescale): the 16-byte little-endian two's-complement values."""
    limbs = np.empty((len(cents), 2), dtype=np.int64)
    limbs[:, 0] = cents
    limbs[:, 1] = cents >> 63
    bits = None
    if not valid.all():
        bits = pa.py_buffer(
            np.packbits(valid.astype(np.uint8), bitorder="little").tobytes())
    return pa.Array.from_buffers(MONEY, len(cents),
                                 [bits, pa.py_buffer(limbs.tobytes())],
                                 null_count=int((~valid).sum()))


def money_to_decimal(table: pa.Table) -> pa.Table:
    """Every float64 column as decimal128(7,2) of the same cents."""
    for i, f in enumerate(table.schema):
        if not pa.types.is_float64(f.type):
            continue
        col = table.column(i).combine_chunks()
        valid = np.asarray(col.is_valid())
        x = col.fill_null(0.0).to_numpy(zero_copy_only=False)
        cents = np.rint(x * 100).astype(np.int64)
        if np.abs(cents).max(initial=0) >= 10 ** 7:
            raise ValueError(f"{f.name}: an amount does not fit "
                             f"decimal(7,2)")
        table = table.set_column(i, pa.field(f.name, MONEY),
                                 _decimal_from_cents(cents, valid))
    return table


def make_tables(names, scale: float, data_seed: int, splits: int,
                seed: int) -> dict:
    return {n: reorder(money_to_decimal(GENERATORS[n](scale, data_seed)),
                       splits, seed)
            for n in names}


def write_parquet_splits(tables, out_dir: str, splits: int,
                         row_group_size: int = 1 << 16):
    """{name: [[file], [file], ...]} in the parquet_scan file_groups shape;
    `tpcds_data.write_parquet_splits` with decimals stored as Spark stores
    them."""
    paths = {}
    for name, t in tables.items():
        d = os.path.join(out_dir, name)
        os.makedirs(d, exist_ok=True)
        nparts = _n_files(t, splits)
        per = -(-t.num_rows // nparts)
        groups = []
        for i in range(nparts):
            p = os.path.join(d, f"part-{i:05d}.parquet")
            pq.write_table(t.slice(i * per, per), p,
                           row_group_size=row_group_size,
                           store_decimal_as_integer=True)
            groups.append([p])
        paths[name] = groups
    return paths

"""Seeded TPC-DS-shaped tables: the benchmark's own copy of the generator.

Copied from `blaze_tpu/itest/tpcds_data.py` (the original is listed in
PERF.md's open questions).  Data stands where a model's weights would: a
change to it (money as decimal(7,2), skewed keys) has to arrive as a new
generator file named by a new configuration, never as an edit under the
cells that already run on this one.

Same schemas, key relationships, row counts and dsdgen date clustering as
the original; only the tables the benchmark's queries touch are kept.

What the two seeds do.  The configuration's `data_seed` draws the values,
and with them every cardinality: rows passing a filter, matches of a join,
groups of a task.  The run's `--seed` only reorders rows, inside blocks of
SEED_BLOCK_ROWS rows counted from the start of each file (`reorder`), so
every batch, task and partition holds the same rows in another order.  The
program compiles one XLA program per array shape, and on the chip a new
cardinality was some 300 new programs and 500 s of set-up (PERF.md, PR 23):
a seed that redrew the values would make every run a compiling run.
"""

from __future__ import annotations

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

SF1_ROWS = {
    "store_returns": 287_514,
    "store_sales": 2_880_404,
    "store": 12,
    "customer": 100_000,
    "customer_address": 50_000,
    "customer_demographics": 1_920_800,
    "date_dim": 73_049,
    "item": 18_000,
}
FIXED_SIZE = ("store", "date_dim")
D0 = 2450815            # first d_date_sk
SALES_DATE_DAYS = 1826  # facts span ~5 years (1998-2002)


def rows(name: str, scale: float) -> int:
    base = SF1_ROWS[name]
    if name in FIXED_SIZE:
        return base
    if name == "customer_demographics":
        return min(base, max(1, int(base * max(scale, 0.01))))
    return max(1, int(base * scale))


def _rng(seed: int, table: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), sorted(SF1_ROWS).index(table)])


def _date_ordered(tbl: pa.Table, date_col: str) -> pa.Table:
    # dsdgen emits fact rows per calendar date, so real loads carry
    # date-key clustering and selective row-group statistics
    return tbl.sort_by([(date_col, "ascending")])


def gen_date_dim(scale: float, seed: int) -> pa.Table:
    n = rows("date_dim", scale)
    day = np.arange(n)
    moy = np.minimum((day % 365) // 31 + 1, 12)
    return pa.table({
        "d_date_sk": pa.array(D0 + day),
        "d_year": pa.array((1998 + day // 365).astype(np.int32)),
        "d_moy": pa.array(moy.astype(np.int32)),
        "d_dom": pa.array(((day % 31) + 1).astype(np.int32)),
        "d_dow": pa.array((day % 7).astype(np.int32)),
        "d_week_seq": pa.array((day // 7 + 1).astype(np.int32)),
        "d_qoy": pa.array(((moy - 1) // 3 + 1).astype(np.int32)),
    })


def gen_store(scale: float, seed: int) -> pa.Table:
    n = rows("store", scale)
    rng = _rng(seed, "store")
    states = np.array(["TN", "CA", "NY", "TX", "WA"])
    return pa.table({
        "s_store_sk": pa.array(np.arange(1, n + 1)),
        "s_state": pa.array(states[rng.integers(0, len(states), n)]),
        "s_store_name": pa.array([f"store_{i}" for i in range(1, n + 1)]),
    })


def gen_customer(scale: float, seed: int) -> pa.Table:
    n = rows("customer", scale)
    rng = _rng(seed, "customer")
    return pa.table({
        "c_customer_sk": pa.array(np.arange(1, n + 1)),
        "c_customer_id": pa.array(
            np.char.add("C", np.char.zfill(
                np.arange(1, n + 1).astype(str), 11))),
        "c_current_addr_sk": pa.array(
            rng.integers(1, rows("customer_address", scale) + 1, n)),
        "c_current_cdemo_sk": pa.array(
            rng.integers(1, rows("customer_demographics", scale) + 1, n)),
        "c_birth_year": pa.array(
            rng.integers(1924, 1993, n).astype(np.int32)),
    })


def gen_store_returns(scale: float, seed: int) -> pa.Table:
    n = rows("store_returns", scale)
    rng = _rng(seed, "store_returns")
    date_n = min(rows("date_dim", scale), SALES_DATE_DAYS)
    null_mask = rng.random(n) < 0.02
    cust = rng.integers(1, rows("customer", scale) + 1, n)
    return _date_ordered(pa.table({
        "sr_returned_date_sk": pa.array(rng.integers(D0, D0 + date_n, n)),
        "sr_customer_sk": pa.array(cust, mask=null_mask),
        "sr_store_sk": pa.array(rng.integers(1, rows("store", scale) + 1, n)),
        "sr_return_amt": pa.array(np.round(rng.random(n) * 500, 2)),
        "sr_ticket_number": pa.array(np.arange(1, n + 1)),
        "sr_item_sk": pa.array(rng.integers(1, rows("item", scale) + 1, n)),
        "sr_return_quantity": pa.array(
            rng.integers(1, 50, n).astype(np.int32)),
        "sr_reason_sk": pa.array(rng.integers(1, 36, n)),
        "sr_net_loss": pa.array(np.round(rng.random(n) * 60, 2)),
    }), "sr_returned_date_sk")


def gen_store_sales(scale: float, seed: int) -> pa.Table:
    n = rows("store_sales", scale)
    rng = _rng(seed, "store_sales")
    date_n = min(rows("date_dim", scale), SALES_DATE_DAYS)
    return _date_ordered(pa.table({
        "ss_sold_date_sk": pa.array(rng.integers(D0, D0 + date_n, n)),
        "ss_customer_sk": pa.array(
            rng.integers(1, rows("customer", scale) + 1, n)),
        "ss_store_sk": pa.array(rng.integers(1, rows("store", scale) + 1, n)),
        "ss_item_sk": pa.array(rng.integers(1, rows("item", scale) + 1, n)),
        "ss_ext_sales_price": pa.array(np.round(rng.random(n) * 300, 2)),
        "ss_quantity": pa.array(rng.integers(1, 100, n).astype(np.int32)),
        "ss_ticket_number": pa.array(np.arange(1, n + 1)),
        "ss_cdemo_sk": pa.array(
            rng.integers(1, rows("customer_demographics", scale) + 1, n)),
        "ss_promo_sk": pa.array(rng.integers(1, 301, n)),
        "ss_list_price": pa.array(np.round(rng.random(n) * 320, 2)),
        "ss_coupon_amt": pa.array(np.round(rng.random(n) * 40, 2)),
        "ss_sales_price": pa.array(np.round(rng.random(n) * 280, 2)),
        "ss_net_profit": pa.array(np.round(rng.random(n) * 120 - 20, 2)),
        "ss_hdemo_sk": pa.array(rng.integers(1, 7_201, n)),
        "ss_addr_sk": pa.array(
            rng.integers(1, rows("customer_address", scale) + 1, n)),
        "ss_sold_time_sk": pa.array(rng.integers(0, 86_400, n)),
    }), "ss_sold_date_sk")


def gen_item(scale: float, seed: int) -> pa.Table:
    n = rows("item", scale)
    rng = _rng(seed, "item")
    cats = np.array(["Books", "Home", "Sports", "Music", "Electronics"])
    brands = np.array([f"brand_{i}" for i in range(50)])
    classes = np.array([f"class_{i}" for i in range(16)])
    brand_ids = rng.integers(1, 51, n)
    return pa.table({
        "i_item_sk": pa.array(np.arange(1, n + 1)),
        "i_item_id": pa.array(
            np.char.add("I", np.char.zfill(
                np.arange(1, n + 1).astype(str), 9))),
        "i_category": pa.array(cats[rng.integers(0, len(cats), n)]),
        "i_class": pa.array(classes[rng.integers(0, len(classes), n)]),
        "i_brand_id": pa.array(brand_ids.astype(np.int32)),
        "i_brand": pa.array(brands[brand_ids - 1]),
        "i_manager_id": pa.array(rng.integers(1, 100, n).astype(np.int32)),
        "i_manufact_id": pa.array(
            rng.integers(1, 1001, n).astype(np.int32)),
        "i_current_price": pa.array(np.round(rng.random(n) * 100, 2)),
    })


GENERATORS = {
    "date_dim": gen_date_dim,
    "store": gen_store,
    "customer": gen_customer,
    "store_returns": gen_store_returns,
    "store_sales": gen_store_sales,
    "item": gen_item,
}

# rows are reordered inside blocks of this many; the program's batches
# (32,768 rows) and the row groups (65,536) are multiples of it
SEED_BLOCK_ROWS = 1024

# tables with more rows than this are split over the configured number of
# files (one scan file group each); dimension tables stay one file
SPLIT_MIN_ROWS = 10_000


def write_parquet_splits(tables, out_dir: str, splits: int,
                         row_group_size: int = 1 << 16):
    """{name: [[file], [file], ...]} in the parquet_scan file_groups shape."""
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
                           row_group_size=row_group_size)
            groups.append([p])
        paths[name] = groups
    return paths


def _n_files(table: pa.Table, splits: int) -> int:
    return splits if table.num_rows > SPLIT_MIN_ROWS else 1


def reorder(table: pa.Table, splits: int, seed: int) -> pa.Table:
    """The same rows, shuffled inside blocks of SEED_BLOCK_ROWS rows that
    start at each file's first row."""
    n = table.num_rows
    per = -(-n // _n_files(table, splits))
    row = np.arange(n)
    in_file = row % per
    block = (row // per) * (per // SEED_BLOCK_ROWS + 1) \
        + in_file // SEED_BLOCK_ROWS
    rng = np.random.default_rng([int(seed), n])
    return table.take(np.lexsort((rng.random(n), block)))


def make_tables(names, scale: float, data_seed: int, splits: int,
                seed: int) -> dict:
    return {n: reorder(GENERATORS[n](scale, data_seed), splits, seed)
            for n in names}

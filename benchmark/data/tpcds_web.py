"""`tpcds_data.py`'s store tables beside a web channel and a calendar.

TPC-DS q51 compares an item's running web sales with its running store
sales, day by day.  `tpcds_data.py` has no `web_sales` and its `date_dim`
carries neither the calendar date nor the month sequence that the query's
predicate is written in; the tables in place may not change under the cells
that run on them, so this is a generator of its own, named by its own
configuration.

  store_sales  `tpcds_data.py`'s, row for row for the same `data_seed` (what
  item         `tpcds-sf1-x1` draws).
  web_sales    `rows("web_sales")` rows (719,384 at scale 1, dsdgen's count),
               under dsdgen's column names for what this generator carries:
               sold dates uniform over the same 1,826 days with 2% NULL,
               items and customers uniform, 8 to 16 lines an order number,
               float64 money, date order (NULL dates last).
  date_dim     `tpcds_data.py`'s 73,049 days with two more columns: `d_date`
               (date32, the calendar day of `d_date_sk`, 1998-01-01 onward)
               and `d_month_seq` = (d_year - 1900) * 12 + d_moy - 1, so that
               1200..1211 is the generator's year 2000 (days 730 to 1,094).

The interface and the meaning of the two seeds are `tpcds_data.py`'s:
`data_seed` draws every value, `--seed` reorders rows inside 1,024-row
blocks of each file.
"""

from __future__ import annotations

import datetime

import numpy as np
import pyarrow as pa

from benchmark.data.tpcds_data import (  # noqa: F401  the same interface
    D0, GENERATORS, SALES_DATE_DAYS, SEED_BLOCK_ROWS, SF1_ROWS, _date_ordered,
    reorder, write_parquet_splits)
from benchmark.data import tpcds_data

WEB_SALES_SF1_ROWS = 719_384
ORDER_LINES = (8, 16)    # line items an order, both ends included
NULL_DATE_SHARE = 0.02
# d_date_sk D0 is 1998-01-01, as in dsdgen's calendar
DAY0 = (datetime.date(1998, 1, 1) - datetime.date(1970, 1, 1)).days
TABLES = ("store_sales", "item", "web_sales", "date_dim")


def rows(name: str, scale: float) -> int:
    if name == "web_sales":
        return max(1, int(WEB_SALES_SF1_ROWS * scale))
    return tpcds_data.rows(name, scale)


def gen_web_sales(scale: float, seed: int) -> pa.Table:
    n = rows("web_sales", scale)
    rng = np.random.default_rng([int(seed), 51])
    date_n = min(rows("date_dim", scale), SALES_DATE_DAYS)
    lo, hi = ORDER_LINES
    lines = rng.integers(lo, hi + 1, n // lo + 1)
    order = np.repeat(np.arange(1, len(lines) + 1), lines)[:n]
    return _date_ordered(pa.table({
        "ws_sold_date_sk": pa.array(rng.integers(D0, D0 + date_n, n),
                                    mask=rng.random(n) < NULL_DATE_SHARE),
        "ws_item_sk": pa.array(rng.integers(1, rows("item", scale) + 1, n)),
        "ws_bill_customer_sk": pa.array(
            rng.integers(1, rows("customer", scale) + 1, n)),
        "ws_order_number": pa.array(order),
        "ws_quantity": pa.array(rng.integers(1, 101, n).astype(np.int32)),
        "ws_sales_price": pa.array(np.round(rng.random(n) * 300, 2)),
        "ws_ext_sales_price": pa.array(np.round(rng.random(n) * 30_000, 2)),
        "ws_net_profit": pa.array(np.round(rng.random(n) * 12_000 - 2_000,
                                           2)),
    }), "ws_sold_date_sk")


def gen_date_dim(scale: float, seed: int) -> pa.Table:
    t = tpcds_data.gen_date_dim(scale, seed)
    day = np.arange(t.num_rows, dtype=np.int32)
    year = t.column("d_year").to_numpy().astype(np.int32)
    moy = t.column("d_moy").to_numpy().astype(np.int32)
    return t.append_column(
        "d_date", pa.array(DAY0 + day).cast(pa.date32())
    ).append_column("d_month_seq", pa.array((year - 1900) * 12 + moy - 1))


_OWN = {"web_sales": gen_web_sales, "date_dim": gen_date_dim}


def make_tables(names, scale: float, data_seed: int, splits: int,
                seed: int) -> dict:
    unknown = set(names) - set(TABLES)
    if unknown:
        raise KeyError(f"tpcds_web makes {TABLES}, not {sorted(unknown)}")
    return {n: reorder((_OWN.get(n) or GENERATORS[n])(scale, data_seed),
                       splits, seed)
            for n in names}

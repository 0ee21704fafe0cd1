"""Seeded TPC-DS-shaped store tables in which a return IS a sale's line item.

`tpcds_data.py` draws `store_returns` independently of `store_sales`
(`sr_ticket_number = arange`, `sr_item_sk` random), so a return matches a
sale on (item, ticket) with probability 1/18,000 and the fact-to-fact joins
(q17, q25, q29, q93) carry next to no rows.  dsdgen draws a return from a
sale.  This file does too; the one in place may not change under the cells
that run on it, so this is a generator of its own, named by its own
configuration.

  store_sales    tickets of 8 to 16 line items that share customer, store,
                 date and time, as dsdgen draws them: (item, ticket) is
                 unique, the ticket alone is not.  Ticket numbers rise with
                 the date.
  store_returns  `rows("store_returns")` of those line items, without
                 replacement: item, ticket, customer and store copied, the
                 return 1 to 90 days after the sale, the returned quantity
                 between 1 and the quantity sold, the reason uniform over
                 `reason`'s rows.
  reason         35 rows, `r_reason_desc` = 'reason <sk>'.

Both fact tables are in date order.  Money is float64 and foreign keys are
uniform, as in `tpcds_data.py`; no foreign key is NULL.  The interface and
the meaning of the two seeds are `tpcds_data.py`'s: `data_seed` draws every
value, `--seed` reorders rows inside 1,024-row blocks of each file.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from benchmark.data.tpcds_data import (  # noqa: F401  the same interface
    D0, SALES_DATE_DAYS, SEED_BLOCK_ROWS, SF1_ROWS, reorder,
    write_parquet_splits)

REASON_ROWS = 35
TICKET_LINES = (8, 16)   # line items a ticket, both ends included
RETURN_DAYS = (1, 90)    # days from the sale to its return
TABLES = ("store_sales", "store_returns", "reason")


def rows(name: str, scale: float) -> int:
    if name == "reason":
        return REASON_ROWS
    return max(1, int(SF1_ROWS[name] * scale))


def _rng(seed: int, table: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), 93, TABLES.index(table)])


def _sales_columns(scale: float, seed: int) -> dict:
    """store_sales as numpy columns, in date (and so ticket) order."""
    n = rows("store_sales", scale)
    rng = _rng(seed, "store_sales")
    lo, hi = TICKET_LINES
    lines = rng.integers(lo, hi + 1, n // lo + 1)
    lines = lines[:int(np.searchsorted(np.cumsum(lines), n)) + 1]
    lines[-1] -= int(lines.sum()) - n          # the last ticket is cut to fit
    tickets = len(lines)
    date_n = min(SF1_ROWS["date_dim"], SALES_DATE_DAYS)
    per_ticket = {
        "ss_sold_date_sk": np.sort(rng.integers(D0, D0 + date_n, tickets)),
        "ss_ticket_number": np.arange(1, tickets + 1),
        "ss_customer_sk": rng.integers(1, rows("customer", scale) + 1,
                                       tickets),
        "ss_store_sk": rng.integers(1, SF1_ROWS["store"] + 1, tickets),
        "ss_sold_time_sk": rng.integers(0, 86_400, tickets),
    }
    cols = {k: np.repeat(v, lines) for k, v in per_ticket.items()}
    items = rows("item", scale)
    item = rng.integers(1, items + 1, n)
    # an item occurs once in a ticket: redraw the later of each equal pair
    ticket = cols["ss_ticket_number"]
    while True:
        order = np.lexsort((item, ticket))
        dup = np.zeros(n, bool)
        dup[order[1:]] = (ticket[order[1:]] == ticket[order[:-1]]) \
            & (item[order[1:]] == item[order[:-1]])
        if not dup.any():
            break
        item[dup] = rng.integers(1, items + 1, int(dup.sum()))
    cols["ss_item_sk"] = item
    cols["ss_quantity"] = rng.integers(1, 100, n).astype(np.int32)
    cols["ss_sales_price"] = np.round(rng.random(n) * 280, 2)
    cols["ss_list_price"] = np.round(rng.random(n) * 320, 2)
    cols["ss_ext_sales_price"] = np.round(rng.random(n) * 300, 2)
    cols["ss_coupon_amt"] = np.round(rng.random(n) * 40, 2)
    cols["ss_net_profit"] = np.round(rng.random(n) * 120 - 20, 2)
    cols["ss_cdemo_sk"] = rng.integers(
        1, rows("customer_demographics", scale) + 1, n)
    cols["ss_hdemo_sk"] = rng.integers(1, 7_201, n)
    cols["ss_addr_sk"] = rng.integers(
        1, rows("customer_address", scale) + 1, n)
    cols["ss_promo_sk"] = rng.integers(1, 301, n)
    return cols


def _returns_columns(sales: dict, scale: float, seed: int) -> dict:
    n = rows("store_returns", scale)
    rng = _rng(seed, "store_returns")
    line = np.sort(rng.choice(len(sales["ss_item_sk"]), n, replace=False))
    days = rng.integers(RETURN_DAYS[0], RETURN_DAYS[1] + 1, n)
    sold = sales["ss_quantity"][line]
    qty = (1 + np.floor(rng.random(n) * sold)).astype(np.int32)
    cols = {
        "sr_returned_date_sk": sales["ss_sold_date_sk"][line] + days,
        "sr_customer_sk": sales["ss_customer_sk"][line],
        "sr_store_sk": sales["ss_store_sk"][line],
        "sr_return_amt": np.round(qty * sales["ss_sales_price"][line], 2),
        "sr_ticket_number": sales["ss_ticket_number"][line],
        "sr_item_sk": sales["ss_item_sk"][line],
        "sr_return_quantity": qty,
        "sr_reason_sk": rng.integers(1, REASON_ROWS + 1, n),
        "sr_net_loss": np.round(rng.random(n) * 60, 2),
    }
    order = np.argsort(cols["sr_returned_date_sk"], kind="stable")
    return {k: v[order] for k, v in cols.items()}


def gen_reason() -> pa.Table:
    sk = np.arange(1, REASON_ROWS + 1)
    return pa.table({"r_reason_sk": pa.array(sk),
                     "r_reason_desc": pa.array([f"reason {i}" for i in sk])})


def make_tables(names, scale: float, data_seed: int, splits: int,
                seed: int) -> dict:
    unknown = set(names) - set(TABLES)
    if unknown:
        raise KeyError(f"tpcds_returns makes {TABLES}, not {sorted(unknown)}")
    made = {"reason": gen_reason()}
    if {"store_sales", "store_returns"} & set(names):
        sales = _sales_columns(scale, data_seed)
        made["store_sales"] = pa.table(sales)
        if "store_returns" in names:
            made["store_returns"] = pa.table(
                _returns_columns(sales, scale, data_seed))
    return {n: reorder(made[n], splits, seed) for n in names}

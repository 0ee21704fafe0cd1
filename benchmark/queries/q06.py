"""The repo's q06-shaped query: store_sales rows whose item is priced above
1.2x its category's average, counted by store.

Plan copied from `blaze_tpu/itest/queries.py` q06 (broadcast join of the
filtered item table into the fact scan, partial/final count by store, one
exchange to a single sorted result); oracle on pandas.  Entry point:
`dag_scheduler`.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from benchmark.queries.ir import (Ids, agg, binop, c, ci, exchange,
                                  filter_, join, lit, partial_final, scan)

TABLES = ["store_sales", "item"]
FACT = "store_sales"
KEYS = ["store"]
ORDERED = True
# the count fold moves one int64 key and one selection byte per row; a
# slot holds the key, an int64 count and a used flag
FOLD_ROW_BYTES = 8 + 1
FOLD_SLOT_BYTES = 8 + 8 + 1


def plan(paths, tables, partitions: int) -> dict:
    ids = Ids(paths)
    cat_avg = agg(
        agg(scan(paths, tables, "item"), [(c("i_category"), "cat")],
            [("avg", "partial", "avg_price", [c("i_current_price")])]),
        [(ci(0), "cat")],
        [("avg", "final", "avg_price", [ci(1), ci(2)])])
    it_j = join(ids, "broadcast_join", scan(paths, tables, "item"), cat_avg,
                [c("i_category")], [c("cat")])
    it_flt = filter_(it_j, binop(">", c("i_current_price"),
                                 binop("*", c("avg_price"),
                                       lit(1.2, "float64"))))
    ss_j = join(ids, "broadcast_join", scan(paths, tables, "store_sales"),
                it_flt, [c("ss_item_sk")], [c("i_item_sk")])
    counted = partial_final(
        ids, ss_j, [(c("ss_store_sk"), "store")],
        [("count", "cnt", [c("ss_sold_date_sk")])], partitions)
    single = exchange(ids, counted, [ci(0)], 1)
    return {"kind": "sort", "input": single,
            "specs": [{"expr": ci(0), "descending": False,
                       "nulls_first": True}]}


def oracle(tables, money=np.float64) -> pa.Table:
    ss = tables["store_sales"].select(
        ["ss_item_sk", "ss_store_sk", "ss_sold_date_sk"]).to_pandas()
    it = tables["item"].select(
        ["i_item_sk", "i_category", "i_current_price"]).to_pandas()
    it["i_current_price"] = it["i_current_price"].astype(money)
    avg = it.groupby("i_category", as_index=False).i_current_price.mean() \
        .rename(columns={"i_current_price": "avg_price"})
    j = it.merge(avg, on="i_category")
    sel = j[j.i_current_price > money(1.2) * j.avg_price.astype(money)]
    m = ss.merge(sel, left_on="ss_item_sk", right_on="i_item_sk")
    out = (m.groupby("ss_store_sk", as_index=False)
           .agg(cnt=("ss_sold_date_sk", "count"))
           .rename(columns={"ss_store_sk": "store"})
           .sort_values("store"))
    return pa.table({"store": out["store"].to_numpy().astype(np.int64),
                     "cnt": out["cnt"].to_numpy().astype(np.int64)})


def fold_work(tables) -> list:
    """[(input rows, groups)] of the SQL's two aggregations: the average
    price by category over `item`, and the count by store over the sales
    whose item passed."""
    it = tables["item"].select(
        ["i_item_sk", "i_category", "i_current_price"]).to_pandas()
    avg = it.groupby("i_category", dropna=False).i_current_price \
        .transform("mean")
    sel = it[it.i_current_price > 1.2 * avg]
    ss = tables["store_sales"].select(["ss_item_sk", "ss_store_sk"]) \
        .to_pandas()
    kept = ss[ss.ss_item_sk.isin(sel.i_item_sk)]
    return [(len(it), it.i_category.nunique(dropna=False)),
            (len(kept), kept.ss_store_sk.nunique(dropna=False))]

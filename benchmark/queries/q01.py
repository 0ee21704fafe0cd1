"""TPC-DS q01, the north-star query: customers whose returns at a store
in 2000 exceed 1.2x that store's average, for stores in TN, first 100 ids.

Plan copied from `blaze_tpu/itest/queries.py` q01: three broadcast joins,
four exchanges, a sort-merge join, sort + limit, on top of q01pair's
aggregation; oracle on pandas.  Entry point: `dag_scheduler`.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from benchmark.queries.ir import (Ids, agg, binop, c, ci, exchange,
                                  filter_, join, lit, partial_final,
                                  project, scan, sort_limit)
from benchmark.queries.q01pair import (  # noqa: F401  the same fold
    FOLD_ROW_BYTES, FOLD_SLOT_BYTES, group_count, year_returns)

TABLES = ["store_returns", "date_dim", "store", "customer"]
FACT = "store_returns"
KEYS = ["c_customer_id"]
ORDERED = True


def plan(paths, tables, partitions: int) -> dict:
    ids = Ids(paths)
    dd_flt = filter_(scan(paths, tables, "date_dim"),
                     binop("==", c("d_year"), lit(2000, "int32")))
    sr_dd = join(ids, "broadcast_join",
                 scan(paths, tables, "store_returns"), dd_flt,
                 [c("sr_returned_date_sk")], [c("d_date_sk")])
    ctr = partial_final(
        ids, sr_dd,
        [(c("sr_customer_sk"), "ctr_customer_sk"),
         (c("sr_store_sk"), "ctr_store_sk")],
        [("sum", "ctr_total_return", [c("sr_return_amt")])], partitions)
    avg_in = exchange(ids, ctr, [ci(1)], partitions)
    avg_by_store = agg(
        agg(avg_in, [(ci(1), "avg_store_sk")],
            [("avg", "partial", "avg_return", [ci(2)])]),
        [(ci(0), "avg_store_sk")],
        [("avg", "final", "avg_return", [ci(1), ci(2)])])
    ctr2 = exchange(ids, ctr, [ci(1)], partitions)
    joined = join(ids, "sort_merge_join", ctr2, avg_by_store,
                  [ci(1)], [ci(0)])
    flt = filter_(joined, binop(">", c("ctr_total_return"),
                                binop("*", c("avg_return"),
                                      lit(1.2, "float64"))))
    st_flt = filter_(scan(paths, tables, "store"),
                     binop("==", c("s_state"), lit("TN", "utf8")))
    j_store = join(ids, "broadcast_join", flt, st_flt,
                   [c("ctr_store_sk")], [c("s_store_sk")])
    j_cust = join(ids, "broadcast_join", j_store,
                  scan(paths, tables, "customer"),
                  [c("ctr_customer_sk")], [c("c_customer_sk")])
    proj = project(j_cust, [c("c_customer_id")], ["c_customer_id"])
    single = exchange(ids, proj, [ci(0)], 1)
    return sort_limit(single, [(ci(0), False)], 100)


def oracle(tables, money=np.float64) -> pa.Table:
    sr = tables["store_returns"].select(
        ["sr_returned_date_sk", "sr_customer_sk", "sr_store_sk",
         "sr_return_amt"]).to_pandas()
    dd = tables["date_dim"].select(["d_date_sk", "d_year"]).to_pandas()
    st = tables["store"].to_pandas()
    cu = tables["customer"].select(
        ["c_customer_sk", "c_customer_id"]).to_pandas()
    sr["sr_return_amt"] = sr["sr_return_amt"].astype(money)
    m = sr.merge(dd[dd.d_year == 2000], left_on="sr_returned_date_sk",
                 right_on="d_date_sk")
    # GROUP BY keeps the NULL-customer group (SQL semantics); only the
    # inner join to customer drops it
    ctr = (m.groupby(["sr_customer_sk", "sr_store_sk"], as_index=False,
                     dropna=False).sr_return_amt.sum()
           .rename(columns={"sr_return_amt": "ctr_total"}))
    ctr["ctr_total"] = ctr["ctr_total"].astype(money)
    avg = ctr.groupby("sr_store_sk", as_index=False).ctr_total.mean() \
        .rename(columns={"ctr_total": "avg_return"})
    j = ctr.merge(avg, on="sr_store_sk")
    j = j[j.ctr_total > money(1.2) * j.avg_return.astype(money)]
    j = j.merge(st[st.s_state == "TN"], left_on="sr_store_sk",
                right_on="s_store_sk")
    j = j.merge(cu, left_on="sr_customer_sk", right_on="c_customer_sk")
    out = j[["c_customer_id"]].sort_values("c_customer_id")[:100]
    return pa.table({"c_customer_id":
                     pa.array(out["c_customer_id"].tolist(), pa.string())})


def fold_work(tables) -> list:
    """[(input rows, groups)] of the SQL's two aggregations: the year's
    returns by (customer, store), and those totals by store (the
    average).  The inner join to `date_dim` is the pair's key range: the
    year's days are contiguous keys."""
    f = year_returns(tables)
    ctr = f.group_by(["sr_customer_sk", "sr_store_sk"],
                     use_threads=False).aggregate([])
    return [(f.num_rows, ctr.num_rows),
            (ctr.num_rows, group_count(ctr, ["sr_store_sk"]))]

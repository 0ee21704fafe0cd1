"""TPC-DS q93: what customers really bought once returns for one reason are
taken off, the 100 smallest totals first.

  select ss_customer_sk, sum(act_sales) sumsales
  from (select ss_item_sk, ss_ticket_number, ss_customer_sk,
               case when sr_return_quantity is not null
                    then (ss_quantity - sr_return_quantity) * ss_sales_price
                    else ss_quantity * ss_sales_price end act_sales
        from store_sales left outer join store_returns
             on (sr_item_sk = ss_item_sk
                 and sr_ticket_number = ss_ticket_number), reason
        where sr_reason_sk = r_reason_sk and r_reason_desc = 'reason 28') t
  group by ss_customer_sk order by sumsales, ss_customer_sk limit 100

The plan is Spark 3's at default settings.  The predicate on `reason`
rejects NULLs, so the outer join becomes inner; without the CBO the written
join order stands; neither fact table is under the broadcast threshold.  So:
scan store_sales (five columns) -> hash exchange on (item, ticket) -> sort;
scan store_returns -> the same exchange -> sort; SortMergeJoin, inner, two
keys; broadcast join with the filtered `reason` AFTER it; project the CASE;
partial / final sum by customer; the first 100 by (sumsales, customer).
(`blaze_tpu/itest/queries_ext.py` q93 filters first, joins `left` and uses a
hash join: another plan.)  Entry point: `dag_scheduler`.

The oracle is written from the SQL, not from the plan: pandas on the host,
float64 throughout.

The order of the answer is decided exactly on both sides: with the
configuration's `data_seed` (20260927) at scale 1, of the first 101 sums of
the oracle's full answer no two lie within 1e-6 of each other unless they
are equal to the bit.  The equal ones are 0.0, a line item returned in full,
(q - q) * price on either side; among them the customer decides.
`tests/test_bench_q93.py` checks the same at the scale it runs.

So the 100 rows that answer are customer ids beside 0.0: they hold the join
and the order to account, and neither the CASE's arithmetic nor the sum.
`plan_full` and `full_oracle` are the same query less its last step, every
customer's sum (7,723 at scale 1, 7,354 of them above zero): the cell's
entry (`entries/dag_scheduler_smj.py`) runs it after every warm-up query
and holds it to `check.py`'s limits, where money in float32 comes out as
not correct (PERF.md, PR 26).
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from benchmark.queries.ir import (Ids, binop, c, ci, exchange, filter_, join,
                                  lit, partial_final, project, scan,
                                  sort_limit)

TABLES = ["store_sales", "store_returns", "reason"]
FACT = "store_sales"
KEYS = ["ss_customer_sk"]
ORDERED = True
REASON = "reason 28"
# the sum fold moves one int64 key, one float64 value and one selection byte
# per row; a slot holds the key, the sum, a validity byte and a used flag
FOLD_ROW_BYTES = 8 + 8 + 1
FOLD_SLOT_BYTES = 8 + 8 + 1 + 1

# the merge join reads two int64 keys and their validity bytes from every
# row, and moves a pair's nine columns (36 + 28 bytes of values, nine
# validity bytes)
SMJ_KEY_BYTES = 2 * (8 + 1)
SMJ_PAIR_BYTES = 36 + 28 + 9

SS_COLUMNS = ["ss_item_sk", "ss_ticket_number", "ss_customer_sk",
              "ss_quantity", "ss_sales_price"]
SR_COLUMNS = ["sr_item_sk", "sr_ticket_number", "sr_return_quantity",
              "sr_reason_sk"]


def _sorted_side(ids, paths, tables, name, columns, keys, partitions):
    """scan of the query's columns -> hash exchange on the join keys ->
    sort on them, ascending and NULLs first: a SortMergeJoin's child."""
    pruned = dict(scan(paths, tables, name), projection=columns)
    ex = exchange(ids, pruned, [c(k) for k in keys], partitions)
    return {"kind": "sort", "input": ex,
            "specs": [{"expr": c(k), "descending": False,
                       "nulls_first": True} for k in keys]}


def _f64(e: dict) -> dict:
    return {"kind": "cast", "child": e, "type": {"id": "float64"}}


def _sums(paths, tables, partitions: int):
    """(ids, the plan up to every customer's sum, in `partitions` reduce
    partitions)."""
    ids = Ids(paths)
    ss = _sorted_side(ids, paths, tables, "store_sales", SS_COLUMNS,
                      ["ss_item_sk", "ss_ticket_number"], partitions)
    sr = _sorted_side(ids, paths, tables, "store_returns", SR_COLUMNS,
                      ["sr_item_sk", "sr_ticket_number"], partitions)
    joined = join(ids, "sort_merge_join", ss, sr,
                  [c("ss_item_sk"), c("ss_ticket_number")],
                  [c("sr_item_sk"), c("sr_ticket_number")])
    re_flt = filter_(scan(paths, tables, "reason"),
                     binop("==", c("r_reason_desc"), lit(REASON, "utf8")))
    with_reason = join(ids, "broadcast_join", joined, re_flt,
                       [c("sr_reason_sk")], [c("r_reason_sk")])
    act_sales = {
        "kind": "case",
        "branches": [[{"kind": "is_not_null",
                       "child": c("sr_return_quantity")},
                      binop("*", _f64(binop("-", c("ss_quantity"),
                                            c("sr_return_quantity"))),
                            c("ss_sales_price"))]],
        "else": binop("*", _f64(c("ss_quantity")), c("ss_sales_price"))}
    proj = project(with_reason, [c("ss_customer_sk"), act_sales],
                   ["ss_customer_sk", "act_sales"])
    return ids, partial_final(ids, proj, [(ci(0), "ss_customer_sk")],
                              [("sum", "sumsales", [ci(1)])], partitions)


def plan(paths, tables, partitions: int) -> dict:
    ids, summed = _sums(paths, tables, partitions)
    single = exchange(ids, summed, [ci(0)], 1)
    return sort_limit(single, [(ci(1), False), (ci(0), False)], 100)


def plan_full(paths, tables, partitions: int) -> dict:
    """`plan` less its last exchange and the sort with its limit: every
    customer's sum, through the same stages and programs, in no order."""
    return _sums(paths, tables, partitions)[1]


def full_answer(tables, money=np.float64):
    """Every customer's sum, ordered as the query orders them (pandas)."""
    ss = tables["store_sales"].select(SS_COLUMNS).to_pandas()
    sr = tables["store_returns"].select(SR_COLUMNS).to_pandas()
    re = tables["reason"].to_pandas()
    ss["ss_sales_price"] = ss["ss_sales_price"].astype(money)
    m = ss.merge(sr, how="left", left_on=["ss_item_sk", "ss_ticket_number"],
                 right_on=["sr_item_sk", "sr_ticket_number"])
    # the WHERE clause: a row of `reason` for the return's reason
    m = m.merge(re[re.r_reason_desc == REASON], left_on="sr_reason_sk",
                right_on="r_reason_sk")
    returned = m.sr_return_quantity.notna()
    kept = (m.ss_quantity - m.sr_return_quantity.fillna(0)).astype(money)
    act = (kept * m.ss_sales_price).where(
        returned, m.ss_quantity.astype(money) * m.ss_sales_price)
    m = m.assign(act_sales=act.astype(money))
    out = m.groupby("ss_customer_sk", as_index=False, dropna=False) \
        .agg(sumsales=("act_sales", "sum"))
    return out.sort_values(["sumsales", "ss_customer_sk"],
                           na_position="first", kind="stable")


def _table(out) -> pa.Table:
    return pa.table({
        "ss_customer_sk": out["ss_customer_sk"].to_numpy().astype(np.int64),
        "sumsales": out["sumsales"].to_numpy().astype(np.float64)})


def oracle(tables, money=np.float64) -> pa.Table:
    return _table(full_answer(tables, money)[:100])


def full_oracle(tables, money=np.float64) -> pa.Table:
    """What `plan_full` has to give: compared as a set, by customer."""
    return _table(full_answer(tables, money))


def fold_work(tables) -> list:
    """[(input rows, groups)] of the SQL's one aggregation: the line items
    returned for the reason, by customer."""
    sr = tables["store_returns"].select(SR_COLUMNS).to_pandas()
    re = tables["reason"].to_pandas()
    sr = sr[sr.sr_reason_sk.isin(re[re.r_reason_desc == REASON].r_reason_sk)]
    ss = tables["store_sales"].select(SS_COLUMNS[:3]).to_pandas()
    m = ss.merge(sr, left_on=["ss_item_sk", "ss_ticket_number"],
                 right_on=["sr_item_sk", "sr_ticket_number"])
    return [(len(m), m.ss_customer_sk.nunique(dropna=False))]

"""TPC-DS q67 (query67.tpl, DMS = 1200): a year's store sales rolled up over
eight keys, five of them strings, every rolled-up row ranked inside its
category, the hundred best of each kept.

  SELECT * FROM (
    SELECT i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
           d_moy, s_store_id, sumsales,
           rank() OVER (PARTITION BY i_category
                        ORDER BY sumsales DESC) rk
    FROM (SELECT i_category, i_class, i_brand, i_product_name, d_year,
                 d_qoy, d_moy, s_store_id,
                 sum(coalesce(ss_sales_price * ss_quantity, 0)) sumsales
          FROM store_sales, date_dim, store, item
          WHERE ss_sold_date_sk = d_date_sk AND ss_item_sk = i_item_sk
            AND ss_store_sk = s_store_sk
            AND d_month_seq BETWEEN 1200 AND 1211
          GROUP BY ROLLUP(i_category, i_class, i_brand, i_product_name,
                          d_year, d_qoy, d_moy, s_store_id)) dw1) dw2
  WHERE rk <= 100
  ORDER BY i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
           d_moy, s_store_id, sumsales, rk
  LIMIT 100

The plan is Spark 3.0-3.4's at default settings (no CBO, 10 MB broadcast
threshold, no WindowGroupLimit): scan of `store_sales`' five columns ->
broadcast joins with the filtered `date_dim`, with `store` and with `item`
(each under a Project that keeps what the query reads) -> Expand to NINE
projection lists (the eight keys, then one fewer from the right each time,
the dropped ones NULL literals of the key's type, and `spark_grouping_id`
as bigint: 0, 1, 3, ... 255) -> partial sum by the nine -> exchange on the
nine -> final sum -> exchange on `i_category` -> sort (`i_category` ASC
NULLS FIRST, `sumsales` DESC NULLS LAST) -> Window rank -> filter -> the
first 100 by all ten columns.  (`blaze_tpu/itest/queries.py` q67 rolls two
keys up over three levels: another plan.)  Entry point:
`dag_scheduler_rollup`.

The oracle is written from the SQL, not from the plan, over the STRINGS:
pyarrow's hash aggregation a rollup level, rows summed in row order (one
thread), pandas' `rank(method="min")` a category (NULL is a category: the
grand total's), the query's own order.

The eight keys and the level identify a rolled-up row; no key of the data
is NULL, so the eight keys alone do.  The answer's order is decided by
them: `sumsales` and `rk` never break a tie.

What a float decides.  `d_year` is one value inside the twelve months, so
the (.., product) and (.., product, year) levels sum the SAME rows: on both
sides they are summed in the same order a group, so the sums are equal bit
for bit and share a rank, as they do for Spark over decimals.  Apart from
those, single-sale groups of the finest levels tie by coincidence
(price x quantity in cents), or differ by an ulp where IEEE rounds 0.07 x
100 and 7.00 x 1 apart: there a rank is decided by the last bit, and
`near_ties` marks the rows whose sum lies within `check.REL_TOL` of a
neighbour's in its category; the entry holds their `rk` to the span of
their cluster and every other row's exactly.

`plan_full` and `full_oracle` are the query less its filter and its last
step: every rolled-up row with its sum and its rank.
"""

from __future__ import annotations

import numpy as np
import pandas as pd
import pyarrow as pa

from benchmark.queries.ir import (Ids, binop, c, ci, exchange, filter_, join,
                                  lit, partial_final, project, scan,
                                  sort_limit)

TABLES = ["store_sales", "date_dim", "store", "item"]
FACT = "store_sales"
DMS = 1200
TOP = 100
STRINGS = ["i_category", "i_class", "i_brand", "i_product_name", "s_store_id"]
KEYS = ["i_category", "i_class", "i_brand", "i_product_name", "d_year",
        "d_qoy", "d_moy", "s_store_id"]
KEY_TYPES = ["utf8"] * 4 + ["int32"] * 3 + ["utf8"]
OUT = KEYS + ["sumsales", "rk"]
ORDERED = True
# the rollup's levels: how many of the eight keys a level keeps, and its
# spark_grouping_id (a bit a dropped key, the last key the lowest bit)
LEVELS = [(kept, (1 << (len(KEYS) - kept)) - 1)
          for kept in range(len(KEYS), -1, -1)]
# what `kernel_costs.fold_min_bytes` prices an input row of the rollup at
# (a joined sale, `fold_work`): five int32 codes, three int32 calendar
# keys, the float64 product and a selection byte (no grouping id: that is
# the Expand's, a rolled-up group has one and a sale has none); a group's
# slot holds the nine keys, the sum, a validity byte and the owner lane
FOLD_ROW_BYTES = 5 * 4 + 3 * 4 + 8 + 1
FOLD_SLOT_BYTES = 5 * 4 + 3 * 4 + 8 + 8 + 1 + 4
# the table's 32-bit lanes a probe round touches a row: the ten key lanes
# (the int64 grouping id is two) and the owner lane; and what the row
# brings and leaves: the float64 product read, the float64 sum updated
# (`kernel_costs_rollup.py`)
FOLD_KEY_LANES = 10
FOLD_VALUE_BYTES = 8
# the window node reads its partition key (an int32 code) and its order key
# (the float64 sum) once a row, a validity byte each; rank has no argument
# and writes one int32 a row
WINDOW_KEY_BYTES = 4 + 1 + 8 + 1
WINDOW_ARG_BYTES = 0
WINDOW_OUT_BYTES = 4 + 1


def _null(t: str) -> dict:
    return {"kind": "literal", "value": None, "type": {"id": t}}


def _rolled_up(ids, paths, tables, partitions: int) -> dict:
    """The inner query: (the eight keys, sumsales), every rollup level."""
    year = project(
        filter_(scan(paths, tables, "date_dim"),
                binop(">=", c("d_month_seq"), lit(DMS)),
                binop("<=", c("d_month_seq"), lit(DMS + 11))),
        [c("d_date_sk"), c("d_year"), c("d_qoy"), c("d_moy")],
        ["d_date_sk", "d_year", "d_qoy", "d_moy"])
    sales = filter_(
        dict(scan(paths, tables, "store_sales"),
             projection=["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                         "ss_quantity", "ss_sales_price"]),
        {"kind": "is_not_null", "child": c("ss_sold_date_sk")},
        {"kind": "is_not_null", "child": c("ss_store_sk")},
        {"kind": "is_not_null", "child": c("ss_item_sk")})
    dated = project(
        join(ids, "broadcast_join", sales, year, [c("ss_sold_date_sk")],
             [c("d_date_sk")]),
        [c("ss_item_sk"), c("ss_store_sk"), c("ss_quantity"),
         c("ss_sales_price"), c("d_year"), c("d_qoy"), c("d_moy")],
        ["ss_item_sk", "ss_store_sk", "ss_quantity", "ss_sales_price",
         "d_year", "d_qoy", "d_moy"])
    store = project(scan(paths, tables, "store"),
                    [c("s_store_sk"), c("s_store_id")],
                    ["s_store_sk", "s_store_id"])
    stored = project(
        join(ids, "broadcast_join", dated, store, [c("ss_store_sk")],
             [c("s_store_sk")]),
        [c("ss_item_sk"), c("ss_quantity"), c("ss_sales_price"), c("d_year"),
         c("d_qoy"), c("d_moy"), c("s_store_id")],
        ["ss_item_sk", "ss_quantity", "ss_sales_price", "d_year", "d_qoy",
         "d_moy", "s_store_id"])
    item = project(scan(paths, tables, "item"),
                   [c("i_item_sk"), c("i_brand"), c("i_class"),
                    c("i_category"), c("i_product_name")],
                   ["i_item_sk", "i_brand", "i_class", "i_category",
                    "i_product_name"])
    rows = project(
        join(ids, "broadcast_join", stored, item, [c("ss_item_sk")],
             [c("i_item_sk")]),
        [c("ss_quantity"), c("ss_sales_price")] + [c(k) for k in KEYS],
        ["ss_quantity", "ss_sales_price"] + KEYS)
    expanded = {
        "kind": "expand", "input": rows,
        "projections": [
            [ci(0), ci(1)]
            + [ci(2 + i) if i < kept else _null(t)
               for i, t in enumerate(KEY_TYPES)]
            + [lit(gid)]
            for kept, gid in LEVELS],
        "names": ["ss_quantity", "ss_sales_price"] + KEYS
        + ["spark_grouping_id"]}
    amount = {"kind": "coalesce", "args": [
        binop("*", ci(1), {"kind": "cast", "child": ci(0),
                           "type": {"id": "float64"}}),
        lit(0.0, "float64")]}
    groups = [(ci(2 + i), k) for i, k in enumerate(KEYS)] \
        + [(ci(2 + len(KEYS)), "spark_grouping_id")]
    summed = partial_final(ids, expanded, groups,
                           [("sum", "sumsales", [amount])], partitions)
    return project(summed, [ci(i) for i in range(len(KEYS))]
                   + [ci(len(KEYS) + 1)], KEYS + ["sumsales"])


def _ranked(paths, tables, partitions: int):
    """(ids, the plan up to the WHERE clause, in `partitions` partitions)."""
    ids = Ids(paths)
    by_category = exchange(ids, _rolled_up(ids, paths, tables, partitions),
                           [ci(0)], partitions)
    amount = ci(len(KEYS))
    order = [{"expr": ci(0), "descending": False, "nulls_first": True},
             {"expr": amount, "descending": True, "nulls_first": False}]
    return ids, {
        "kind": "window",
        "input": {"kind": "sort", "input": by_category, "specs": order},
        "functions": [{"kind": "rank", "name": "rk"}],
        "partition_by": [ci(0)], "order_by": order[1:]}


def plan(paths, tables, partitions: int) -> dict:
    ids, ranked = _ranked(paths, tables, partitions)
    best = filter_(ranked, binop("<=", ci(len(OUT) - 1), lit(TOP, "int32")))
    single = exchange(ids, best, [ci(0)], 1)
    return sort_limit(single, [(ci(i), False) for i in range(len(OUT))], TOP)


def plan_full(paths, tables, partitions: int) -> dict:
    """`plan` less its filter, its last exchange and the sort with its
    limit: every rolled-up row with its rank, through the same stages and
    programs, in no order."""
    return _ranked(paths, tables, partitions)[1]


def _sales(tables, money) -> pa.Table:
    """The year's sales joined to their dimensions: the eight keys and the
    amount a row, in the fact table's row order."""
    ss = tables["store_sales"].select(
        ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk", "ss_quantity",
         "ss_sales_price"]).to_pandas()
    dd = tables["date_dim"].select(
        ["d_date_sk", "d_year", "d_qoy", "d_moy", "d_month_seq"]).to_pandas()
    dd = dd[(dd.d_month_seq >= DMS) & (dd.d_month_seq <= DMS + 11)]
    st = tables["store"].select(["s_store_sk", "s_store_id"]).to_pandas()
    it = tables["item"].select(
        ["i_item_sk", "i_brand", "i_class", "i_category",
         "i_product_name"]).to_pandas()
    m = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk") \
        .merge(st, left_on="ss_store_sk", right_on="s_store_sk") \
        .merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    amount = (m.ss_sales_price.astype(money)
              * m.ss_quantity.astype(money)).fillna(0).astype(money)
    cols = {k: pa.array(m[k], type=pa.string() if t == "utf8"
                        else pa.int32(), from_pandas=True)
            for k, t in zip(KEYS, KEY_TYPES)}
    cols["amount"] = pa.array(amount.to_numpy())
    return pa.table(cols)


def rollup(tables, money=np.float64, levels=LEVELS) -> pd.DataFrame:
    """Every rolled-up row: the eight keys (NULL where the level drops
    one), `sumsales`, and `rk`, its rank by `sumsales` inside its category,
    ties sharing the lower rank."""
    sales = _sales(tables, money)
    frames = []
    for kept, _gid in levels:
        if kept:
            g = sales.group_by(KEYS[:kept], use_threads=False).aggregate(
                [("amount", "sum")]).to_pandas()
        else:
            g = pd.DataFrame({"amount_sum": [
                sales.column("amount").to_pandas().sum()]})
        for k in KEYS[kept:]:
            g[k] = None
        frames.append(g[KEYS + ["amount_sum"]])
    out = pd.concat(frames, ignore_index=True) \
        .rename(columns={"amount_sum": "sumsales"})
    out["sumsales"] = out.sumsales.astype(money).astype(np.float64)
    out["rk"] = out.groupby("i_category", dropna=False).sumsales \
        .rank(method="min", ascending=False).astype(np.int32)
    return out


def _table(out: pd.DataFrame) -> pa.Table:
    cols = {k: pa.array(out[k], type=pa.string() if t == "utf8"
                        else pa.int32(), from_pandas=True)
            for k, t in zip(KEYS, KEY_TYPES)}
    cols["sumsales"] = pa.array(out["sumsales"].to_numpy(np.float64))
    cols["rk"] = pa.array(out["rk"].to_numpy(np.int32))
    return pa.table(cols)


def in_query_order(t: pa.Table) -> pa.Table:
    """Rows by all ten columns, ascending, NULLs first: the query's order."""
    import pyarrow.compute as pc
    return t.take(pc.sort_indices(
        t, sort_keys=[(n, "ascending", "at_start") for n in t.column_names]))


def answer(ranked: pd.DataFrame) -> pa.Table:
    """The query's last three steps over every ranked row."""
    return in_query_order(_table(ranked[ranked.rk <= TOP]))[:TOP]


def oracle(tables, money=np.float64) -> pa.Table:
    return answer(rollup(tables, money))


def full_oracle(tables, money=np.float64) -> pa.Table:
    """What `plan_full` has to give: compared as a set, by the eight keys."""
    return _table(rollup(tables, money))


def near_ties(full: pa.Table, rel_tol: float):
    """(near, low, high) a row of `full` (a ranked rollup): whether its sum
    lies within `rel_tol` of a neighbour's in its category, and the span of
    ranks its cluster of such neighbours holds: a row outside a cluster has
    low = high = its rank, a row inside may take any rank of the span and be
    right to the tolerance."""
    df = full.select(["i_category", "sumsales"]).to_pandas()
    cat = df.i_category.fillna("\0").to_numpy()
    order = np.lexsort((-df.sumsales.to_numpy(), cat))
    s, cs = df.sumsales.to_numpy()[order], cat[order]
    gap = np.abs(np.diff(s)) <= rel_tol * np.maximum(np.abs(s[1:]), 1e-300)
    joined = np.concatenate([[False], gap & (cs[1:] == cs[:-1])])
    cluster = np.cumsum(~joined)          # one id a cluster, in order
    first = np.concatenate([[True], cs[1:] != cs[:-1]])
    pos = np.arange(len(s)) - np.maximum.accumulate(
        np.where(first, np.arange(len(s)), 0))
    starts = np.flatnonzero(~joined)
    sizes = np.diff(np.concatenate([starts, [len(s)]]))
    low = pos[starts][cluster - 1] + 1
    high = low + sizes[cluster - 1] - 1
    near = sizes[cluster - 1] > 1
    back = np.empty(len(s), dtype=np.int64)
    back[order] = np.arange(len(s))
    return near[back], low[back], high[back]


def fold_work(tables) -> list:
    """[(input rows, groups)] of the SQL's one aggregation, the rollup:
    the year's joined sales ONCE (an Expand that hands them on nine times
    is the program's way, not the query's work) and every rolled-up group
    of the nine levels."""
    sales = _sales(tables, np.float64).select(KEYS)
    groups = sum(
        sales.group_by(KEYS[:kept], use_threads=False).aggregate([])
        .num_rows if kept else 1 for kept, _gid in LEVELS)
    return [(sales.num_rows, groups)]

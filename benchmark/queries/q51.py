"""TPC-DS q51 (query51.tpl, DMS = 1200): the days on which an item's running
web sales, at their highest so far, stand above its running store sales.

  WITH web_v1 AS (
    SELECT ws_item_sk item_sk, d_date,
           sum(sum(ws_sales_price)) OVER (PARTITION BY ws_item_sk
             ORDER BY d_date ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW)
             cume_sales
    FROM web_sales, date_dim
    WHERE ws_sold_date_sk = d_date_sk AND d_month_seq BETWEEN 1200 AND 1211
      AND ws_item_sk IS NOT NULL
    GROUP BY ws_item_sk, d_date),
  store_v1 AS (the same over store_sales: ss_item_sk, ss_sales_price,
               ss_sold_date_sk)
  SELECT * FROM (
    SELECT item_sk, d_date, web_sales, store_sales,
           max(web_sales) OVER (PARTITION BY item_sk ORDER BY d_date
             ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW) web_cumulative,
           max(store_sales) OVER (the same window) store_cumulative
    FROM (SELECT CASE WHEN web.item_sk IS NOT NULL THEN web.item_sk
                      ELSE store.item_sk END item_sk,
                 CASE WHEN web.d_date IS NOT NULL THEN web.d_date
                      ELSE store.d_date END d_date,
                 web.cume_sales web_sales, store.cume_sales store_sales
          FROM web_v1 web FULL OUTER JOIN store_v1 store
               ON (web.item_sk = store.item_sk
                   AND web.d_date = store.d_date)) x) y
  WHERE web_cumulative > store_cumulative
  ORDER BY item_sk, d_date LIMIT 100

The plan is Spark 3's at default settings (no CBO).  A side: scan of the
fact table's three columns -> broadcast join with the filtered `date_dim`
(`d_date_sk`, `d_date`) -> partial sum by (item, date) -> exchange on
(item, date) -> final sum -> exchange on item -> sort (item, date) ->
Window (running sum).  Both sides -> exchange on (item, date) -> sort ->
SortMergeJoin, FULL OUTER, two keys -> project the two CASEs -> exchange
on item -> sort -> Window (two running maxima, NULLs skipped) -> filter ->
the first 100 by (item, date).  Seven exchanges and five sorts below the
answer's own.  (`blaze_tpu/itest/queries.py` q51 filters on `date_sk`
ranges, has one exchange a side and puts a `coalesce` where the second
window is: another plan.)  Entry point: `dag_scheduler_window`.

The windows are planned with the program's running frame, which gives rows
of equal order keys the frame-end value (RANGE).  The query asks for ROWS;
after the GROUP BY, and after the full join on both keys, (item, date) is
unique, so the two frames agree on every row.

The oracle is written from the SQL, not from the plan: pandas on the host,
float64, sums and maxima in row order inside a partition.

The order of the answer is decided exactly on both sides: it is by the
keys alone, (item_sk, d_date), which are unique in the answer and neither
NULL (the CASEs take the side that has the row), so no float decides a
row's place.  What a float does decide is WHICH rows pass
`web_cumulative > store_cumulative`; both sides are running sums of cents
drawn independently, and at scale 1 with the configuration's `data_seed`
no passing or failing row has the two within 1e-6 of each other
(`tests/test_bench_q51.py` checks the same at the scale it runs).

`plan_full` and `full_oracle` are the query less its last step: every
passing row.  The cell's entry runs it after every warm-up query and holds
it to `check.py`'s limits.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from benchmark.queries.ir import (Ids, binop, c, ci, exchange, filter_, join,
                                  lit, partial_final, project, scan,
                                  sort_limit)

TABLES = ["store_sales", "web_sales", "date_dim"]
FACT = "store_sales"
KEYS = ["item_sk", "d_date"]
ORDERED = True
DMS = 1200
# the sum fold moves an int64 item, a date32, a float64 price and one
# selection byte per row; a slot holds the two keys, the sum, a validity
# byte and a used flag
FOLD_ROW_BYTES = 8 + 4 + 8 + 1
FOLD_SLOT_BYTES = 8 + 4 + 8 + 1 + 1
# every window node here reads its partition key (the int64 item) and its
# order key (the date32 day) once a row, and reads one float64 argument and
# writes one float64 result a function, a validity byte a column: what
# `sources/window_roofline.py` prices a scanned run's rows and functions at
WINDOW_KEY_BYTES = 8 + 1 + 4 + 1
WINDOW_ARG_BYTES = 8 + 1
WINDOW_OUT_BYTES = 8 + 1

SIDES = {"web": ("web_sales", "ws_sold_date_sk", "ws_item_sk",
                 "ws_sales_price"),
         "store": ("store_sales", "ss_sold_date_sk", "ss_item_sk",
                   "ss_sales_price")}
OUT = ["item_sk", "d_date", "web_sales", "store_sales", "web_cumulative",
       "store_cumulative"]


def _sort(inp: dict, keys) -> dict:
    return {"kind": "sort", "input": inp,
            "specs": [{"expr": k, "descending": False, "nulls_first": True}
                      for k in keys]}


def _window(inp: dict, fn: str, args, names) -> dict:
    """Running `fn` of each of `args` by item (column 0) in date order
    (column 1)."""
    return {"kind": "window", "input": inp,
            "functions": [{"kind": "agg", "fn": fn, "name": n,
                           "running": True, "args": [a]}
                          for a, n in zip(args, names)],
            "partition_by": [ci(0)],
            "order_by": [{"expr": ci(1), "descending": False,
                          "nulls_first": True}]}


def _cume(ids, paths, tables, side: str, partitions: int) -> dict:
    """One of the WITH views: (item_sk, d_date, cume_sales)."""
    fact, date_col, item_col, price_col = SIDES[side]
    year = project(
        filter_(scan(paths, tables, "date_dim"),
                binop(">=", c("d_month_seq"), lit(DMS)),
                binop("<=", c("d_month_seq"), lit(DMS + 11))),
        [c("d_date_sk"), c("d_date")], ["d_date_sk", "d_date"])
    sales = filter_(
        dict(scan(paths, tables, fact),
             projection=[date_col, item_col, price_col]),
        {"kind": "is_not_null", "child": c(item_col)})
    dated = join(ids, "broadcast_join", sales, year, [c(date_col)],
                 [c("d_date_sk")])
    rows = project(dated, [c(item_col), c("d_date"), c(price_col)],
                   ["item_sk", "d_date", "price"])
    daily = partial_final(ids, rows, [(ci(0), "item_sk"), (ci(1), "d_date")],
                          [("sum", "sales", [ci(2)])], partitions)
    by_item = _sort(exchange(ids, daily, [ci(0)], partitions),
                    [ci(0), ci(1)])
    cume = _window(by_item, "sum", [ci(2)], ["cume_sales"])
    return project(cume, [ci(0), ci(1), ci(3)],
                   ["item_sk", "d_date", "cume_sales"])


def _either(a: dict, b: dict) -> dict:
    return {"kind": "case",
            "branches": [[{"kind": "is_not_null", "child": a}, a]],
            "else": b}


def _passing(paths, tables, partitions: int):
    """(ids, the plan up to the WHERE clause, in `partitions` partitions)."""
    ids = Ids(paths)
    web, store = (
        _sort(exchange(ids, _cume(ids, paths, tables, side, partitions),
                       [ci(0), ci(1)], partitions), [ci(0), ci(1)])
        for side in ("web", "store"))
    both = join(ids, "sort_merge_join", web, store, [ci(0), ci(1)],
                [ci(0), ci(1)], jt="full")
    x = project(both, [_either(ci(0), ci(3)), _either(ci(1), ci(4)), ci(2),
                       ci(5)], OUT[:4])
    by_item = _sort(exchange(ids, x, [ci(0)], partitions), [ci(0), ci(1)])
    y = _window(by_item, "max", [ci(2), ci(3)], OUT[4:])
    return ids, filter_(y, binop(">", ci(4), ci(5)))


def plan(paths, tables, partitions: int) -> dict:
    ids, passing = _passing(paths, tables, partitions)
    single = exchange(ids, passing, [ci(0)], 1)
    return sort_limit(single, [(ci(0), False), (ci(1), False)], 100)


def plan_full(paths, tables, partitions: int) -> dict:
    """`plan` less its last exchange and the sort with its limit: every
    passing row, through the same stages and programs, in no order."""
    return _passing(paths, tables, partitions)[1]


def _view(tables, side: str, money):
    """A WITH view on pandas: sorted by (item_sk, d_date)."""
    fact, date_col, item_col, price_col = SIDES[side]
    f = tables[fact].select([date_col, item_col, price_col]).to_pandas()
    dd = tables["date_dim"].select(
        ["d_date_sk", "d_date", "d_month_seq"]).to_pandas()
    dd = dd[(dd.d_month_seq >= DMS) & (dd.d_month_seq <= DMS + 11)]
    f = f[f[item_col].notna()]
    m = f.merge(dd, left_on=date_col, right_on="d_date_sk")
    m = m.assign(item_sk=m[item_col].astype(np.int64),
                 price=m[price_col].astype(money))
    g = m.groupby(["item_sk", "d_date"], as_index=False) \
        .agg(sales=("price", "sum")) \
        .sort_values(["item_sk", "d_date"], kind="stable")
    g["cume_sales"] = g.groupby("item_sk").sales.cumsum().astype(money)
    return g[["item_sk", "d_date", "cume_sales"]]


def full_answer(tables, money=np.float64):
    """Every passing row, ordered as the query orders them (pandas)."""
    web = _view(tables, "web", money).rename(
        columns={"cume_sales": "web_sales"})
    store = _view(tables, "store", money).rename(
        columns={"cume_sales": "store_sales"})
    # an outer merge on both keys IS the two CASEs: a key comes from the
    # side that has the row
    x = web.merge(store, how="outer", on=["item_sk", "d_date"]) \
        .sort_values(["item_sk", "d_date"], kind="stable") \
        .reset_index(drop=True)
    by_item = x.groupby("item_sk")
    for src, dst in (("web_sales", "web_cumulative"),
                     ("store_sales", "store_cumulative")):
        # a running max skips NULLs: a NULL row reads the max so far
        x[dst] = by_item[src].cummax()
        x[dst] = x.groupby("item_sk")[dst].ffill()
    # a NULL on either side fails the comparison
    return x[x.web_cumulative > x.store_cumulative][OUT]


def _table(out) -> pa.Table:
    cols = {"item_sk": pa.array(out["item_sk"].to_numpy().astype(np.int64)),
            "d_date": pa.array(out["d_date"].to_numpy(), type=pa.date32())}
    for name in OUT[2:]:
        cols[name] = pa.array(out[name].to_numpy().astype(np.float64),
                              from_pandas=True)
    return pa.table(cols)


def oracle(tables, money=np.float64) -> pa.Table:
    return _table(full_answer(tables, money)[:100])


def full_oracle(tables, money=np.float64) -> pa.Table:
    """What `plan_full` has to give: compared as a set, by (item, date)."""
    return _table(full_answer(tables, money))


def fold_work(tables) -> list:
    """[(input rows, groups)] of the SQL's two aggregations, one a WITH
    view: the year's sales with an item, by (item, date)."""
    dd = tables["date_dim"].select(["d_date_sk", "d_month_seq"]).to_pandas()
    days = dd[(dd.d_month_seq >= DMS) & (dd.d_month_seq <= DMS + 11)] \
        .d_date_sk
    work = []
    for side in ("web", "store"):
        fact, date_col, item_col, _price = SIDES[side]
        f = tables[fact].select([date_col, item_col]).to_pandas()
        f = f[f[item_col].notna() & f[date_col].isin(days)]
        # a day is one `d_date_sk`: groups by (item, date key)
        work.append((len(f), len(f.drop_duplicates())))
    return work

"""TPC-DS q01 over the specification's money type, `decimal(7,2)`.

`q01.py`'s plan with the types a Spark 3 plan (non-ANSI) carries when the
tables are dsdgen's: `sr_return_amt decimal(7,2)`, the sum
`decimal(17,2)`, the average `decimal(21,6)`, the SQL literal `1.2` typed
`decimal(2,1)`, the threshold `decimal(24,7)`, the comparison at scale 7.
Entry point: `dag_scheduler_dec`.

`plan_full` is the query less its joins to `store` and `customer`, its
sort and its limit: every (customer, store) whose total passes its store's
threshold, with the total and the threshold.  The first 100 ids say little
about the arithmetic; the full answer holds every decimal value and type.

The oracles are written from the SQL and from Spark's decimal rules with
Python integers over unscaled values: no float, nothing of the program.
"""

from __future__ import annotations

from decimal import Decimal

import numpy as np
import pyarrow as pa

from benchmark.queries.ir import (Ids, agg, binop, c, ci, exchange,
                                  filter_, join, partial_final, project,
                                  scan, sort_limit)
from benchmark.queries.q01 import fold_work  # noqa: F401  q01's two

TABLES = ["store_returns", "date_dim", "store", "customer"]
FACT = "store_returns"
KEYS = ["c_customer_id"]
ORDERED = True
FULL_KEYS = ["ctr_customer_sk", "ctr_store_sk"]
# least bytes the fold has to move for one input row: two int64 keys, the
# amount as the int32 of unscaled cents a decimal(7,2) is stored in, one
# selection byte (the final aggregation's rows carry an int64 partial sum:
# 4 bytes more, left out of a least); and for one table slot: the keys, one
# int64 accumulator of unscaled cents, one used flag
FOLD_ROW_BYTES = 8 + 8 + 4 + 1
FOLD_SLOT_BYTES = 8 + 8 + 8 + 1

AMOUNT = pa.decimal128(7, 2)       # sr_return_amt, as TPC-DS declares it
TOTAL = pa.decimal128(17, 2)       # sum(decimal(7,2)): precision + 10
AVERAGE = pa.decimal128(21, 6)     # avg(decimal(17,2)): (p + 4, s + 4)
FACTOR = pa.decimal128(2, 1)       # the SQL literal 1.2
THRESHOLD = pa.decimal128(24, 7)   # (21,6) * (2,1): (p1 + p2 + 1, s1 + s2)


def _dec(t: pa.DataType) -> dict:
    return {"id": "decimal", "precision": t.precision, "scale": t.scale}


def _filtered(ids: Ids, paths, tables, partitions: int) -> dict:
    """The joined (customer, store, total, store, average) rows that pass
    `ctr_total_return > avg_return * 1.2`."""
    dd_flt = filter_(scan(paths, tables, "date_dim"),
                     binop("==", c("d_year"),
                           {"kind": "literal", "value": 2000,
                            "type": {"id": "int32"}}))
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
    return filter_(joined, binop(">", c("ctr_total_return"), _threshold()))


def _threshold() -> dict:
    return binop("*", c("avg_return"),
                 {"kind": "literal", "value": Decimal("1.2"),
                  "type": _dec(FACTOR)})


def plan(paths, tables, partitions: int) -> dict:
    ids = Ids(paths)
    flt = _filtered(ids, paths, tables, partitions)
    st_flt = filter_(scan(paths, tables, "store"),
                     binop("==", c("s_state"),
                           {"kind": "literal", "value": "TN",
                            "type": {"id": "utf8"}}))
    j_store = join(ids, "broadcast_join", flt, st_flt,
                   [c("ctr_store_sk")], [c("s_store_sk")])
    j_cust = join(ids, "broadcast_join", j_store,
                  scan(paths, tables, "customer"),
                  [c("ctr_customer_sk")], [c("c_customer_sk")])
    proj = project(j_cust, [c("c_customer_id")], ["c_customer_id"])
    single = exchange(ids, proj, [ci(0)], 1)
    return sort_limit(single, [(ci(0), False)], 100)


def plan_full(paths, tables, partitions: int) -> dict:
    ids = Ids(paths)
    flt = _filtered(ids, paths, tables, partitions)
    return project(flt, [c("ctr_customer_sk"), c("ctr_store_sk"),
                         c("ctr_total_return"), _threshold()],
                   FULL_KEYS + ["ctr_total_return", "threshold"])


# ---- the oracles: Python integers over unscaled values ---------------------

def _unscaled(col: pa.ChunkedArray) -> list:
    """A decimal128 column's unscaled values as Python ints, None for
    NULL: the low 8 bytes of each 16-byte little-endian value (every
    decimal(7,2) fits them)."""
    arr = col.combine_chunks()
    if not pa.types.is_decimal(arr.type) or arr.type.precision > 18:
        raise TypeError(f"not a narrow decimal column: {arr.type}")
    lo = np.frombuffer(arr.buffers()[1], dtype=np.int64).reshape(-1, 2)[
        arr.offset:arr.offset + len(arr), 0].tolist()
    if arr.null_count:
        valid = arr.is_valid().to_pylist()
        return [v if ok else None for v, ok in zip(lo, valid)]
    return lo


def _bounded(unscaled, t: pa.DataType):
    """Non-ANSI overflow: a value past its type's bound is NULL, never
    wrapped."""
    if unscaled is None or abs(unscaled) >= 10 ** t.precision:
        return None
    return unscaled


def _div_half_up(num: int, den: int) -> int:
    """num / den rounded HALF_UP (ties away from zero), den > 0."""
    q, r = divmod(abs(num), den)
    q += 2 * r >= den
    return -q if num < 0 else q


def _totals(tables) -> dict:
    """{(customer or None, store): sum of cents or None}: the year's
    returns by (customer, store), the NULL-customer group kept."""
    sr = tables["store_returns"]
    dd = tables["date_dim"]
    year = set(sk for sk, y in zip(dd["d_date_sk"].to_pylist(),
                                   dd["d_year"].to_pylist()) if y == 2000)
    totals = {}
    for date, cust, store, amt in zip(
            sr["sr_returned_date_sk"].to_pylist(),
            sr["sr_customer_sk"].to_pylist(),
            sr["sr_store_sk"].to_pylist(),
            _unscaled(sr["sr_return_amt"])):
        if date not in year or store is None:
            continue  # the inner join to date_dim; a NULL store joins nothing
        key = (cust, store)
        if amt is None:
            totals.setdefault(key, None)  # sum skips NULL amounts
        else:
            totals[key] = (totals.get(key) or 0) + amt
    # sum(decimal(7,2)) is decimal(17,2); NULL for a group of NULLs only
    return {k: _bounded(v, TOTAL) for k, v in totals.items()}


def _passing(tables) -> list:
    """[(customer, store, total at scale 2, threshold at scale 7)] of every
    group whose total exceeds its store's threshold."""
    totals = _totals(tables)
    by_store = {}
    for (_cust, store), total in totals.items():
        if total is not None:  # avg skips NULL totals
            s = by_store.setdefault(store, [0, 0])
            s[0] += total
            s[1] += 1
    thresholds = {}
    for store, (total, count) in by_store.items():
        # avg(decimal(17,2)) = sum * 10^4 / count, HALF_UP, decimal(21,6)
        average = _bounded(_div_half_up(total * 10 ** 4, count), AVERAGE)
        # decimal(21,6) * decimal(2,1) is exact at decimal(24,7)
        thresholds[store] = _bounded(
            None if average is None else average * 12, THRESHOLD)
    out = []
    for (cust, store), total in totals.items():
        limit = thresholds.get(store)
        # decimal(17,2) > decimal(24,7) compares both at scale 7; a NULL
        # on either side passes nothing
        if total is not None and limit is not None \
                and total * 10 ** 5 > limit:
            out.append((cust, store, total, limit))
    return out


def _decimals(unscaled: list, t: pa.DataType) -> pa.Array:
    return pa.array([None if v is None else Decimal(v).scaleb(-t.scale)
                     for v in unscaled], type=t)


def _float_tables(tables, money) -> dict:
    """The low-precision control's input: amounts as `money` floats."""
    sr = tables["store_returns"]
    i = sr.schema.get_field_index("sr_return_amt")
    amt = sr["sr_return_amt"].cast(pa.float64())
    return dict(tables, store_returns=sr.set_column(
        i, "sr_return_amt", amt.cast(pa.from_numpy_dtype(money))))


def _passing_float(tables, money):
    """`_passing` with every amount, sum, average and product held in
    `money`: pandas frame of (customer, store, total, threshold)."""
    sr = _float_tables(tables, money)["store_returns"].select(
        ["sr_returned_date_sk", "sr_customer_sk", "sr_store_sk",
         "sr_return_amt"]).to_pandas()
    dd = tables["date_dim"].select(["d_date_sk", "d_year"]).to_pandas()
    m = sr.merge(dd[dd.d_year == 2000], left_on="sr_returned_date_sk",
                 right_on="d_date_sk")
    ctr = (m.groupby(["sr_customer_sk", "sr_store_sk"], as_index=False,
                     dropna=False).sr_return_amt.sum()
           .rename(columns={"sr_return_amt": "total"}))
    ctr["total"] = ctr["total"].astype(money)
    avg = ctr.groupby("sr_store_sk", as_index=False).total.mean() \
        .rename(columns={"total": "avg_return"})
    j = ctr.merge(avg, on="sr_store_sk")
    j["threshold"] = (money(1.2) * j.avg_return.astype(money)).astype(money)
    return j[j.total > j.threshold]


def full_oracle(tables, money=None) -> pa.Table:
    """Every passing (customer, store) with its total and its threshold.
    `money` is the low-precision control: a float type the amounts are
    held and summed in; the answer's decimals are then float64 columns."""
    if money is not None:
        j = _passing_float(tables, money)
        return pa.table({
            FULL_KEYS[0]: pa.array(j.sr_customer_sk, pa.int64(),
                                   from_pandas=True),
            FULL_KEYS[1]: pa.array(j.sr_store_sk, pa.int64()),
            "ctr_total_return": pa.array(j.total.astype(np.float64)),
            "threshold": pa.array(j.threshold.astype(np.float64))})
    rows = _passing(tables)
    return pa.table({
        FULL_KEYS[0]: pa.array([r[0] for r in rows], pa.int64()),
        FULL_KEYS[1]: pa.array([r[1] for r in rows], pa.int64()),
        "ctr_total_return": _decimals([r[2] for r in rows], TOTAL),
        "threshold": _decimals([r[3] for r in rows], THRESHOLD)})


def oracle(tables, money=None) -> pa.Table:
    """The first 100 customer ids, in order, of the passing groups at
    stores in TN."""
    if money is not None:
        j = _passing_float(tables, money)
        passing = zip(j.sr_customer_sk.tolist(), j.sr_store_sk.tolist())
    else:
        passing = ((r[0], r[1]) for r in _passing(tables))
    st = tables["store"]
    tn = set(sk for sk, s in zip(st["s_store_sk"].to_pylist(),
                                 st["s_state"].to_pylist()) if s == "TN")
    cu = tables["customer"]
    ids = dict(zip(cu["c_customer_sk"].to_pylist(),
                   cu["c_customer_id"].to_pylist()))
    # the inner join to customer drops the NULL-customer group
    out = sorted(ids[cust] for cust, store in passing
                 if store in tn and cust == cust and cust in ids)
    return pa.table({"c_customer_id": pa.array(out[:100], pa.string())})

"""TPC-DS q01's inner pipeline as a map/reduce pair of TaskDefinitions.

  map    parquet scan of one store_returns split -> date filter (the
         d_year = 2000 key range Spark's DPP pushes into the fact scan)
         -> partial sum(sr_return_amt) by (customer, store) -> hash
         shuffle write
  reduce shuffle read -> final sum by (customer, store)

Copied from `bench.py` (stage1_td / stage2_td / run_baseline), which is
program-side and a declared deletion target.  Entry point: `runtime_pair`.
"""

from __future__ import annotations

import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from benchmark.queries.ir import c, ci, lit, binop

TABLES = ["store_returns", "date_dim"]
FACT = "store_returns"
KEYS = ["ctr_customer_sk", "ctr_store_sk"]
ORDERED = False
# least bytes the fold has to move for one input row: two int64 keys, one
# float64 value, one selection byte; and for one group's slot: the same
# keys, one float64 accumulator, one used flag (the least a slot needs, not
# what the program charges: 29 B since PR 47)
FOLD_ROW_BYTES = 8 + 8 + 8 + 1
FOLD_SLOT_BYTES = 8 + 8 + 8 + 1

_PROJECTION = ["sr_returned_date_sk", "sr_customer_sk", "sr_store_sk",
               "sr_return_amt"]
_SR_SCHEMA = {"fields": [
    {"name": "sr_returned_date_sk", "type": {"id": "int64"},
     "nullable": True},
    {"name": "sr_customer_sk", "type": {"id": "int64"}, "nullable": True},
    {"name": "sr_store_sk", "type": {"id": "int64"}, "nullable": True},
    {"name": "sr_return_amt", "type": {"id": "float64"}, "nullable": True},
    {"name": "sr_ticket_number", "type": {"id": "int64"}, "nullable": True},
]}
_PARTIAL_SCHEMA = {"fields": [
    {"name": "ctr_customer_sk", "type": {"id": "int64"}, "nullable": True},
    {"name": "ctr_store_sk", "type": {"id": "int64"}, "nullable": True},
    {"name": "ctr_total_return.sum", "type": {"id": "float64"},
     "nullable": True},
]}
RESOURCE_ID = "bench_q01pair_shuffle"


def _date_sk_range(date_dim: pa.Table):
    keys = date_dim.filter(pc.equal(date_dim["d_year"], 2000))["d_date_sk"]
    return int(pc.min(keys).as_py()), int(pc.max(keys).as_py())


def plan(paths, tables, partitions: int) -> dict:
    """{"map": f(map_id, shuffle_dir) -> task dict,
        "reduce": f(reduce_id) -> task dict, ...} for `runtime_pair`."""
    lo, hi = _date_sk_range(tables["date_dim"])
    groups = paths["store_returns"]
    n_maps, n_reduces = len(groups), partitions

    def map_task(map_id: int, shuffle_dir: str) -> dict:
        # the wire carries one file group per task: siblings blank out
        file_groups = [g if i == map_id else []
                       for i, g in enumerate(groups)]
        node = {
            "kind": "shuffle_writer",
            "partitioning": {"kind": "hash", "exprs": [ci(0), ci(1)],
                             "num_partitions": n_reduces},
            "data_file": os.path.join(shuffle_dir,
                                      f"shuffle_{map_id}.data"),
            "index_file": os.path.join(shuffle_dir,
                                       f"shuffle_{map_id}.index"),
            "input": {
                "kind": "hash_agg",
                "groupings": [
                    {"expr": c("sr_customer_sk"), "name": KEYS[0]},
                    {"expr": c("sr_store_sk"), "name": KEYS[1]}],
                "aggs": [{"fn": "sum", "mode": "partial",
                          "name": "ctr_total_return",
                          "args": [c("sr_return_amt")]}],
                "input": {
                    "kind": "filter",
                    "predicates": [
                        binop(">=", c("sr_returned_date_sk"), lit(lo)),
                        binop("<=", c("sr_returned_date_sk"), lit(hi))],
                    "input": {"kind": "parquet_scan",
                              "schema": _SR_SCHEMA,
                              "projection": _PROJECTION,
                              "file_groups": file_groups}}}}
        return {"stage_id": 1, "partition_id": map_id,
                "num_partitions": n_maps, "plan": node}

    def reduce_task(reduce_id: int) -> dict:
        node = {
            "kind": "hash_agg",
            "groupings": [{"expr": ci(0), "name": KEYS[0]},
                          {"expr": ci(1), "name": KEYS[1]}],
            "aggs": [{"fn": "sum", "mode": "final",
                      "name": "ctr_total_return", "args": [ci(2)]}],
            "input": {"kind": "ipc_reader", "resource_id": RESOURCE_ID,
                      "schema": _PARTIAL_SCHEMA,
                      "num_partitions": n_reduces}}
        return {"stage_id": 2, "partition_id": reduce_id,
                "num_partitions": n_reduces, "plan": node}

    return {"map": map_task, "reduce": reduce_task, "n_maps": n_maps,
            "n_reduces": n_reduces, "resource_id": RESOURCE_ID}


def oracle(tables, money=np.float64) -> pa.Table:
    """The same query on pyarrow.  `money` is the type amounts are held and
    summed in; float32 is the low-precision control."""
    sr = tables["store_returns"].select(_PROJECTION)
    lo, hi = _date_sk_range(tables["date_dim"])
    mask = pc.and_(pc.greater_equal(sr["sr_returned_date_sk"], lo),
                   pc.less_equal(sr["sr_returned_date_sk"], hi))
    f = sr.filter(mask)
    amt = f["sr_return_amt"].cast(pa.from_numpy_dtype(money))
    f = f.set_column(f.schema.get_field_index("sr_return_amt"),
                     "sr_return_amt", amt)
    out = f.group_by(["sr_customer_sk", "sr_store_sk"]).aggregate(
        [("sr_return_amt", "sum")])
    total = out["sr_return_amt_sum"].cast(pa.from_numpy_dtype(money))
    return pa.table({KEYS[0]: out["sr_customer_sk"],
                     KEYS[1]: out["sr_store_sk"],
                     "ctr_total_return": total.cast(pa.float64())})


def year_returns(tables) -> pa.Table:
    """The year's returns, the three columns the aggregation reads."""
    sr = tables["store_returns"].select(_PROJECTION)
    lo, hi = _date_sk_range(tables["date_dim"])
    return sr.filter(pc.and_(
        pc.greater_equal(sr["sr_returned_date_sk"], lo),
        pc.less_equal(sr["sr_returned_date_sk"], hi)))


def group_count(t: pa.Table, keys) -> int:
    """Groups of `keys` in `t`, a NULL key a group of its own (SQL)."""
    return t.group_by(list(keys), use_threads=False).aggregate([]).num_rows


def fold_work(tables) -> list:
    """[(input rows, groups)] of every aggregation of the SQL, from the
    tables alone: what `fold_roofline` prices, whatever the program does
    to answer.  One here: the year's returns by (customer, store)."""
    f = year_returns(tables)
    return [(f.num_rows, group_count(f, ["sr_customer_sk", "sr_store_sk"]))]

"""Plan-IR dict constructors shared by the query files.

The IR is the program's public plan format (what `plan/proto_serde.py`
encodes into TaskDefinition bytes); these helpers only spell its dicts.
Exchange and broadcast ids come from the input paths and a counter, so a
plan built twice over the same files is the same plan.
"""

from __future__ import annotations

import itertools
import zlib
from typing import Dict, List, Tuple

import pyarrow as pa


class Ids:
    """Stage and broadcast ids for one plan: the same for the same input
    files, different for different ones.  The program keeps broadcast build
    sides by id across queries, so two plans over different data must not
    share one."""

    def __init__(self, paths: dict):
        self._salt = zlib.crc32(repr(sorted(paths.items())).encode())
        self._next = itertools.count(1)

    def stage(self) -> int:
        return (self._salt % 1_000_000) * 1000 + next(self._next)

    def broadcast(self) -> str:
        return f"bench-bc-{self._salt:08x}-{next(self._next)}"


def c(name: str) -> dict:
    return {"kind": "column", "name": name}


def ci(index: int) -> dict:
    return {"kind": "column", "index": index}


def lit(v, t: str = "int64") -> dict:
    return {"kind": "literal", "value": v, "type": {"id": t}}


def binop(op: str, l: dict, r: dict) -> dict:
    return {"kind": "binary", "op": op, "l": l, "r": r}


def scan(paths: Dict[str, List[List[str]]], tables: Dict[str, pa.Table],
         name: str) -> dict:
    from blaze_tpu.plan.types import schema_to_dict
    from blaze_tpu.schema import Schema
    return {"kind": "parquet_scan",
            "schema": schema_to_dict(Schema.from_arrow(tables[name].schema)),
            "file_groups": paths[name]}


def filter_(inp: dict, *preds: dict) -> dict:
    return {"kind": "filter", "input": inp, "predicates": list(preds)}


def project(inp: dict, exprs: List[dict], names: List[str]) -> dict:
    return {"kind": "project", "input": inp, "exprs": exprs, "names": names}


def exchange(ids: Ids, inp: dict, keys: List[dict], partitions: int) -> dict:
    return {"kind": "local_exchange",
            "partitioning": {"kind": "hash", "exprs": keys,
                             "num_partitions": partitions},
            "stage_id": ids.stage(), "input": inp}


def join(ids: Ids, kind: str, left: dict, right: dict, lkeys: List[dict],
         rkeys: List[dict], jt: str = "inner", build: str = "right") -> dict:
    d = {"kind": kind, "left": left, "right": right, "left_keys": lkeys,
         "right_keys": rkeys, "join_type": jt}
    if kind != "sort_merge_join":
        d["build_side"] = build
    if kind == "broadcast_join":
        d["broadcast_id"] = ids.broadcast()
    return d


def agg(inp: dict, groups: List[Tuple[dict, str]],
        aggs: List[Tuple[str, str, str, List[dict]]]) -> dict:
    """aggs: (fn, mode, name, args)."""
    return {"kind": "hash_agg", "input": inp,
            "groupings": [{"expr": e, "name": n} for e, n in groups],
            "aggs": [{"fn": f, "mode": m, "name": n, "args": a}
                     for f, m, n, a in aggs]}


def sort_limit(inp: dict, specs: List[Tuple[dict, bool]], limit: int) -> dict:
    return {"kind": "limit", "limit": limit,
            "input": {"kind": "sort", "input": inp,
                      "specs": [{"expr": e, "descending": d,
                                 "nulls_first": not d} for e, d in specs],
                      "fetch": limit}}


def partial_final(ids: Ids, inp: dict, group_names: List[Tuple[dict, str]],
                  fns: List[Tuple[str, str, List[dict]]],
                  partitions: int) -> dict:
    """partial agg -> hash exchange on the group keys -> final agg, the
    pair Spark emits; accumulator columns rebind by position."""
    partial = agg(inp, group_names,
                  [(f, "partial", n, a) for f, n, a in fns])
    ng = len(group_names)
    ex = exchange(ids, partial, [ci(i) for i in range(ng)], partitions)
    final_groups = [(ci(i), name)
                    for i, (_e, name) in enumerate(group_names)]
    final_aggs = []
    pos = ng
    for f, n, _a in fns:
        nacc = 2 if f == "avg" else 1
        final_aggs.append((f, "final", n,
                           [ci(pos + t) for t in range(nacc)]))
        pos += nacc
    return agg(ex, final_groups, final_aggs)

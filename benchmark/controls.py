#!/usr/bin/env python3
"""The controls: answers that have to come out as NOT correct.

  python3 benchmark/controls.py --workload <cell> --seeds 1,2,3 [--scale s]

A control is the oracle put in the program's place with one thing wrong,
held to the same comparison and limits as a run (`check.py`):

  float32     money held and summed in float32, the nearest precision below
              the float64 the configurations state;
  lost_split  the fact table without the rows of its second file: one map
              task's output lost, which breaks the guarantee that every
              input row is counted exactly once.

It needs no chip and nothing of the program.  Where a query's answer is
discrete (counts, the first 100 ids) float32 can leave it unchanged; the
line then says so, and `lost_split` is the control that has to fail.
Exit code 0 when, for every seed, at least one control failed the check
and `lost_split` did.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np
import pyarrow as pa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def lost_split(tables: dict, fact: str, splits: int, index: int = 1) -> dict:
    t = tables[fact]
    per = -(-t.num_rows // splits)
    kept = pa.concat_tables([t.slice(0, index * per),
                             t.slice((index + 1) * per)])
    return dict(tables, **{fact: kept})


def control_answers(query, tables: dict, splits: int) -> dict:
    return {"float32": query.oracle(tables, money=np.float32),
            "lost_split": query.oracle(
                lost_split(tables, query.FACT, splits))}


def main(argv=None) -> int:
    from benchmark import check
    from benchmark.manifest import Cell
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--scale", type=float, default=None,
                    help="override the configuration's scale (tests)")
    args = ap.parse_args(argv)
    cell = Cell(args.workload, ROOT)
    cfg = cell.config
    gen = cell.module("data", cfg["generator"])
    query = cell.module("queries", cell.traffic["query"])
    scale = cfg["scale"] if args.scale is None else args.scale
    held = True
    for seed in (int(s) for s in args.seeds.split(",")):
        tables = gen.make_tables(query.TABLES, scale, cfg["data_seed"],
                                 cfg["splits"], seed)
        want = query.oracle(tables)
        failed = {}
        for name, got in control_answers(query, tables,
                                         cfg["splits"]).items():
            ok, line = check.verdict(check.compare(
                got, want, query.KEYS, query.ORDERED))
            failed[name] = not ok
            print(f"{cell.name} seed {seed} control {name}: "
                  f"{'NOT correct' if not ok else 'SAME ANSWER'}; {line}",
                  flush=True)
        held = held and failed["lost_split"]
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one benchmark cell and print the program's ledger after it.

  python3 tools/cell_with_ledger.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>          (from a checkout's root)

`benchmark/run.py`'s own `main`, in this process, with its arguments; then
one more line on stdout, `LEDGER <json>`: `xla_stats.program_load_summary`
of the records that ended before the first query of the window (the
twenty (program, call site) pairs with most seconds among it, requests
by kind, `trimmed`), and `loads_in_window`: the ledger's records since
that query began, which has to be empty for a run that is `correct`; and
`exchange_tiers`: the rows and bytes the process's map tasks committed by
exchange tier since it started (warm-up included: `resident` on the chip,
`file`, `spilled` from resident to files) with `resident_rows_share`, the
resident tier's share of those rows in %.
The first query's start is the newest `trace_events.json`'s (a traced
run); an untraced run reports the whole ledger and `loads_in_window`
null.  The exit code is `run.py`'s.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.getcwd()
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from benchmark import run
    rc = run.main(argv)
    from benchmark.sources import span_gap
    from blaze_tpu.bridge import xla_stats
    traced = "--trace" in argv and argv[argv.index("--trace") + 1] == "1"
    rec = span_gap.newest_trace_events(ROOT) if traced else None
    start = rec["query_starts_ns"][0] if rec else None
    in_window = None if start is None else [
        {k: r[k] for k in ("program", "phase", "site")}
        for r in xla_stats.program_loads(since_ns=start)]
    print("LEDGER " + json.dumps({
        "summary": xla_stats.program_load_summary(until_ns=start),
        "loads_in_window": in_window,
        "exchange_tiers": exchange_tiers(xla_stats.shuffle_stats())}),
        flush=True)
    return rc


def exchange_tiers(shuffle: dict) -> dict:
    """Rows and bytes by exchange tier out of `xla_stats.shuffle_stats()`;
    a program without the tier counters reports nothing."""
    tiers = {t: {"rows": shuffle[f"shuffle_{t}_rows"],
                 "bytes": shuffle[f"shuffle_{t}_bytes"]}
             for t in ("resident", "file", "spilled")
             if f"shuffle_{t}_rows" in shuffle}
    if not tiers:
        return {}
    rows = tiers["resident"]["rows"] + tiers["file"]["rows"]
    tiers["resident_rows_share"] = \
        100.0 * tiers["resident"]["rows"] / rows if rows else None
    return tiers


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

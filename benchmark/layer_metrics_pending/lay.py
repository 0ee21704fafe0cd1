#!/usr/bin/env python3
"""Lay the pending per-layer metrics over a COPY of the benchmark.

  python3 benchmark/layer_metrics_pending/lay.py <root of a checkout's copy>

`BENCHMARK.json` holds as many per-layer entries as it may (128), so the
metrics of this directory have readers (`benchmark/sources/`) and no
entry.  This appends one entry a pending file to `<root>/BENCHMARK.json`
(none names its cells, so every cell reports them) and copies the file
into `<root>/benchmark/layer_metrics/`: what a `benchmark` PR does for
good once the twins are merged (and then deletes this directory, tool
and all), and what a builder does to a throw-away copy (`git archive`
under `.scratch/`, or the chip tool's machine) to read them with
`benchmark/run.py --trace 1`.  It refuses a root that is a git checkout.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys

PENDING = os.path.dirname(os.path.abspath(__file__))


def lay(root: str) -> list:
    """Returns the names laid, in the manifest's new order."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    have = {m["name"] for m in manifest["per_layer"]}
    laid = []
    for src in sorted(glob.glob(os.path.join(PENDING, "*.json"))):
        with open(src) as f:
            spec = json.load(f)
        if spec["name"] in have:
            continue
        manifest["per_layer"].append({
            "name": spec["name"], "unit": spec["unit"],
            "better": spec["better"], "source": spec["manifest_source"],
            "layer": spec["layer"], "moves": spec["moves"]})
        shutil.copy(src, os.path.join(root, manifest["paths"][0],
                                      "layer_metrics"))
        laid.append(spec["name"])
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1)
    return laid


def main(argv) -> int:
    if len(argv) != 2:
        sys.stderr.write(__doc__)
        return 2
    root = os.path.abspath(argv[1])
    if os.path.exists(os.path.join(root, ".git")):
        sys.stderr.write(f"{root} is a git checkout: lay the pending "
                         f"metrics over a copy\n")
        return 2
    print("laid:", " ".join(lay(root)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""Everything the harness knows about a cell comes from here: the entry
of `BENCHMARK.json` and the data files it names.  A later PR adds files
and manifest entries and edits nothing that is there.

  configs/<config>.json        the deployment (the manifest names the file)
  traffic/<traffic>.json       loop, clients, query, entry point
  queries/<query>.py           plan builder + independent oracle
  entries/<entry>.py           how a plan is handed to the program
  data/<generator>.py          seeded tables
  layer_metrics/<metric>.json  one per-layer metric: source + what to read
  sources/<source>.py          one reader per source
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """benchmark/<kind>/<name>.py, found by name."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads`, with every file it names resolved."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.manifest["paths"][0])
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"there are {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = self.entry["chips"]
        cfg_entry = next(c for c in self.manifest["configs"]
                         if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            self.bench_dir, "traffic", f"{self.entry['traffic']}.json"))

    def module(self, kind: str, name: str):
        return load_module(kind, name, self.bench_dir)

    def _reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self):
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def layer_metrics(self):
        """[(manifest entry, metric file)] for this cell's per-layer
        metrics."""
        out = []
        for m in self.manifest["per_layer"]:
            if self._reports(m):
                out.append((m, load_json(os.path.join(
                    self.bench_dir, "layer_metrics", f"{m['name']}.json"))))
        return out

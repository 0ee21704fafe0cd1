"""Tests of the benchmark's harness.  They run on the CPU: what they show is
that the manifest and its data files agree, that the trace reduction and the
window rule compute what they say, and that each cell's plan, oracle and
comparison work end to end at a tiny scale.  No time read here means
anything.

Run them with `python -m pytest benchmark/tests -q` (they are not under
`tests/`, which a PR of kind `benchmark` may not touch).
"""

from __future__ import annotations

import gzip
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, window  # noqa: E402
from benchmark.manifest import Cell, load_json  # noqa: E402
from benchmark.sources import (counter, device_trace,  # noqa: E402
                               metric_tree, span)

BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
REHEARSED = CELLS
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# every configuration cut to a size a test can hold (a scale factor)
TINY = {"tpcds-sf10-x1": 0.05, "tpcds-sf1-x1": 0.02,
        "tpcds-sf1-returns-x1": 0.02, "tpcds-sf1-x4": 0.02,
        "tpcds-sf100-x1": 0.05, "tpcds-sf1-returns-x4": 0.02,
        "tpcds-sf10-decimal-x1": 0.05, "tpcds-sf1-window-x1": 0.05,
        "tpcds-sf1-rollup-x1": 0.05}


# ---- the manifest ---------------------------------------------------------

def test_manifest_names_units_and_files():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert MANIFEST["paths"] == ["benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in [m["name"] for m in MANIFEST["end_to_end"]]
    used = set()
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        used.add(w["config"])
        cell = Cell(w["name"], ROOT)   # resolves every file the cell names
        cell.module("queries", cell.traffic["query"])
        cell.module("entries", cell.traffic["entry"])
        cell.module("data", cell.config["generator"])
        assert cell.config["chips"] == w["chips"]
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used, f"configuration {c['name']} has no cell"
        assert c["file"].startswith("benchmark/")
        cfg = load_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) == set(cfg["reduced"])
        for key in ("source", "scale", "tables", "splits", "partitions",
                    "chips", "guarantees", "reduced", "assumed"):
            assert key in cfg, (c["name"], key)
    assert len(open(os.path.join(ROOT, "BENCHMARK.json")).read()) < 65536


def test_every_layer_metric_has_its_file_and_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        spec = load_json(os.path.join(BENCH, "layer_metrics",
                                      m["name"] + ".json"))
        for key in ("name", "layer", "moves", "unit", "better"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec["manifest_source"] == m["source"]
        assert os.path.isfile(os.path.join(BENCH, "sources",
                                           spec["source"] + ".py"))
        assert spec["denominator"].strip()
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS)
    for cell in CELLS:
        assert any(cell in m.get("workloads", CELLS)
                   for m in MANIFEST["per_layer"])


def test_one_unit_convention():
    """Shares are in % (0-100) with a named denominator; bytes per row are
    over fact rows scanned; counts and seconds of a query are per query."""
    for m in MANIFEST["per_layer"]:
        spec = load_json(os.path.join(BENCH, "layer_metrics",
                                      m["name"] + ".json"))
        r = spec["read"]
        if m["name"].endswith(("_share", "_roofline")):
            assert m["unit"] == "%", m["name"]
            assert not spec["denominator"].startswith("none")
            if spec["source"] == "counter":
                assert r["scale"] == 100.0 and isinstance(r["den"], list)
        else:
            assert m["unit"] != "%", m["name"]
        if m["unit"] == "B/row":
            assert r["den"] == "fact_rows_scanned"
        if spec["source"] in ("counter", "span") and m["unit"] != "%" \
                and m["unit"] != "B/row" and m["name"] != "fold_final_slots":
            # (`fold_final_slots` is per task: slots a task's table ends at)
            assert r["den"] == "queries", m["name"]


# ---- the sources ----------------------------------------------------------

def test_counter_source():
    ctx = {"counters": {"a": 30, "b": 10}, "queries": 2, "fact_rows": 5}
    read = counter.read
    assert read({"read": {"num": ["a"], "den": "queries"}}, ctx) == 15
    assert read({"read": {"num": ["a"], "den": "fact_rows_scanned"}},
                ctx) == 3
    assert read({"read": {"num": ["b"], "den": ["a", "b"],
                          "scale": 100.0}}, ctx) == 25.0
    assert read({"read": {"num": ["zz"], "den": "queries"}}, ctx) is None
    zero = dict(ctx, counters={"a": 0, "b": 0})
    assert read({"read": {"num": ["a"], "den": ["a", "b"]}}, zero) is None


def test_metric_tree_source_uses_own_time():
    tree = {"name": "ShuffleWriterExec", "values": {"elapsed_compute_ns": 100},
            "children": [
                {"name": "AggExec", "values": {"elapsed_compute_ns": 80},
                 "children": [{"name": "ParquetScanExec",
                               "values": {"elapsed_compute_ns": 30},
                               "children": []}]}]}
    own = metric_tree.own_times([tree, tree])
    assert own == {"ShuffleWriterExec": 40, "AggExec": 100,
                   "ParquetScanExec": 60}
    ctx = {"trees": [tree]}
    assert metric_tree.read({"read": {"operators": ["AggExec"]}}, ctx) == 50.0
    assert metric_tree.read({"read": {"operators": ["JoinExec"]}},
                            ctx) is None


def test_span_source():
    ctx = {"spans": [{"name": "task", "dur_ns": 2_000_000_000},
                     {"name": "task", "dur_ns": 1_000_000_000},
                     {"name": "other", "dur_ns": 5}], "queries": 2}
    assert span.read({"read": {"span": "task", "stat": "count",
                               "den": "queries"}}, ctx) == 1.0
    assert span.read({"read": {"span": "task", "stat": "seconds"}}, ctx) == 3
    assert span.read({"read": {"span": "none", "stat": "count"}}, ctx) is None


def synthetic_trace():
    """One device, a 10 us window of one query, on the profiler's clock;
    the program's spans are on a clock 1,000,000 ns behind it."""
    events = {"devices": {"/device:TPU:0": {
        "lines": ["XLA Modules", "XLA Ops"],
        "busy": [[1000, 3000], [5000, 6000], [9000, 10500]],
        "programs": [["jit_fold_impl", 1000, 2000], ["jit__take", 5000, 1000],
                     ["jit_fold_impl", 9000, 1500]]}},
        "annotations": [["bench_query", 0, 10000]]}
    off = 1_000_000
    spans = [
        {"name": "task", "t0_ns": 500 - off, "t1_ns": 4500 - off,
         "dur_ns": 4000},
        {"name": "stage_loop_chunk", "t0_ns": 800 - off, "t1_ns": 3600 - off,
         "dur_ns": 2800},
        {"name": "shuffle_exchange", "t0_ns": 400 - off, "t1_ns": 8000 - off,
         "dur_ns": 7600},
        {"name": "operator:AggExec", "t0_ns": 0, "t1_ns": 10, "dur_ns": 10}]
    return events, spans, [0 - off]


def test_trace_reduction_on_a_synthetic_trace():
    events, spans, starts = synthetic_trace()
    s = device_trace.reduce(events, spans, starts)
    assert s["window_s"] == pytest.approx(10e-6)
    # the last interval is clipped at the window's end
    assert s["busy_s"] == pytest.approx((2000 + 1000 + 1000) / 1e9)
    assert s["programs"]["jit_fold_impl"] == pytest.approx(3.5e-6)
    assert s["programs"]["jit__take"] == pytest.approx(1e-6)
    # gaps: [0,1000) mid 500 -> task; [3000,5000) mid 4000 -> task;
    # [6000,9000) mid 7500 -> exchange only; no gap after 10000
    assert s["gaps"] == pytest.approx({"in_task": 3e-6, "exchange": 3e-6})
    ctx = {"trace": s, "queries": 1}
    assert device_trace.read({"read": {"stat": "idle_share"}},
                             ctx) == pytest.approx(60.0)
    assert device_trace.read(
        {"read": {"stat": "gap_seconds", "categories": ["exchange",
                                                        "between_tasks"],
                  "den": "queries"}}, ctx) == pytest.approx(3e-6)
    assert device_trace.read(
        {"read": {"stat": "program_seconds", "pattern": "^jit_fold",
                  "den": "queries"}}, ctx) == pytest.approx(3.5e-6)
    assert device_trace.read(
        {"read": {"stat": "program_seconds", "pattern": "^nothing"}},
        ctx) is None
    b = device_trace.breakdown(s)
    assert b["device_ops"][0][0] == "jit_fold_impl"
    assert {g[0] for g in b["idle_gaps"]} == {"in_task", "exchange"}


def test_trace_reduction_on_the_recorded_trace():
    """A trace of one q06 query at SF1 recorded on a TPU v5e (PR 23), cut
    to its first events.  The numbers asserted were computed by hand from
    the file with `merge` alone."""
    path = os.path.join(BENCH, "tests", "data", "trace_q06_v5e.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    s = device_trace.reduce(rec["events"], rec["spans"],
                            rec["query_starts_ns"])
    dev = next(iter(rec["events"]["devices"].values()))
    q = [a for a in rec["events"]["annotations"] if a[0] == "bench_query"]
    lo, hi = q[0][1], q[-1][1] + q[-1][2]
    busy = sum(min(e, hi) - max(b, lo) for b, e in device_trace.merge(
        [tuple(iv) for iv in dev["busy"]]) if e > lo and b < hi)
    assert s["busy_s"] == pytest.approx(busy / 1e9)
    assert s["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < s["busy_s"] < s["window_s"]
    assert sum(s["gaps"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    assert s["programs"] and all(v > 0 for v in s["programs"].values())
    assert rec["expected"]["programs_top"] == \
        device_trace.breakdown(s)["device_ops"][0][0]
    assert rec["expected"]["gap_top"] == \
        device_trace.breakdown(s)["idle_gaps"][0][0]


def test_merge_and_program_name():
    assert device_trace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == \
        [[1, 4], [5, 8]]
    assert device_trace.program_name("jit_fold_impl(123)") == "jit_fold_impl"
    assert device_trace.program_name("jit_fold_impl") == "jit_fold_impl"


# ---- the window rule ------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("query_s,seconds,expected_s,n", [
    (2.7, 45.0, 2.7, 16),     # 16 queries end at 43.2 s; 1.8 s left < 2.7
    (16.0, 45.0, 16.0, 2),    # after two, 13 s are left
    (35.0, 45.0, 35.0, 1),
    (60.0, 45.0, 60.0, 1),    # one query always runs
    (5.0, 10.0, 5.0, 1),      # 4.95 s left after the first: not enough
    (5.0, 10.2, 5.0, 2),
])
def test_window_rule_on_a_fake_clock(query_s, seconds, expected_s, n):
    clock = FakeClock()

    def run_one():
        clock.t += 0.05        # what the harness does around the query
        clock.t += query_s
        return query_s

    walls = window.run_window(run_one, seconds, expected_s, clock)
    assert walls == [query_s] * n
    # never overshoots unless the one query it must run is longer itself
    assert clock.t - 100.0 <= max(seconds, query_s + 0.05) + 1e-9


def test_the_seed_reorders_rows_and_changes_no_size():
    """Every file, and every 1,024-row block of it, holds the same rows
    under any seed; only their order differs."""
    import numpy as np

    from benchmark.data import tpcds_data as gen
    a, b = (gen.make_tables(["store_sales"], 0.05, 7, 4, seed)["store_sales"]
            ["ss_ticket_number"].to_numpy() for seed in (1, 2_147_483_999))
    assert len(a) == len(b) and (a != b).mean() > 0.9
    per = -(-len(a) // 4)
    for f in range(4):
        xa, xb = a[f * per:(f + 1) * per], b[f * per:(f + 1) * per]
        full = len(xa) // gen.SEED_BLOCK_ROWS * gen.SEED_BLOCK_ROWS
        shape = (-1, gen.SEED_BLOCK_ROWS)
        assert (np.sort(xa[:full].reshape(shape), 1)
                == np.sort(xb[:full].reshape(shape), 1)).all()
        assert sorted(xa[full:]) == sorted(xb[full:])
    again = gen.make_tables(["store_sales"], 0.05, 7, 4, 1)["store_sales"]
    assert (again["ss_ticket_number"].to_numpy() == a).all()


# ---- each cell's plan, oracle and comparison, at a tiny scale -------------

def tiny_root(tmp_path, extra=None):
    """A checkout in miniature: the benchmark's files as they are, the
    configurations cut to a tiny scale in a copy, never in place."""
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = json.loads(json.dumps(MANIFEST))
    for c in bm["configs"]:
        p = os.path.join(root, c["file"])
        cfg = load_json(p)
        cfg.update(scale=TINY[c["name"]], tables={})
        with open(p, "w") as f:
            json.dump(cfg, f)
    if extra:
        extra(root, bm)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root


def drive(root, cell_name, device_path, trace=0, seconds=0.3,
          seed=2_500_000_123):
    """`run.drive`, everything after the look for a chip, on as many of
    the CPU's devices as the cell has chips."""
    import jax

    from benchmark import run as bench_run
    cell = Cell(cell_name, root)
    device_path(cell.chips)
    peaks = load_json(os.path.join(cell.bench_dir, "peaks.json"))
    return bench_run.drive(cell, seed, seconds, trace,
                           jax.devices()[:cell.chips],
                           peaks["devices"]["TPU v5 lite"],
                           time.perf_counter())


SETUP_PARTS = ["setup_import_s", "setup_gen_s", "setup_write_s",
               "setup_native_s", "setup_load_s", "setup_warm_s"]
# what may lie between the six parts of set-up and `setup_s` (PERF.md,
# section 3): the oracle's and `fold_work`'s seconds are taken out, so what
# is left is a few statements' worth
SETUP_REMAINDER_S = 0.5


def _setup_parts(out: str) -> dict:
    line = [ln for ln in out.splitlines() if ln.startswith('{"setup_parts"')]
    return json.loads(line[-1])["setup_parts"]


def test_the_untraced_line_is_the_contracts(tmp_path, device_path, capsys):
    root = tiny_root(tmp_path)
    res = drive(root, CELLS[0], device_path, trace=0)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"query_wall_s", "setup_s"}
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    # every number compared beside its limit: the line's last key, and the
    # last lines of standard error
    assert set(res["compared"]) == set(check.LIMITS) | {"compiles_in_window"}
    for name, c in res["compared"].items():
        assert c["value"] <= c["limit"], name
    io = capsys.readouterr()
    assert "float_max_rel_err=" in io.out and "(limit 1e-09)" in io.out
    last = io.err.strip().splitlines()[-len(res["compared"]):]
    assert [ln.split("=")[0] for ln in last] == [
        f"compared {name}" for name in res["compared"]]
    parts = _setup_parts(io.out)
    assert parts["setup_s"] == res["metrics"]["setup_s"]["value"]


@pytest.mark.parametrize("cell_name", REHEARSED)
def test_cpu_rehearsal_of_each_cell(tmp_path, cell_name, device_path,
                                    capsys):
    """The traced run of every cell at a tiny scale: correct, the manifest's
    names and nothing else, the set-up's six parts and what they sum to."""
    root = tiny_root(tmp_path)
    traced = drive(root, cell_name, device_path, trace=1)
    assert traced["correct"] is True and traced["failed"] == 0
    reported = set(traced["metrics"])
    # what needs a device plane has nothing to read on the CPU; the rest
    # is there, named as the manifest names it
    assert {"query_wall_max_s", "oracle_wall_s", "tasks_per_query",
            "compiles_in_window", "exchange_s_share", "programs_loaded",
            "scan_row_groups_pruned_share"} | set(SETUP_PARTS) <= reported
    listed = {m["name"] for m, _spec in Cell(cell_name, root).layer_metrics()}
    assert reported <= listed
    for name, m in traced["metrics"].items():
        if m["unit"] == "%":
            assert 0.0 <= m["value"] <= 100.0, name
    parts = _setup_parts(capsys.readouterr().out)
    got = {name: traced["metrics"][name]["value"] for name in SETUP_PARTS}
    assert got == {name: parts[name[len("setup_"):]] for name in SETUP_PARTS}
    assert all(v >= 0 for v in got.values())
    between = parts["setup_s"] - sum(got.values())
    assert 0 <= between < SETUP_REMAINDER_S, (between, parts)


def test_a_cell_is_added_with_new_files_only(tmp_path, device_path):
    """A configuration, a traffic mix, a query and a per-layer metric over
    an existing source, each as a new file plus a manifest entry."""
    def extra(root, bm):
        b = os.path.join(root, "benchmark")
        before = {}
        for d, _dirs, files in os.walk(b):
            for f in files:
                p = os.path.join(d, f)
                before[p] = open(p, "rb").read()
        cfg = load_json(os.path.join(b, "configs", "tpcds-sf1-x1.json"))
        cfg.update(scale=0.03, tables={})
        with open(os.path.join(b, "configs", "tpcds-new.json"), "w") as f:
            json.dump(cfg, f)
        with open(os.path.join(b, "traffic", "closed1_q06b.json"), "w") as f:
            json.dump({"loop": "closed", "clients": 1, "query": "q06b",
                       "entry": "dag_scheduler", "trace_seconds": 1}, f)
        with open(os.path.join(b, "queries", "q06b.py"), "w") as f:
            f.write("from benchmark.queries.q06 import *  # noqa\n"
                    "from benchmark.queries.q06 import plan, oracle\n")
        with open(os.path.join(b, "layer_metrics",
                               "prefetch_waits.json"), "w") as f:
            json.dump({"name": "prefetch_waits", "layer": "scan decode + H2D",
                       "moves": "query_wall_s", "unit": "count",
                       "better": "lower", "source": "counter",
                       "manifest_source": "program_counter",
                       "read": {"num": ["prefetch_waits"], "den": "queries"},
                       "denominator": "queries completed"}, f)
        bm["configs"].append({"name": "tpcds-new", "source": "x",
                              "file": "benchmark/configs/tpcds-new.json",
                              "reduced": [], "why": "x"})
        bm["workloads"].append({"name": "new_cell", "config": "tpcds-new",
                                "traffic": "closed1_q06b", "chips": 1,
                                "why": "x"})
        bm["per_layer"].append({"name": "prefetch_waits", "unit": "count",
                                "better": "lower",
                                "source": "program_counter",
                                "layer": "scan decode + H2D",
                                "moves": "query_wall_s",
                                "workloads": ["new_cell"]})
        for p, content in before.items():
            assert open(p, "rb").read() == content, f"{p} was edited"

    root = tiny_root(tmp_path, extra)
    res = drive(root, "new_cell", device_path, trace=1)
    assert res["correct"] is True
    assert "prefetch_waits" in res["metrics"]
    assert drive(root, "new_cell", device_path, trace=0)["correct"] is True


# ---- the gate -------------------------------------------------------------

def run_cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = run_cli(ROOT, "--workload", CELLS[0], "--seed", "1", "--seconds",
                "1", "--trace", "0")
    assert p.returncode == 2
    assert "no accelerator" in p.stderr
    assert '"correct"' not in p.stdout
    assert not os.path.isdir(os.path.join(ROOT, ".bench_work", CELLS[0],
                                          "tables"))


def test_run_refuses_a_directory_without_the_program(tmp_path):
    root = str(tmp_path / "bare")
    os.makedirs(root)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    p = run_cli(root, "--workload", CELLS[0], "--seed", "1", "--seconds",
                "1", "--trace", "0")
    assert p.returncode not in (0, 2)
    assert '"correct"' not in p.stdout

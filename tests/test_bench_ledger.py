"""The benchmark's readers of the program ledger and of the spans that
exist (ISSUE 51): `sources/program_loads.py`, `sources/eager_by_op.py`,
`sources/span_wall.py` over `sources/speakers.py`, and their thirteen
metric files.  The manifest holds as many entries as it may, so the files
wait in `benchmark/layer_metrics_pending/` beside `lay.py`, which lays
them over a copy; a `benchmark` PR that enters them moves each file to
`layer_metrics/` and deletes that directory, and every test here holds
in either state: nothing pins how many entries the manifest has.
Synthetic contexts, the recorded traces, and one rehearsal of a cell
through `run.drive` on the CPU.
"""

import glob
import gzip
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Cell, load_json  # noqa: E402
from benchmark.sources import (chips, device_trace, eager_by_op,  # noqa: E402
                               program_loads, span_gap_op, span_wall)
from benchmark.sources.speakers import Speakers  # noqa: E402
from blaze_tpu.bridge import xla_stats  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
PENDING_DIR = os.path.join(BENCH, "layer_metrics_pending")
ENTERED_DIR = os.path.join(BENCH, "layer_metrics")
MANIFEST = load_json(os.path.join(ROOT, "BENCHMARK.json"))
LEDGER = ("programs_wall_s", "programs_trace_s", "programs_lower_s",
          "programs_backend_s", "programs_cache_retrieval_s",
          "programs_loaded_eager")
EAGER = ("eager_device_s", "eager_agg_device_s",
         "eager_exchange_write_device_s", "eager_exchange_read_device_s",
         "eager_rest_device_s")
WALL = ("d2h_wait_wall_s", "prefetch_wait_wall_s")
THIRTEEN = LEDGER + EAGER + WALL
WAITING = sorted(n for n in THIRTEEN
                 if os.path.isfile(os.path.join(PENDING_DIR, n + ".json")))
# each metric's file, where it waits or where it was entered
SPECS = {n: load_json(os.path.join(
    PENDING_DIR if n in WAITING else ENTERED_DIR, n + ".json"))
    for n in THIRTEEN}
TABLE = span_gap_op.load_table()
MS = 1_000_000
RECORDED = [os.path.join(ROOT, "tests", "data", "trace_q93_x4_v5e.json.gz"),
            os.path.join(BENCH, "tests", "data", "trace_q06_v5e.json.gz")]


def _lay_tool():
    """`layer_metrics_pending/lay.py` while the directory exists."""
    path = os.path.join(PENDING_DIR, "lay.py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location("lay_pending", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LAY = _lay_tool()


def span(name, t0, t1, tid, sid, parent=None, **attrs):
    s = {"name": name, "t0_ns": t0 * MS, "t1_ns": t1 * MS,
         "dur_ns": (t1 - t0) * MS, "sid": sid, "tid": tid,
         "thread": f"blaze-task-{tid}"}
    if parent is not None:
        s["parent"] = parent
    if attrs:
        s["attrs"] = attrs
    return s


def read(name, ctx, **kw):
    spec = SPECS[name]
    mod = {"program_loads": program_loads, "eager_by_op": eager_by_op,
           "span_wall": span_wall}[spec["source"]]
    return mod.read(spec, ctx, **kw)


def entry_of(spec):
    """The manifest entry a metric file stands for: no `workloads`, so
    every cell reports it."""
    return {"name": spec["name"], "unit": spec["unit"],
            "better": spec["better"], "source": spec["manifest_source"],
            "layer": spec["layer"], "moves": spec["moves"]}


# -- the thirteen metric files, waiting or entered ----------------------------------

@pytest.mark.parametrize("name", THIRTEEN)
def test_a_metric_waits_with_no_entry_or_is_entered_as_its_file_says(name):
    entries = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    entered = os.path.isfile(os.path.join(ENTERED_DIR, name + ".json"))
    if name in WAITING:
        assert not entries and not entered
    else:
        assert entered and entries == [entry_of(SPECS[name])]


@pytest.mark.parametrize("name", THIRTEEN)
def test_a_metric_file_is_held_to_the_manifests_rules(name):
    spec = SPECS[name]
    assert spec["name"] == name
    assert set(spec) == {"name", "layer", "moves", "unit", "better", "source",
                         "manifest_source", "read", "denominator"}
    assert spec["layer"] in {m["layer"] for m in MANIFEST["per_layer"]
                             if m["name"] not in THIRTEEN}
    assert spec["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    assert spec["manifest_source"] in ("device_trace", "program_span",
                                       "program_counter", "host_clock")
    assert spec["better"] == "lower" and spec["denominator"].strip()
    assert spec["unit"] == ("count" if name == "programs_loaded_eager"
                            else "s")
    source = os.path.join(BENCH, "sources", spec["source"] + ".py")
    assert os.path.isfile(source)
    if spec["moves"] == "query_wall_s":
        assert spec["read"]["den"] == "queries"
        assert spec["denominator"].startswith("queries completed")
    else:
        assert spec["layer"] == "compile"
        assert spec["denominator"].startswith("none:")
    if spec["source"] == "eager_by_op" and spec["read"]["families"]:
        assert "ATTRIBUTED BY TIME" in spec["denominator"]
    # no file reads what another reads, accepted or waiting
    reads = json.dumps([spec["source"], spec["read"]], sort_keys=True)
    others = [load_json(p) for p in glob.glob(
        os.path.join(ENTERED_DIR, "*.json"))
        if os.path.basename(p) != name + ".json"] \
        + [SPECS[n] for n in WAITING if n != name]
    assert all(json.dumps([o["source"], o["read"]], sort_keys=True) != reads
               for o in others)


def test_the_four_eager_parts_cover_every_family_once():
    parts = [f for n in EAGER[1:] for f in SPECS[n]["read"]["families"]]
    assert sorted(parts) == sorted(list(TABLE["families"])
                                   + [span_gap_op.OTHER, span_gap_op.NO_OP])
    assert SPECS["eager_device_s"]["read"]["families"] is None


def copy_with_the_waiting_laid(root):
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return LAY.lay(root) if LAY else []


@pytest.fixture(scope="module")
def laid(tmp_path_factory):
    """A copy of the benchmark that holds all thirteen: the waiting ones
    laid over it by the tool."""
    root = str(tmp_path_factory.mktemp("laid"))
    return root, copy_with_the_waiting_laid(root)


def test_laying_appends_one_entry_a_file_and_moves_nothing(laid):
    root, names = laid
    assert names == WAITING
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    accepted = len(MANIFEST["per_layer"])
    assert manifest["per_layer"][:accepted] == MANIFEST["per_layer"]
    assert [m["name"] for m in manifest["per_layer"][accepted:]] == names
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end"):
        assert manifest[key] == MANIFEST[key]
    for m in manifest["per_layer"][accepted:]:
        spec = load_json(os.path.join(root, "benchmark", "layer_metrics",
                                      m["name"] + ".json"))
        assert spec == SPECS[m["name"]] and m == entry_of(spec)
    if LAY:
        assert LAY.lay(root) == []      # a second time: nothing


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_reports_the_thirteen_once_laid(laid, cell):
    root, names = laid
    listed = [m["name"] for m, _spec in Cell(cell, root).layer_metrics()]
    assert sorted(n for n in listed if n in THIRTEEN) == sorted(THIRTEEN)
    assert len(listed) == len(Cell(cell, ROOT).layer_metrics()) + len(names)
    for _m, spec in Cell(cell, root).layer_metrics():
        assert callable(Cell(cell, root).module(
            "sources", spec["source"]).read)


@pytest.mark.skipif(LAY is None, reason="the thirteen are entered: the "
                    "pending directory and its tool are gone")
def test_the_tool_refuses_a_git_checkout(tmp_path):
    (tmp_path / ".git").mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    done = subprocess.run(
        [sys.executable, os.path.join(PENDING_DIR, "lay.py"), str(tmp_path)],
        capture_output=True, text=True)
    assert done.returncode == 2 and "git checkout" in done.stderr
    assert load_json(str(tmp_path / "BENCHMARK.json")) == MANIFEST


# -- program_loads: set-up's program seconds, asked of the program ------------------

def _ledger(monkeypatch):
    rec = dict(program="jit__take", kind="eager", depth=0,
               site="plan/fused.py:_drain_table:1800")
    monkeypatch.setattr(xla_stats, "_program_loads", [
        dict(rec, phase="trace", t0_ns=0, t1_ns=10 * MS, tid=1),
        dict(rec, phase="lower", t0_ns=10 * MS, t1_ns=15 * MS, tid=1),
        dict(rec, phase="backend", t0_ns=15 * MS, t1_ns=45 * MS, tid=1,
             cache_hit=True, retrieval_ns=20 * MS),
        dict(rec, phase="backend", t0_ns=30 * MS, t1_ns=60 * MS, tid=2,
             cache_hit=False, program="jit_fold_impl__runtime_stage_loop",
             kind="metered", site="runtime/loop.py:run_partition:9"),
        # inside the window: a reader of set-up does not see it
        dict(rec, phase="backend", t0_ns=120 * MS, t1_ns=130 * MS, tid=1,
             cache_hit=False)])


def test_the_reader_takes_what_ended_before_the_first_query(
        monkeypatch, tmp_path):
    _ledger(monkeypatch)
    os.makedirs(tmp_path / ".bench_work" / "x.trace")
    with open(tmp_path / ".bench_work" / "x.trace" / "trace_events.json",
              "w") as f:
        json.dump({"events": {}, "query_starts_ns": [100 * MS, 200 * MS],
                   "spans": []}, f)
    ctx = {"queries": 2, "spans": []}
    got = {n: read(n, ctx, root=str(tmp_path)) for n in LEDGER}
    assert got == {"programs_wall_s": pytest.approx(0.060),
                   "programs_trace_s": pytest.approx(0.010),
                   "programs_lower_s": pytest.approx(0.005),
                   "programs_backend_s": pytest.approx(0.060),
                   "programs_cache_retrieval_s": pytest.approx(0.020),
                   "programs_loaded_eager": 1}
    assert got["programs_wall_s"] <= got["programs_trace_s"] \
        + got["programs_lower_s"] + got["programs_backend_s"]
    assert got["programs_cache_retrieval_s"] <= got["programs_backend_s"]
    # the summary is taken once a run
    assert ctx["program_loads_summary"]["requests_metered"] == 1


def test_without_a_trace_record_the_windows_first_span_bounds_it(
        monkeypatch, tmp_path):
    _ledger(monkeypatch)
    ctx = {"queries": 1, "spans": [span("task", 50, 70, 1, 1),
                                   span("d2h", 47, 48, 1, 2)]}
    # a stale record (another run's count of queries) is no record
    os.makedirs(tmp_path / ".bench_work" / "x.trace")
    with open(tmp_path / ".bench_work" / "x.trace" / "trace_events.json",
              "w") as f:
        json.dump({"events": {}, "query_starts_ns": [1, 2, 3],
                   "spans": []}, f)
    assert read("programs_backend_s", ctx, root=str(tmp_path)) \
        == pytest.approx(0.030)      # the one that ended by 47 ms
    assert read("programs_wall_s", {"queries": 1, "spans": []},
                root=str(tmp_path)) == pytest.approx(0.070)   # no bound
    # a record of as many queries from another process's clock is none either
    with open(tmp_path / ".bench_work" / "x.trace" / "trace_events.json",
              "w") as f:
        json.dump({"events": {}, "query_starts_ns": [900 * MS],
                   "spans": []}, f)
    assert read("programs_backend_s", dict(ctx, chips_record=None),
                root=str(tmp_path)) == pytest.approx(0.030)
    fresh = {"queries": 1, "spans": ctx["spans"]}
    assert read("programs_backend_s", fresh, root=str(tmp_path)) \
        == pytest.approx(0.030)


def test_a_parent_without_the_accessor_reads_nothing(monkeypatch, tmp_path):
    monkeypatch.delattr(xla_stats, "program_load_summary")
    ctx = {"queries": 1, "spans": []}
    assert [read(n, ctx, root=str(tmp_path)) for n in LEDGER] == [None] * 6


def test_a_ledger_that_has_trimmed_records_reads_nothing(
        monkeypatch, tmp_path):
    """The cap drops the OLDEST records, which are set-up's: a sum that
    lacks them would read low, so the reader reports none."""
    _ledger(monkeypatch)
    assert read("programs_wall_s", {"queries": 1, "spans": []},
                root=str(tmp_path)) == pytest.approx(0.070)
    monkeypatch.setitem(xla_stats._backend, "program_loads_trimmed", 3)
    ctx = {"queries": 1, "spans": []}
    assert [read(n, ctx, root=str(tmp_path)) for n in LEDGER] == [None] * 6


def test_the_audit_takes_no_mention_in_the_docs_for_a_reader():
    """ROADMAP D8's audit (`tools/audit_observability.py`): a name the
    docs list is unread unless code reads it; the one counter the ledger
    keeps has its reader, the program-load source."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import audit_observability as audit
    got = audit.audit()
    assert "program_loads_trimmed" not in got["unread_counters"]
    assert not any(k.startswith("program_") for k in got["unread_counters"])
    # documented and exported, and still nobody's: the docs do not count
    assert "backend_compile_ns" in got["unread_counters"]
    assert "backend_compile_ns" in got["docs_only_counters"]
    assert set(got["docs_only_spans"]) <= set(got["unread_spans"])
    assert "xla_compile" not in got["unread_spans"]


# -- speakers: span_gap_op's rule, restated -------------------------------------------

@pytest.mark.parametrize("path", RECORDED, ids=["q93_x4", "q06"])
def test_the_restated_rule_gives_span_gap_ops_idle_seconds(path):
    with gzip.open(path) as f:
        rec = json.load(f)
    spans = rec["spans"]
    want = span_gap_op.summarize(rec, spans, TABLE)
    lo, hi, offset = chips.window(rec)
    who = Speakers(spans, TABLE)
    under = device_trace.merge([(s["t0_ns"], s["t1_ns"]) for s in who.real
                                if s["name"] in ("task", "stage_loop_chunk")])
    families = dict.fromkeys(who.families, 0.0)
    idle = {}
    for dev in rec["events"]["devices"].values():
        busy = device_trace._clip(dev["busy"], lo, hi)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            t = (g0 + g1) / 2 - offset
            if g1 <= g0 or not span_gap_op._covered(under, t):
                continue
            said = who.at(t) or [(span_gap_op.NO_OP, "task", [])]
            for family, first, _names in said:
                families[family] += (g1 - g0) / 1e9 / len(said)
                idle[first] = idle.get(first, 0.0) \
                    + (g1 - g0) / 1e9 / len(said)
    n = len(rec["events"]["devices"])
    assert {k: v / n for k, v in families.items()} \
        == pytest.approx(want["families"])
    assert {k: v / n for k, v in idle.items()} \
        == pytest.approx(want["span_idle"])


@pytest.mark.parametrize("path", RECORDED, ids=["q93_x4", "q06"])
def test_the_sweep_says_what_asking_at_a_point_says(path):
    with gzip.open(path) as f:
        rec = json.load(f)
    who = Speakers(rec["spans"], TABLE)
    segments = list(who.segments())
    assert segments and all(t0 < t1 for t0, t1, _said in segments)
    assert all(a[1] <= b[0] for a, b in zip(segments, segments[1:]))
    for t0, t1, said in segments[::7]:
        for t in (t0, (t0 + t1) / 2, t1 - 1):
            assert sorted(map(repr, who.at(t))) == sorted(map(repr, said))
    # outside every segment nobody speaks
    for a, b in zip(segments, segments[1:]):
        if b[0] > a[1]:
            assert who.at((a[1] + b[0]) / 2) == []
    # and the bisection over the sweep is `at`, chip by chip
    lookup = who.lookup()
    chips_seen = {(s.get("attrs") or {}).get("device") for s in who.real
                  if s["name"] == "task"}
    for t0, t1, _said in segments[::5]:
        for device in chips_seen | {None}:
            assert sorted(map(repr, lookup((t0 + t1) / 2, device))) \
                == sorted(map(repr, who.at((t0 + t1) / 2, device)))
    assert lookup(segments[0][0] - 1) == [] == lookup(segments[-1][1])


# -- span_wall: a wait cut to the wall -----------------------------------------------

def four_tasks(waits):
    """Four task threads over 0-100 ms; `waits`: tid -> (t0, t1) of a d2h
    under an op span."""
    spans = []
    for tid in (1, 2, 3, 4):
        spans.append(span("task", 0, 100, tid, tid * 10, mode="sync"))
        spans.append(span("op:AggExec", 5, 95, tid, tid * 10 + 1, tid * 10))
        if tid in waits:
            spans.append(span("d2h", *waits[tid], tid, tid * 10 + 2,
                              tid * 10 + 1, bytes=8))
    return spans


def test_a_wait_on_two_of_four_speaking_threads_reads_half_its_length():
    spans = four_tasks({1: (20, 60), 2: (20, 60)})
    ctx = {"spans": spans, "queries": 1}
    assert read("d2h_wait_wall_s", ctx) == pytest.approx(0.020)
    # the counter's way sums to twice that; four waiting read the whole
    assert sum(s["dur_ns"] for s in spans if s["name"] == "d2h") \
        == 80 * MS
    every = four_tasks({t: (20, 60) for t in (1, 2, 3, 4)})
    assert read("d2h_wait_wall_s", {"spans": every, "queries": 2}) \
        == pytest.approx(0.040 / 2)
    # no span of the name: nothing to read, not 0
    assert read("prefetch_wait_wall_s", ctx) is None
    # spans without `tid` (before PR 35): nothing to read
    old = [{k: v for k, v in s.items() if k != "tid"} for s in spans]
    assert read("d2h_wait_wall_s", {"spans": old, "queries": 1}) is None


def test_a_wait_never_exceeds_the_time_a_task_was_open():
    # waits that overlap on every thread, and one outside any task
    spans = four_tasks({t: (10, 90) for t in (1, 2, 3, 4)})
    spans.append(span("d2h", 150, 190, 9, 900, bytes=8))
    ctx = {"spans": spans, "queries": 1}
    assert read("d2h_wait_wall_s", ctx) == pytest.approx(0.080)
    assert sum(s["dur_ns"] for s in spans if s["name"] == "d2h") / 1e9 \
        > 0.100                      # the sum passes the tasks' wall


def test_a_wait_for_a_pipeline_stage_is_the_stages_own_spans():
    """The task thread waits 10-90 ms for its shuffle writer's prefetch
    thread, which itself waits 30-50 ms for a leaf: 20 ms, not 80."""
    stage = "blaze-prefetch-shuffle_map"
    spans = [span("task", 0, 100, 1, 10, mode="producer"),
             span("prefetch_wait", 10, 90, 1, 11, 10, source="shuffle_map"),
             dict(span("op:FusedPartialAggExec", 10, 90, 2, 20, 10),
                  thread=stage),
             dict(span("prefetch_wait", 30, 50, 2, 21, 20,
                       source="parquet_scan"), thread=stage),
             dict(span("d2h", 60, 70, 2, 22, 20, bytes=8), thread=stage),
             dict(span("produce:parquet_scan", 30, 50, 3, 30),
                  thread="blaze-prefetch-parquet_scan")]
    ctx = {"spans": spans, "queries": 1}
    assert read("prefetch_wait_wall_s", ctx) == pytest.approx(0.020)
    assert read("d2h_wait_wall_s", ctx) == pytest.approx(0.010)


# -- eager_by_op: whose the eager programs are ----------------------------------------

def two_planes():
    """Two chips, a task on each over 0-100 ms (profiler clock = spans'
    clock + 1000 ms): chip 0's thread drains (agg) while chip 1's writes
    the shuffle, at the same instants."""
    spans = [span("task", 0, 100, 1, 10, mode="loop", device=0),
             span("op:FusedPartialAggExec", 10, 90, 1, 11, 10),
             span("agg_drain", 20, 60, 1, 12, 11, table="hash"),
             span("task", 0, 100, 2, 20, mode="producer", device=1),
             span("op:ShuffleWriterExec", 10, 90, 2, 21, 20)]

    def plane(take_ms):
        return {"lines": [], "busy": [[1020 * MS, 1060 * MS]], "programs": [
            ["jit__take", 1030 * MS, take_ms * MS],
            ["jit_convert_element_type", 1040 * MS, 2 * MS],
            ["jit_fold_impl__runtime_stage_loop", 1050 * MS, 30 * MS],
            ["jit__take", 1300 * MS, 7 * MS],      # outside the window
            ["jit_packbits", 1095 * MS, 1 * MS]]}  # no operator open
    rec = {"events": {"devices": {"/device:TPU:0": plane(10),
                                  "/device:TPU:1": plane(4)},
                      "annotations": [["bench_query", 1000 * MS, 110 * MS]]},
           "query_starts_ns": [0]}
    return rec, spans


def test_over_two_planes_each_planes_programs_go_to_its_own_chips_task():
    rec, spans = two_planes()
    got = eager_by_op.summarize(rec, spans, TABLE)
    # chip 0: 10 + 2 ms under the drain; chip 1: 4 + 2 ms under the writer;
    # 1 ms a chip with only `task` open; the mean over the planes
    assert got["families"]["agg"] == pytest.approx(0.012 / 2)
    assert got["families"]["exchange_write"] == pytest.approx(0.006 / 2)
    assert got["families"]["no_op"] == pytest.approx(0.002 / 2)
    assert got["total_s"] == pytest.approx((0.013 + 0.007) / 2)
    assert sum(got["families"].values()) == pytest.approx(got["total_s"])
    # one plane alone asks every speaker: the gather is split between the two
    rec["events"]["devices"].pop("/device:TPU:1")
    one = eager_by_op.summarize(rec, spans, TABLE)
    assert one["families"]["agg"] == pytest.approx(0.006)
    assert one["families"]["exchange_write"] == pytest.approx(0.006)


def test_the_trace_and_the_spans_alone_decide_whose_a_program_is():
    """Attribution is by time alone: what the process's ledger holds when
    the trace is read (a record of `jit_packbits` asked for by the drain)
    moves nothing, so a trace read twice gives the same families."""
    import jax.numpy as jnp
    from blaze_tpu.bridge import xla_stats
    rec, spans = two_planes()
    first = eager_by_op.summarize(rec, spans, TABLE)
    jnp.packbits(jnp.arange(1213) > 7)      # the ledger learns a program
    assert any(r["program"] == "jit_packbits"
               for r in xla_stats.program_loads())
    assert eager_by_op.summarize(rec, spans, TABLE) == first
    # with only `task` open at its start, it is nobody's: 1 ms a chip
    assert first["families"]["no_op"] == pytest.approx(0.002 / 2)
    assert set(first) == {"total_s", "families"}


def test_the_readers_four_parts_sum_to_the_whole(monkeypatch, tmp_path):
    rec, spans = two_planes()
    os.makedirs(tmp_path / ".bench_work" / "x.trace")
    with open(tmp_path / ".bench_work" / "x.trace" / "trace_events.json",
              "w") as f:
        json.dump(dict(rec, spans=spans), f)
    ctx = {"queries": 1, "spans": spans}
    got = {n: read(n, ctx, root=str(tmp_path)) for n in EAGER}
    assert got["eager_device_s"] == pytest.approx(0.010)
    assert sum(got[n] for n in EAGER[1:]) \
        == pytest.approx(got["eager_device_s"])
    assert got["eager_exchange_read_device_s"] == 0.0
    # what `breakdown` lists without a kernel's name is the same sum
    summary = device_trace.reduce(rec["events"], spans,
                                  rec["query_starts_ns"])
    assert sum(v for k, v in summary["programs"].items()
               if eager_by_op.is_eager(k)) \
        == pytest.approx(got["eager_device_s"])
    # a parent without the ledger reads the same: the trace decides alone
    monkeypatch.delattr(xla_stats, "program_loads")
    again = {"queries": 1, "spans": spans}
    assert read("eager_agg_device_s", again, root=str(tmp_path)) \
        == pytest.approx(got["eager_agg_device_s"])
    # no `op:*` span, or no device plane: nothing to read
    bare = [s for s in spans if not s["name"].startswith("op:")]
    assert read("eager_device_s", {"queries": 1, "spans": bare},
                root=str(tmp_path)) is None
    assert read("eager_device_s", {"queries": 1, "spans": spans},
                root=str(tmp_path / "nowhere")) is None


@pytest.mark.parametrize("name", [
    "jit__take", "jit_convert_element_type", "jit__lambda", "jit_scatter-add",
    "jit_fold_impl__runtime_stage_loop", "jit__lambda__fused_rehash",
    "jit__assemble_tiles__sort_assemble", "jit_f__shuffle_hash_pmod"])
def test_the_reader_and_the_program_call_the_same_names_eager(name):
    assert eager_by_op.is_eager(name) \
        == (xla_stats.program_kind(name)[0] == "eager")


def test_on_the_recorded_four_chip_trace_the_parts_sum_and_planes_narrow():
    with gzip.open(RECORDED[0]) as f:
        rec = json.load(f)
    got = eager_by_op.summarize(rec, rec["spans"], TABLE)
    assert got["total_s"] > 0
    assert sum(got["families"].values()) == pytest.approx(got["total_s"])
    summary = device_trace.reduce(rec["events"], rec["spans"],
                                  rec["query_starts_ns"])
    assert got["total_s"] == pytest.approx(sum(
        v for k, v in summary["programs"].items()
        if eager_by_op.is_eager(k)))


# -- the whole cell through the harness, the thirteen laid --------------------------

def test_a_rehearsed_cells_traced_line_carries_the_ledgers_metrics(
        monkeypatch, tmp_path):
    import jax
    import blaze_tpu.bridge.placement as P
    from benchmark import run as bench_run
    from blaze_tpu import config
    from blaze_tpu.memory import MemManager
    monkeypatch.setattr(P, "host_resident", lambda: False)
    config.conf.set(config.MESH_DEVICES.key, 1)
    MemManager.init(4 << 30)
    root = str(tmp_path / "root")
    os.makedirs(root)
    copy_with_the_waiting_laid(root)
    path = os.path.join(root, "benchmark", "configs", "tpcds-sf10-x1.json")
    cfg = load_json(path)
    cfg.update(scale=0.02, tables={})
    with open(path, "w") as f:
        json.dump(cfg, f)
    cell = Cell("sf10_q01pair_x1", root)
    peaks = load_json(os.path.join(cell.bench_dir, "peaks.json"))
    xla_stats.reset()    # the ledger is the process's: other tests' too
    t0 = time.perf_counter()
    try:
        res = bench_run.drive(cell, 2_951_000_123, 0.3, 1, jax.devices()[:1],
                              peaks["devices"]["TPU v5 lite"], t0)
    finally:
        config.conf.unset(config.MESH_DEVICES.key)
    assert res["correct"] is True
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert set(LEDGER) | set(WALL) <= set(got)
    assert not set(EAGER) & set(got)        # no device plane on the CPU
    by_phase = got["programs_trace_s"] + got["programs_lower_s"] \
        + got["programs_backend_s"]
    assert 0 < got["programs_wall_s"] <= by_phase * (1 + 1e-9)
    assert got["programs_wall_s"] <= time.perf_counter() - t0
    assert got["programs_cache_retrieval_s"] <= got["programs_backend_s"]
    assert got["programs_loaded_eager"] > 0
    # the waits on the wall: under the sums, and under the longest query
    assert 0 < got["d2h_wait_wall_s"] <= got["d2h_wait_s"] * (1 + 1e-9)
    assert got["d2h_wait_wall_s"] <= got["query_wall_max_s"]
    assert got["prefetch_wait_wall_s"] <= got["query_wall_max_s"]
    # nothing was asked for inside the window, by the harness's count and
    # by the ledger's
    assert got["compiles_in_window"] == 0
    with open(os.path.join(root, ".bench_work", "sf10_q01pair_x1.trace",
                           "trace_events.json")) as f:
        starts = json.load(f)["query_starts_ns"]
    assert xla_stats.program_loads(since_ns=starts[0]) == []
    assert xla_stats.program_loads(until_ns=starts[0])

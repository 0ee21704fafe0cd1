"""The `span_gap_op` source: device-idle gaps inside tasks given to the
operator that held the thread.  Synthetic spans over synthetic device
planes, where every share can be said beforehand, and the trace recorded
on a TPU v5e (PR 23), where the families have to sum to what
`device_trace.reduce` calls `in_task` + `stage_loop_chunk`.  Runs on the
CPU; no time read here means anything."""

from __future__ import annotations

import gzip
import itertools
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import load_json  # noqa: E402
from benchmark.sources import device_trace, span_gap_op  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
FAMILIES = ["op_idle_scan_s", "op_idle_exchange_write_s",
            "op_idle_exchange_read_s", "op_idle_join_s", "op_idle_sort_s",
            "op_idle_agg_s", "op_idle_other_s", "op_idle_no_op_s"]
NEW = FAMILIES + ["idle_coalesce_s", "idle_loop_glue_s", "gc_pause_idle_s"]
MS = 1_000_000
OFFSET = 7_000 * MS     # profiler clock = perf_counter + OFFSET
TASK_THREAD, STAGE_THREAD, LEAF_THREAD = (
    "blaze-task-0.0", "blaze-prefetch-shuffle_map",
    "blaze-prefetch-parquet_scan")
# one query of 100 ms; the device idles in [0,10) [20,30) [40,50) [60,70)
# [80,90): five gaps of 10 ms with midpoints 5, 25, 45, 65, 85
BUSY = [[10, 20], [30, 40], [50, 60], [70, 80], [90, 100]]


class Spans:
    """Spans on the program's clock, in ms, with the fields the tracer
    writes."""

    def __init__(self):
        self.out, self._sid = [], itertools.count(1)

    def add(self, name, t0, t1, tid, thread=TASK_THREAD, parent=None,
            **attrs):
        s = {"name": name, "t0_ns": t0 * MS, "t1_ns": t1 * MS,
             "dur_ns": (t1 - t0) * MS, "sid": next(self._sid),
             "thread": thread, "tid": tid}
        if parent is not None:
            s["parent"] = parent["sid"]
        if attrs:
            s["attrs"] = attrs
        self.out.append(s)
        return s


def events(planes=(BUSY,)):
    return {"annotations": [["bench_query", OFFSET, 100 * MS]],
            "devices": {f"/device:TPU:{i}": {
                "lines": [], "programs": [],
                "busy": [[OFFSET + a * MS, OFFSET + b * MS] for a, b in busy]}
                for i, busy in enumerate(planes)}}


def write_events(root, ev, starts=(0,), cell="sf1_q93_x1"):
    d = os.path.join(root, ".bench_work", f"{cell}.trace")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "trace_events.json"), "w") as f:
        json.dump({"events": ev, "query_starts_ns": list(starts),
                   "spans": []}, f)


def read_all(root, spans, queries=1):
    ctx = {"spans": spans, "queries": queries}
    out = {}
    for name in NEW:
        spec = load_json(os.path.join(BENCH, "layer_metrics",
                                      name + ".json"))
        assert spec["source"] == "span_gap_op"
        out[name] = span_gap_op.read(spec, ctx, root=root)
    return out


def seconds(**ms):
    """{metric: seconds} with every family not named at 0.0."""
    want = dict.fromkeys(FAMILIES, 0.0)
    for k, v in ms.items():
        want[f"op_idle_{k}_s"] = v / 1e3
    return want


def families(got):
    return {k: pytest.approx(got[k], abs=1e-12) for k in FAMILIES}


def test_a_readback_is_its_operators(tmp_path):
    """A gap under `d2h` inside `op:SortExec` inside
    `op:SortMergeJoinExec` is the sort family's; with only `task` open it
    is nobody's."""
    root = str(tmp_path)
    write_events(root, events())
    sp = Spans()
    task = sp.add("task", 2, 98, tid=1)
    smj = sp.add("op:SortMergeJoinExec", 20, 50, tid=1, parent=task)
    sort = sp.add("op:SortExec", 22, 48, tid=1, parent=smj)
    sp.add("d2h", 23, 27, tid=1, parent=sort)
    got = read_all(root, sp.out)
    assert families(got) == seconds(sort=20, no_op=30)
    assert sum(got[k] for k in FAMILIES) == pytest.approx(0.050)
    assert got["idle_coalesce_s"] == got["idle_loop_glue_s"] == 0.0
    assert got["gc_pause_idle_s"] == 0.0


def pipeline(sp, tid_task, tid_stage, tid_leaf, t1=98):
    """A map task as the runtime lays it out: the task thread waits in
    the shuffle writer for its child's chain, which runs on a thread
    called `blaze-prefetch-shuffle_map` and itself waits for a leaf."""
    task = sp.add("task", 2, t1, tid=tid_task)
    writer = sp.add("op:ShuffleWriterExec", 3, t1 - 1, tid=tid_task,
                    parent=task)
    sp.add("prefetch_wait", 4, t1 - 2, tid=tid_task, parent=writer,
           source="shuffle_map")
    item = sp.add("produce:shuffle_map", 4, 60, tid=tid_stage,
                  thread=STAGE_THREAD, parent=writer)
    agg = sp.add("op:FusedPartialAggExec", 5, 58, tid=tid_stage,
                 thread=STAGE_THREAD, parent=item)
    scan = sp.add("op:ParquetScanExec", 6, 30, tid=tid_stage,
                  thread=STAGE_THREAD, parent=agg)
    sp.add("prefetch_wait", 7, 29, tid=tid_stage, thread=STAGE_THREAD,
           parent=scan, source="parquet_scan")
    leaf = sp.add("produce:parquet_scan", 7, 29, tid=tid_leaf,
                  thread=LEAF_THREAD, parent=scan)
    sp.add("h2d", 8, 28, tid=tid_leaf, thread=LEAF_THREAD, parent=leaf)
    return task


def test_the_stage_thread_speaks_for_the_waiting_task_thread(tmp_path):
    """The task thread is in a `prefetch_wait` for
    `blaze-prefetch-shuffle_map` all along: silent.  The stage thread's
    innermost operator takes the gap (a wait for the LEAF is the scan's);
    once the stage thread has nothing open the waiter speaks itself, as
    the shuffle writer it waits in.  The leaf's h2d takes nothing."""
    root = str(tmp_path)
    write_events(root, events())
    sp = Spans()
    pipeline(sp, tid_task=1, tid_stage=2, tid_leaf=3)
    got = read_all(root, sp.out)
    assert families(got) == seconds(agg=20, scan=10, exchange_write=20)


def test_two_tasks_open_at_once_take_half_each(tmp_path):
    root = str(tmp_path)
    write_events(root, events())
    sp = Spans()
    a = sp.add("task", 2, 98, tid=1)
    sp.add("op:SortExec", 2, 98, tid=1, parent=a)
    b = sp.add("task", 2, 50, tid=2, thread="blaze-task-0.1")
    sp.add("op:IpcReaderExec", 2, 50, tid=2, thread="blaze-task-0.1",
           parent=b)
    got = read_all(root, sp.out)
    assert families(got) == seconds(sort=15 + 20, exchange_read=15)


def test_threads_of_one_name_are_told_apart_by_tid(tmp_path):
    """Two map tasks, both chains on threads called
    `blaze-prefetch-shuffle_map`: each stage thread speaks for its own
    task.  The second task ends at 50 ms and its chain is shifted by
    nothing, so until then every gap is halved between equal families."""
    root = str(tmp_path)
    write_events(root, events())
    one, two = Spans(), Spans()
    pipeline(one, tid_task=1, tid_stage=2, tid_leaf=3)
    two._sid = itertools.count(100)
    pipeline(two, tid_task=11, tid_stage=12, tid_leaf=13)
    alone = read_all(root, one.out)
    both = read_all(root, one.out + two.out)
    assert families(both) == {k: pytest.approx(alone[k], abs=1e-12)
                              for k in FAMILIES}
    # by name alone the two chains would be one thread whose spans
    # cross: the tids keep both walks whole
    assert {s["tid"] for s in one.out + two.out
            if s["thread"] == STAGE_THREAD} == {2, 12}


def test_glue_spans_and_collector_pauses(tmp_path):
    """`span_idle`: the first span of the walk that is no boundary span.
    `gc_pause`: whole gaps under a collection on any thread, in a task or
    not."""
    root = str(tmp_path)
    write_events(root, events())
    sp = Spans()
    task = sp.add("task", 12, 98, tid=1)           # the first gap: no task
    agg = sp.add("op:FusedPartialAggExec", 13, 97, tid=1, parent=task)
    window = sp.add("loop_window", 21, 29, tid=1, parent=agg)
    sp.add("h2d", 22, 28, tid=1, parent=window)
    sp.add("table_init", 41, 49, tid=1, parent=agg)
    other = sp.add("op:FilterExec", 61, 69, tid=1, parent=agg)
    joined = sp.add("coalesce", 62, 68, tid=1, parent=other)
    sp.add("d2h", 63, 67, tid=1, parent=joined)
    sp.add("gc_pause", 1, 9, tid=5, thread="MainThread", generation=2,
           collected=10)
    sp.add("gc_pause", 84, 86, tid=1, parent=agg, generation=0, collected=0)
    got = read_all(root, sp.out)
    assert families(got) == seconds(agg=30, other=10)
    assert got["idle_loop_glue_s"] == pytest.approx(0.020)
    assert got["idle_coalesce_s"] == pytest.approx(0.010)
    assert got["gc_pause_idle_s"] == pytest.approx(0.020)


def test_no_operator_span_reads_nothing_and_no_gap_reads_zero(tmp_path):
    """The parent commit emits no `op:*` span: every metric is left out
    and nothing raises, with or without a `tid` on its spans.  A program
    that emits them and idles in none reads 0.0, not nothing (PR 30 was
    refused for a listed metric that read nothing)."""
    root = str(tmp_path)
    write_events(root, events())
    sp = Spans()
    task = sp.add("task", 2, 98, tid=1)
    sp.add("d2h", 23, 27, tid=1, parent=task)
    assert read_all(root, sp.out) == dict.fromkeys(NEW)
    old = [{k: v for k, v in s.items() if k != "tid"} for s in sp.out]
    assert read_all(root, old) == dict.fromkeys(NEW)
    # an operator that ran wholly while the device was busy
    sp.add("op:SortExec", 11, 19, tid=1, parent=task)
    got = read_all(root, sp.out)
    assert families(got) == seconds(no_op=50)
    assert all(isinstance(got[k], float) for k in NEW)


def test_four_device_planes_are_averaged(tmp_path):
    """Each plane's gaps go to the threads that speak at their midpoints;
    then the mean over the planes, as `device_trace.reduce` takes it."""
    root = str(tmp_path)
    idle_all = []                       # one gap of 100 ms, midpoint 50
    planes = (BUSY, [[0, 100]], idle_all, [[0, 40], [60, 100]])
    write_events(root, events(planes))
    sp = Spans()
    task = sp.add("task", 0, 100, tid=1)
    sp.add("op:BroadcastJoinExec", 40, 60, tid=1, parent=task)
    got = read_all(root, sp.out)
    # plane 0: 10 ms join (midpoint 45), 40 ms nobody's; plane 1: busy;
    # plane 2: 100 ms join; plane 3: 20 ms join
    assert families(got) == seconds(join=(10 + 100 + 20) / 4, no_op=40 / 4)
    one = device_trace.reduce(events(planes), sp.out, [0])
    assert sum(got[k] for k in FAMILIES) == pytest.approx(
        one["gaps"]["in_task"])


def test_a_stale_or_missing_file_is_not_read(tmp_path):
    root = str(tmp_path)
    sp = Spans()
    task = sp.add("task", 2, 98, tid=1)
    sp.add("op:SortExec", 20, 50, tid=1, parent=task)
    assert read_all(root, sp.out) == dict.fromkeys(NEW)
    write_events(root, events())
    assert read_all(root, sp.out, queries=2) == dict.fromkeys(NEW)
    assert read_all(root, sp.out)["op_idle_sort_s"] == pytest.approx(0.020)


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(BENCH, "tests", "data",
                                "trace_q06_v5e.json.gz"), "rt") as f:
        return json.load(f)


def test_the_families_sum_to_the_gaps_under_task_on_the_recording(
        recorded, tmp_path):
    """The recorded q06 trace (one device plane, four `task` spans that
    overlap, no thread ids): with a `tid` a task and operator spans laid
    over two thirds of each task, the eight families are `in_task` +
    `stage_loop_chunk` of the accepted reduction, to the last digit."""
    root = str(tmp_path)
    d = os.path.join(root, ".bench_work", "sf1_q06_x1.trace")
    os.makedirs(d)
    with open(os.path.join(d, "trace_events.json"), "w") as f:
        json.dump({"events": recorded["events"],
                   "query_starts_ns": recorded["query_starts_ns"],
                   "spans": []}, f)
    sid = itertools.count(1)
    spans = []
    tasks = [s for s in recorded["spans"] if s["name"] == "task"]
    assert len(tasks) == 4
    window_end = recorded["query_starts_ns"][0] + int(
        recorded["events"]["annotations"][0][2])
    for i, t in enumerate(tasks):
        task = dict(t, sid=next(sid), tid=100 + i, thread=f"blaze-task-0.{i}")
        spans.append(task)
        # the recording is cut: lay the operators over the part of the
        # task that the traced window still holds
        third = (min(t["t1_ns"], window_end) - t["t0_ns"]) // 3
        for j, name in enumerate(["op:ParquetScanExec",
                                  "op:BroadcastJoinExec"]):
            a = t["t0_ns"] + j * third
            spans.append({"name": name, "t0_ns": a, "t1_ns": a + third,
                          "dur_ns": third, "sid": next(sid),
                          "parent": task["sid"], "tid": 100 + i,
                          "thread": task["thread"]})
    spans += [s for s in recorded["spans"] if s["name"] != "task"]
    got = read_all(root, spans)
    old = device_trace.reduce(recorded["events"], spans,
                              recorded["query_starts_ns"])
    assert sum(got[k] for k in FAMILIES) == pytest.approx(
        old["gaps"]["in_task"] + old["gaps"].get("stage_loop_chunk", 0.0),
        rel=1e-12)
    for k in ("op_idle_scan_s", "op_idle_join_s", "op_idle_no_op_s"):
        assert got[k] > 0, k
    assert got["op_idle_sort_s"] == got["op_idle_agg_s"] == 0.0


def test_every_operator_of_the_program_has_a_family_or_is_other():
    """`op_families.json` names operator classes: one that the program
    no longer has would be a dead line, and the families the issue lists
    are all there."""
    import importlib
    from blaze_tpu.ops.base import ExecutionPlan
    for module in ("ops", "ops.orc", "ops.joins.bnlj", "plan.fused",
                   "shuffle.reader", "shuffle.writer"):
        importlib.import_module("blaze_tpu." + module)

    def classes(c):
        for sub in c.__subclasses__():
            yield sub.__name__
            yield from classes(sub)
    have = set(classes(ExecutionPlan))
    table = span_gap_op.load_table()
    assert list(table["families"]) == ["scan", "exchange_write",
                                       "exchange_read", "join", "sort", "agg"]
    named = [n for names in table["families"].values() for n in names]
    assert len(named) == len(set(named))
    prefix = span_gap_op.OP_PREFIX
    dead = [n for n in named
            if n.startswith(prefix) and n[len(prefix):] not in have]
    assert not dead, dead
    from blaze_tpu.bridge import tracing
    for n in named + table["boundary"] + [span_gap_op.TASK]:
        assert n in tracing.SPAN_NAMES or (
            n.startswith(prefix) and prefix + "*" in tracing.SPAN_NAMES), n


def test_the_new_metrics_are_in_the_manifest_and_name_no_cell():
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    tail = [entries[name] for name in NEW]
    layers = {"op_idle_scan_s": "scan decode + H2D",
              "op_idle_exchange_write_s": "exchange",
              "op_idle_exchange_read_s": "exchange",
              "op_idle_join_s": "join", "op_idle_sort_s": "join",
              "op_idle_agg_s": "fused aggregation",
              "idle_loop_glue_s": "fused aggregation"}
    for m in tail:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}                      # no `workloads`
        assert (m["unit"], m["better"], m["moves"], m["source"]) == \
            ("s", "lower", "query_wall_s", "device_trace")
        assert m["layer"] == layers.get(
            m["name"], "plan decode + per-task runtime")
        spec = load_json(os.path.join(BENCH, "layer_metrics",
                                      m["name"] + ".json"))
        assert spec["read"]["den"] == "queries"

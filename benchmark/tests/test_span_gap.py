"""The `span_gap` source on the trace recorded on a TPU v5e (PR 23): with
the program's boundary spans laid inside the recording's `task` spans it
splits what `gap_categories.json` calls `in_task`, and the parts sum to
it.  Runs on the CPU; no time read here means anything."""

from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import load_json  # noqa: E402
from benchmark.sources import device_trace, span_gap  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
NEW_SPANS = ["d2h", "h2d", "prefetch_wait", "agg_drain", "join_probe",
             "join_build"]
IDLE = ["idle_prefetch_wait_s", "idle_h2d_s", "idle_d2h_s",
        "idle_agg_drain_s", "idle_join_host_s", "idle_task_other_s"]


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(BENCH, "tests", "data",
                                "trace_q06_v5e.json.gz"), "rt") as f:
        return json.load(f)


def lay_spans(rec):
    """The recording's spans plus synthetic boundary spans inside its
    longest `task`: fifths of its traced part as prefetch_wait, d2h (nested in a
    join_probe that also covers the next fifth) and agg_drain, and one
    `h2d` on a prefetch thread over the whole task."""
    task = max((s for s in rec["spans"] if s["name"] == "task"),
               key=lambda s: s["dur_ns"])
    # the recording is cut: lay the spans over the part of the task that
    # the traced window still holds (its end, on the program's clock)
    query = rec["events"]["annotations"][0]
    end = min(task["t1_ns"], rec["query_starts_ns"][0] + int(query[2]))
    t0, fifth = task["t0_ns"], (end - task["t0_ns"]) // 5

    def span(name, i, n=1, thread="MainThread"):
        a, b = t0 + i * fifth, t0 + (i + n) * fifth
        return {"name": name, "t0_ns": a, "t1_ns": b, "dur_ns": b - a,
                "thread": thread}
    return rec["spans"] + [
        span("prefetch_wait", 0), span("join_probe", 1, 2), span("d2h", 1),
        span("agg_drain", 3),
        span("h2d", 0, 5, thread="blaze-prefetch-parquet_scan")]


def write_events(root, rec, cell="sf1_q06_x1"):
    d = os.path.join(root, ".bench_work", f"{cell}.trace")
    os.makedirs(d)
    with open(os.path.join(d, "trace_events.json"), "w") as f:
        json.dump({"events": rec["events"],
                   "query_starts_ns": rec["query_starts_ns"],
                   "spans": []}, f)


def read_all(root, spans, queries=1):
    ctx = {"spans": spans, "queries": queries}
    out = {}
    for name in IDLE:
        spec = load_json(os.path.join(BENCH, "layer_metrics",
                                      name + ".json"))
        assert spec["source"] == "span_gap"
        out[name] = span_gap.read(spec, ctx, root=root)
    return out


def test_the_parts_sum_to_the_old_in_task(recorded, tmp_path):
    root = str(tmp_path)
    write_events(root, recorded)
    spans = lay_spans(recorded)
    old = device_trace.reduce(recorded["events"], spans,
                              recorded["query_starts_ns"])
    got = read_all(root, spans)
    assert all(v is not None for v in got.values())
    # every synthetic span lies inside a task span, so nothing moves in
    # from the other buckets: the six parts are the old in_task, exactly
    assert sum(got.values()) == pytest.approx(old["gaps"]["in_task"],
                                              rel=1e-12)
    for name in ("idle_prefetch_wait_s", "idle_d2h_s",
                 "idle_agg_drain_s", "idle_join_host_s",
                 "idle_task_other_s"):
        assert got[name] > 0, name
    # the one h2d span ran on a prefetch thread and is dropped: it
    # would have taken every gap of the task
    assert got["idle_h2d_s"] == 0.0
    new = span_gap.summarize({"events": recorded["events"],
                              "query_starts_ns":
                                  recorded["query_starts_ns"]}, spans)
    for cat in ("stage_loop_chunk", "exchange", "between_tasks",
                "between_queries"):
        assert new["gaps"].get(cat, 0.0) == pytest.approx(
            old["gaps"].get(cat, 0.0))
    assert sum(new["gaps"].values()) == pytest.approx(
        old["window_s"] - old["busy_s"])


def test_d2h_is_taken_before_the_join_that_contains_it(recorded, tmp_path):
    root = str(tmp_path)
    write_events(root, recorded)
    spans = lay_spans(recorded)
    with_d2h = read_all(root, spans)
    without = read_all(root, [s for s in spans if s["name"] != "d2h"])
    assert without["idle_d2h_s"] is None        # no such span: no reading
    assert without["idle_join_host_s"] == pytest.approx(
        with_d2h["idle_join_host_s"] + with_d2h["idle_d2h_s"])


def test_a_pipeline_stage_on_a_prefetch_thread_is_kept(recorded, tmp_path):
    """The shuffle writer prefetches its child's operator chain: the map
    task's joins and readbacks run on `blaze-prefetch-shuffle_map` while
    the task thread waits for it.  That thread's spans stay, and the
    wait for it explains nothing and goes."""
    root = str(tmp_path)
    write_events(root, recorded)
    stage = "blaze-prefetch-shuffle_map"
    spans = [dict(s, thread=stage)
             if s.get("thread") == "MainThread" else s
             for s in lay_spans(recorded)]
    task = max((s for s in recorded["spans"] if s["name"] == "task"),
               key=lambda s: s["dur_ns"])
    waits_for_stage = dict(task, name="prefetch_wait",
                           attrs={"source": "shuffle_map"},
                           thread="blaze-task-0.0")
    kept = span_gap.task_thread_spans(spans + [waits_for_stage])
    assert waits_for_stage not in kept
    assert {s["name"] for s in kept if s.get("thread") == stage} == \
        {"prefetch_wait", "join_probe", "d2h", "agg_drain"}
    assert not [s for s in kept
                if s.get("thread") == "blaze-prefetch-parquet_scan"]
    assert read_all(root, spans + [waits_for_stage]) == \
        read_all(root, lay_spans(recorded))


def test_a_program_without_the_spans_reports_nothing(recorded, tmp_path):
    """The parent commit emits none of the new spans: every metric is
    left out, `idle_task_other_s` too, and nothing raises."""
    root = str(tmp_path)
    write_events(root, recorded)
    got = read_all(root, recorded["spans"])
    assert got == {name: None for name in IDLE}


def test_no_trace_events_no_reading(recorded, tmp_path):
    assert read_all(str(tmp_path), lay_spans(recorded)) == \
        {name: None for name in IDLE}


def test_a_stale_file_is_not_read(recorded, tmp_path):
    """The newest file is another run's if its query starts do not match
    the queries this run completed."""
    root = str(tmp_path)
    write_events(root, recorded)
    assert read_all(root, lay_spans(recorded), queries=2) == \
        {name: None for name in IDLE}


def test_the_newest_file_wins(recorded, tmp_path):
    root = str(tmp_path)
    write_events(root, dict(recorded, query_starts_ns=[1, 2, 3]),
                 cell="older")
    old = os.path.join(root, ".bench_work", "older.trace",
                       "trace_events.json")
    os.utime(old, (1, 1))
    write_events(root, recorded, cell="newer")
    assert span_gap.newest_trace_events(root)["query_starts_ns"] == \
        recorded["query_starts_ns"]


def test_one_reduction_a_run(recorded, tmp_path, monkeypatch):
    root = str(tmp_path)
    write_events(root, recorded)
    calls = []
    real = device_trace.reduce
    monkeypatch.setattr(device_trace, "reduce",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    read_all(root, lay_spans(recorded))
    assert len(calls) == 1


def test_category_table_keeps_the_old_order_around_the_new_spans():
    old = load_json(os.path.join(BENCH, "sources", "gap_categories.json"))
    new = load_json(os.path.join(BENCH, "sources",
                                 "gap_categories_task.json"))
    names = [c["category"] for c in new["categories"]]
    assert names == ["stage_loop_chunk", "d2h", "h2d", "prefetch_wait",
                     "agg_drain", "join_host", "task_other", "exchange",
                     "between_tasks"]
    assert new["otherwise"] == old["otherwise"]
    by = {c["category"]: c["spans"] for c in new["categories"]}
    for c in old["categories"]:
        want = "task_other" if c["category"] == "in_task" \
            else c["category"]
        assert by[want] == c["spans"]
    assert sorted(s for c in names[1:6] for s in by[c]) == \
        sorted(NEW_SPANS)


def test_the_new_metrics_are_in_the_manifest_with_their_cells():
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in IDLE + ["h2d_s", "scan_decode_s", "prefetch_wait_s",
                        "d2h_wait_s"]:
        m = entries[name]
        assert (m["unit"], m["better"], m["moves"]) == \
            ("s", "lower", "query_wall_s")
    # the two q06 cells since PR 50 merged the `x4_` twin into the entry
    assert entries["idle_join_host_s"]["workloads"] == ["sf1_q06_x1",
                                                        "sf1_q06_x4"]
    # over the mesh a map task's table is drained on the device: no
    # `agg_drain` span, so the four-chip cell is not in this one's list
    assert "sf1_q06_x4" not in entries["idle_agg_drain_s"]["workloads"]

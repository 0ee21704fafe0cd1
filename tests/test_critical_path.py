"""Critical-path bottleneck attribution (bridge/critical_path.py),
the explain-analyze footer built on it, and live query progress
(serving/progress.py) + the `tools.top` renderer.
"""

import pytest

from blaze_tpu.bridge import critical_path
from blaze_tpu.plan.explain import format_bottleneck_footer
from blaze_tpu.serving import progress

_MS = 1_000_000


def _span(name, t0_ms, dur_ms, sid=1, parent=None, **attrs):
    t0, dur = t0_ms * _MS, dur_ms * _MS
    r = {"name": name, "t0_ns": t0, "t1_ns": t0 + dur, "dur_ns": dur,
         "sid": sid, "thread": "t", "attrs": dict(attrs)}
    if parent is not None:
        r["parent"] = parent
    return r


# -- attribution -------------------------------------------------------------

def test_categories_sum_to_wall_exactly():
    spans = [
        _span("admission_wait", 0, 50, sid=1),
        _span("task", 50, 300, sid=2),
        _span("shuffle_exchange", 100, 80, sid=3),   # inside the task
        _span("stage_loop_chunk", 200, 60, sid=4),   # inside the task
        _span("produce:parquet_scan", 260, 30, sid=5),
        # 350..400 uncovered, then a final exchange
        _span("device_exchange", 400, 100, sid=6),
    ]
    att = critical_path.attribute(spans)
    total = sum(att[c] for c in critical_path.CATEGORIES)
    assert total == pytest.approx(att["wall_s"], rel=1e-9)
    assert att["wall_s"] == pytest.approx(0.500)
    assert att["admission_wait"] == pytest.approx(0.050)
    # exchange beats the covering task span (priority order)
    assert att["exchange_wire"] == pytest.approx(0.180)
    assert att["device_compute"] == pytest.approx(0.060)
    assert att["scan_decode"] == pytest.approx(0.030)
    assert att["host_compute"] == pytest.approx(0.130)
    # the uncovered 50ms precedes an exchange segment -> barrier
    assert att["barrier_idle"] == pytest.approx(0.050)
    assert att["dispatch_gap"] == 0.0


def test_uncovered_gap_not_before_exchange_is_dispatch_gap():
    spans = [_span("task", 0, 100), _span("task", 200, 100, sid=2)]
    att = critical_path.attribute(spans)
    assert att["dispatch_gap"] == pytest.approx(0.100)
    assert att["barrier_idle"] == 0.0


def test_xla_compile_instant_counts_its_ns_attr():
    spans = [{"name": "xla_compile", "t0_ns": 0, "t1_ns": 0, "dur_ns": 0,
              "sid": 1, "attrs": {"ns": 100 * _MS}},
             _span("task", 100, 100, sid=2)]
    att = critical_path.attribute(spans)
    assert att["device_compute"] == pytest.approx(0.100)


def test_malformed_spans_are_skipped_not_fatal():
    spans = [None, 42, {"name": 7}, {"name": "task", "t0_ns": "x"},
             _span("task", 0, 10)]
    att = critical_path.attribute(spans)
    assert att["host_compute"] == pytest.approx(0.010)


def test_report_none_without_usable_spans():
    assert critical_path.bottleneck_report([]) is None
    assert critical_path.bottleneck_report(
        [{"name": "task", "t0_ns": 5, "t1_ns": 5, "dur_ns": 0}]) is None


def test_report_shape_and_dominant():
    spans = [_span("task", 0, 100), _span("device_exchange", 0, 80, sid=2)]
    rep = critical_path.bottleneck_report(spans, wall_s=0.11)
    assert rep["v"] == 1
    assert rep["dominant"] == "exchange_wire"
    assert rep["dominant_fraction"] == pytest.approx(0.8)
    assert rep["query_wall_s"] == pytest.approx(0.11)
    assert sum(rep["categories"].values()) == pytest.approx(rep["wall_s"])


def test_critical_path_descends_longest_children():
    spans = [
        _span("task", 0, 300, sid=1),
        _span("agg_drain", 0, 100, sid=2, parent=1),
        _span("produce:parquet_scan", 100, 180, sid=3, parent=1),
    ]
    path = critical_path.critical_path(spans)
    assert [e["name"] for e in path] == \
        ["task", "produce:parquet_scan"]
    assert path[1]["category"] == "scan_decode"


# -- explain footer ----------------------------------------------------------

def test_footer_none_keeps_disabled_path_identical():
    assert format_bottleneck_footer(None) is None
    assert format_bottleneck_footer({"span_count": 0}) is None


def test_footer_renders_dominant_and_categories():
    rep = critical_path.bottleneck_report(
        [_span("task", 0, 100), _span("device_exchange", 0, 80, sid=2)])
    line = format_bottleneck_footer(rep)
    assert line.startswith("bottleneck: wall=0.100s")
    assert "dominant=exchange_wire (80%)" in line
    assert "host_compute=0.020s" in line


# -- live progress -----------------------------------------------------------

@pytest.fixture(autouse=True)
def fresh_progress():
    progress.reset()
    yield
    progress.reset()


def test_progress_lifecycle_and_rates():
    progress.note_query_start("q1", fingerprint="fp", prior_wall_s=10.0)
    progress.note_stage_start("q1", 0, 4)
    progress.note_task_done("q1", 0)
    progress.note_rows("q1", 0, rows=100, bytes_=1000)
    p = progress.progress("q1")
    assert p["state"] == "running"
    assert p["tasks_done"] == 1 and p["tasks_total"] == 4
    assert p["rows"] == 100 and p["bytes"] == 1000
    assert p["eta_source"] == "prior"  # prior wins while one exists
    assert 0.0 <= p["eta_s"] <= 10.0
    progress.note_query_done("q1", "finished", wall_s=0.5)
    done = progress.progress("q1")
    assert done["state"] == "done" and done["status"] == "finished"
    assert done["elapsed_s"] == pytest.approx(0.5)
    snap = progress.snapshot_all()
    assert snap["running"] == []
    assert [q["query_id"] for q in snap["recent"]] == ["q1"]


def test_progress_fraction_eta_without_prior():
    progress.note_query_start("q2")
    progress.note_stage_start("q2", 0, 10)
    for _ in range(5):
        progress.note_task_done("q2", 0)
    p = progress.progress("q2")
    assert p["eta_source"] == "fraction"
    assert p["eta_s"] is not None and p["eta_s"] >= 0.0


def test_progress_unknown_query_is_none():
    assert progress.progress("nope") is None


def test_progress_stage_reentry_accumulates_totals():
    progress.note_query_start("q3")
    progress.note_stage_start("q3", 0, 2)
    progress.note_stage_start("q3", 0, 1)  # recovery re-entry
    assert progress.progress("q3")["tasks_total"] == 3


# -- tools.top renderer ------------------------------------------------------

def test_top_render_table_and_serving_line():
    from blaze_tpu.tools import top
    progress.note_query_start("q4", prior_wall_s=2.0)
    progress.note_stage_start("q4", 0, 2)
    progress.note_task_done("q4", 0)
    snap = progress.snapshot_all()
    serving = {"services": [
        {"queue_depth": 1, "running": 2, "max_concurrent": 4,
         "max_queue": 16, "counters": {},
         "tenants": {"acme": {"completed": 7, "p50_ms": 1.0,
                              "p99_ms": 2.0}}}]}
    text = top.render(snap, serving)
    assert "QUERY" in text and "q4" in text
    assert "0/1" in text   # stages column
    assert "1/2" in text   # tasks column
    assert "serving: running=2 queued=1 completed=7 services=1" in text

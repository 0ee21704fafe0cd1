"""Source `chips` on a synthetic trace of two chips: the reduction needs
nothing but the record `run.py` writes beside the trace and the program's
spans, so it is held here without a chip."""

import json
import os

import pytest

from benchmark.sources import chips

S = 1_000_000_000     # ns in a second
OFFSET = 5 * S        # profiler clock minus perf_counter


def record():
    """A window of 10 s, two queries.  Chip 0 is busy 0-3 s and 6-7 s,
    chip 1 busy 1-2 s; the exchange program runs 0.1 s on each."""
    return {"events": {
        "devices": {
            "/device:TPU:0": {
                "busy": [[OFFSET, OFFSET + 3 * S],
                         [OFFSET + 6 * S, OFFSET + 7 * S]],
                "programs": [["jit_stage__mesh_exchange_rows",
                              OFFSET + 2 * S, S // 10],
                             ["jit_fold_impl__runtime_stage_loop",
                              OFFSET, S]]},
            "/device:TPU:1": {
                "busy": [[OFFSET + 1 * S, OFFSET + 2 * S]],
                "programs": [["jit_stage__mesh_exchange_rows",
                              OFFSET + 1 * S, S // 10]]}},
        "annotations": [["bench_query", OFFSET, 5 * S],
                        ["bench_query", OFFSET + 5 * S, 5 * S]]},
        "query_starts_ns": [0, 5 * S]}


def spans():
    # a device_exchange open 2.5-4.5 s: every chip idle from 3 s on
    return [{"name": "device_exchange", "t0_ns": 5 * S // 2,
             "t1_ns": 9 * S // 2, "dur_ns": 2 * S},
            {"name": "task", "t0_ns": 0, "t1_ns": 3 * S, "dur_ns": 3 * S}]


@pytest.fixture
def root(tmp_path):
    d = tmp_path / ".bench_work" / "cell.trace"
    os.makedirs(d)
    with open(d / "trace_events.json", "w") as f:
        json.dump(record(), f)
    return str(tmp_path)


def ctx():
    return {"queries": 2, "spans": spans(),
            "counters": {"shuffle_device_row_bytes": 23000},
            "peaks": {"hbm_bytes_per_s": 819e9}}


def read(root, **r):
    return chips.read({"read": r}, ctx(), root)


def test_busy_seconds_of_the_least_and_the_most_busy_chip(root):
    assert read(root, stat="busy_s", pick="min", den="queries") == 0.5
    assert read(root, stat="busy_s", pick="max", den="queries") == 2.0
    assert read(root, stat="busy_s", pick="max") == 4.0
    assert read(root, stat="busy_balance") == 25.0


def test_exposed_time_is_where_every_chip_idles_under_the_span(root):
    assert read(root, stat="all_idle_s", spans=["device_exchange"],
                den="queries") == pytest.approx(0.75)
    # a program that emits no such span has nothing to read
    assert read(root, stat="all_idle_s", spans=["no_such_span"]) is None


def test_roofline_divides_all_bytes_by_all_chips_time(root):
    got = read(root, stat="exchange_roofline",
               pattern="__mesh_exchange_rows$")
    assert got == pytest.approx(100 * 4 * 23000 / 0.2 / 819e9)
    assert read(root, stat="exchange_roofline",
                pattern="__nothing$") is None


def test_a_stale_or_missing_trace_reads_nothing(root, tmp_path):
    c = dict(ctx(), queries=3)     # the file holds two queries
    assert chips.read({"read": {"stat": "busy_s", "pick": "min"}}, c,
                      root) is None
    assert chips.read({"read": {"stat": "busy_s", "pick": "min"}}, ctx(),
                      str(tmp_path / "elsewhere")) is None


def test_span_gap_sorts_each_chips_idle_time_and_takes_the_mean(root):
    """The `x4_idle_*` metrics are `span_gap` over the four-chip cell's
    trace: one reduction over every device plane."""
    from benchmark.sources import span_gap
    c = ctx()
    c["spans"] = [dict(s, thread="task-0", attrs={}) for s in c["spans"]]
    got = span_gap.read({"read": {"categories": ["task_other"],
                                  "needs": ["task"], "den": "queries"}},
                        c, root)
    # a gap goes by its midpoint: under the task span (0-3 s) chip 0
    # never idles, chip 1's gap 0-1 s lies in it and its gap 2-10 s does
    # not: 1 s over two chips and two queries
    assert got == pytest.approx(0.25)

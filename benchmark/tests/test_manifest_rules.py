"""Rules the manifest and its metric files are held to since PR 50: one
entry a mechanism (as far as the twins that remain allow), an entry for
every file, an end-to-end metric for every `moves`, and a `fold_roofline`
whose numerator is the query's work and nothing the program counts.
"""

from __future__ import annotations

import glob
import json
import os
import sys

import pyarrow as pa
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import kernel_costs  # noqa: E402
from benchmark.manifest import load_json, load_module  # noqa: E402
from benchmark.sources import device_trace  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = load_json(os.path.join(ROOT, "BENCHMARK.json"))
ENTRIES = {m["name"]: m for m in MANIFEST["per_layer"]}
FILES = {os.path.basename(p)[:-len(".json")]: load_json(p)
         for p in glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))}

# the accepted readers that still stand under a cell's own name, each with
# the entry it repeats: `tests/test_bench_q93_x4.py`, `test_bench_q01_dec.py`,
# `test_bench_q51.py` and `test_bench_q67.py` hold them by name and by place,
# and a PR of kind `benchmark` may not touch `tests/` (PERF.md, section 7).
# Each goes into its accepted entry's `workloads` list once those tests
# follow; until then none may be added.
TWINS_LEFT = {
    "q93x4_exchange_collective_s": "exchange_collective_s",
    "q93x4_mesh_exchange_mb": "mesh_exchange_mb",
    "q93x4_exchange_roofline": "exchange_roofline",
    "q93x4_chip_busy_min_share": "chip_busy_min_share",
    "q93x4_smj_device_s": "smj_device_s",
    "dec_expr_eager_share": "expr_eager_share",
    "q51_sort_device_s": "sort_device_s",
    "q51_sort_resident_share": "sort_resident_share",
    "q51_smj_device_s": "smj_device_s",
    "q51_smj_streamed_runs": "smj_streamed_runs",
    "q51_probe_gather_device_s": "probe_gather_device_s",
    "q51_join_direct_probe_share": "join_direct_probe_share",
    "q51_join_device_probe_share": "join_device_probe_share",
    "q51_scan_decode_s": "scan_decode_s",
    "q51_idle_h2d_s": "idle_h2d_s",
    "q51_idle_d2h_s": "idle_d2h_s",
    "q51_idle_prefetch_wait_s": "idle_prefetch_wait_s",
    "q51_idle_task_other_s": "idle_task_other_s",
    "q51_expr_eager_share": "expr_eager_share",
    "q67_sort_resident_share": "sort_resident_share",
    "q67_sort_device_s": "sort_device_s",
    "q67_window_resident_share": "window_resident_share",
    "q67_window_device_s": "window_device_s",
    "q67_join_device_probe_share": "join_device_probe_share",
    "q67_probe_gather_device_s": "probe_gather_device_s",
    "q67_expr_eager_share": "expr_eager_share",
    "q67_scan_decode_s": "scan_decode_s",
    "q67_idle_h2d_s": "idle_h2d_s",
    "q67_idle_d2h_s": "idle_d2h_s",
}
MERGED_BY_PR_50 = {  # accepted entry: the cell its `x4_` twin reported in
    name: "sf1_q06_x4" for name in (
        "join_s_share", "scan_decode_s", "idle_prefetch_wait_s",
        "idle_h2d_s", "idle_d2h_s", "idle_join_host_s", "idle_task_other_s")}


def _reads(spec: dict) -> str:
    return json.dumps([spec["source"], spec["read"]], sort_keys=True)


# ---- one entry a mechanism ------------------------------------------------

def test_no_two_metric_files_read_the_same_thing_but_the_twins_that_remain():
    by_read = {}
    for name, spec in FILES.items():
        by_read.setdefault(_reads(spec), []).append(name)
    shared = sorted(sorted(v) for v in by_read.values() if len(v) > 1)
    want = {}
    for twin, accepted in TWINS_LEFT.items():
        want.setdefault(accepted, [accepted]).append(twin)
    assert shared == sorted(sorted(v) for v in want.values())
    # and a twin is a twin: the accepted reader, word for word, in one cell
    for twin, accepted in TWINS_LEFT.items():
        assert FILES[twin]["read"] == FILES[accepted]["read"], twin
        assert FILES[twin]["source"] == FILES[accepted]["source"], twin
        for key in ("unit", "better", "source", "moves", "layer"):
            assert ENTRIES[twin][key] == ENTRIES[accepted][key], (twin, key)
        cell, = ENTRIES[twin]["workloads"]
        assert cell not in ENTRIES[accepted]["workloads"], twin


def test_the_x4_twins_are_merged_into_their_accepted_entries():
    for name, cell in MERGED_BY_PR_50.items():
        assert "x4_" + name not in ENTRIES and "x4_" + name not in FILES
        assert ENTRIES[name]["workloads"].count(cell) == 1
        assert ENTRIES[name]["workloads"][-1] == cell
    # over the mesh a map task's table is drained on the device: no
    # `agg_drain` span to read, and a reader that finds nothing refuses a PR
    assert "sf1_q06_x4" not in ENTRIES["idle_agg_drain_s"]["workloads"]


def test_every_file_has_an_entry_and_every_entry_a_file():
    assert set(FILES) == set(ENTRIES)
    assert len(MANIFEST["per_layer"]) == len(ENTRIES) <= 128
    for name, spec in FILES.items():
        m = ENTRIES[name]
        for key in ("name", "layer", "moves", "unit", "better"):
            assert spec[key] == m[key], (name, key)
        assert spec["manifest_source"] == m["source"], name


def test_every_moves_names_an_end_to_end_metric_and_each_is_moved():
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert e2e == {"query_wall_s", "setup_s"}
    moved = {}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        moved.setdefault(m["moves"], []).append(m["name"])
    assert set(moved) == e2e
    assert moved["setup_s"] == [
        "programs_loaded", "setup_import_s", "setup_gen_s", "setup_write_s",
        "setup_native_s", "setup_load_s", "setup_warm_s"]
    for name in moved["setup_s"]:
        # every cell pays set-up: none of them names its cells
        assert "workloads" not in ENTRIES[name], name
        assert FILES[name]["source"] == "harness", name
    keys = [FILES[n]["read"]["key"] for n in moved["setup_s"][1:]]
    assert keys == ["import_s", "gen_s", "write_s", "native_s", "load_s",
                    "warm_s"]


def test_one_layer_for_one_mechanism():
    assert ENTRIES["sort_resident_share"]["layer"] == \
        ENTRIES["sort_device_s"]["layer"] == "kernels"
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert layers == {
        "client", "plan decode + per-task runtime", "scan decode + H2D",
        "expression programs", "fused aggregation", "join", "window",
        "strings as codes", "kernels", "drain + D2H", "exchange", "compile",
        "device"}


# ---- fold_work: the query's aggregations, counted by hand -----------------

def _table(**cols) -> pa.Table:
    return pa.table(cols)


def _returns_2000():
    """Six returns, four of them in 2000 (days 10-12): customers (1, 1, 2,
    NULL) at stores (7, 7, 7, 8): three groups, in two stores."""
    dd = _table(d_date_sk=pa.array([9, 10, 11, 12, 13], pa.int64()),
                d_year=pa.array([1999, 2000, 2000, 2000, 2001], pa.int32()))
    sr = _table(
        sr_returned_date_sk=pa.array([9, 10, 11, 12, 12, 13], pa.int64()),
        sr_customer_sk=pa.array([1, 1, 1, 2, None, 2], pa.int64()),
        sr_store_sk=pa.array([7, 7, 7, 7, 8, 7], pa.int64()),
        sr_return_amt=pa.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0]),
        sr_ticket_number=pa.array([1, 2, 3, 4, 5, 6], pa.int64()))
    return {"store_returns": sr, "date_dim": dd}


def _q06_tables():
    """Four items in two categories; items 2 and 4 are priced above 1.2x
    their category's average (10, 30 -> 20; 1, 5 -> 3); five sales, three
    of them of those items, at two stores."""
    item = _table(i_item_sk=pa.array([1, 2, 3, 4], pa.int64()),
                  i_category=pa.array(["a", "a", "b", "b"]),
                  i_current_price=pa.array([10.0, 30.0, 1.0, 5.0]))
    ss = _table(ss_item_sk=pa.array([1, 2, 2, 4, 3], pa.int64()),
                ss_store_sk=pa.array([5, 5, 6, 6, 6], pa.int64()),
                ss_sold_date_sk=pa.array([1, 1, 1, 1, 1], pa.int64()))
    return {"store_sales": ss, "item": item}


def _q93_tables():
    """Four line items; (item 1, ticket 1) and (item 2, ticket 1) were
    returned for the reason, (item 3, ticket 2) for another: two rows reach
    the aggregation, of one customer."""
    ss = _table(ss_item_sk=pa.array([1, 2, 3, 4], pa.int64()),
                ss_ticket_number=pa.array([1, 1, 2, 2], pa.int64()),
                ss_customer_sk=pa.array([9, 9, 8, 8], pa.int64()),
                ss_quantity=pa.array([1, 1, 1, 1], pa.int64()),
                ss_sales_price=pa.array([1.0, 1.0, 1.0, 1.0]))
    sr = _table(sr_item_sk=pa.array([1, 2, 3], pa.int64()),
                sr_ticket_number=pa.array([1, 1, 2], pa.int64()),
                sr_return_quantity=pa.array([1, 1, 1], pa.int64()),
                sr_reason_sk=pa.array([28, 28, 3], pa.int64()))
    reason = _table(r_reason_sk=pa.array([3, 28], pa.int64()),
                    r_reason_desc=pa.array(["reason 3", "reason 28"]))
    return {"store_sales": ss, "store_returns": sr, "reason": reason}


def _q51_tables():
    """Days 1-2 lie in the twelve months, day 3 does not.  Store: items (1,
    1, 2, NULL, 1) on days (1, 1, 2, 1, 3): three rows in, two groups.
    Web: items (1, 2) on days (2, NULL): one row, one group."""
    dd = _table(d_date_sk=pa.array([1, 2, 3], pa.int64()),
                d_date=pa.array([1, 2, 3], pa.int32()).cast(pa.date32()),
                d_month_seq=pa.array([1200, 1211, 1212], pa.int32()))
    ss = _table(ss_sold_date_sk=pa.array([1, 1, 2, 1, 3], pa.int64()),
                ss_item_sk=pa.array([1, 1, 2, None, 1], pa.int64()),
                ss_sales_price=pa.array([1.0] * 5))
    ws = _table(ws_sold_date_sk=pa.array([2, None], pa.int64()),
                ws_item_sk=pa.array([1, 2], pa.int64()),
                ws_sales_price=pa.array([1.0] * 2))
    return {"store_sales": ss, "web_sales": ws, "date_dim": dd}


def _q67_tables():
    """Three sales in the year (a fourth lies outside it), two of one item
    and one of another of the same brand, all on one day at one store: the
    five levels that keep the product name (8 to 4 keys) have 2 groups
    each, the four that do not (brand, class, category, nothing) 1 each:
    14."""
    dd = _table(d_date_sk=pa.array([1, 2], pa.int64()),
                d_year=pa.array([2000, 2001], pa.int32()),
                d_qoy=pa.array([1, 1], pa.int32()),
                d_moy=pa.array([1, 1], pa.int32()),
                d_month_seq=pa.array([1200, 1212], pa.int32()))
    ss = _table(ss_sold_date_sk=pa.array([1, 1, 1, 2], pa.int64()),
                ss_item_sk=pa.array([1, 1, 2, 1], pa.int64()),
                ss_store_sk=pa.array([1, 1, 1, 1], pa.int64()),
                ss_quantity=pa.array([1, 1, 1, 1], pa.int64()),
                ss_sales_price=pa.array([1.0] * 4))
    store = _table(s_store_sk=pa.array([1], pa.int64()),
                   s_store_id=pa.array(["S1"]))
    item = _table(i_item_sk=pa.array([1, 2], pa.int64()),
                  i_brand=pa.array(["b", "b"]), i_class=pa.array(["c", "c"]),
                  i_category=pa.array(["k", "k"]),
                  i_product_name=pa.array(["p1", "p2"]))
    return {"store_sales": ss, "date_dim": dd, "store": store, "item": item}


@pytest.mark.parametrize("query,tables,work", [
    ("q01pair", _returns_2000, [(4, 3)]),
    ("q01", _returns_2000, [(4, 3), (3, 2)]),
    ("q01_dec", _returns_2000, [(4, 3), (3, 2)]),
    ("q06", _q06_tables, [(4, 2), (3, 2)]),
    ("q93", _q93_tables, [(2, 1)]),
    ("q51", _q51_tables, [(1, 1), (3, 2)]),
    ("q67", _q67_tables, [(3, 14)]),
])
def test_fold_work_of_each_query_file_is_the_count_written_out_by_hand(
        query, tables, work):
    q = load_module("queries", query)
    assert hasattr(q, "FOLD_ROW_BYTES") and hasattr(q, "FOLD_SLOT_BYTES")
    assert [tuple(w) for w in q.fold_work(tables())] == work


def test_every_query_file_with_fold_widths_declares_fold_work():
    for path in glob.glob(os.path.join(BENCH, "queries", "q*.py")):
        q = load_module("queries", os.path.basename(path)[:-len(".py")])
        assert hasattr(q, "FOLD_ROW_BYTES") == hasattr(q, "fold_work"), path


# ---- fold_roofline: the same work whatever implements it ------------------

def test_fold_min_bytes_prices_rows_read_once_and_slots_written_once():
    assert kernel_costs.fold_min_bytes([(4, 3), (3, 2)], 25, 29) == \
        4 * 25 + 3 * 29 + 3 * 25 + 2 * 29
    assert kernel_costs.fold_min_bytes([], 25, 29) == 0


def _fold_ctx(counters, devices=1, fold_s=0.5, work=((1000, 10),)):
    q = load_module("queries", "q01pair")
    return {"trace": {"programs": {"jit_fold_impl__runtime_stage_loop": fold_s,
                                   "jit__take": 9.0},
                      "devices": devices, "busy_s": 1.0, "window_s": 2.0,
                      "gaps": {}},
            "queries": 2, "query": q, "counters": counters,
            "fold_work": [tuple(w) for w in work],
            "peaks": {"hbm_bytes_per_s": 1e9}}


def test_fold_roofline_does_not_move_with_what_the_program_counts():
    spec = FILES["fold_roofline"]
    assert spec["read"] == {"stat": "fold_roofline",
                            "pattern": "^jit_fold_impl"}
    wasteful = _fold_ctx({"stage_loop_rows": 5_000_000,
                          "stage_loop_tasks": 8, "stage_loop_lanes": 1 << 23})
    pruned = _fold_ctx({"stage_loop_rows": 1_000, "stage_loop_tasks": 2})
    bare = _fold_ctx({})
    want = 100.0 * 2 * (1000 * 25 + 10 * 25) / 0.5 / 1e9
    for ctx in (wasteful, pruned, bare):
        assert device_trace.read(spec, ctx) == pytest.approx(want)
    # a fold that takes less time for the same query reads higher
    assert device_trace.read(spec, _fold_ctx({}, fold_s=0.25)) == \
        pytest.approx(2 * want)
    # four chips: `programs` is the mean over the chips, the share divides
    # by what the four spent together
    assert device_trace.read(spec, _fold_ctx({}, devices=4)) == \
        pytest.approx(want / 4)
    # nothing to read: no fold program in the window, no work declared
    no_fold = _fold_ctx({})
    no_fold["trace"]["programs"] = {"jit__take": 9.0}
    assert device_trace.read(spec, no_fold) is None
    assert device_trace.read(spec, dict(bare, fold_work=None)) is None
    assert device_trace.read(spec, dict(bare, trace={})) is None
    # and the reader asks the context for nothing the program counted
    ctx = dict(bare)
    del ctx["counters"]
    assert device_trace.read(spec, ctx) == pytest.approx(want)


def test_idle_share_with_the_gaps_between_queries_taken_out():
    t = {"busy_s": 2.0, "window_s": 10.0, "programs": {}, "devices": 1,
         "gaps": {"between_queries": 2.0, "in_task": 6.0}}
    ctx = {"trace": t, "queries": 3}
    assert device_trace.read(FILES["device_idle_share"], ctx) == \
        pytest.approx(80.0)
    assert device_trace.read(FILES["device_idle_in_query_share"], ctx) == \
        pytest.approx(75.0)   # 6 idle seconds of the 8 inside queries
    t["gaps"] = {"in_task": 8.0}
    assert device_trace.read(FILES["device_idle_in_query_share"], ctx) == \
        pytest.approx(80.0)


def test_pruned_share_reads_the_scans_counters():
    from benchmark.sources import counter
    spec = FILES["scan_row_groups_pruned_share"]
    ctx = {"counters": {"scan_row_groups": 44, "scan_row_groups_pruned": 34},
           "queries": 2}
    assert counter.read(spec, ctx) == pytest.approx(100.0 * 34 / 44)
    assert counter.read(spec, {"counters": {"scan_row_groups": 44,
                                            "scan_row_groups_pruned": 0},
                               "queries": 2}) == 0.0
    assert counter.read(spec, {"counters": {}, "queries": 2}) is None
    assert counter.read(spec, {"counters": {"scan_row_groups": 0,
                                            "scan_row_groups_pruned": 0},
                               "queries": 2}) is None

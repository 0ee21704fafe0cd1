"""The benchmark's q93 (benchmark/queries/q93.py) at scale 0.01 from the
generator in which a return is a sale's line item
(benchmark/data/tpcds_returns.py): the generator's promises, and the plan
through `DagScheduler` against its oracle with the merge join on the device
path."""

import importlib.util
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCALE, DATA_SEED, SPLITS = 0.01, 20260927, 4


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}",
        os.path.join(ROOT, "benchmark", kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def gen():
    return _load("data", "tpcds_returns")


@pytest.fixture(scope="module")
def q93():
    return _load("queries", "q93")


@pytest.fixture(scope="module")
def tables(gen, q93):
    return gen.make_tables(q93.TABLES, SCALE, DATA_SEED, SPLITS, 2_900_000_123)


def test_a_return_is_a_line_item_of_a_sale(gen, tables):
    ss, sr = tables["store_sales"].to_pandas(), \
        tables["store_returns"].to_pandas()
    assert len(ss) == gen.rows("store_sales", SCALE) == 28_804
    assert len(sr) == gen.rows("store_returns", SCALE) == 2_875
    assert tables["reason"].num_rows == 35
    assert sorted(tables["reason"].to_pylist(),
                  key=lambda r: r["r_reason_sk"]) == [
        {"r_reason_sk": i, "r_reason_desc": f"reason {i}"}
        for i in range(1, 36)]
    line = ["ss_item_sk", "ss_ticket_number"]
    assert not ss.duplicated(line).any()
    assert ss.ss_ticket_number.duplicated().any()   # the ticket alone is not
    lines = ss.groupby("ss_ticket_number").size()
    assert lines.iloc[:-1].between(8, 16).all()     # the last one is cut
    shared = ss.groupby("ss_ticket_number")[
        ["ss_customer_sk", "ss_store_sk", "ss_sold_date_sk",
         "ss_sold_time_sk"]].nunique()
    assert (shared == 1).all().all()
    m = sr.merge(ss, left_on=["sr_item_sk", "sr_ticket_number"],
                 right_on=line, how="left", indicator=True)
    assert len(m) == len(sr) and (m._merge == "both").all()
    assert not sr.duplicated(["sr_item_sk", "sr_ticket_number"]).any()
    assert (m.sr_return_quantity >= 1).all()
    assert (m.sr_return_quantity <= m.ss_quantity).all()
    assert (m.sr_customer_sk == m.ss_customer_sk).all()
    assert (m.sr_store_sk == m.ss_store_sk).all()
    assert (m.sr_returned_date_sk - m.ss_sold_date_sk).between(1, 90).all()
    assert sr.sr_reason_sk.between(1, 35).all()
    assert not ss.isna().any().any() and not sr.isna().any().any()


def test_seed_changes_order_and_no_value(gen, q93, tables):
    other = gen.make_tables(q93.TABLES, SCALE, DATA_SEED, SPLITS, 7)
    for name in ("store_sales", "store_returns"):
        a, b = tables[name].to_pandas(), other[name].to_pandas()
        assert not a.equals(b)
        cols = list(a.columns)
        assert a.sort_values(cols).reset_index(drop=True).equals(
            b.sort_values(cols).reset_index(drop=True))
        # date order survives inside each file's 1,024-row blocks only as
        # far as the blocks go: the blocks themselves stay in date order
        date = "ss_sold_date_sk" if name == "store_sales" \
            else "sr_returned_date_sk"
        per = -(-len(a) // SPLITS)
        first = a[date].to_numpy()[:per]
        blocks = [first[i:i + gen.SEED_BLOCK_ROWS]
                  for i in range(0, len(first), gen.SEED_BLOCK_ROWS)]
        assert all(x.max() <= y.min() for x, y in zip(blocks, blocks[1:]))
    assert tables["reason"].sort_by("r_reason_sk").equals(
        other["reason"].sort_by("r_reason_sk"))
    redrawn = gen.make_tables(q93.TABLES, SCALE, DATA_SEED + 1, SPLITS,
                              2_900_000_123)
    assert not redrawn["store_sales"].equals(tables["store_sales"])


def test_the_order_of_the_answer_is_decided_exactly(q93, tables):
    """q93.py's promise about its first 101 sums, at this scale."""
    sums = q93.full_answer(tables)["sumsales"].to_numpy()[:101]
    gaps = np.diff(sums)
    assert len(sums) > 50 and ((gaps == 0) | (gaps > 1e-6)).all()


def test_q93_plan_on_the_device_path_matches_its_oracle(
        gen, q93, tables, tmp_path, monkeypatch):
    import blaze_tpu.bridge.placement as P
    from blaze_tpu import config
    from blaze_tpu.bridge import profiling, xla_stats
    from blaze_tpu.plan.stages import DagScheduler
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import check
    monkeypatch.setattr(P, "host_resident", lambda: False)
    paths = gen.write_parquet_splits(tables, str(tmp_path), SPLITS, 4096)
    plan = q93.plan(paths, tables, 4)
    key = config.DAG_SINGLE_TASK_BYTES.key   # tiny inputs run as one task
    old = config.DAG_SINGLE_TASK_BYTES.get()
    config.conf.set(key, 0)
    seen = {id(t) for t in profiling.recent_metrics()}
    before = xla_stats.snapshot()
    try:
        with DagScheduler() as sched:
            got = sched.run_collect(plan)
            assert sched.exec_mode == "staged" and len(sched.stages) >= 4
    finally:
        config.conf.set(key, old)
    d = xla_stats.delta(before)
    ok, line = check.verdict(check.compare(got, q93.oracle(tables), q93.KEYS,
                                           q93.ORDERED))
    assert ok, line
    assert got.num_rows == q93.oracle(tables).num_rows > 50
    # the join carried every return, each matched to its one sale, through
    # the device programs
    joined = 0
    stack = [t for t in profiling.recent_metrics() if id(t) not in seen]
    while stack:
        node = stack.pop()
        if node.get("name") == "SortMergeJoinExec":
            joined += node["values"].get("output_rows", 0)
        stack.extend(node.get("children") or [])
    returns, sales = (tables[n].num_rows
                      for n in ("store_returns", "store_sales"))
    assert joined == returns
    assert d["smj_device_pairs"] == returns
    assert d["smj_device_rows"] == returns + sales
    assert d["smj_streamed_runs"] == 0
    # only the sales side of a partition has the 1,024 rows a device sort
    # is worth
    assert 0 < d["sort_device_rows"] <= returns + sales
    # and those partitions never left the device while they were sorted:
    # the cell's result line carries `sort_resident_share`
    from benchmark.manifest import load_json
    from benchmark.sources import counter
    entry, = [e for e in load_json(os.path.join(ROOT, "BENCHMARK.json"))[
        "per_layer"] if e["name"] == "sort_resident_share"]
    assert entry["workloads"] == ["sf1_q93_x1", "sf1_q93_x4", "sf10_q01_x1",
                                  "sf10_q01_dec_x1"]
    spec = load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  "sort_resident_share.json"))
    assert counter.read(spec, {"counters": d, "queries": 1}) == 100.0
    assert d["sort_resident_rows"] >= sales
    parent = {k: v for k, v in d.items() if k != "sort_resident_rows"}
    assert counter.read(spec, {"counters": parent, "queries": 1}) is None


def _check():
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import check
    return check


def test_money_in_float32_fails_the_full_answer_and_not_the_first_100(
        q93, tables):
    """Why the cell compares the full answer too: the control the other
    cells' limit rests on (`controls.py` `float32`) passes the 100 rows
    that answer, which are 0.0, and fails every customer's sum."""
    check = _check()
    ok, _ = check.verdict(check.compare(
        q93.oracle(tables, money=np.float32), q93.oracle(tables), q93.KEYS,
        q93.ORDERED))
    want = q93.full_oracle(tables)
    nums = check.compare(q93.full_oracle(tables, money=np.float32), want,
                         q93.KEYS, False)
    refused, line = check.verdict(nums)
    assert not refused and "float_max_rel_err" in line
    assert nums["row_count_diff"] == nums["key_mismatches"] == 0
    assert 1e-8 < nums["float_max_rel_err"] < 1e-5
    assert (want.column("sumsales").to_numpy() > 0).mean() > 0.9
    if (q93.oracle(tables).column("sumsales").to_numpy() == 0).all():
        assert ok   # as at scale 1: the answer alone lets float32 through


def test_the_cell_entry_holds_the_full_answer_to_the_oracle(
        gen, q93, tables, tmp_path, monkeypatch):
    """`entries/dag_scheduler_smj.py` after a warm-up query: the plan less
    its last step on the device path against `full_oracle`, and a wrong
    sum, which the first 100 rows would let through, is a problem."""
    import blaze_tpu.bridge.placement as P
    from blaze_tpu import config
    _check()
    entry_mod = _load("entries", "dag_scheduler_smj")
    monkeypatch.setattr(P, "host_resident", lambda: False)
    paths = gen.write_parquet_splits(tables, str(tmp_path), SPLITS, 4096)
    cfg = {"partitions": 4}
    key = config.DAG_SINGLE_TASK_BYTES.key
    old = config.DAG_SINGLE_TASK_BYTES.get()
    config.conf.set(key, 0)
    try:
        entry = entry_mod.Entry(q93, paths, tables, cfg, str(tmp_path))
        entry.begin()
        got = entry.run()
        entry.end()
        assert entry.problem() is None
        assert got.num_rows == q93.oracle(tables).num_rows
        plan, want = entry.full
        assert want.num_rows >= got.num_rows > 50
        # one customer's sum off by a cent's millionth part
        sums = want.column("sumsales").to_numpy().copy()
        i = int(np.argmax(sums))
        sums[i] *= 1 + 1e-8
        entry.full = (plan, want.set_column(1, "sumsales", [sums]))
        why = entry.problem()
        assert why and "float_max_rel_err" in why and "EXCEEDED" in why
    finally:
        config.conf.set(key, old)


def test_idle_inside_the_sort_and_the_merge_join_is_read_from_their_spans(
        tmp_path):
    """`sources/span_gap_smj.py` under `gap_categories_smj.json`: an idle
    gap of the device inside an `smj_merge` or `sort_device` span goes to
    the operator, transfers and all; a program without the spans (the
    parent) and a stale trace read as absent."""
    import json
    _check()
    from benchmark.manifest import load_json
    from benchmark.sources import span_gap_smj
    specs = {n: load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       f"{n}.json"))
             for n in ("idle_smj_merge_s", "idle_sort_device_s")}
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {e["name"]: e for e in manifest["per_layer"]}
    for name in specs:
        assert entries[name]["workloads"] == ["sf1_q93_x1", "sf10_q01_x1"]

    def span(name, a, b, thread="task-0"):
        return {"name": name, "t0_ns": a, "t1_ns": b, "dur_ns": b - a,
                "thread": thread, "attrs": {}}

    # busy 100-200 and 600-700 of a 1,000 ns query: gaps of 100, 400, 300
    rec = {"events": {"annotations": [["bench_query", 0, 1000]],
                      "devices": {"0": {"busy": [[100, 200], [600, 700]],
                                        "programs": []}}},
           "query_starts_ns": [0]}
    trace = tmp_path / ".bench_work" / "cell.trace"
    trace.mkdir(parents=True)
    (trace / "trace_events.json").write_text(json.dumps(rec))
    spans = [span("task", 0, 1000), span("smj_merge", 150, 500),
             span("d2h", 300, 450), span("sort_device", 750, 900),
             span("h2d", 760, 800)]

    def read(name, spans, queries=1):
        return span_gap_smj.read(specs[name],
                                 {"spans": spans, "queries": queries},
                                 root=str(tmp_path))

    assert read("idle_smj_merge_s", spans) == pytest.approx(400e-9)
    assert read("idle_sort_device_s", spans) == pytest.approx(300e-9)
    parent = [s for s in spans if s["name"] in ("task", "d2h", "h2d")]
    assert read("idle_smj_merge_s", parent) is None
    assert read("idle_sort_device_s", parent) is None
    assert read("idle_smj_merge_s", spans, queries=2) is None

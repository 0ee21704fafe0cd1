"""End-to-end query profiler: standardized operator metrics, span
tracing, XLA compile accounting, and EXPLAIN ANALYZE.

Covers the acceptance query shape (ParquetScan -> Filter -> Project ->
HashAggregate with a hash-partition shuffle) through explain_analyze on
the staged wire path, the per-partition MetricNode merge (child names
must survive merging), the tracer, and meter_jit compile/cache-hit
classification.
"""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.bridge.metrics import BASELINE_METRICS, MetricNode
from blaze_tpu.memory import MemManager


@pytest.fixture(autouse=True)
def budget():
    MemManager.init(4 << 30)


# -- MetricNode merge (the multi-partition tree merge) -----------------------

def _tree(rows, ns, mem):
    root = MetricNode(name="AggExec")
    root.add("output_rows", rows)
    root.add("elapsed_compute_ns", ns)
    root.set_max("mem_used", mem)
    child = root.child(0, name="ScanExec")
    child.add("output_rows", rows * 2)
    return root


def test_merge_preserves_child_names_and_sums():
    merged = MetricNode()
    merged.merge_from(_tree(10, 100, 5))
    merged.merge_from(_tree(7, 50, 9))
    assert merged.name == "AggExec"
    # regression: merging into a bare skeleton used to drop child names
    assert merged.children[0].name == "ScanExec"
    assert merged.get("output_rows") == 17
    assert merged.get("elapsed_compute_ns") == 150
    assert merged.children[0].get("output_rows") == 34
    # mem_used is a peak: max across partitions, never a sum
    assert merged.get("mem_used") == 9


def test_merge_across_real_multi_partition_execution():
    from blaze_tpu.ops import FilterExec, MemoryScanExec
    from blaze_tpu.exprs import BinaryExpr, col, lit

    t = pa.table({"a": pa.array(range(300), type=pa.int64())})
    scan = MemoryScanExec.from_arrow(t, 3)  # 3 partitions
    plan = FilterExec(scan, [BinaryExpr("<", col(0), lit(150))])

    merged = MetricNode()
    for p in range(plan.num_partitions):
        before = plan.collect_metrics()
        for _ in plan.execute(p):
            pass
        merged.merge_from(plan.collect_metrics().diff(before))
    assert merged.name == "FilterExec"
    assert merged.children[0].name == "MemoryScanExec"
    assert merged.get("output_rows") == 150
    assert merged.children[0].get("output_rows") == 300
    assert merged.get("elapsed_compute_ns") > 0


def test_snapshot_diff_roundtrip():
    a = _tree(10, 100, 5)
    snap = a.snapshot()
    a.add("output_rows", 3)
    a.children[0].add("output_rows", 1)
    d = a.diff(snap)
    assert d.get("output_rows") == 3
    assert d.children[0].get("output_rows") == 1
    assert d.get("elapsed_compute_ns") == 0
    rt = MetricNode.from_dict(a.to_dict())
    assert rt.to_dict() == a.to_dict()


# -- tracing -----------------------------------------------------------------

def test_tracer_spans_context_and_jsonl(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tracing.start_tracing(path)
    try:
        with tracing.execution_context(query="q-test", stage=1):
            with tracing.execution_context(partition=2):
                with tracing.span("task", mode="sync"):
                    pass
            tracing.instant("xla_compile", kernel="k1", ns=12)
    finally:
        # a collection may run anywhere: its span is not this test's
        spans = [s for s in tracing.stop_tracing()
                 if s["name"] != "gc_pause"]
    assert [s["name"] for s in spans] == ["task", "xla_compile"]
    task = spans[0]
    assert task["ctx"] == {"query": "q-test", "stage": 1, "partition": 2}
    assert task["attrs"] == {"mode": "sync"}
    assert task["dur_ns"] >= 0
    # the instant sees the outer frames only (partition frame popped)
    assert spans[1]["ctx"] == {"query": "q-test", "stage": 1}
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert [s["name"] for s in lines
            if s["name"] != "gc_pause"] == ["task", "xla_compile"]


def test_tracing_disabled_is_noop():
    assert not tracing.enabled()
    before = len(tracing.spans())
    with tracing.span("never"):
        pass
    tracing.emit_span("never", 123)
    assert len(tracing.spans()) == before


def test_operator_totals_in_the_metric_tree_not_in_spans():
    """Per-operator totals live in the metric tree (elapsed_compute_ns);
    the tracer holds real intervals only, so no `operator:*` record."""
    from blaze_tpu.bridge.runtime import execute_plan
    from blaze_tpu.ops import FilterExec, MemoryScanExec
    from blaze_tpu.exprs import BinaryExpr, col, lit

    t = pa.table({"a": pa.array(range(64), type=pa.int64())})
    plan = FilterExec(MemoryScanExec.from_arrow(t, 1),
                      [BinaryExpr("<", col(0), lit(32))])
    tracing.start_tracing()
    try:
        execute_plan(plan)
    finally:
        spans = tracing.stop_tracing()
    names = {s["name"] for s in spans}
    assert "task" in names
    assert not any(n.startswith("operator:") for n in names)
    # `op:*` is another thing: one real interval a pull, no total
    assert {"op:FilterExec", "op:MemoryScanExec"} <= names
    tree = plan.collect_metrics()
    assert tree.name == "FilterExec"
    assert tree.values["elapsed_compute_ns"] > 0
    assert tree.children[0].values["elapsed_compute_ns"] > 0
    task = next(s for s in spans if s["name"] == "task")
    assert task["ctx"]["partition"] == 0


# -- XLA compile accounting --------------------------------------------------

def test_meter_jit_classifies_compiles_and_cache_hits():
    import jax.numpy as jnp

    xla_stats.reset()
    f = xla_stats.meter_jit(lambda x: x * 2 + 1, name="test.kernel")
    a = jnp.arange(8)
    f(a)          # compile
    f(a)          # cache hit
    f(a + 1)      # same shape: cache hit
    f(jnp.arange(16))  # new shape: compile
    rep = xla_stats.compile_report()
    e = rep["kernels"]["test.kernel"]
    assert e["calls"] == 4
    assert e["compiles"] == 2
    assert e["cache_hits"] == 2
    assert e["compile_ns"] > 0
    assert e["distinct_signatures"] == 2
    assert not e["shape_churn"]
    assert rep["totals"]["compiles"] == 2


def test_meter_jit_flags_shape_churn():
    import jax.numpy as jnp

    xla_stats.reset()
    f = xla_stats.meter_jit(lambda x: x.sum(), name="churny")
    for n in range(1, xla_stats.SHAPE_CHURN_THRESHOLD + 2):
        f(jnp.arange(n))
    e = xla_stats.compile_report()["kernels"]["churny"]
    assert e["shape_churn"]
    assert e["compiles"] == xla_stats.SHAPE_CHURN_THRESHOLD + 1


def test_a_metered_compile_is_one_span_a_phase_and_meter_jit_adds_none():
    import jax.numpy as jnp

    xla_stats.reset()
    f = xla_stats.meter_jit(lambda x: x + 1, name="traced.kernel")
    tracing.start_tracing()
    try:
        f(jnp.arange(4))   # compile -> one interval a phase
        f(jnp.arange(4))   # cache hit -> nothing
    finally:
        spans = tracing.stop_tracing()
    compiles = [s for s in spans if s["name"] == "xla_compile"]
    metered = [s for s in compiles if "kernel" in s["attrs"]]
    assert [s["attrs"]["phase"] for s in metered] == \
        ["trace", "lower", "backend"]
    for s in metered:
        assert s["attrs"]["kernel"] == "traced_kernel"
        assert s["attrs"]["program"] == "jit__lambda__traced_kernel"
        assert s["dur_ns"] > 0
    # every one comes from JAX's own compile events, the glue's too
    assert all(s["attrs"]["source"] == "backend" for s in compiles)
    assert xla_stats.compile_report()["kernels"]["traced.kernel"][
        "compiles"] == 1


def test_transfer_accounting_from_batch_layer():
    from blaze_tpu.bridge.placement import host_resident
    if host_resident():
        pytest.skip("H2D accounting requires device placement")
    from blaze_tpu.batch import ColumnBatch
    before = xla_stats.snapshot()
    cb = ColumnBatch.from_arrow(pa.RecordBatch.from_arrays(
        [pa.array(np.arange(1024, dtype=np.int64))], names=["a"]))
    cb.to_arrow()
    d = xla_stats.delta(before)
    assert d["h2d_bytes"] > 0


# -- explain_analyze ---------------------------------------------------------

def test_explain_analyze_in_process_plan():
    from blaze_tpu.ops import FilterExec, MemoryScanExec, ProjectExec
    from blaze_tpu.exprs import BinaryExpr, col, lit
    from blaze_tpu.plan import explain_analyze

    t = pa.table({"a": pa.array(range(100), type=pa.int64()),
                  "b": pa.array(np.linspace(0, 1, 100))})
    scan = MemoryScanExec.from_arrow(t, batch_rows=32)
    flt = FilterExec(scan, [BinaryExpr("<", col(0), lit(50))])
    plan = ProjectExec(flt, [col(0)], ["a"])

    prof = explain_analyze(plan, keep_result=True)
    assert prof.output_rows == 50
    assert prof.result.num_rows == 50
    text = prof.render_text()
    # the planner collapses Filter->Project into one FilterProjectExec
    for op in ("FilterProjectExec", "MemoryScanExec"):
        assert op in text
    assert "XLA:" in text and "transfers:" in text

    def every_node(n):
        yield n
        for c in n.children:
            yield from every_node(c)

    for node in every_node(prof.tree):
        assert node.values.get("output_rows", 0) > 0, node.name
        assert node.values.get("elapsed_compute_ns", 0) > 0, node.name


@pytest.fixture
def staged_mode():
    from blaze_tpu import config
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    try:
        yield
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)


def _acceptance_plan(tmp_path):
    """ParquetScan -> Filter -> Project -> partial HashAgg ->
    hash-partition shuffle -> final HashAgg (the TPC-DS q01 inner
    shape)."""
    rng = np.random.default_rng(11)
    n = 20_000
    t = pa.table({"k": pa.array(rng.integers(0, 200, n), type=pa.int64()),
                  "v": pa.array(rng.random(n))})
    paths = []
    for i in range(2):
        p = str(tmp_path / f"in-{i}.parquet")
        pq.write_table(t.slice(i * (n // 2), n // 2), p)
        paths.append(p)
    schema = {"fields": [
        {"name": "k", "type": {"id": "int64"}, "nullable": True},
        {"name": "v", "type": {"id": "float64"}, "nullable": True}]}
    plan = {
        "kind": "hash_agg",
        "groupings": [{"expr": {"kind": "column", "index": 0},
                       "name": "k"}],
        "aggs": [{"fn": "sum", "mode": "final", "name": "s",
                  "args": [{"kind": "column", "index": 1}]}],
        "input": {
            "kind": "local_exchange",
            "partitioning": {"kind": "hash",
                             "exprs": [{"kind": "column", "index": 0}],
                             "num_partitions": 3},
            "input": {
                "kind": "hash_agg",
                "groupings": [{"expr": {"kind": "column", "name": "k"},
                               "name": "k"}],
                "aggs": [{"fn": "sum", "mode": "partial", "name": "s",
                          "args": [{"kind": "column", "name": "v"}]}],
                "input": {
                    "kind": "project",
                    "exprs": [{"kind": "column", "index": 0},
                              {"kind": "column", "index": 1}],
                    "names": ["k", "v"],
                    "input": {
                        "kind": "filter",
                        "predicates": [
                            {"kind": "binary", "op": ">=",
                             "l": {"kind": "column", "name": "k"},
                             "r": {"kind": "literal", "value": 10,
                                   "type": {"id": "int64"}}}],
                        "input": {"kind": "parquet_scan",
                                  "schema": schema,
                                  "file_groups": [[paths[0]],
                                                  [paths[1]]]}}}}}}
    return plan, t


def test_explain_analyze_staged_acceptance(tmp_path, staged_mode):
    from blaze_tpu.bridge import profiling
    from blaze_tpu.plan import explain_analyze

    plan, t = _acceptance_plan(tmp_path)
    prof = explain_analyze(plan, work_dir=str(tmp_path / "dag"),
                           query_id="accept-q01", keep_result=True)
    assert prof.exec_mode == "staged"
    assert prof.partitions == 3

    # the shuffle split is stitched back: the full operator chain shows
    # in ONE tree, scan at the leaf
    text = prof.render_text()
    # Filter->Project arrives collapsed to one FilterProjectExec node
    for op in ("IpcReaderExec", "ShuffleWriterExec", "FilterProjectExec",
               "ParquetScanExec"):
        assert op in text, text

    def every_node(n):
        yield n
        for c in n.children:
            yield from every_node(c)

    nodes = list(every_node(prof.tree))
    assert len(nodes) >= 6
    for node in nodes:
        assert node.values.get("output_rows", 0) > 0, (node.name, text)
        assert node.values.get("elapsed_compute_ns", 0) > 0, node.name

    # XLA accounting is part of the profile (zero on the host-vectorized
    # path, but the keys must be reported)
    assert "total_compiles" in prof.xla
    assert "total_cache_hits" in prof.xla
    assert "XLA: compiles=" in text

    # result rode along and matches the oracle
    import pandas as pd
    want = (t.to_pandas().query("k >= 10").groupby("k", as_index=False)
            .v.sum().rename(columns={"v": "s"}))
    got = prof.result.to_pandas().sort_values("k").reset_index(drop=True)
    pd.testing.assert_frame_equal(
        got, want.sort_values("k").reset_index(drop=True),
        check_exact=False)

    # the same profile is registered for the HTTP service
    stored = profiling.get_profile("accept-q01")
    assert stored is not None
    assert stored["tree"]["values"]["output_rows"] > 0
    assert stored["output_rows"] == prof.output_rows


def test_dag_scheduler_collects_stage_metrics(tmp_path, staged_mode):
    from blaze_tpu.plan.stages import DagScheduler

    plan, _t = _acceptance_plan(tmp_path)
    sched = DagScheduler(work_dir=str(tmp_path / "dag"))
    sched.run_collect(plan)
    # one tree per stage, merged across that stage's tasks
    assert set(sched.stage_metrics) == {0, 1}
    map_tree = sched.stage_metrics[0]
    assert map_tree.name == "ShuffleWriterExec"
    assert map_tree.get("output_rows") > 0
    result_tree = sched.collect_metrics()
    assert result_tree is sched.stage_metrics[1]
    assert result_tree.get("output_rows") > 0

"""Device-resident stage loop (ISSUE 8): the scheduler's loop path is
bit-identical to the staged per-batch executor, records its placement,
falls back WHOLESALE on injected faults and degraded queries (never a
divergent result, never a burned retry), and tears down within one
chunk of a cancellation with a clean leak report."""

import logging
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu import config, faults
from blaze_tpu.bridge import xla_stats
from blaze_tpu.bridge.context import TaskContext, task_scope
from blaze_tpu.memory import MemManager
from blaze_tpu.plan.stages import DagScheduler
from blaze_tpu.serving import QueryCancelled, QueryContext


@pytest.fixture(autouse=True)
def clean_slate():
    faults.clear()
    MemManager.init(4 << 30)
    try:
        yield
    finally:
        faults.clear()


@pytest.fixture
def loop_on():
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")
    try:
        yield
    finally:
        config.conf.unset(config.STAGE_DEVICE_LOOP_ENABLE.key)


@pytest.fixture
def staged_path():
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    try:
        yield
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)


def _two_stage_plan(tmp_path, n=8000, n_reduce=3, tag=""):
    """partial sum -> hash exchange -> final sum.  WIDE int64 keys: the
    compact 0..199 range would take the dense lane, which the stage
    compiler rejects — the loop is the hash lane's fold."""
    rng = np.random.default_rng(7)
    k = rng.integers(0, 200, n) * 1000003 + 17
    t = pa.table({"k": pa.array(k, type=pa.int64()),
                  "v": pa.array(rng.random(n))})
    paths = []
    for i in range(2):
        p = str(tmp_path / f"in{tag}-{i}.parquet")
        pq.write_table(t.slice(i * (n // 2), n // 2), p)
        paths.append(p)
    schema = {"fields": [
        {"name": "k", "type": {"id": "int64"}, "nullable": True},
        {"name": "v", "type": {"id": "float64"}, "nullable": True}]}
    return {
        "kind": "hash_agg",
        "groupings": [{"expr": {"kind": "column", "index": 0},
                       "name": "k"}],
        "aggs": [{"fn": "sum", "mode": "final", "name": "s",
                  "args": [{"kind": "column", "index": 1}]}],
        "input": {
            "kind": "local_exchange",
            "partitioning": {"kind": "hash",
                             "exprs": [{"kind": "column", "index": 0}],
                             "num_partitions": n_reduce},
            "input": {
                "kind": "hash_agg",
                "groupings": [{"expr": {"kind": "column", "name": "k"},
                               "name": "k"}],
                "aggs": [{"fn": "sum", "mode": "partial", "name": "s",
                          "args": [{"kind": "column", "name": "v"}]}],
                "input": {"kind": "parquet_scan", "schema": schema,
                          "file_groups": [[paths[0]], [paths[1]]]}}}}


def _sorted_df(tbl):
    return tbl.to_pandas().sort_values("k").reset_index(drop=True)


def _fused_partial(tmp_path, n=4000, tag="fp"):
    """A standalone fused partial agg (the loop-eligible stage root)."""
    from blaze_tpu.plan.column_pruning import prune_columns
    from blaze_tpu.plan.fused import fuse_plan
    from blaze_tpu.plan.planner import collapse_filter_project, create_plan
    rng = np.random.default_rng(3)
    k = rng.integers(0, 200, n) * 1000003 + 17
    t = pa.table({"k": pa.array(k, type=pa.int64()),
                  "v": pa.array(rng.random(n))})
    p = str(tmp_path / f"{tag}.parquet")
    pq.write_table(t, p)
    schema = {"fields": [
        {"name": "k", "type": {"id": "int64"}, "nullable": True},
        {"name": "v", "type": {"id": "float64"}, "nullable": True}]}
    plan = {"kind": "hash_agg",
            "groupings": [{"expr": {"kind": "column", "index": 0},
                           "name": "k"}],
            "aggs": [{"fn": "sum", "mode": "partial", "name": "s",
                      "args": [{"kind": "column", "index": 1}]}],
            "input": {"kind": "parquet_scan", "schema": schema,
                      "file_groups": [[p]]}}
    return fuse_plan(prune_columns(collapse_filter_project(
        create_plan(plan))))


# -- bit-identity + placement -----------------------------------------------

def test_scheduler_loop_bit_identical_and_placed(tmp_path, staged_path,
                                                 loop_on):
    plan = _two_stage_plan(tmp_path)
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "off")
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-off")).run_collect(plan))
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")

    before = xla_stats.snapshot()
    sched = DagScheduler(work_dir=str(tmp_path / "dag-on"))
    got = _sorted_df(sched.run_collect(plan))

    assert got.equals(clean)  # bit-identical, not approximately equal
    d = xla_stats.delta(before)
    assert d["stage_loop_tasks"] >= 2  # both map tasks took the loop
    assert d["stage_loop_fallbacks"] == 0
    assert d["stage_loop_staged_dispatches_avoided"] >= 0
    comp = {p["compute"] for p in sched.stage_placement.values()}
    assert "device-loop" in comp, sched.stage_placement


def test_fused_execute_loop_vs_staged_identical(tmp_path, loop_on):
    before = xla_stats.snapshot()
    t_on = _fused_partial(tmp_path).execute_collect()
    d = xla_stats.delta(before)
    assert d["stage_loop_tasks"] >= 1  # the loop branch actually ran
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "off")
    t_off = _fused_partial(tmp_path).execute_collect()
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")

    def rows(cb):
        df = pa.Table.from_batches([cb.to_arrow()]).to_pandas()
        return sorted(map(tuple, df.itertuples(index=False)))

    assert rows(t_on) == rows(t_off)


# -- wholesale fallback -----------------------------------------------------

def test_injected_fault_falls_back_wholesale(tmp_path, staged_path,
                                             loop_on):
    plan = _two_stage_plan(tmp_path, tag="flt")
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "off")
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-clean")).run_collect(plan))
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")

    before = xla_stats.snapshot()
    with faults.scoped(("device-loop", dict(p=1.0))):
        sched = DagScheduler(work_dir=str(tmp_path / "dag-chaos"))
        got = _sorted_df(sched.run_collect(plan))

    assert got.equals(clean)
    d = xla_stats.delta(before)
    assert d["stage_loop_fallbacks"] >= 1
    assert d["stage_loop_tasks"] == 0  # no loop task reached the drain
    # a fallback is an in-attempt re-run, NOT a task retry
    assert d["task_retries"] == 0
    # scripted chaos is a DECLARED degradation: counted, not an error
    assert d["unexpected_fallbacks"] == 0
    assert any("injected fault" in r
               for r in xla_stats.stage_loop_fallback_reasons())
    comp = {p["compute"] for p in sched.stage_placement.values()}
    assert "device-loop" not in comp, sched.stage_placement


def _clean_result(tmp_path, plan):
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "off")
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-clean")).run_collect(plan))
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")
    return clean


def _assert_loud(caplog, d, site, text):
    """An UNDECLARED device-tier failure: still a fallback, but an ERROR
    log with its traceback and its text kept in xla_stats — with tracing
    off, the default, where tracing.instant is a no-op."""
    from blaze_tpu.bridge import tracing
    assert not tracing.enabled()
    assert d["unexpected_fallbacks"] >= 1
    kept = [e for e in xla_stats.fallback_errors() if e["site"] == site]
    assert kept and text in kept[-1]["message"], xla_stats.fallback_errors()
    assert kept[-1]["error"] == "ValueError"
    logged = [r for r in caplog.records
              if r.levelno == logging.ERROR and r.exc_info
              and r.name == "blaze_tpu.stages"]
    assert logged, [r.getMessage() for r in caplog.records]
    assert text in caplog.text and "Traceback" in caplog.text


def test_undeclared_stage_loop_error_is_logged_and_kept(
        tmp_path, staged_path, loop_on, monkeypatch, caplog):
    plan = _two_stage_plan(tmp_path, tag="und")
    clean = _clean_result(tmp_path, plan)
    from blaze_tpu.runtime import loop as device_loop

    def refuse(*_a, **_k):  # what a Mosaic lowering error looks like
        raise ValueError("Cannot store scalars to VMEM")

    monkeypatch.setattr(device_loop, "run_partition", refuse)
    before = xla_stats.snapshot()
    with caplog.at_level(logging.ERROR):
        got = _sorted_df(DagScheduler(
            work_dir=str(tmp_path / "dag-und")).run_collect(plan))
    assert got.equals(clean)  # the staged re-run still answers
    d = xla_stats.delta(before)
    assert d["stage_loop_fallbacks"] >= 1
    _assert_loud(caplog, d, "stage_loop", "Cannot store scalars to VMEM")


def test_undeclared_device_producer_error_is_logged_and_kept(
        tmp_path, staged_path, loop_on, monkeypatch, caplog):
    plan = _two_stage_plan(tmp_path, tag="undp")
    clean = _clean_result(tmp_path, plan)

    def refuse(self, *_a, **_k):
        raise ValueError("RESOURCE_EXHAUSTED: vmem")

    monkeypatch.setattr(DagScheduler, "_exchange_sync", refuse)
    before = xla_stats.snapshot()
    with caplog.at_level(logging.ERROR):
        got = _sorted_df(DagScheduler(
            work_dir=str(tmp_path / "dag-undp")).run_collect(plan))
    assert got.equals(clean)  # the file shuffle still answers
    d = xla_stats.delta(before)
    assert d["shuffle_device_fallbacks"] >= 1
    _assert_loud(caplog, d, "device_shuffle", "RESOURCE_EXHAUSTED: vmem")


def test_degraded_query_declines_loop(tmp_path, staged_path, loop_on):
    plan = _two_stage_plan(tmp_path, tag="deg")
    # baseline: the same degraded query with the loop OFF — rung 1 turns
    # the partial agg into a pass-through in BOTH paths, so the declined
    # loop must land on exactly the staged degraded bit pattern
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "off")
    q0 = QueryContext("q-deg-off")
    q0.degrade()
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-deg-off"),
        query_ctx=q0).run_collect(plan))
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")

    ctx = QueryContext("q-deg-on")
    assert ctx.degrade() == "agg-passthrough"  # rung 1 declines the loop
    before = xla_stats.snapshot()
    sched = DagScheduler(work_dir=str(tmp_path / "dag-deg-on"),
                         query_ctx=ctx)
    got = _sorted_df(sched.run_collect(plan))

    assert got.equals(clean)
    d = xla_stats.delta(before)
    assert d["stage_loop_fallbacks"] >= 1
    assert d["stage_loop_tasks"] == 0


# -- cancellation -----------------------------------------------------------

def test_cancel_noticed_at_chunk_boundary(tmp_path, loop_on):
    """Deterministic mid-loop cancel: the source stream fires the token
    after the first chunk's batches are pulled, so the loop must stop at
    the NEXT chunk boundary — teardown bounded by one chunk."""
    from blaze_tpu.plan import stage_compiler
    from blaze_tpu.runtime import loop as device_loop
    config.conf.set(config.STAGE_DEVICE_LOOP_CHUNK.key, 2)
    config.conf.set(config.BATCH_SIZE.key, 512)
    try:
        fp = _fused_partial(tmp_path, n=6000, tag="cancel")  # ~12 batches
        prog = stage_compiler.compile_task_plan(fp)
        assert prog is not None
        ctx = QueryContext("q-mid-cancel")

        def stream():
            for i, b in enumerate(prog.source.execute(0)):
                if i == 2:  # one full chunk delivered; cancel before next
                    ctx.cancel("mid-loop teardown")
                yield b

        task = TaskContext(query=ctx)
        with task_scope(task):
            with pytest.raises(QueryCancelled):
                device_loop.run_partition(prog, 0, ctx="t",
                                          source_stream=stream())
        # exactly one chunk folded before the boundary check fired
        assert task.loop_chunks == 1, task.loop_chunks
    finally:
        config.conf.unset(config.STAGE_DEVICE_LOOP_CHUNK.key)
        config.conf.unset(config.BATCH_SIZE.key)


def test_cancelled_query_leaves_no_leaks(tmp_path, staged_path, loop_on):
    plan = _two_stage_plan(tmp_path, n=100_000, tag="leak")
    ctx = QueryContext("q-leak")
    timer = threading.Timer(0.05, ctx.cancel, args=("bored",))
    sched = DagScheduler(work_dir=str(tmp_path / "dag-leak"),
                         query_ctx=ctx)
    timer.start()
    try:
        with pytest.raises(QueryCancelled):
            sched.run_collect(plan)
    finally:
        timer.cancel()
    report = sched.leak_report()
    assert all(v == [] for v in report.values()), report

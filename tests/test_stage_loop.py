"""Device-resident stage loop (ISSUE 8): the scheduler's loop path is
bit-identical to the staged per-batch executor, records its placement,
falls back WHOLESALE on injected faults and degraded queries (never a
divergent result, never a burned retry), and tears down within one
chunk of a cancellation with a clean leak report."""

import logging
import threading
import time

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
    threads_before = set(threading.enumerate())
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
    # an attempt that was mid-chunk when the token fired ends on its own
    # time (`run_tasks` shuts its pool down without waiting): wait for it
    # here, or what it counts lands in the next test's window
    deadline = time.monotonic() + 20
    for t in set(threading.enumerate()) - threads_before:
        t.join(timeout=max(0.0, deadline - time.monotonic()))


# -- table sizing: reserve before fold, in every mode (ISSUE 25) -------------

@pytest.fixture
def small_tables():
    """Floor of 16 slots, 512-row batches, 2 batches a chunk, and the
    device hash table on the staged lane too (host placement would take
    Arrow's aggregation, which never builds it)."""
    config.conf.set(config.ON_DEVICE_AGG_CAPACITY.key, 16)
    config.conf.set(config.BATCH_SIZE.key, 512)
    config.conf.set(config.STAGE_DEVICE_LOOP_CHUNK.key, 2)
    config.conf.set(config.FUSED_HOST_VECTORIZED_ENABLE.key, False)
    try:
        yield
    finally:
        config.conf.unset(config.ON_DEVICE_AGG_CAPACITY.key)
        config.conf.unset(config.BATCH_SIZE.key)
        config.conf.unset(config.STAGE_DEVICE_LOOP_CHUNK.key)
        config.conf.unset(config.FUSED_HOST_VECTORIZED_ENABLE.key)


@pytest.fixture
def capacities(monkeypatch):
    """The table's slot count at every fold call, in order."""
    from blaze_tpu.runtime import loop as device_loop
    seen = []
    real = device_loop._fold_factory

    def factory(*a, **k):
        fold = real(*a, **k)

        def spy(carry, *rest):
            seen.append(int(carry.owner.shape[0]))
            return fold(carry, *rest)
        return spy

    monkeypatch.setattr(device_loop, "_fold_factory", factory)
    return seen


def _wide(k):
    # WIDE int64 keys: a compact range would take the dense lane
    return np.asarray(k, dtype=np.int64) * 1000003 + 17


def _sum_agg(tmp_path, keys, mode, tag):
    """sum(v) group by k over one parquet file, fused (loop-eligible)."""
    from blaze_tpu.plan.column_pruning import prune_columns
    from blaze_tpu.plan.fused import fuse_plan
    from blaze_tpu.plan.planner import collapse_filter_project, create_plan
    vals = np.random.default_rng(11).random(len(keys))
    t = pa.table({"k": pa.array(keys, type=pa.int64()),
                  "v": pa.array(vals)})
    p = str(tmp_path / f"{tag}.parquet")
    pq.write_table(t, p, row_group_size=512)
    schema = {"fields": [
        {"name": "k", "type": {"id": "int64"}, "nullable": True},
        {"name": "v", "type": {"id": "float64"}, "nullable": True}]}
    plan = {"kind": "hash_agg",
            "groupings": [{"expr": {"kind": "column", "index": 0},
                           "name": "k"}],
            "aggs": [{"fn": "sum", "mode": mode, "name": "s",
                      "args": [{"kind": "column", "index": 1}]}],
            "input": {"kind": "parquet_scan", "schema": schema,
                      "file_groups": [[p]]}}
    fused = fuse_plan(prune_columns(collapse_filter_project(
        create_plan(plan))))
    want = t.to_pandas().groupby("k", as_index=False).agg(s=("v", "sum"))
    return fused, want.sort_values("k").reset_index(drop=True)


def _emitted(plan):
    out = [b.compact().to_arrow() for b in plan.execute(0)]
    df = pa.Table.from_batches([b for b in out if b.num_rows]).to_pandas()
    df.columns = ["k", "s"]  # partial mode names its sum `s.sum`
    return df


def _reserved_for(rows):
    from blaze_tpu.runtime import loop as device_loop
    return device_loop._slots_for(rows, 16)


def _merged(df):
    """What a final aggregation makes of partial output."""
    return df.groupby("k", as_index=False).agg(s=("s", "sum")) \
        .sort_values("k").reset_index(drop=True)


def _assert_sums(got, want):
    assert got.k.tolist() == want.k.tolist()
    np.testing.assert_allclose(got.s.to_numpy(), want.s.to_numpy(),
                               rtol=1e-12)


def _colliding(slots, n, skip=()):
    """`n` wide keys whose home slot in a table of `slots` is slot 0:
    they need `n` probe rounds however empty the table is."""
    from blaze_tpu.kernels import hashing as H
    cand = _wide(np.arange(1, 400 * slots))
    h = H.hash_columns([(cand, np.ones(len(cand), bool), "int64")],
                       seed=42, xp=np, algo="xxhash64")
    hit = cand[(h & (slots - 1)) == 0]
    hit = hit[~np.isin(hit, skip)]
    assert len(hit) >= n
    return hit[:n]


def test_partial_mode_grows_past_the_floor_in_the_loop(
        tmp_path, loop_on, small_tables):
    # 3000 groups against a floor of 16: partial mode used to give up at
    # the first overflow and re-run staged
    keys = _wide(np.random.default_rng(5).integers(0, 3000, 6000))
    plan, want = _sum_agg(tmp_path, keys, "partial", "pgrow")
    before = xla_stats.snapshot()
    got = _emitted(plan)
    d = xla_stats.delta(before)
    assert d["stage_loop_tasks"] == 1
    assert d["stage_loop_fallbacks"] == 0
    assert d["partial_agg_skip_events"] == 0
    assert len(got) == len(want)  # ONE fully aggregated table
    _assert_sums(_merged(got), want)
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "off")
    staged, _ = _sum_agg(tmp_path, keys, "partial", "pgrow-off")
    before = xla_stats.snapshot()
    staged_out = _emitted(staged)
    # the staged lane keeps its skip semantics: pass-through partials
    assert xla_stats.delta(before)["partial_agg_skip_events"] == 1
    assert len(staged_out) > len(want)
    _assert_sums(_merged(staged_out), _merged(got))


def test_one_chunk_partition_sizes_once(tmp_path, loop_on, small_tables,
                                        capacities):
    keys = _wide(np.random.default_rng(6).integers(0, 700, 1000))
    plan, want = _sum_agg(tmp_path, keys, "final", "once")
    before = xla_stats.snapshot()
    got = _merged(_emitted(plan))
    d = xla_stats.delta(before)
    _assert_sums(got, want)
    assert capacities == [_reserved_for(1000)] and capacities[0] >= 4000
    assert d["stage_loop_reserves"] == 1
    assert d["stage_loop_rehash_lanes"] == 0
    assert d["stage_loop_regrows"] == 0
    assert d["stage_loop_fallbacks"] == 0
    assert (xla_stats.stage_loop_stats()["stage_loop_max_slots"]
            >= capacities[0])


def test_growing_cardinality_resizes_with_hysteresis(
        tmp_path, loop_on, small_tables, capacities):
    # every row a new group, 1024 rows a chunk, 16 chunks
    plan, want = _sum_agg(tmp_path, _wide(np.arange(16384)), "final",
                          "grow")
    before = xla_stats.snapshot()
    got = _merged(_emitted(plan))
    d = xla_stats.delta(before)
    _assert_sums(got, want)
    assert d["stage_loop_regrows"] == 0 and d["stage_loop_fallbacks"] == 0
    assert len(capacities) == 16
    assert capacities == sorted(capacities)
    steps = [i for i in range(1, 16) if capacities[i] > capacities[i - 1]]
    assert 1 <= len(steps) <= np.log2(capacities[-1] / capacities[0])
    # never at two successive chunks of equal cardinality
    assert all(b - a > 1 for a, b in zip(steps, steps[1:])) and steps[0] > 1
    assert d["stage_loop_reserves"] == 1 + len(steps)
    assert d["stage_loop_rehash_lanes"] == sum(
        capacities[i - 1] for i in steps)
    # each rehash ran over the power of two that holds the 1,024 * i
    # groups the table had by then (2,048 lanes or more, its slots or
    # fewer), not over its slots
    from blaze_tpu.parallel.stage import rehash_width
    assert d["stage_loop_rehash_probe_lanes"] == sum(
        rehash_width(1024 * i, capacities[i - 1]) for i in steps)
    assert d["stage_loop_rehash_probe_lanes"] \
        < d["stage_loop_rehash_lanes"]
    # the groups held and the rows about to arrive never pass the
    # trigger load
    from blaze_tpu.runtime import loop as device_loop
    assert all(1024 * (i + 1) <= c * device_loop._TRIGGER_LOAD
               for i, c in enumerate(capacities))


def test_a_rehash_over_fewer_lanes_than_groups_is_refused_on_the_host(
        tmp_path, loop_on, small_tables, monkeypatch):
    """The compaction would drop the groups beyond its lanes without a
    sign, so the width is checked against the count the host holds
    before the program is asked for: the task fails and no rehash
    ran."""
    from blaze_tpu.runtime import loop as device_loop
    monkeypatch.setattr(device_loop, "rehash_width",
                        lambda groups, slots: 512)
    plan, _want = _sum_agg(tmp_path, _wide(np.arange(16384)), "final",
                           "short")
    before = xla_stats.snapshot()
    with pytest.raises(AssertionError, match="would drop groups"):
        _emitted(plan)
    d = xla_stats.delta(before)
    assert d["stage_loop_rehash_probe_lanes"] == 0
    assert d["stage_loop_fallbacks"] == 0  # not a quiet re-run


@pytest.mark.parametrize("mode", ["partial", "final"])
def test_past_max_slots_every_mode_falls_back(tmp_path, loop_on,
                                              small_tables, monkeypatch,
                                              mode):
    from blaze_tpu.runtime import loop as device_loop
    monkeypatch.setattr(device_loop, "_MAX_SLOTS", 64)
    keys = _wide(np.random.default_rng(8).integers(0, 3000, 6000))
    plan, want = _sum_agg(tmp_path, keys, mode, f"max-{mode}")
    before = xla_stats.snapshot()
    got = _merged(_emitted(plan))
    d = xla_stats.delta(before)
    _assert_sums(got, want)  # the staged lane answers, losslessly
    assert d["stage_loop_fallbacks"] == 1 and d["stage_loop_tasks"] == 0
    assert xla_stats.stage_loop_fallback_reasons().get(
        "table would exceed 64 slots", 0) >= 1


def test_low_cardinality_never_rehashes(tmp_path, loop_on, small_tables,
                                        capacities):
    keys = _wide(np.random.default_rng(9).integers(0, 12, 8192))
    plan, want = _sum_agg(tmp_path, keys, "partial", "low")
    before = xla_stats.snapshot()
    got = _merged(_emitted(plan))
    d = xla_stats.delta(before)
    _assert_sums(got, want)
    assert len(capacities) == 8 and len(set(capacities)) == 1
    assert d["stage_loop_reserves"] == 1
    assert d["stage_loop_rehash_lanes"] == 0
    assert d["stage_loop_regrows"] == 0


def test_capacity_sequence_is_deterministic(tmp_path, loop_on,
                                            small_tables, capacities):
    keys = _wide(np.arange(8192))
    plan, _ = _sum_agg(tmp_path, keys, "partial", "det")
    _emitted(plan)
    first = list(capacities)
    del capacities[:]
    again, want = _sum_agg(tmp_path, keys, "partial", "det")
    before = xla_stats.snapshot()
    got = _merged(_emitted(again))
    d = xla_stats.delta(before)
    _assert_sums(got, want)
    assert capacities == first and len(set(first)) > 1
    assert d["stage_loop_programs_built"] == 0
    assert d["backend_compiles"] == 0 and d["total_compiles"] == 0


def _overflowing_keys():
    """Two 512-row batches, one chunk: 256 plain groups, then 40 keys
    that share a home slot in the table reserved for the chunk and so
    need 40 probe rounds of the 16 there are."""
    plain = _wide(np.arange(256))
    bad = _colliding(_reserved_for(1024), 40, skip=plain)
    return np.concatenate([np.repeat(plain, 2), np.resize(bad, 512)])


@pytest.mark.parametrize("chunk", [2, 4])
def test_residual_overflow_regrows_and_resumes(tmp_path, loop_on,
                                               small_tables, capacities,
                                               chunk):
    # a chunk of 4 folds the same two batches as a tail window widened
    # with two masked-out ones: the resume at batch 1 is the same
    config.conf.set(config.STAGE_DEVICE_LOOP_CHUNK.key, chunk)
    plan, want = _sum_agg(tmp_path, _overflowing_keys(), "partial", "ovf")
    before = xla_stats.snapshot()
    got = _emitted(plan)
    d = xla_stats.delta(before)
    assert len(got) == len(want) == 296
    _assert_sums(_merged(got), want)
    assert d["stage_loop_regrows"] >= 1 and d["stage_loop_reserves"] == 1
    assert d["stage_loop_fallbacks"] == 0
    assert capacities[0] == _reserved_for(1024)
    assert capacities[1] >= 2 * capacities[0]
    assert d["stage_loop_rehash_lanes"] >= capacities[0]


@pytest.mark.parametrize("how", ["reserved", "reactive"])
def test_exchange_fence_runs_before_every_rehash(tmp_path, loop_on,
                                                 small_tables, monkeypatch,
                                                 how):
    from blaze_tpu.plan import fused
    from blaze_tpu.runtime import loop as device_loop
    events = []
    real = fused._rehash_jit

    def rehash(*a, **k):
        events.append("rehash")
        return real(*a, **k)

    monkeypatch.setattr(fused, "_rehash_jit", rehash)
    keys = (_wide(np.arange(8192)) if how == "reserved"
            else _overflowing_keys())
    plan, want = _sum_agg(tmp_path, keys, "final", f"fence-{how}")
    before = xla_stats.snapshot()
    with device_loop.exchange_fence(lambda: events.append("fence")):
        got = _merged(_emitted(plan))
    d = xla_stats.delta(before)
    _assert_sums(got, want)
    assert (d["stage_loop_regrows"] > 0) == (how == "reactive")
    assert "rehash" in events
    assert events == ["fence", "rehash"] * (len(events) // 2)


# -- partial-aggregation skipping inside the loop (ISSUE 27) -----------------

@pytest.fixture
def skip_conf(small_tables):
    """A first look after 1,000 live rows (the second batch of a chunk
    of four 512-row batches), a switch past 0.9 groups a live row, and
    the exchange through shuffle files: the device-to-device exchange
    (the 8 virtual devices would take it) folds ONE carry, no switch."""
    config.conf.set(config.PARTIAL_AGG_SKIPPING_MIN_ROWS.key, 1000)
    config.conf.set(config.STAGE_DEVICE_LOOP_CHUNK.key, 4)
    config.conf.set(config.SHUFFLE_DEVICE.key, "off")
    try:
        yield
    finally:
        config.conf.unset(config.PARTIAL_AGG_SKIPPING_MIN_ROWS.key)
        config.conf.unset(config.PARTIAL_AGG_SKIPPING_ENABLE.key)
        config.conf.unset(config.SHUFFLE_DEVICE.key)


_AGGS = {
    # case -> [(fn, argument column or None, name)]
    "sum_float": [("sum", "v", "s")],
    "int_sum_counts": [("sum", "i", "s"), ("count", None, "n"),
                       ("count", "i", "c")],
    "min_max": [("min", "v", "lo"), ("max", "v", "hi"), ("min", "i", "ilo"),
                ("max", "i", "ihi")],
    "avg_as_sum_and_count": [("sum", "v", "s"), ("count", "v", "c")],
}


def _skip_table(n=8192, null_keys=False, null_args=False, seed=13,
                keys=None):
    """Nearly one group a row on two wide keys; `f` drives the filter."""
    rng = np.random.default_rng(seed)
    k = _wide(rng.integers(0, 16 * n, n)) if keys is None else keys
    k2 = rng.integers(0, 3, n) * 1000003 + 5
    v = rng.random(n) * 100
    i = rng.integers(-1000, 1000, n)
    drop = rng.random(n) < 0.1

    def nullable(a, t, on, share=1.0):
        # every NULL key lands in one of three groups: keep them few
        return pa.array(a, type=t,
                        mask=drop & (rng.random(n) < share) if on else None)

    return pa.table({"k": nullable(k, pa.int64(), null_keys, 0.2),
                     "k2": pa.array(k2, type=pa.int64()),
                     "v": nullable(v, pa.float64(), null_args),
                     "i": nullable(i, pa.int64(), null_args),
                     "f": pa.array(rng.integers(0, 10, n))})


def _skip_agg(table, aggs, mode="partial", filtered=False):
    """The fused aggregation by (k, k2) over a memory scan, under a
    filter that keeps 70% of the rows where `filtered`."""
    from blaze_tpu.exprs import BinaryExpr, col, lit
    from blaze_tpu.ops import (AggExec, AggMode, FilterExec, MemoryScanExec,
                               make_agg)
    from blaze_tpu.plan.fused import FusedPartialAggExec, fuse_plan
    names = table.schema.names
    node = MemoryScanExec.from_arrow(table, batch_rows=512)
    if filtered:
        node = FilterExec(node, [BinaryExpr(">=", col(names.index("f"), "f"),
                                            lit(3))])
    m = {"partial": AggMode.PARTIAL, "complete": AggMode.COMPLETE,
         "merge": AggMode.PARTIAL_MERGE, "final": AggMode.FINAL}[mode]
    plan = fuse_plan(AggExec(
        node, [(col(0, "k"), "k"), (col(1, "k2"), "k2")],
        [(make_agg(fn, [col(names.index(a), a)] if a else []), m, name)
         for fn, a, name in aggs]))
    assert isinstance(plan, FusedPartialAggExec)
    assert plan.fused_mode == "sorted"  # the hash lane: the loop's
    return plan


def _partial_rows(plan):
    out = [b.compact().to_arrow() for b in plan.execute(0)]
    return pa.Table.from_batches([b for b in out if b.num_rows],
                                 schema=plan.schema.to_arrow())


def _final_merge(partial, aggs):
    """The FINAL aggregation over partial output, on the eager engine
    (independent of the loop), sorted by key."""
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import AggExec, AggMode, MemoryScanExec, make_agg
    merged = AggExec(
        MemoryScanExec.from_arrow(partial),
        [(col(0, "k"), "k"), (col(1, "k2"), "k2")],
        [(make_agg(fn, [col(2 + j)]), AggMode.PARTIAL_MERGE, name)
         for j, (fn, _a, name) in enumerate(aggs)]).execute_collect()
    df = merged.to_arrow().to_pandas()
    df.columns = ["k", "k2"] + [name for _f, _a, name in aggs]
    return df.sort_values(["k", "k2"], na_position="first") \
        .reset_index(drop=True)


def _assert_same_answer(got, want):
    """Keys, counts and integer sums exactly; float sums to 1e-12."""
    assert len(got) == len(want)
    for c in want.columns:
        g, w = got[c], want[c]
        assert g.isna().tolist() == w.isna().tolist(), c
        if w.dtype.kind == "f" and c not in ("k", "k2"):
            np.testing.assert_allclose(g.to_numpy(), w.to_numpy(),
                                       rtol=1e-12, err_msg=c)
        else:
            assert g.dropna().tolist() == w.dropna().tolist(), c


def _run_skipping(plan_of, enable=True):
    """(partial output, counters) of one run in the loop."""
    config.conf.set(config.PARTIAL_AGG_SKIPPING_ENABLE.key, enable)
    before = xla_stats.snapshot()
    out = _partial_rows(plan_of())
    return out, xla_stats.delta(before)


@pytest.mark.parametrize("shape", ["plain", "filtered", "null_keys",
                                   "null_args", "filtered_nulls"])
@pytest.mark.parametrize("case", sorted(_AGGS))
def test_high_cardinality_partial_switches_and_merges_to_the_same_answer(
        loop_on, skip_conf, case, shape):
    aggs = _AGGS[case]
    t = _skip_table(null_keys=shape in ("null_keys", "filtered_nulls"),
                    null_args=shape in ("null_args", "filtered_nulls"))

    def plan():
        return _skip_agg(t, aggs, filtered=shape.startswith("filtered"))

    skipped, d = _run_skipping(plan)
    assert d["stage_loop_tasks"] == 1 and d["stage_loop_fallbacks"] == 0
    assert d["partial_agg_skip_events"] == 1
    assert d["partial_agg_skipped_rows"] > 0
    live = 8192 if not shape.startswith("filtered") else int(
        np.sum(t["f"].to_numpy() >= 3))
    # the first look came at the batch boundary that reached minRows
    assert 1000 <= d["partial_agg_switch_rows"] < 1000 + 512
    assert d["partial_agg_skipped_rows"] == \
        live - d["partial_agg_switch_rows"]
    # folded rows are counted as folded, passed rows as passed
    assert d["stage_loop_rows"] == 512 * d["stage_loop_batches"] < 8192
    assert skipped.num_rows > 0.9 * live

    grouped, d_off = _run_skipping(plan, enable=False)
    assert d_off["partial_agg_skip_events"] == 0
    assert d_off["partial_agg_skipped_rows"] == 0
    assert d_off["stage_loop_rows"] == 8192
    assert grouped.schema == skipped.schema
    want = _final_merge(grouped, aggs)
    _assert_same_answer(_final_merge(skipped, aggs), want)

    # the staged lane's pass-through (its table overflows at 16 slots
    # and it passes batch-local groups on) merges to the same
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "off")
    staged, d_st = _run_skipping(plan)
    assert d_st["partial_agg_skip_events"] == 1 and d_st[
        "stage_loop_tasks"] == 0
    _assert_same_answer(_final_merge(staged, aggs), want)


@pytest.mark.parametrize("exchange", ["files", "device"])
def test_scheduler_map_tasks_switch_and_the_final_stage_answers(
        tmp_path, staged_path, loop_on, skip_conf, exchange):
    """Through DagScheduler: every map task of a high-cardinality partial
    stage switches, and the FINAL stage makes the same groups and sums of
    passed-through rows as of partial groups.  The device-to-device
    exchange drains `run_partition`'s ONE carry: it never switches, and
    its carry lacks no row."""
    if exchange == "device":
        config.conf.set(config.SHUFFLE_DEVICE.key, "on")
    plan = _two_stage_plan(tmp_path, tag="skip")
    rng = np.random.default_rng(21)
    t = pa.table({"k": pa.array(_wide(rng.integers(0, 1 << 20, 8000))),
                  "v": pa.array(rng.random(8000))})
    for i, group in enumerate(
            plan["input"]["input"]["input"]["file_groups"]):
        pq.write_table(t.slice(i * 4000, 4000), group[0],
                       row_group_size=512)
    config.conf.set(config.PARTIAL_AGG_SKIPPING_ENABLE.key, False)
    before = xla_stats.snapshot()
    want = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-noskip")).run_collect(plan))
    assert xla_stats.delta(before)["partial_agg_skip_events"] == 0
    config.conf.set(config.PARTIAL_AGG_SKIPPING_ENABLE.key, True)
    before = xla_stats.snapshot()
    got = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-skip")).run_collect(plan))
    d = xla_stats.delta(before)
    assert d["stage_loop_fallbacks"] == 0 and d["task_retries"] == 0
    if exchange == "files":
        assert d["partial_agg_skip_events"] == 2  # both map tasks
        assert d["partial_agg_skipped_rows"] == 2 * (4000 - 1024)
    else:
        assert d["shuffle_device_exchanges"] >= 1
        assert d["partial_agg_skip_events"] == 0
        assert d["partial_agg_skipped_rows"] == 0
    _assert_sums(got, want)


@pytest.mark.parametrize("case", ["twelve_groups", "under_min_rows",
                                  "final", "complete", "merge"])
def test_these_never_switch(loop_on, skip_conf, case):
    n = 800 if case == "under_min_rows" else 8192
    keys = (_wide(np.random.default_rng(2).integers(0, 12, n))
            if case == "twelve_groups" else None)
    mode = case if case in ("final", "complete", "merge") else "partial"
    t = _skip_table(n=n, keys=keys)
    before = xla_stats.snapshot()
    out = _partial_rows(_skip_agg(t, _AGGS["sum_float"], mode=mode))
    d = xla_stats.delta(before)
    assert d["stage_loop_tasks"] == 1 and d["stage_loop_fallbacks"] == 0
    assert d["partial_agg_skip_events"] == 0
    assert d["partial_agg_skipped_rows"] == 0
    assert d["stage_loop_rows"] == n
    want = t.to_pandas().groupby(["k", "k2"]).ngroups
    assert out.num_rows == want  # ONE fully aggregated table
    chunks = -(-n // 2048)
    if case == "twelve_groups":
        # looked at after the first look's stop and after every fold
        assert d["partial_agg_probe_rows"] > 0
        assert d["stage_loop_calls"] == chunks + 1
    else:
        # nothing was evaluated, and no fold stopped for a look
        assert d["partial_agg_probe_rows"] == 0
        assert d["stage_loop_calls"] == chunks


def test_low_cardinality_head_high_cardinality_tail_switches_later(
        loop_on, skip_conf):
    # one chunk of 12 groups, then every row a group: the cumulative
    # ratio passes 0.9 at the end of the tenth chunk of 2,048 rows
    head = np.random.default_rng(4).integers(0, 12, 2048)
    keys = _wide(np.concatenate([head, 100 + np.arange(30720)]))
    t = _skip_table(n=len(keys), keys=keys)

    def plan():
        return _skip_agg(t, _AGGS["int_sum_counts"])

    skipped, d = _run_skipping(plan)
    assert d["partial_agg_skip_events"] == 1
    assert d["partial_agg_switch_rows"] == 10 * 2048
    assert d["partial_agg_skipped_rows"] == \
        len(keys) - d["partial_agg_switch_rows"]
    grouped, _d = _run_skipping(plan, enable=False)
    _assert_same_answer(_final_merge(skipped, _AGGS["int_sum_counts"]),
                        _final_merge(grouped, _AGGS["int_sum_counts"]))


def test_fault_after_the_switch_fails_the_task_not_the_rows(
        tmp_path, staged_path, loop_on, skip_conf):
    """A switched partition has emitted: a `device-loop` fault at a later
    chunk boundary is the task's failure (one retry, staged), never a
    staged re-run appended to what was emitted."""
    plan = _two_stage_plan(tmp_path, tag="flt-skip")
    scan = plan["input"]["input"]["input"]
    rng = np.random.default_rng(22)
    t = pa.table({"k": pa.array(_wide(rng.integers(0, 1 << 20, 8000))),
                  "v": pa.array(rng.random(8000))})
    pq.write_table(t, scan["file_groups"][0][0], row_group_size=512)
    scan["file_groups"] = scan["file_groups"][:1]  # ONE map task
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-clean")).run_collect(plan))
    assert len(clean) == t.to_pandas().k.nunique()

    before = xla_stats.snapshot()
    # the switch comes inside the first chunk, at 1,024 live rows: the
    # third chunk boundary is past it
    with faults.scoped(("device-loop", dict(at=(3,)))):
        got = _sorted_df(DagScheduler(
            work_dir=str(tmp_path / "dag-chaos")).run_collect(plan))
    d = xla_stats.delta(before)
    assert d["partial_agg_skip_events"] >= 1  # it had switched
    assert d["stage_loop_fallbacks"] == 0     # and did not fall back
    assert d["task_retries"] == 1
    _assert_sums(got, clean)  # no row twice, no row lost


def test_fault_after_the_switch_propagates_from_execute(loop_on,
                                                        skip_conf):
    plan = _skip_agg(_skip_table(), _AGGS["sum_float"])
    before = xla_stats.snapshot()
    emitted = 0
    with faults.scoped(("device-loop", dict(at=(3,)))):
        with pytest.raises(faults.InjectedFault):
            for b in plan.execute(0):
                emitted += b.selected_count()
    d = xla_stats.delta(before)
    assert emitted > 0 and d["partial_agg_skip_events"] == 1
    assert d["stage_loop_fallbacks"] == 0


def test_skipping_disabled_is_the_loop_without_it(loop_on, skip_conf):
    """`enable=false`: one fold call a chunk and the same carry as the
    entry that never switches; and a first look that does not switch
    (ratio 1.0 cannot be passed) leaves that same carry too, through the
    same fold program."""
    from blaze_tpu.plan import stage_compiler
    from blaze_tpu.runtime import loop as device_loop
    t = _skip_table()

    def carry_of(**conf):
        prog = stage_compiler.compile_task_plan(
            _skip_agg(t, _AGGS["int_sum_counts"], filtered=True))
        before = xla_stats.snapshot()
        with config.scoped(**conf), task_scope(TaskContext()), \
                device_loop.charged_table(prog) as table:
            carry, rest = device_loop._fold_partition(
                prog, 0, "t", None, device_loop._may_switch(prog), table)
            plain = device_loop.run_partition(prog, 0)
        assert rest is None
        return carry, plain, xla_stats.delta(before)

    def same(a, b):
        import jax
        return all(np.array_equal(np.asarray(x), np.asarray(y),
                                  equal_nan=True)
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))

    off, plain, d = carry_of(
        **{config.PARTIAL_AGG_SKIPPING_ENABLE.key: False})
    assert same(off, plain)
    assert d["stage_loop_calls"] == 2 * 4  # two runs, a call a chunk
    assert d["partial_agg_probe_rows"] == 0
    looked, plain, d = carry_of(
        **{config.PARTIAL_AGG_SKIPPING_RATIO.key: 1.0})
    assert same(looked, off) and same(plain, off)
    assert d["stage_loop_calls"] == 2 * 4 + 1  # the first look's stop
    assert d["partial_agg_probe_rows"] > 0
    # the early stop and the resumed chunk are the jit signature the
    # whole chunk has: one program per window, nothing traced anew
    assert d["total_compiles"] == 0 and d["backend_compiles"] == 0


def test_passed_through_chunks_are_spans_and_a_program_of_their_own(
        loop_on, skip_conf):
    """Each pass-through chunk is a `partial_passthrough` span (the idle
    gaps inside it can be named), and its program is not a `fold_impl`:
    `fold_device_s` and `fold_roofline` read `^jit_fold_impl`."""
    from blaze_tpu.bridge import tracing
    tracing.start_tracing()
    try:
        _partial_rows(_skip_agg(_skip_table(), _AGGS["sum_float"]))
        spans = [s for s in tracing.spans()
                 if s["name"] == "partial_passthrough"]
        folds = [s for s in tracing.spans()
                 if s["name"] == "stage_loop_chunk"]
    finally:
        tracing.stop_tracing()
        tracing.reset_conf_probe()
    # the switch fell in the first of four chunks: its last two batches
    # and the three chunks after it were passed through
    assert [s["attrs"]["batches"] for s in spans] == [2, 4, 4, 4]
    assert [s["attrs"]["chunk"] for s in spans] == [0, 1, 2, 3]
    assert len(folds) == 1
    kernels = xla_stats.compile_report()["kernels"]
    assert kernels["runtime.stage_loop_passthrough"]["calls"] >= 4
    assert xla_stats.program_name(
        "passthrough_impl", "runtime.stage_loop_passthrough"
    ) == "passthrough_impl__runtime_stage_loop_passthrough"


@pytest.mark.parametrize("kind", ["count", "count_star", "sum", "min",
                                  "max"])
def test_a_rows_accumulator_form_is_the_table_of_that_one_row(kind):
    """`row_contribution` is what `scatter_accumulate` leaves in a slot
    that holds ONE row, NULL argument and masked row included."""
    import jax.numpy as jnp
    from blaze_tpu.parallel.stage import (init_accumulators,
                                          row_contribution,
                                          scatter_accumulate)
    vd = jnp.asarray([3.5, -1.0, 7.25, 0.0, 9.0])
    vv = jnp.asarray([True, False, True, True, False])
    mask = jnp.asarray([True, True, False, True, True])
    k = "count" if kind == "count_star" else kind
    arg = (None, None) if kind == "count_star" else (vd, vv)
    accs, valid = init_accumulators([k], [jnp.float64], 5)
    accs, valid = scatter_accumulate(jnp.arange(5), [(k, *arg)], mask,
                                     accs, valid)
    val, cv = row_contribution(k, *arg, mask, accs[0].dtype)
    assert val.dtype == accs[0].dtype
    np.testing.assert_array_equal(np.asarray(val), np.asarray(accs[0]))
    if k != "count":  # a count is never NULL
        np.testing.assert_array_equal(np.asarray(cv), np.asarray(valid[0]))


def test_a_switch_at_a_windows_last_batch_passes_the_next_window_on(
        loop_on, skip_conf):
    """minRows 2,000 is reached at the fourth batch of the first window
    of four: nothing of that window is left to pass through."""
    config.conf.set(config.PARTIAL_AGG_SKIPPING_MIN_ROWS.key, 2000)
    from blaze_tpu.bridge import tracing
    t = _skip_table()
    tracing.start_tracing()
    try:
        skipped, d = _run_skipping(
            lambda: _skip_agg(t, _AGGS["int_sum_counts"]))
        spans = [s["attrs"] for s in tracing.spans()
                 if s["name"] == "partial_passthrough"]
    finally:
        tracing.stop_tracing()
        tracing.reset_conf_probe()
    assert d["partial_agg_switch_rows"] == 2048
    assert d["partial_agg_skipped_rows"] == 8192 - 2048
    assert [(a["chunk"], a["batches"]) for a in spans] == [
        (1, 4), (2, 4), (3, 4)]
    grouped, _d = _run_skipping(
        lambda: _skip_agg(t, _AGGS["int_sum_counts"]), enable=False)
    _assert_same_answer(_final_merge(skipped, _AGGS["int_sum_counts"]),
                        _final_merge(grouped, _AGGS["int_sum_counts"]))


# -- the window is one device program (ISSUE 36) -----------------------------

def _window_batches(count, short_tail):
    """`count` batches of an int64, a float64 with NULLs and a string
    column (host: no device form), the last one short where asked."""
    from blaze_tpu.batch import ColumnBatch
    rng = np.random.default_rng(36)
    out = []
    for b in range(count):
        n = 100 if short_tail and b == count - 1 else 512
        v = rng.random(n)
        out.append(ColumnBatch.from_arrow(pa.RecordBatch.from_arrays(
            [pa.array(rng.integers(-9, 9, n), type=pa.int64()),
             pa.array(v, mask=v < 0.2),
             pa.array([f"s{i}" for i in range(n)])],
            names=["i", "v", "s"])))
    return out


def _numpy_window(items, width):
    """The window as it was assembled before it was one program: every
    array padded to the window's capacity, stacked, and the batch axis
    padded to the width the caller folds at."""
    cap = max(m.shape[0] for _c, m in items)

    def wide(arrays):
        a = np.stack([np.pad(np.asarray(a), (0, cap - a.shape[0]))
                      for a in arrays])
        return np.pad(a, ((0, width - len(arrays)), (0, 0)))

    cols = tuple(None if col is None else
                 (wide([c[i][0] for c, _m in items]),
                  wide([c[i][1] for c, _m in items]))
                 for i, col in enumerate(items[0][0]))
    masks = wide([m for _c, m in items])
    return cols, masks, masks[:len(items)].sum(axis=1)


@pytest.mark.parametrize("tail", ["even", "short"])
@pytest.mark.parametrize("pad_tail", [False, True], ids=["as_is", "widened"])
@pytest.mark.parametrize("count", [1, 3, 8])
def test_window_program_is_the_numpy_formulation_bit_for_bit(count, pad_tail,
                                                             tail):
    from blaze_tpu.plan import fused as F
    batches = _window_batches(count, tail == "short")
    # a short batch alone is its window's capacity: nothing to pad
    short = int(tail == "short" and count > 1)
    want_cols, want_masks, want_rows = _numpy_window(
        [F._source_inputs(b) for b in batches], 8 if pad_tail else count)
    before = xla_stats.snapshot()
    (window,) = F._batch_windows(iter(batches), 8, pad_tail=pad_tail)
    d = xla_stats.delta(before)
    cols, masks, rows, n = window
    assert n == count
    assert cols[2] is None and want_cols[2] is None  # the string column
    for got, want in zip(cols[:2], want_cols[:2]):
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            # bit for bit: a float's NaN payload would fail array_equal
            assert np.asarray(g).tobytes() == w.tobytes()
    assert masks.dtype == np.bool_
    np.testing.assert_array_equal(np.asarray(masks), want_masks)
    np.testing.assert_array_equal(np.asarray(rows), want_rows)
    assert np.asarray(rows).tolist() == [b.num_rows for b in batches]
    assert d["stage_loop_windows"] == 1
    assert d["stage_loop_windows_fused"] == 1 - short


def test_a_switched_partition_passes_a_widened_tail_window_on(loop_on,
                                                              skip_conf):
    """16 batches of 512 rows and one of 200 in chunks of three: the
    last window holds two batches, the short one padded to the other's
    capacity, widened to three.  The switch passes it on as it passes
    the whole ones."""
    config.conf.set(config.STAGE_DEVICE_LOOP_CHUNK.key, 3)
    from blaze_tpu.bridge import tracing
    t = _skip_table(n=8392)
    tracing.start_tracing()
    try:
        skipped, d = _run_skipping(
            lambda: _skip_agg(t, _AGGS["int_sum_counts"]))
        spans = tracing.spans()
    finally:
        tracing.stop_tracing()
        tracing.reset_conf_probe()
    windows = [s["attrs"] for s in spans if s["name"] == "loop_window"]
    assert [(a["batches"], a["padded"]) for a in windows] == \
        [(3, 0)] * 5 + [(2, 1)]
    assert d["stage_loop_windows"] == 6
    assert d["stage_loop_windows_fused"] == 5
    # the first look falls in the first window; every later one passes
    passed = [(s["attrs"]["chunk"], s["attrs"]["batches"]) for s in spans
              if s["name"] == "partial_passthrough"]
    assert passed[-1] == (5, 2) and [c for c, _b in passed[1:]] == \
        [1, 2, 3, 4, 5]
    assert d["partial_agg_skip_events"] == 1
    assert d["partial_agg_skipped_rows"] == \
        8392 - d["partial_agg_switch_rows"]
    grouped, _d = _run_skipping(
        lambda: _skip_agg(t, _AGGS["int_sum_counts"]), enable=False)
    _assert_same_answer(_final_merge(skipped, _AGGS["int_sum_counts"]),
                        _final_merge(grouped, _AGGS["int_sum_counts"]))


# -- ISSUE 39: a reduce task's fold runs over batches of one tile -----------

@pytest.mark.parametrize("chunk", [2, 8])
@pytest.mark.parametrize("tile", [1024, 32768])
def test_final_fold_over_the_ipc_reader_runs_at_the_tile(loop_on, tile,
                                                         chunk, monkeypatch):
    """Two map tasks' blocks as the shuffle writer cuts them (pieces of
    the batch size and a tail): the FINAL aggregation's windows are
    assembled at the tile, not at twice it, and a second run of the same
    partition builds nothing.  In chunks of two the tail has a window of
    its own, at its own bucket; in the default chunk of eight it rides
    with the four full tiles and is the one batch padded."""
    from blaze_tpu import batch as batch_mod
    from blaze_tpu.bridge import tracing
    from blaze_tpu.bridge.resource import put_resource
    from blaze_tpu.plan.column_pruning import prune_columns
    from blaze_tpu.plan.fused import fuse_plan
    from blaze_tpu.plan.planner import collapse_filter_project, create_plan
    from blaze_tpu.shuffle.ipc import write_batches_to_bytes
    config.conf.set(config.BATCH_SIZE.key, tile)
    config.conf.set(config.STAGE_DEVICE_LOOP_CHUNK.key, chunk)
    monkeypatch.setattr(batch_mod, "_host_resident", lambda: False)
    pieces = [tile, tile, tile * 3 // 16]
    n = 2 * sum(pieces)
    rng = np.random.default_rng(39)
    k = rng.integers(0, n // 2, n) * 1000003 + 17
    t = pa.table({"k": pa.array(k, type=pa.int64()),
                  "v": pa.array(rng.random(n))})
    starts = np.cumsum([0] + pieces * 2)
    put_resource(f"final-{tile}", [write_batches_to_bytes(
        t.slice(int(s), p).to_batches()[0]
        for s, p in zip(starts[b * 3:], pieces)) for b in range(2)])
    schema = {"fields": [
        {"name": "k", "type": {"id": "int64"}, "nullable": True},
        {"name": "v", "type": {"id": "float64"}, "nullable": True}]}

    def plan():
        return fuse_plan(prune_columns(collapse_filter_project(create_plan({
            "kind": "hash_agg",
            "groupings": [{"expr": {"kind": "column", "index": 0},
                           "name": "k"}],
            "aggs": [{"fn": "sum", "mode": "final", "name": "s",
                      "args": [{"kind": "column", "index": 1}]}],
            "input": {"kind": "ipc_reader", "schema": schema,
                      "resource_id": f"final-{tile}"}}))))
    try:
        tracing.start_tracing()
        try:
            before = xla_stats.snapshot()
            out = pa.Table.from_batches(
                [b.to_arrow() for b in plan().execute(0)])
            d = xla_stats.delta(before)
            windows = [s["attrs"] for s in tracing.spans()
                       if s["name"] == "loop_window"]
        finally:
            tracing.stop_tracing()
            tracing.reset_conf_probe()
        # four full tiles and a tail of 3/8 of one
        tail_cap = batch_mod.bucket_capacity(2 * pieces[2])
        assert tail_cap < tile
        if chunk == 2:
            shapes, lanes = [(2, 0), (2, 0), (1, 0)], 4 * tile + tail_cap
        else:
            shapes, lanes = [(5, 1)], 5 * tile
        assert [(a["batches"], a["padded"]) for a in windows] == shapes
        assert d["stage_loop_tasks"] == 1 and d["stage_loop_fallbacks"] == 0
        assert d["stage_loop_windows"] == len(shapes)
        # every window is the one program's alone but the tail's, where
        # the tail rides with batches of another capacity
        assert d["stage_loop_windows_fused"] == \
            sum(1 for _b, padded in shapes if not padded)
        assert d["stage_loop_batches"] == 5
        assert d["stage_loop_rows"] == n
        assert d["stage_loop_lanes"] == lanes
        assert xla_stats.chip_stats()[0]["stage_loop_lanes"] >= \
            d["stage_loop_lanes"]
        # the answer, by group
        keys, inverse = np.unique(k, return_inverse=True)
        want = np.bincount(inverse, weights=t["v"].to_numpy())
        got = out.sort_by("k")
        assert got.column("k").to_pylist() == keys.tolist()
        np.testing.assert_allclose(got.column(1).to_numpy(), want,
                                   rtol=1e-12)
        # the same partition again: the same capacities, so no program
        before = xla_stats.snapshot()
        assert list(plan().execute(0))
        d = xla_stats.delta(before)
        assert d["total_compiles"] == 0 and d["backend_compiles"] == 0
        assert d["stage_loop_lanes"] == lanes
    finally:
        config.conf.unset(config.BATCH_SIZE.key)
        config.conf.unset(config.STAGE_DEVICE_LOOP_CHUNK.key)


@pytest.mark.parametrize("counters,want", [
    # the pair's four reduce tasks alone: 143K rows in 5 x 32,768 lanes
    ({"stage_loop_rows": 4 * 143_536, "stage_loop_lanes": 4 * 5 * 32_768},
     100.0 * 143_536 / (5 * 32_768)),
    # a parent counts the rows and not the lanes: nothing to read
    ({"stage_loop_rows": 4 * 143_536}, None),
    # a window in which the stage loop folded nothing
    ({"stage_loop_rows": 0, "stage_loop_lanes": 0}, None),
], ids=["rows_over_lanes", "parent_without_the_counter", "no_fold"])
def test_fold_lane_fill_share_is_rows_over_lanes(counters, want):
    import os
    from benchmark.manifest import Cell
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = Cell("sf10_q01pair_x1", root)
    (entry, spec), = [(m, s) for m, s in cell.layer_metrics()
                      if m["name"] == "fold_lane_fill_share"]
    # list-less: every cell that reports query_wall_s reports it
    assert "workloads" not in entry and entry["source"] == "program_counter"
    assert (spec["unit"], spec["better"], spec["layer"]) == \
        (entry["unit"], entry["better"], entry["layer"]) == \
        ("%", "higher", "fused aggregation")
    got = cell.module("sources", spec["source"]).read(
        spec, {"counters": counters, "queries": 1})
    assert got == (want if want is None else pytest.approx(want))


# -- the owner lane: null bits and the undo's counter (ISSUE 47) -------------
# A slot's int32 `owner` says which keys of its group are NULL; the drains
# read the keys' validity from it over the used slots alone.

def _null_key_agg(tmp_path, k, tag, groups=64, rows=3000):
    """sum(v) group by `k` WIDE int64 columns, fused.  Group g holds
    NULL in column j when (g + j) % 5 == 0, so every column is NULL in
    some groups, some groups in several columns (group 0 of one column:
    the all-NULL key), and two groups differ in their values, in their
    NULLs or in both.  Returns (plan, {key tuple: sum})."""
    from blaze_tpu.plan.column_pruning import prune_columns
    from blaze_tpu.plan.fused import fuse_plan
    from blaze_tpu.plan.planner import collapse_filter_project, create_plan
    rng = np.random.default_rng(k)
    gid = rng.integers(0, groups, rows)
    vals = rng.random(rows)
    cols, fields = {}, []
    for j in range(k):
        data = _wide((gid * (j + 3)) % 7 + gid)
        null = (gid + j) % 5 == 0
        cols[f"k{j}"] = pa.array(data, type=pa.int64(),
                                 mask=null)
        fields.append({"name": f"k{j}", "type": {"id": "int64"},
                       "nullable": True})
    cols["v"] = pa.array(vals)
    fields.append({"name": "v", "type": {"id": "float64"}, "nullable": True})
    t = pa.table(cols)
    p = str(tmp_path / f"{tag}.parquet")
    pq.write_table(t, p, row_group_size=512)
    plan = {"kind": "hash_agg",
            "groupings": [{"expr": {"kind": "column", "index": j},
                           "name": f"k{j}"} for j in range(k)],
            "aggs": [{"fn": "sum", "mode": "partial", "name": "s",
                      "args": [{"kind": "column", "index": k}]}],
            "input": {"kind": "parquet_scan", "schema": {"fields": fields},
                      "file_groups": [[p]]}}
    want = {}
    keys = [t.column(j).to_pylist() for j in range(k)]
    for i, key in enumerate(zip(*keys)):
        want[key] = want.get(key, 0.0) + vals[i]
    return fuse_plan(prune_columns(collapse_filter_project(
        create_plan(plan)))), want


def _assert_groups(got_keys, got_sums, want):
    got = {}
    for key, s in zip(zip(*got_keys), got_sums):
        assert key not in got, "a group came out of the table twice"
        got[key] = s
    assert set(got) == set(want)
    for key, s in want.items():
        assert got[key] == pytest.approx(s, rel=1e-12)


@pytest.mark.parametrize("consumer", ["emit_hash", "drain_device"])
@pytest.mark.parametrize("k", [1, 2, 8, 31])
def test_null_keys_round_trip_through_the_owner_lane(tmp_path, loop_on,
                                                     small_tables, k,
                                                     consumer):
    from blaze_tpu.plan import fused, stage_compiler
    from blaze_tpu.runtime import loop as device_loop
    plan, want = _null_key_agg(tmp_path, k, f"nulls{k}{consumer}")
    assert isinstance(plan, fused.FusedPartialAggExec)
    before = xla_stats.snapshot()
    if consumer == "emit_hash":
        out = pa.Table.from_batches(
            [b.compact().to_arrow() for b in plan.execute(0)])
        keys = [out.column(j).to_pylist() for j in range(k)]
        sums = out.column(k).to_pylist()
    else:
        prog = stage_compiler.compile_task_plan(plan)
        with task_scope(TaskContext()):
            carry = device_loop.run_partition(prog, 0, ctx="t")
            datas, valids, n = device_loop.drain_device(prog, carry)
        assert n == len(want) == int(carry.groups)
        keys = [[int(d) if v else None for d, v in
                 zip(np.asarray(datas[j]), np.asarray(valids[j]))]
                for j in range(k)]
        sums = np.asarray(datas[k]).tolist()
    d = xla_stats.delta(before)
    assert d["stage_loop_tasks"] == 1 and d["stage_loop_fallbacks"] == 0
    assert d["stage_loop_undone_steps"] == 0
    _assert_groups(keys, sums, want)


def test_more_key_columns_than_null_bits_are_declined_where_planned(
        tmp_path, loop_on, small_tables):
    """32 grouping columns have no room in an int32 owner: the node is
    left as the aggregation an unfusable one is, and answers."""
    from blaze_tpu.parallel.stage import MAX_KEY_COLUMNS
    from blaze_tpu.plan import fused
    k = MAX_KEY_COLUMNS + 1
    plan, want = _null_key_agg(tmp_path, k, "nulls32", rows=600)
    assert not isinstance(plan, fused.FusedPartialAggExec)
    before = xla_stats.snapshot()
    out = pa.Table.from_batches(
        [b.compact().to_arrow() for b in plan.execute(0)])
    assert xla_stats.delta(before)["stage_loop_tasks"] == 0
    _assert_groups([out.column(j).to_pylist() for j in range(k)],
                   out.column(k).to_pylist(), want)


@pytest.mark.parametrize("chunk", [2, 4])
def test_an_overflow_in_the_fold_counts_its_undone_step(tmp_path, loop_on,
                                                        small_tables, chunk):
    """`stage_loop_undone_steps`: 0 from reset(), one for the fold's step
    whose winners were taken back (counted on the device, read with the
    overflow scalars) and one more for every rehash that had to double
    again; by chip, and in the explain footer beside `regrows=`."""
    from blaze_tpu.bridge.metrics import MetricNode
    from blaze_tpu.plan.explain import QueryProfile
    xla_stats.reset()
    assert xla_stats.snapshot()["stage_loop_undone_steps"] == 0
    config.conf.set(config.STAGE_DEVICE_LOOP_CHUNK.key, chunk)
    plan, want = _sum_agg(tmp_path, _overflowing_keys(), "partial", "undo")
    before = xla_stats.snapshot()
    got = _emitted(plan)
    d = xla_stats.delta(before)
    _assert_sums(_merged(got), want)
    # every regrow follows a fold step that was undone; a rehash that
    # overflowed was undone too, without a regrow of its own
    assert d["stage_loop_regrows"] >= 1
    assert d["stage_loop_undone_steps"] >= d["stage_loop_regrows"]
    assert sum(c["stage_loop_undone_steps"]
               for c in xla_stats.chip_stats().values()) == \
        d["stage_loop_undone_steps"]
    footer = QueryProfile("q", 0, MetricNode("root"), 1, "local",
                          xla=d).render_text()
    assert (f"regrows={d['stage_loop_regrows']} "
            f"undone={d['stage_loop_undone_steps']} ") in footer
    # a table sized before the fold undoes nothing
    plan, want = _sum_agg(tmp_path, _wide(np.arange(3000) % 700), "partial",
                          "noundo")
    before = xla_stats.snapshot()
    _assert_sums(_merged(_emitted(plan)), want)
    assert xla_stats.delta(before)["stage_loop_undone_steps"] == 0

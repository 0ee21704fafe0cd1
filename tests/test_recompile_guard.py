"""Steady-state compilation guard (ISSUE 3 acceptance): a repeated
filter->project query must run warm with ZERO XLA recompiles and an expr
program cache hit rate >= 0.9 — per-partition evaluator instances and
repeated runs must all resolve to the one fingerprint-keyed program.

ISSUE 8 extends the guard to StageProgram: the device-resident stage
loop must build ONE program per (chain, reduce-kinds, dtype, grow)
fingerprint, hit the cache on every later run, and keep steady state at
zero recompiles even while the capacity ladder regrows the hash table
mid-partition."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu import config
from blaze_tpu.bridge import xla_stats
from blaze_tpu.exprs import BinaryExpr, col, lit
from blaze_tpu.exprs.program import clear_program_cache
from blaze_tpu.ops import FilterProjectExec, MemoryScanExec


@pytest.fixture(autouse=True)
def _fresh():
    clear_program_cache()
    yield
    clear_program_cache()


def _plan(tbl, partitions=1):
    scan = MemoryScanExec.from_arrow(tbl, num_partitions=partitions,
                                     batch_rows=256)
    return FilterProjectExec(
        scan,
        [BinaryExpr(">", col(0), lit(0)),
         BinaryExpr("<", col(1), lit(40.0))],
        [col(0), BinaryExpr("*", col(1), lit(2.0)),
         BinaryExpr("+", col(0), col(0))],
        ["a", "b2", "a2"])


def _table(n=2048, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({"a": pa.array(rng.integers(-50, 50, n)),
                     "b": pa.array(rng.random(n) * 100)})


def test_steady_state_zero_recompiles():
    tbl = _table()
    _plan(tbl).execute_collect()  # warm-up: builds + compiles the program
    before = xla_stats.snapshot()
    for run in range(10):
        out = _plan(tbl).execute_collect()
        assert out.num_rows > 0
    d = xla_stats.delta(before)
    assert d["total_compiles"] == 0, \
        f"steady-state recompiles: {d['total_compiles']}"
    assert d["expr_programs_built"] == 0
    # every steady-state run is a cache hit: 10/10
    looked_up = d["expr_programs_built"] + d["expr_program_cache_hits"]
    hit_rate = d["expr_program_cache_hits"] / looked_up if looked_up else 0.0
    assert hit_rate >= 0.9, f"expr cache hit rate {hit_rate:.2f} < 0.9"
    # and every batch dispatched through the fused program, none eagerly
    assert d["expr_fused_batches"] > 0
    assert d["expr_eager_batches"] == 0


def test_partitions_share_one_program():
    # satellite: per-partition evaluator instances must meter under ONE
    # kernel name — no false per-partition recompiles
    tbl = _table(4096, seed=1)
    plan = _plan(tbl, partitions=4)
    before = xla_stats.snapshot()
    plan.execute_collect()
    d = xla_stats.delta(before)
    assert d["expr_programs_built"] == 1
    assert d["expr_program_cache_hits"] >= 3  # partitions 2..4
    assert d["total_compiles"] <= 1, \
        f"per-partition recompiles detected: {d['total_compiles']}"


def test_cross_query_program_reuse():
    # two distinct scans, same expression chain + dtypes: the second
    # query reuses the first's compiled program without any compile
    _plan(_table(seed=2)).execute_collect()
    before = xla_stats.snapshot()
    _plan(_table(seed=3)).execute_collect()
    d = xla_stats.delta(before)
    assert d["expr_programs_built"] == 0
    assert d["total_compiles"] == 0


# -- ISSUE 8: StageProgram guard (device-resident stage loop) ---------------

@pytest.fixture
def loop_on():
    from blaze_tpu.plan import stage_compiler
    stage_compiler._SEEN_FINGERPRINTS.clear()
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")
    try:
        yield
    finally:
        config.conf.unset(config.STAGE_DEVICE_LOOP_ENABLE.key)


def _loop_agg_plan(tmp_path, tag="a", n=4000, mode="partial",
                   value="float64", seed=5, groups=200):
    """hash_agg over a 2-partition parquet scan.  Keys are WIDE int64
    (compact 0..199 ranges take the dense lane, which the stage compiler
    rejects — the loop is the hash lane's fold)."""
    rng = np.random.default_rng(seed)
    k = rng.integers(0, groups, n) * 1000003 + 17
    if value == "int64":
        v = pa.array(rng.integers(0, 1000, n), type=pa.int64())
    else:
        v = pa.array(rng.random(n))
    t = pa.table({"k": pa.array(k, type=pa.int64()), "v": v})
    paths = []
    for i in range(2):
        p = str(tmp_path / f"loop-{tag}-{i}.parquet")
        pq.write_table(t.slice(i * (n // 2), n // 2), p)
        paths.append(p)
    schema = {"fields": [
        {"name": "k", "type": {"id": "int64"}, "nullable": True},
        {"name": "v", "type": {"id": value}, "nullable": True}]}
    return {"kind": "hash_agg",
            "groupings": [{"expr": {"kind": "column", "index": 0},
                           "name": "k"}],
            "aggs": [{"fn": "sum", "mode": mode, "name": "s",
                      "args": [{"kind": "column", "index": 1}]}],
            "input": {"kind": "parquet_scan", "schema": schema,
                      "file_groups": [[paths[0]], [paths[1]]]}}


def _fused(plan_dict):
    from blaze_tpu.plan.column_pruning import prune_columns
    from blaze_tpu.plan.fused import fuse_plan
    from blaze_tpu.plan.planner import collapse_filter_project, create_plan
    return fuse_plan(prune_columns(collapse_filter_project(
        create_plan(plan_dict))))


def test_stage_loop_steady_state_zero_recompiles(tmp_path, loop_on):
    plan = _fused(_loop_agg_plan(tmp_path))
    nparts = plan.num_partitions
    for p in range(nparts):  # warm-up: builds the program, compiles fold
        assert list(plan.execute(p))
    before = xla_stats.snapshot()
    runs = 0
    for _ in range(3):
        fresh = _fused(_loop_agg_plan(tmp_path))  # new plan instances
        for p in range(nparts):
            assert list(fresh.execute(p))
            runs += 1
    d = xla_stats.delta(before)
    assert d["total_compiles"] == 0, \
        f"steady-state recompiles: {d['total_compiles']}"
    assert d["stage_loop_programs_built"] == 0
    assert d["stage_loop_program_cache_hits"] >= runs
    assert d["stage_loop_fallbacks"] == 0
    # and the loop actually ran every partition (not the staged path)
    assert d["stage_loop_tasks"] == runs


def test_stage_loop_new_dtype_signature_builds_new_program(tmp_path,
                                                           loop_on):
    plan = _fused(_loop_agg_plan(tmp_path, tag="f"))
    assert list(plan.execute(0))
    before = xla_stats.snapshot()
    other = _fused(_loop_agg_plan(tmp_path, tag="i", value="int64"))
    assert list(other.execute(0))
    d = xla_stats.delta(before)
    # int64 accumulator => new dtype signature => exactly one new program
    assert d["stage_loop_programs_built"] == 1
    assert d["stage_loop_fallbacks"] == 0


@pytest.fixture
def rung_ladder():
    """A floor of 16 slots, 256-row batches folded one a chunk, and
    groups that keep arriving: the loop reserves at the first chunk and
    re-sizes a table that holds groups at a later one."""
    config.conf.set(config.ON_DEVICE_AGG_CAPACITY.key, 16)
    config.conf.set(config.BATCH_SIZE.key, 256)
    config.conf.set(config.STAGE_DEVICE_LOOP_CHUNK.key, 1)
    try:
        yield
    finally:
        config.conf.unset(config.ON_DEVICE_AGG_CAPACITY.key)
        config.conf.unset(config.BATCH_SIZE.key)
        config.conf.unset(config.STAGE_DEVICE_LOOP_CHUNK.key)


def test_stage_loop_capacity_rungs_compile_once(tmp_path, loop_on,
                                                rung_ladder):
    # the warm run compiles every rung's rehash + the fold at every
    # rung; the repeat run climbs the same ladder with ZERO new compiles
    def plan():
        return _fused(_loop_agg_plan(tmp_path, tag="rung", mode="final",
                                     groups=4000))
    assert list(plan().execute(0))
    before = xla_stats.snapshot()
    assert list(plan().execute(0))
    d = xla_stats.delta(before)
    assert d["total_compiles"] == 0, \
        f"capacity-rung recompiles: {d['total_compiles']}"
    # the ladder actually climbed, by reservation: the first chunk's
    # allocation, then a rehash of a table that held groups
    assert d["stage_loop_reserves"] > 1
    assert d["stage_loop_rehash_lanes"] > 0
    assert d["stage_loop_fallbacks"] == 0


# -- ISSUE 36: the window program -------------------------------------------

@pytest.mark.parametrize("chunk,counts", [(1, {1}), (3, {3, 2}), (8, {8})])
def test_stage_loop_window_program_a_batch_count(tmp_path, loop_on, chunk,
                                                 counts):
    """A partition of 8 batches at one capacity in chunks of `chunk`: at
    most one window program a distinct batch count, and a second run of
    the same partition asks for none."""
    config.conf.set(config.BATCH_SIZE.key, 256)
    config.conf.set(config.STAGE_DEVICE_LOOP_CHUNK.key, chunk)
    try:
        def windows():
            k = xla_stats.compile_report()["kernels"].get(
                "runtime.stage_loop_window", {})
            return k.get("calls", 0), k.get("compiles", 0)

        def plan():
            return _fused(_loop_agg_plan(tmp_path, tag=f"w{chunk}"))
        calls, compiles = windows()
        before = xla_stats.snapshot()
        assert list(plan().execute(0))
        d = xla_stats.delta(before)
        n_windows = -(-8 // chunk)
        assert d["stage_loop_windows"] == n_windows
        assert d["stage_loop_windows_fused"] == n_windows
        assert windows()[0] - calls == n_windows
        assert windows()[1] - compiles <= len(counts)
        calls, compiles = windows()
        before = xla_stats.snapshot()
        assert list(plan().execute(0))
        d = xla_stats.delta(before)
        assert windows() == (calls + n_windows, compiles)
        assert d["total_compiles"] == 0 and d["backend_compiles"] == 0
        assert d["stage_loop_fallbacks"] == 0
    finally:
        config.conf.unset(config.BATCH_SIZE.key)
        config.conf.unset(config.STAGE_DEVICE_LOOP_CHUNK.key)

"""Tracing at the host/device boundary (ISSUE 24): device programs carry
their kernel name, every crossing goes through `xputil.to_host` /
`xputil.to_device` and is counted with its time, and the intervals inside
a task (`prefetch_wait`, `produce:*`, `join_*`, `agg_drain`) are real
spans that carry the task's context onto helper threads."""

import ast
import os
import re
import threading
import time

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu import config, xputil
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.memory import MemManager
from blaze_tpu.ops.base import PrefetchIterator

_HERE = os.path.dirname(os.path.abspath(__file__))
_PKG = os.path.join(os.path.dirname(_HERE), "blaze_tpu")


def _sources():
    for root, _dirs, files in os.walk(_PKG):
        for fn in sorted(files):
            if fn.endswith(".py"):
                path = os.path.join(root, fn)
                with open(path) as f:
                    yield os.path.relpath(path, _PKG), f.read()


@pytest.fixture
def traced():
    tracing.start_tracing()
    try:
        yield
    finally:
        tracing.stop_tracing()
        tracing.reset_conf_probe()


def _named(name):
    return [s for s in tracing.spans() if s["name"] == name]


# -- program names -----------------------------------------------------------

def _module_name(metered, *args, **kwargs) -> str:
    text = metered._blaze_jitted.lower(*args, **kwargs).as_text()
    return re.search(r"module @(\S+)", text).group(1)


def _hash_carry(slots=64):
    from blaze_tpu.parallel.stage import init_hash_carry
    return init_hash_carry([jnp.int64], ("sum",), [jnp.float64], slots)


def _lower_probe_counts():
    from blaze_tpu.kernels.join import probe_counts
    i64, i32 = jnp.zeros(8, jnp.int64), jnp.zeros(8, jnp.int32)
    return _module_name(probe_counts, i64, i32, i32, i64,
                        jnp.zeros(8, bool))


def _lower_expand_pairs():
    from blaze_tpu.kernels.join import expand_pairs
    i32 = jnp.zeros(8, jnp.int32)
    return _module_name(expand_pairs, i32, i32, cap=1024)


def _lower_probe_gather():
    from blaze_tpu.kernels.join import probe_gather
    i64, i32, ok = (jnp.zeros(8, jnp.int64), jnp.zeros(7, jnp.int32),
                    jnp.ones(8, bool))
    return _module_name(probe_gather, jnp.zeros(7, jnp.int64), i32,
                        (i64,), ((i64, ok),), ((i64, ok),), ((i64, ok),),
                        jnp.int32(8), None, tids=("int64",))


def _lower_hash_valid():
    from blaze_tpu.ops.joins.exec import _hash_valid_jit
    return _module_name(_hash_valid_jit(("int64",)),
                        [(jnp.zeros(8, jnp.int64), jnp.ones(8, bool))])


def _lower_rehash():
    from blaze_tpu.plan.fused import _rehash_jit
    return _module_name(_rehash_jit(("sum",), 128),
                        _hash_carry())


def _lower_hash_step():
    from blaze_tpu.plan.fused import _hash_step_jit
    return _module_name(
        _hash_step_jit(("sum",)), _hash_carry(),
        (jnp.zeros(8, jnp.int64),), (jnp.ones(8, bool),),
        (jnp.zeros(8, jnp.float64),), (jnp.ones(8, bool),),
        jnp.ones(8, bool))


def _lower_hash_pmod():
    from blaze_tpu.shuffle.partitioning import _hash_pmod_jit
    return _module_name(_hash_pmod_jit(("int64",), 4),
                        [(jnp.zeros(8, jnp.int64), jnp.ones(8, bool))])


@pytest.mark.parametrize("lower,module", [
    (_lower_probe_counts, "jit_probe_counts__join_probe_counts"),
    (_lower_expand_pairs, "jit_expand_pairs__join_expand_pairs"),
    (_lower_probe_gather, "jit_probe_gather__join_probe_gather"),
    (_lower_hash_valid, "jit_f__join_hash_valid"),
    (_lower_rehash, "jit__lambda__fused_rehash"),
    (_lower_hash_step, "jit_f__fused_hash_step"),
    (_lower_hash_pmod, "jit_f__shuffle_hash_pmod"),
])
def test_kernel_lowers_to_a_module_named_after_it(lower, module):
    """What the profiler's `XLA Modules` line shows is the HLO module's
    name: `jit_<function>__<kernel>`, the kernel part being the key of
    compile_report()["kernels"] with `.` turned to `_`."""
    assert lower() == module


def _meter_jit_calls():
    """(file, function expression, kernel name) of every meter_jit call
    in the package, the `partial(meter_jit, ...)` decorators included;
    the name is None where the call passes none."""
    def is_meter_jit(node):
        return (isinstance(node, ast.Name) and node.id == "meter_jit") or (
            isinstance(node, ast.Attribute) and node.attr == "meter_jit")

    out = []
    for rel, src in _sources():
        if rel == os.path.join("bridge", "xla_stats.py"):
            continue
        for node in ast.walk(ast.parse(src)):
            if not isinstance(node, ast.Call):
                continue
            if is_meter_jit(node.func):
                fn = ast.unparse(node.args[0]) if node.args else None
            elif node.args and is_meter_jit(node.args[0]):
                fn = None                      # partial(meter_jit, ...)
            else:
                continue
            name = next((ast.unparse(k.value).strip("'\"")
                         for k in node.keywords if k.arg == "name"), None)
            out.append((rel, fn, name))
    return out


def test_every_meter_jit_call_names_its_kernel():
    calls = _meter_jit_calls()
    assert len(calls) >= 18, calls
    unnamed = [c for c in calls if c[2] is None]
    assert not unnamed, f"meter_jit calls without name=: {unnamed}"


@pytest.mark.parametrize("kernel", [
    "runtime.stage_loop", "runtime.stage_loop_fold_expand",
    "fused.dense_fold", "fused.mxu_fold"])
def test_fold_programs_still_match_the_trace_readers_pattern(kernel):
    """`fold_device_s` and `fold_roofline` (benchmark/layer_metrics) find
    the fold programs by `^jit_fold_impl`: the function name stays in
    front of the kernel name."""
    calls = {(fn, k) for _rel, fn, k in _meter_jit_calls()}
    assert ("fold_impl", kernel) in calls
    f = xla_stats.meter_jit(_fold_impl_stand_in(), name=kernel)
    module = _module_name(f, jnp.zeros(4))
    assert re.match(r"^jit_fold_impl", module)
    assert module.endswith("__" + kernel.replace(".", "_"))


def _fold_impl_stand_in():
    def fold_impl(carry):
        return carry + 1
    return fold_impl


def test_program_name_is_the_compile_reports_key():
    xla_stats.reset()
    f = xla_stats.meter_jit(lambda x: x * 2, name="test.boundary/kernel-1")
    f(jnp.arange(4))
    assert "test.boundary/kernel-1" in xla_stats.compile_report()["kernels"]
    assert _module_name(f, jnp.arange(5)) == \
        "jit__lambda__test_boundary_kernel_1"


def test_no_bare_jit_of_an_anonymous_function_in_the_package():
    """A program named `jit__lambda` or after a generic inner function
    cannot be put to a line of code from a trace: every jax.jit in the
    package goes through meter_jit or jits a named function."""
    bad = []
    for rel, src in _sources():
        if rel == os.path.join("bridge", "xla_stats.py"):
            continue
        for m in re.finditer(r"jax\.jit\(\s*lambda", src):
            bad.append((rel, src[:m.start()].count("\n") + 1))
    assert not bad, bad


# -- one way across the boundary ---------------------------------------------

def test_no_device_get_or_device_put_outside_the_helpers():
    offenders = []
    for rel, src in _sources():
        if rel == "xputil.py":
            continue
        for i, line in enumerate(src.splitlines(), 1):
            code = line.split("#", 1)[0]
            if "jax.device_get(" in code or "jax.device_put(" in code:
                # parallel/mesh.py places shards with an explicit
                # sharding: a collective's layout, not a batch crossing
                if rel == os.path.join("parallel", "mesh.py") \
                        and "sharding" in code:
                    continue
                offenders.append((rel, i, line.strip()))
    assert not offenders, offenders


def test_to_host_counts_bytes_transfers_and_blocked_time(traced):
    before = xla_stats.snapshot()
    tree = ([jnp.arange(1000, dtype=jnp.int64), np.arange(7)],
            jnp.ones(16, dtype=bool))
    out = xputil.to_host(tree)
    d = xla_stats.delta(before)
    assert isinstance(out[0][0], np.ndarray) and out[0][0][999] == 999
    assert d["d2h_bytes"] == 8000 + 16      # the numpy leaf is not moved
    assert d["d2h_transfers"] == 1
    assert d["d2h_wait_ns"] > 0
    (span,) = _named("d2h")
    assert span["attrs"] == {"bytes": 8016, "device": 0}
    assert span["dur_ns"] <= d["d2h_wait_ns"]


def test_to_host_of_host_values_is_free(traced):
    before = xla_stats.snapshot()
    a = np.arange(5)
    assert xputil.to_host(a) is a
    assert xputil.asnp(a) is a
    assert xputil.to_host(3) == 3
    d = xla_stats.delta(before)
    assert d["d2h_bytes"] == 0 and d["d2h_wait_ns"] == 0
    assert not _named("d2h")


def test_to_device_counts_bytes_and_time(traced):
    before = xla_stats.snapshot()
    bufs = [np.arange(100, dtype=np.int64), np.ones(100, dtype=bool)]
    placed = xputil.to_device(bufs)
    d = xla_stats.delta(before)
    assert all(not isinstance(p, np.ndarray) for p in placed)
    assert placed[0].dtype == jnp.int64
    assert d["h2d_bytes"] == 900 and d["h2d_transfers"] == 1
    assert d["h2d_ns"] > 0
    (span,) = _named("h2d")
    assert span["attrs"] == {"bytes": 900, "device": 0}


def test_batch_placement_goes_through_the_helper(monkeypatch, traced):
    """`from_arrow` under device placement: one batched put, timed."""
    import blaze_tpu.batch as batch_mod
    monkeypatch.setattr(batch_mod, "_host_resident", lambda: False)
    before = xla_stats.snapshot()
    cb = batch_mod.ColumnBatch.from_arrow(pa.RecordBatch.from_arrays(
        [pa.array(np.arange(1024, dtype=np.int64)),
         pa.array(np.linspace(0, 1, 1024))], names=["a", "b"]))
    d = xla_stats.delta(before)
    assert d["h2d_transfers"] == 1 and d["h2d_ns"] > 0
    assert d["h2d_bytes"] == 2 * cb.capacity * (8 + 1)
    assert len(_named("h2d")) == 1
    before = xla_stats.snapshot()
    rb = cb.to_arrow()
    d = xla_stats.delta(before)
    assert rb.num_rows == 1024
    assert d["d2h_bytes"] == 2 * cb.capacity * (8 + 1)
    assert d["d2h_transfers"] == 1 and d["d2h_wait_ns"] > 0


def test_new_counters_reach_every_surface():
    from blaze_tpu.bridge import profiling
    MemManager.init(4 << 30)
    fams = xla_stats.counter_families()
    assert {"h2d_ns", "d2h_wait_ns"} <= set(fams["transfers"])
    ledger = ("program_loads_trimmed",)
    assert set(fams["backend"]) == {"backend_compiles",
                                    "backend_compile_ns",
                                    "compile_cache_hits", *ledger}
    snap = xla_stats.snapshot()
    text = profiling.prometheus_text()
    for k in ("h2d_ns", "d2h_wait_ns", "backend_compiles",
              "backend_compile_ns", "compile_cache_hits", *ledger):
        assert k in snap
        assert f"blaze_{k}_total" in text


# -- the stage pair: drain readback is counted -------------------------------

@pytest.fixture
def staged_loop():
    MemManager.init(4 << 30)
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    try:
        yield
    finally:
        config.conf.unset(config.STAGE_DEVICE_LOOP_ENABLE.key)
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)


def _pair_plan(tmp_path, n=8000, groups=500):
    """q01's inner pipeline in miniature: partial sum by a wide int64 key
    -> hash exchange -> final sum."""
    rng = np.random.default_rng(24)
    k = rng.integers(0, groups, n) * 1000003 + 17
    t = pa.table({"k": pa.array(k, type=pa.int64()),
                  "v": pa.array(rng.random(n))})
    paths = []
    for i in range(2):
        p = str(tmp_path / f"pair-{i}.parquet")
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
                             "num_partitions": 2},
            "input": {
                "kind": "hash_agg",
                "groupings": [{"expr": {"kind": "column", "name": "k"},
                               "name": "k"}],
                "aggs": [{"fn": "sum", "mode": "partial", "name": "s",
                          "args": [{"kind": "column", "name": "v"}]}],
                "input": {"kind": "parquet_scan", "schema": schema,
                          "file_groups": [[paths[0]], [paths[1]]]}}}}
    return plan, len(np.unique(k))


@pytest.fixture
def file_shuffle():
    """The exchange a one-chip run takes: map tasks drain their tables to
    the host and write shuffle files."""
    config.conf.set(config.SHUFFLE_DEVICE.key, "off")
    try:
        yield
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)


def test_device_exchange_readback_is_counted(tmp_path, staged_loop):
    """With the loop forced on, map tables drain device to device and
    cross to the host once, after the collective exchange."""
    from blaze_tpu.plan.stages import DagScheduler
    plan, n_groups = _pair_plan(tmp_path)
    before = xla_stats.snapshot()
    out = DagScheduler().run_collect(plan)
    d = xla_stats.delta(before)
    assert out.num_rows == n_groups
    assert d["shuffle_device_exchanges"] == 1
    assert d["d2h_bytes"] >= n_groups * (8 + 8 + 1 + 1)
    assert d["d2h_wait_ns"] > 0


def test_pair_run_counts_the_drained_table_and_its_wait(tmp_path,
                                                        staged_loop,
                                                        file_shuffle,
                                                        traced):
    from blaze_tpu.plan.stages import DagScheduler
    plan, n_groups = _pair_plan(tmp_path)
    before = xla_stats.snapshot()
    out = DagScheduler().run_collect(plan)
    d = xla_stats.delta(before)
    assert out.num_rows == n_groups
    assert d["stage_loop_tasks"] >= 2 and d["stage_loop_fallbacks"] == 0
    assert d["shuffle_device_exchanges"] == 0
    # every drained group crosses with its key, sum and two validity
    # bytes, from the map tasks' tables alone
    assert d["d2h_bytes"] >= n_groups * (8 + 8 + 1 + 1)
    assert d["d2h_wait_ns"] > 0 and d["d2h_transfers"] > 0
    drains = _named("agg_drain")
    assert drains and all(s["attrs"]["table"] == "hash" for s in drains)
    inside = [s for s in _named("d2h")
              if any(a["sid"] == s.get("parent") for a in drains)]
    assert sum(s["attrs"]["bytes"] for s in inside) >= \
        n_groups * (8 + 8 + 1 + 1)
    # the drain is work of a task: it carries the task's context
    assert all(s["ctx"].get("stage") is not None for s in drains)
    # the scan ran on prefetch workers, as real intervals
    scans = _named("produce:parquet_scan")
    assert scans and all(
        s["thread"].startswith("blaze-prefetch-") for s in scans)
    assert sum(s["attrs"]["rows"] for s in scans) == 8000
    assert not [s for s in tracing.spans()
                if s["name"].startswith("operator:")]


def test_stage_loop_fold_is_named_after_its_kernel(tmp_path, staged_loop):
    from blaze_tpu.plan.stages import DagScheduler
    from blaze_tpu.runtime.loop import _FOLD_CACHE
    plan, _n = _pair_plan(tmp_path)
    DagScheduler().run_collect(plan)
    # the cache is the process's: an earlier file on this worker may
    # have left its pass-through programs beside the folds
    names = {"jit_" + fold._blaze_jitted.__name__
             for fold in _FOLD_CACHE.values()
             if "passthrough" not in fold._blaze_jitted.__name__}
    assert names == {"jit_fold_impl__runtime_stage_loop"}
    assert "runtime.stage_loop" in xla_stats.compile_report()["kernels"]


# -- prefetch: spans on the worker thread, wait on the consumer --------------

def test_prefetch_worker_spans_carry_the_consumers_context(traced):
    def transform(x):
        with tracing.span("h2d", bytes=0):
            return x

    with tracing.execution_context(query="q-pf", stage=3, partition=1):
        with tracing.span("task", mode="sync"):
            it = PrefetchIterator(iter([pa.table({"a": [1, 2, 3]}),
                                        pa.table({"a": [4]})]),
                                  depth=2, transform=transform,
                                  name="parquet_scan")
            got = list(it)
    assert [t.num_rows for t in got] == [3, 1]
    (task,) = _named("task")
    produced = _named("produce:parquet_scan")
    # the third is the next() that found the end of the stream
    assert [s["attrs"]["rows"] for s in produced] == [3, 1, 0]
    for s in produced:
        assert s["thread"] == "blaze-prefetch-parquet_scan"
        assert s["ctx"] == {"query": "q-pf", "stage": 3, "partition": 1}
        assert s["parent"] == task["sid"]
    nested = _named("h2d")
    assert len(nested) == 2
    assert {s["parent"] for s in nested} == {p["sid"] for p in produced[:2]}
    assert all(s["ctx"]["query"] == "q-pf" for s in nested)


def test_prefetch_worker_has_no_context_to_adopt_outside_a_task(traced):
    list(PrefetchIterator(iter([1, 2]), depth=1, name="ipc_reader"))
    for s in _named("produce:ipc_reader"):
        assert "ctx" not in s and "parent" not in s


def test_prefetch_wait_span_agrees_with_the_counter(traced):
    release = threading.Event()

    def slow():
        yield 1
        release.wait(5)
        time.sleep(0.05)
        yield 2

    before = xla_stats.snapshot()
    it = PrefetchIterator(slow(), depth=1, name="shuffle_map")
    assert next(it) == 1
    release.set()
    assert next(it) == 2
    assert list(it) == []
    d = xla_stats.delta(before)
    waits = _named("prefetch_wait")
    assert len(waits) == 3 and d["prefetch_waits"] == 3
    assert all(s["attrs"] == {"source": "shuffle_map"} for s in waits)
    span_ns = sum(s["dur_ns"] for s in waits)
    assert span_ns >= 40_000_000            # the consumer really waited
    # the counter's clock stops after the span's: same wait, read twice
    assert 0 <= d["prefetch_wait_ns"] - span_ns < 5_000_000


def test_synchronous_prefetch_emits_no_wait(traced):
    assert list(PrefetchIterator(iter([1, 2]), depth=0)) == [1, 2]
    assert not _named("prefetch_wait")


# -- join and drain intervals ------------------------------------------------

def test_join_emits_build_and_probe_intervals(traced):
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.ops.joins.exec import BroadcastJoinExec, JoinType
    left = pa.table({"k": pa.array(np.arange(2000) % 50, pa.int64()),
                     "v": pa.array(np.arange(2000, dtype=np.float64))})
    right = pa.table({"k": pa.array(np.arange(40), pa.int64()),
                      "w": pa.array(np.arange(40, dtype=np.int64))})
    join = BroadcastJoinExec(
        MemoryScanExec.from_arrow(left, batch_rows=512),
        MemoryScanExec.from_arrow(right, batch_rows=64),
        [col(0)], [col(0)], JoinType.INNER, build_side="right")
    with tracing.execution_context(query="q-join", stage=0):
        rows = sum(b.compact().num_rows for b in join.execute(0))
    assert rows == 1600
    builds, probes = _named("join_build"), _named("join_probe")
    assert builds and builds[0]["attrs"]["step"] == "collect"
    # host placement joins in the Arrow lane, over the probe rows the
    # build side's key range lets through
    assert [s["attrs"] for s in probes] == [{"lane": "arrow",
                                             "rows": 1600}]
    assert all(s["ctx"]["query"] == "q-join" for s in builds + probes)


def test_streaming_probe_emits_one_interval_per_batch(traced):
    """The device placement's probe (`_probe_batch`): one join_probe span
    a batch, the index built under join_build on the first."""
    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.ops.joins.exec import (BroadcastJoinExec, JoinType,
                                          build_join_map)
    left = pa.table({"k": pa.array(np.arange(600) % 50, pa.int64())})
    right = pa.table({"k": pa.array(np.arange(40), pa.int64())})
    lscan = MemoryScanExec.from_arrow(left, batch_rows=200)
    rscan = MemoryScanExec.from_arrow(right, batch_rows=64)
    join = BroadcastJoinExec(lscan, rscan, [col(0)], [col(0)],
                             JoinType.INNER, build_side="right")
    jmap = build_join_map(iter(right.to_batches()), rscan.schema, [col(0)])
    out = list(join._stream_probe(
        jmap, (ColumnBatch.from_arrow(b) for b in left.to_batches(200)),
        [col(0)], True))
    assert sum(b.num_rows for b in out) == 480
    probes = _named("join_probe")
    assert [s["attrs"]["rows"] for s in probes] == [200, 200, 200]
    (index,) = _named("join_build")
    assert index["attrs"] == {"rows": 40, "step": "index"}
    assert index["parent"] == probes[0]["sid"]


# -- what JAX really compiles ------------------------------------------------

def test_backend_compiles_see_what_meter_jit_does_not(traced):
    before = xla_stats.snapshot()
    with tracing.execution_context(query="q-compile", stage=5):
        with tracing.span("task", mode="sync"):
            # an eager op with a shape no other test uses: a glue program
            float(jnp.sum(jnp.arange(7919, dtype=jnp.float64) * 1.5))
    d = xla_stats.delta(before)
    assert d["total_compiles"] == 0          # no metered kernel ran
    assert d["backend_compiles"] >= 1 and d["backend_compile_ns"] > 0
    (task,) = _named("task")
    mine = [s for s in _named("xla_compile")
            if s["attrs"].get("source") == "backend"]
    # one span a phase of a request; the backend's are the compiles
    assert len([s for s in mine if s["attrs"]["phase"] == "backend"]) \
        == d["backend_compiles"]
    assert {s["attrs"]["phase"] for s in mine} == {"trace", "lower", "backend"}
    for s in mine:
        assert s["attrs"]["ns"] == s["dur_ns"] > 0
        assert s["attrs"]["site"].startswith(
            "test_boundary_tracing.py:"
            "test_backend_compiles_see_what_meter_jit_does_not:")
        assert s["ctx"] == {"query": "q-compile", "stage": 5}
        assert s["parent"] == task["sid"]


def test_backend_listeners_register_once():
    import jax
    xla_stats.listen_backend_compiles()
    xla_stats.listen_backend_compiles()
    from jax._src import monitoring
    ours = [cb for cb in monitoring.get_event_duration_listeners()
            if getattr(cb, "__module__", "") == xla_stats.__name__]
    assert len(ours) == 1
    assert jax.__version__


# -- who holds the thread: one real-interval span an operator a pull ---------

def _chain():
    """leaf -> pass -> pass over three batches of 100 rows.  The middle
    operator's arrow_batches() routes through its own execute(): the
    re-entrant self-call several real operators make."""
    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.ops.base import ExecutionPlan
    from blaze_tpu.schema import Schema

    t = pa.table({"a": pa.array(range(300), type=pa.int64())})
    batches = [ColumnBatch.from_arrow(rb) for rb in t.to_batches(100)]

    class LeafExec(ExecutionPlan):
        schema = Schema.from_arrow(t.schema)

        def execute(self, partition):
            return iter(batches)

    class PassExec(ExecutionPlan):
        schema = LeafExec.schema

        def execute(self, partition):
            for cb in self.children[0].execute(partition):
                yield cb

        def arrow_batches(self, partition):
            for cb in self.execute(partition):     # the inner self-call
                yield cb.to_arrow()

    class TopExec(PassExec):
        pass

    leaf = LeafExec()
    mid = PassExec([leaf])
    return TopExec([mid]), mid, leaf


def _ops(name=None):
    return [s for s in tracing.spans() if s["name"].startswith("op:")
            and (name is None or s["name"] == name)]


def _attr(span, key, default=None):
    # the pull that finds the end of the stream carries no attrs
    return span.get("attrs", {}).get(key, default)


def _pulls(name):
    return [s for s in _ops(name) if _attr(s, "phase") != "open"]


def test_operator_pulls_are_nested_real_intervals(traced):
    top, mid, _leaf = _chain()
    with tracing.span("task", mode="sync"):
        rows = sum(b.num_rows for b in top.execute(0))
    assert rows == 300
    (task,) = _named("task")
    by_sid = {s["sid"]: s for s in tracing.spans()}
    for name in ("op:TopExec", "op:PassExec", "op:LeafExec"):
        # one for the eager execute() call, one a pull: three batches
        # and the pull that finds the end
        assert len(_ops(name)) - len(_pulls(name)) == 1
        assert [_attr(s, "rows") for s in _pulls(name)] == \
            [100, 100, 100, None], name
    # child inside parent, on one thread, by `parent` and by the clock
    for child, parent in (("op:LeafExec", "op:PassExec"),
                          ("op:PassExec", "op:TopExec"),
                          ("op:TopExec", "task")):
        for s in _pulls(child):
            up = by_sid[s["parent"]]
            assert up["name"] == parent
            assert s["tid"] == up["tid"] == task["tid"]
            assert up["t0_ns"] <= s["t0_ns"] and s["t1_ns"] <= up["t1_ns"]
    # the rows an operator's spans carry are the rows its meter counted
    assert sum(_attr(s, "rows", 0) for s in _pulls("op:PassExec")) == \
        mid.metrics.get("output_rows") == 300


def test_no_span_on_an_operators_re_entrant_self_call(traced):
    top, mid, _leaf = _chain()
    assert sum(rb.num_rows for rb in top.arrow_batches(0)) == 300
    # arrow_batches() is metered; the execute() it calls on itself is
    # not: one `open` and one span a pull, as without the self-call
    for name in ("op:TopExec", "op:PassExec"):
        assert len(_ops(name)) == 1 + 4 and len(_pulls(name)) == 4, name
    assert mid.metrics.get("output_rows") == 300
    assert {s["parent"] for s in _pulls("op:LeafExec")} <= \
        {m["sid"] for m in _pulls("op:PassExec")}


def test_a_pull_with_tracing_off_reads_the_flag_and_nothing_else(
        monkeypatch):
    """Off, the hot path gains one module-level boolean test a pull: no
    name is built, no context manager entered, nothing of the tracer
    called."""
    assert not tracing._enabled

    def boom(*_a, **_k):
        raise AssertionError("the tracer was called with tracing off")

    for fn in ("span", "emit_span", "instant", "enabled", "_emit",
               "_check_name"):
        monkeypatch.setattr(tracing, fn, boom)
    top, _mid, leaf = _chain()
    assert sum(b.num_rows for b in top.execute(0)) == 300
    assert sum(rb.num_rows for rb in top.arrow_batches(0)) == 300
    assert leaf.metrics.get("output_rows") == 600


def test_two_prefetch_workers_of_one_name_have_two_thread_ids(traced):
    """Every map task's chain runs on a thread called
    `blaze-prefetch-shuffle_map`: the name cannot tell them apart, the
    `tid` can."""
    gate = threading.Barrier(2)

    def source(k):
        gate.wait(5)          # both workers alive inside their first item
        yield k
        gate.wait(5)
        yield k

    with tracing.span("task", mode="sync"):
        a = PrefetchIterator(source(1), depth=1, name="shuffle_map")
        b = PrefetchIterator(source(2), depth=1, name="shuffle_map")
        assert list(a) == [1, 1] and list(b) == [2, 2]
    produced = _named("produce:shuffle_map")
    assert {s["thread"] for s in produced} == {"blaze-prefetch-shuffle_map"}
    tids = {s["tid"] for s in produced}
    assert len(tids) == 2 and threading.get_ident() not in tids
    (task,) = _named("task")
    assert {s["parent"] for s in produced} == {task["sid"]}
    waits = _named("prefetch_wait")
    assert waits and {s["tid"] for s in waits} == {threading.get_ident()}


# -- the three glue sites ----------------------------------------------------

def test_coalesce_span_fires_where_batches_are_joined(traced):
    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.ops.base import CoalesceStream

    def batches(n, rows):
        return [ColumnBatch.from_arrow(pa.RecordBatch.from_arrays(
            [pa.array(np.arange(rows, dtype=np.int64))], names=["a"]))
            for _ in range(n)]

    # large batches pass through one by one: nothing is joined
    out = list(CoalesceStream(iter(batches(3, 600)), batch_size=1000))
    assert [b.num_rows for b in out] == [600, 600, 600]
    assert not _named("coalesce")
    out = list(CoalesceStream(iter(batches(7, 300)), batch_size=1000))
    assert [b.num_rows for b in out] == [1200, 900]
    assert [s["attrs"] for s in _named("coalesce")] == [
        {"batches": 4, "rows": 1200, "lane": "concat"},
        {"batches": 3, "rows": 900, "lane": "concat"}]


def test_loop_glue_spans_fire_in_the_stage_loop_and_nowhere_else(
        tmp_path, staged_loop, file_shuffle, traced):
    from blaze_tpu.plan.stages import DagScheduler
    plan, n_groups = _pair_plan(tmp_path)
    assert DagScheduler().run_collect(plan).num_rows == n_groups
    by_sid = {s["sid"]: s for s in tracing.spans()}
    windows, inits = _named("loop_window"), _named("table_init")
    chunks = _named("stage_loop_chunk")
    assert windows and len(windows) == len(chunks)
    assert [w["attrs"]["batches"] for w in windows] == \
        [c["attrs"]["batches"] for c in chunks]
    # the pulls of the source are outside the window's span, under their
    # own op:* spans
    assert not [s for s in tracing.spans()
                if s.get("parent") in {w["sid"] for w in windows}
                and s["name"].startswith("op:")]
    # no chip is pinned on one device: `device` is there and None
    assert inits and all(
        s["attrs"]["slots"] >= 2 and "device" in s["attrs"] for s in inits)
    for s in windows + inits:
        up = by_sid[s["parent"]]
        # a table is made at the first chunk's boundary, inside its span
        assert up["name"] in ("op:FusedPartialAggExec", "task",
                              "stage_loop_chunk"), up["name"]
        assert s["tid"] == up["tid"]


def test_loop_window_opens_once_a_window_and_the_counters_add_up(
        tmp_path, staged_loop, file_shuffle, traced):
    """Map tasks of 4,000 rows in batches of 512 (the last one of 416
    rows: its capacity is the others'), chunks of three: every window
    says how many batches it holds and how many of them had to be padded
    to the window's capacity first; the two counters count the windows
    and those that needed no pad."""
    from blaze_tpu.plan.stages import DagScheduler
    config.conf.set(config.BATCH_SIZE.key, 512)
    config.conf.set(config.STAGE_DEVICE_LOOP_CHUNK.key, 3)
    try:
        plan, n_groups = _pair_plan(tmp_path)
        before = xla_stats.snapshot()
        assert DagScheduler().run_collect(plan).num_rows == n_groups
        d = xla_stats.delta(before)
    finally:
        config.conf.unset(config.BATCH_SIZE.key)
        config.conf.unset(config.STAGE_DEVICE_LOOP_CHUNK.key)
    windows, chunks = _named("loop_window"), _named("stage_loop_chunk")
    assert len(windows) == len(chunks) == d["stage_loop_windows"] > 2
    assert all(set(w["attrs"]) >= {"batches", "padded"} for w in windows)
    assert [w["attrs"]["batches"] for w in windows] == \
        [c["attrs"]["batches"] for c in chunks]
    # each map task: 8 batches as 3 + 3 + 2
    assert sorted(w["attrs"]["batches"] for w in windows)[-4:] == [3] * 4
    assert 2 in [w["attrs"]["batches"] for w in windows]
    assert d["stage_loop_windows_fused"] == \
        sum(w["attrs"]["padded"] == 0 for w in windows)
    assert d["stage_loop_windows"] - d["stage_loop_windows_fused"] == \
        sum(w["attrs"]["padded"] > 0 for w in windows)
    # by chip they sum to the whole
    for k in ("stage_loop_windows", "stage_loop_windows_fused"):
        assert sum(v for name, v in d.items() if name.startswith("chip")
                   and name.endswith("_" + k)) == d[k]


def test_loop_glue_spans_stay_silent_off_the_stage_loop(tmp_path, traced):
    """The same plan on the CPU's default path (host-vectorized
    aggregation, no device loop): neither span."""
    from blaze_tpu.plan.stages import DagScheduler
    MemManager.init(4 << 30)
    plan, n_groups = _pair_plan(tmp_path)
    before = xla_stats.snapshot()
    assert DagScheduler().run_collect(plan).num_rows == n_groups
    assert xla_stats.delta(before)["stage_loop_tasks"] == 0
    assert not _named("loop_window") and not _named("table_init")
    assert _ops()       # the operators still say who held the thread


# -- readbacks through the one door ------------------------------------------

def test_a_sorts_selection_mask_is_read_back_through_to_host(
        monkeypatch, traced):
    """`_SortState._with_key_columns` on a device batch with a selection:
    no value leaves the device but inside `xputil.to_host`, so the mask's
    readback is a `d2h` span and counts in `d2h_bytes`."""
    import jax
    from jax._src import array as jarray
    import blaze_tpu.batch as batch_mod
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import MemoryScanExec, SortExec
    from blaze_tpu.ops.sort import _SortState
    monkeypatch.setattr(batch_mod, "_host_resident", lambda: False)
    cb = batch_mod.ColumnBatch.from_arrow(pa.RecordBatch.from_arrays(
        [pa.array(np.arange(1024, dtype=np.int64)[::-1])], names=["a"]))
    cb = cb.with_selection(jnp.arange(cb.capacity) % 2 == 0)
    scan = MemoryScanExec(cb.schema, [[cb]])
    sort = SortExec(scan, [(col(0), True, True)])
    state = _SortState(sort, scan.schema, sort._specs)

    inside, round_the_door = [], []
    real_get, real_value = jax.device_get, jarray.ArrayImpl._value

    def device_get(tree):
        inside.append(1)
        try:
            return real_get(tree)
        finally:
            inside.pop()

    def value(self):
        if not inside:
            round_the_door.append(self.nbytes)
        return real_value.fget(self)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(value))
    before = xla_stats.snapshot()
    rb = state._with_key_columns(cb)
    d = xla_stats.delta(before)
    assert rb.num_rows == 512
    assert not round_the_door
    # keys, payload and the selection mask twice (to_arrow's and the
    # sort's own): each a counted transfer under a span
    assert d["d2h_transfers"] == len(_named("d2h")) >= 3
    assert d["d2h_bytes"] == sum(s["attrs"]["bytes"] for s in _named("d2h"))
    assert [s["attrs"]["bytes"] for s in _named("d2h")].count(
        cb.capacity) >= 2

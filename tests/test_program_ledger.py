"""The program ledger (ISSUE 51): `bridge/xla_stats.py` keeps one record a
phase (trace, lower, backend) of every program the process asks JAX for,
from JAX's own monitoring events: name as the device trace prints it,
interval on `perf_counter_ns`, thread, kind, call site.  Totals are held to
top-level requests; while tracing is on a record is also an `xla_compile`
span.  And the tracer's three program-side defects of PERF.md section 7.
"""

import gc
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from blaze_tpu import config
from blaze_tpu.bridge import tracing, xla_stats

ME = "test_program_ledger.py:"
PHASES = ["trace", "lower", "backend"]


@pytest.fixture(autouse=True)
def fresh():
    xla_stats.reset()
    yield
    tracing.stop_tracing()
    tracing.reset_conf_probe()
    xla_stats.reset()


def top_level(records, program):
    return [r for r in records if r["program"] == program and not r["depth"]]


# -- names ----------------------------------------------------------------------

@pytest.mark.parametrize("fun_name,program", [
    ("_take", "jit__take"), ("jit(_take)", "jit__take"),
    ("<lambda>", "jit__lambda"), ("jit(<lambda>)", "jit__lambda"),
    ("jit(fold_impl__runtime_stage_loop)",
     "jit_fold_impl__runtime_stage_loop"),
    ("pmap(step)", "pmap_step"), ("scatter-add", "jit_scatter-add")])
def test_one_normalisation_gives_the_name_the_device_trace_prints(
        fun_name, program):
    assert xla_stats.loaded_program_name(fun_name) == program


@pytest.mark.parametrize("program,kind,kernel", [
    ("jit__take", "eager", None), ("jit_convert_element_type", "eager", None),
    ("jit__lambda", "eager", None),
    ("jit_fold_impl__runtime_stage_loop", "metered", "runtime_stage_loop"),
    ("jit__assemble_tiles__sort_assemble", "metered", "sort_assemble"),
    ("jit__lambda__fused_rehash", "metered", "fused_rehash")])
def test_a_name_is_metered_where_a_kernel_follows_the_function(
        program, kind, kernel):
    assert xla_stats.program_kind(program) == (kind, kernel)


def test_the_name_is_the_compiled_modules_own():
    f = xla_stats.meter_jit(lambda x: x + 1, name="ledger.named")
    text = f._blaze_jitted.lower(jnp.arange(5)).as_text()
    program = xla_stats.loaded_program_name("jit(_lambda__ledger_named)")
    assert f"module @{program} " in text


# -- one record a phase of a request ----------------------------------------------

def test_a_fresh_jit_leaves_three_records_and_a_second_call_none():
    f = xla_stats.meter_jit(lambda x: x * 3 + 1, name="ledger.fresh")
    t0 = time.perf_counter_ns()
    f(jnp.arange(1201))
    t1 = time.perf_counter_ns()
    mine = top_level(xla_stats.program_loads(), "jit__lambda__ledger_fresh")
    assert [r["phase"] for r in mine] == PHASES
    for r in mine:
        assert r["kind"] == "metered"
        assert r["tid"] == threading.get_ident()
        assert t0 <= r["t0_ns"] < r["t1_ns"] <= t1
        # no frame of blaze_tpu/ but xla_stats' wrapper stands on the
        # stack: the caller's own frame speaks
        assert r["site"].startswith(
            ME + "test_a_fresh_jit_leaves_three_records_and_a_second_")
    assert [r["t0_ns"] for r in mine] == sorted(r["t0_ns"] for r in mine)
    assert "cache_hit" in mine[2] and "cache_hit" not in mine[0]
    before = len(xla_stats.program_loads())
    snap = xla_stats.snapshot()
    f(jnp.arange(1201))
    assert len(xla_stats.program_loads()) == before
    d = xla_stats.delta(snap)
    assert not any(v for k, v in d.items() if k.startswith("program_"))
    assert xla_stats.program_loads(since_ns=t1) == []
    assert len(xla_stats.program_loads(until_ns=t1)) == before


def test_a_fresh_eager_op_on_a_worker_thread_is_recorded_there():
    seen = {}

    def work():
        seen["tid"] = threading.get_ident()
        seen["out"] = jnp.take(jnp.arange(1319), jnp.array([3, 1, 2]))

    t = threading.Thread(target=work)
    t.start()
    t.join()
    takes = top_level(xla_stats.program_loads(), "jit__take")
    assert [r["phase"] for r in takes] == PHASES
    for r in takes:
        assert r["kind"] == "eager" and r["tid"] == seen["tid"]
        assert r["tid"] != threading.get_ident()
        assert r["site"].startswith(ME + "work:")
    s = xla_stats.program_load_summary()
    assert s["requests_eager"] >= 1
    assert s["requests_metered"] == 0
    assert s["requests_eager"] == xla_stats.backend_stats()["backend_compiles"]


def test_the_call_site_is_the_innermost_frame_inside_the_program():
    from blaze_tpu.kernels import hashing as H
    h = jnp.arange(1409, dtype=jnp.int32)
    t0 = time.perf_counter_ns()
    H.pmod(h, 7)
    sites = {r["site"] for r in xla_stats.program_loads(since_ns=t0)}
    assert sites and all(s.startswith("kernels/hashing.py:pmod:")
                         for s in sites)
    # xputil.py and this file's own wrappers never speak for a caller
    from blaze_tpu.xputil import to_device
    to_device(jnp.arange(1423)) + 1
    assert not any(r["site"].startswith(("xputil.py", "bridge/xla_stats.py"))
                   for r in xla_stats.program_loads())


# -- nesting and the union ----------------------------------------------------------

def test_a_jit_traced_inside_a_jit_is_not_counted_twice():
    inner = jax.jit(lambda a: a * 2)

    def outer(a):
        return inner(a)[:2] + jnp.take(a, jnp.array([0, 1]))

    f = xla_stats.meter_jit(outer, name="ledger.nest")
    x = jnp.arange(1511)
    xla_stats.reset()
    f(x)
    records = xla_stats.program_loads()
    outer_trace, = [r for r in records if r["phase"] == "trace"
                    and r["program"] == "jit_outer__ledger_nest"]
    nested = [r for r in records if r["depth"]]
    assert outer_trace["depth"] == 0 and len(nested) >= 2
    assert {r["phase"] for r in nested} == {"trace"}
    for r in nested:   # inside the parent's interval
        assert outer_trace["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] \
            <= outer_trace["t1_ns"]
    top = [r for r in records if not r["depth"]]
    summary = xla_stats.program_load_summary()
    assert summary["trace_s"] * 1e9 == pytest.approx(sum(
        r["t1_ns"] - r["t0_ns"] for r in top if r["phase"] == "trace"))
    assert summary["requests_metered"] == 1
    assert [p["program"] for p in summary["top"]] == [
        "jit_outer__ledger_nest"]


def test_the_union_never_exceeds_the_sum_nor_the_enclosing_wall():
    t0 = time.perf_counter_ns()

    def work(n):
        jax.jit(lambda a: jnp.cumsum(a) * n)(jnp.arange(1600 + n))

    threads = [threading.Thread(target=work, args=(n,)) for n in range(1, 5)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t1 = time.perf_counter_ns()
    s = xla_stats.program_load_summary()
    by_phase = s["trace_s"] + s["lower_s"] + s["backend_s"]
    assert 0 < s["wall_s"] <= by_phase * (1 + 1e-9)
    assert s["wall_s"] <= (t1 - t0) / 1e9
    assert s["cache_retrieval_s"] <= s["backend_s"]
    assert s["requests_eager"] + s["requests_metered"] \
        == xla_stats.backend_stats()["backend_compiles"] >= 4
    # a summary of what ended before the work began is empty
    assert xla_stats.program_load_summary(until_ns=t0)["wall_s"] == 0.0
    assert xla_stats.program_load_summary(until_ns=t0)["top"] == []


def test_two_hand_made_threads_overlap_once_in_the_union(monkeypatch):
    ms = 1_000_000
    rec = dict(program="jit__take", kind="eager", site="a.py:f:1", depth=0)
    monkeypatch.setattr(xla_stats, "_program_loads", [
        dict(rec, phase="trace", t0_ns=0, t1_ns=10 * ms, tid=1),
        dict(rec, phase="backend", t0_ns=10 * ms, t1_ns=40 * ms, tid=1,
             cache_hit=True, retrieval_ns=20 * ms),
        dict(rec, phase="backend", t0_ns=30 * ms, t1_ns=60 * ms, tid=2,
             cache_hit=False, site="b.py:g:2"),
        dict(rec, phase="trace", t0_ns=2 * ms, t1_ns=4 * ms, tid=1, depth=1),
        dict(rec, phase="lower", t0_ns=90 * ms, t1_ns=95 * ms, tid=2)])
    s = xla_stats.program_load_summary(until_ns=80 * ms)
    assert s["trace_s"] == pytest.approx(0.010)       # the nested 2 ms not
    assert s["backend_s"] == pytest.approx(0.060)
    assert s["lower_s"] == 0.0                         # ended after `until`
    assert s["wall_s"] == pytest.approx(0.060)         # 0-60 ms, once
    assert s["cache_retrieval_s"] == pytest.approx(0.020)
    assert (s["requests_eager"], s["cache_hits"]) == (2, 1)
    assert [(p["site"], round(p["seconds"], 3), p["requests"])
            for p in s["top"]] == [("a.py:f:1", 0.040, 1),
                                   ("b.py:g:2", 0.030, 1)]
    assert [round(s["top"][0][k], 3) for k in
            ("trace_s", "lower_s", "backend_s")] == [0.010, 0.0, 0.030]


# -- the cap, and reset -------------------------------------------------------------

def test_the_cap_trims_the_oldest_and_counts_them(monkeypatch):
    xs = [jnp.arange(1700 + n) for n in range(3)]
    xla_stats.reset()
    monkeypatch.setattr(xla_stats, "_PROGRAM_LOADS_CAP", 4)
    for n, x in enumerate(xs):
        jax.jit(lambda a: a - n)(x)
    kept = xla_stats.program_loads()
    assert len(kept) == 4
    trimmed = xla_stats.backend_stats()["program_loads_trimmed"]
    assert trimmed >= 5 and xla_stats.program_load_summary()["trimmed"] \
        == trimmed
    # the counters are not the records: nothing of theirs is lost
    assert xla_stats.backend_stats()["backend_compiles"] == 3
    assert kept[-1]["phase"] == "backend"


def test_reset_clears_the_ledger_and_its_counters():
    jnp.take(jnp.arange(1801), jnp.array([1]))
    assert xla_stats.program_loads()
    xla_stats.reset()
    assert xla_stats.program_loads() == []
    assert not any(xla_stats.backend_stats().values())
    assert xla_stats.program_load_summary()["top"] == []


def test_the_ledger_keeps_one_counter_and_the_exported_ones_stay():
    # the totals are the summary's, from the records: no counter twins them
    assert set(xla_stats.counter_families()["backend"]) == {
        "backend_compiles", "backend_compile_ns", "compile_cache_hits",
        "program_loads_trimmed"}
    assert "backend_compile_ns" in xla_stats.snapshot()   # exported: stays


# -- the record as a span -------------------------------------------------------------

def test_with_tracing_on_one_xla_compile_span_a_phase_and_no_second_record():
    f = xla_stats.meter_jit(lambda x: x * 5, name="ledger.span")
    tracing.start_tracing()
    try:
        with tracing.span("task", mode="sync"):
            f(jnp.arange(1901))
            f(jnp.arange(1901))
    finally:
        spans = tracing.stop_tracing()
    task, = [s for s in spans if s["name"] == "task"]
    mine = [s for s in spans if s["name"] == "xla_compile"
            and s["attrs"]["program"] == "jit__lambda__ledger_span"]
    assert [s["attrs"]["phase"] for s in mine] == PHASES
    records = top_level(xla_stats.program_loads(), "jit__lambda__ledger_span")
    for s, r in zip(mine, records):
        assert s["dur_ns"] == r["t1_ns"] - r["t0_ns"] > 0   # a real interval
        assert s["attrs"]["kernel"] == "ledger_span"
        assert s["attrs"]["site"] == r["site"]
        assert s["attrs"]["source"] == "backend"
        assert s["parent"] == task["sid"] and s["tid"] == r["tid"]
    assert mine[2]["attrs"]["cache_hit"] is records[2]["cache_hit"]
    # the instant `meter_jit` used to add for the same compile is gone
    assert not [s for s in spans if s["name"] == "xla_compile"
                and s["dur_ns"] == 0]
    assert xla_stats.compile_report()["kernels"]["ledger.span"][
        "compiles"] == 1


def test_with_tracing_off_a_request_leaves_a_record_and_no_span():
    assert not tracing.enabled()
    jnp.take(jnp.arange(2003), jnp.array([4]))
    assert top_level(xla_stats.program_loads(), "jit__take")
    assert [s for s in tracing.spans() if s["name"] == "xla_compile"
            and s["attrs"].get("program") == "jit__take"
            and s["t0_ns"] >= xla_stats.program_loads()[0]["t0_ns"]] == []


def test_the_explain_footer_names_what_a_cold_query_asked_for():
    from blaze_tpu.plan.explain import MetricNode, QueryProfile
    tree = MetricNode(name="MemoryScanExec")
    cold = QueryProfile("q1", 10, tree, 1, "local", xla={}, programs=[
        {"program": "jit__take", "site": "plan/fused.py:_drain_table:1800",
         "seconds": 0.25, "requests": 2}])
    warm = QueryProfile("q2", 10, tree, 1, "local", xla={})
    line = [ln for ln in cold.render_text().split("\n")
            if ln.startswith("XLA:")][0]
    assert "jit__take@plan/fused.py:_drain_table:1800(" in line
    warm_line = [ln for ln in warm.render_text().split("\n")
                 if ln.startswith("XLA:")][0]
    assert line.startswith(warm_line) and "@" not in warm_line
    assert "programs" in cold.to_dict() and "programs" not in warm.to_dict()


def test_explain_analyze_lists_a_cold_querys_programs_and_a_warm_ones_none():
    import pyarrow as pa
    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.plan.explain import explain_analyze
    cb = ColumnBatch.from_arrow(pa.record_batch(
        [pa.array(range(2111), pa.int64())], names=["a"]))

    def profile():
        return explain_analyze(MemoryScanExec(cb.schema, [[cb]]),
                               record=False)
    jnp.take(jnp.arange(2111), jnp.array([7]))   # before the query: not its
    first = profile()
    t0 = time.perf_counter_ns()
    second = profile()
    assert len(first.programs) <= 5
    assert all(p["program"] != "jit__take" for p in first.programs)
    assert second.programs == [] == xla_stats.program_loads(since_ns=t0)


# -- the tracer's three program-side defects ---------------------------------------

def test_a_worker_childs_buffer_trim_is_counted_and_reaches_the_parent(
        monkeypatch):
    from blaze_tpu.parallel import workers
    monkeypatch.setattr(tracing, "_CHILD_BUF_CAP", 5)
    with tracing.remote_task_scope({"query": "q", "task": 1}):
        for i in range(12):
            tracing.instant("worker_heartbeat", pid=i)
        frame = {}
        workers._ship_spans(frame)
    assert [s["attrs"]["pid"] for s in frame["spans"]] == [7, 8, 9, 10, 11]
    assert frame["spans_dropped"] == 7 and frame["mono_ns"] > 0
    assert tracing.take_child_dropped() == 0       # sent once
    later = {}
    with tracing.remote_task_scope({"query": "q", "task": 1}):
        tracing.instant("worker_heartbeat", pid=99)
        workers._ship_spans(later)
    assert "spans_dropped" not in later
    before = xla_stats.obs_stats()["obs_spans_dropped"]
    tracing.start_tracing()
    try:
        tracing.ingest(frame["spans"], worker=3, clock_ns=frame["mono_ns"],
                       dropped=frame["spans_dropped"])
    finally:
        tracing.stop_tracing()
    assert xla_stats.obs_stats()["obs_spans_dropped"] == before + 7


def test_the_conf_knob_installs_the_gc_callback_as_start_tracing_does():
    assert tracing._on_gc not in gc.callbacks
    config.conf.set(config.TRACE_ENABLE.key, True)
    try:
        tracing.reset_conf_probe()
        assert tracing.enabled()
        assert gc.callbacks.count(tracing._on_gc) == 1
        with tracing.span("task", mode="sync"):
            gc.collect()
        assert [s for s in tracing.spans() if s["name"] == "gc_pause"]
        tracing.reset_conf_probe()          # takes it out again
        assert tracing._on_gc not in gc.callbacks
        assert tracing.enabled()
        assert gc.callbacks.count(tracing._on_gc) == 1
        tracing.stop_tracing()              # so does stop_tracing()
        assert tracing._on_gc not in gc.callbacks
    finally:
        config.conf.unset(config.TRACE_ENABLE.key)
        tracing.reset_conf_probe()
    assert not tracing.enabled() and tracing._on_gc not in gc.callbacks
    tracing.start_tracing()
    assert gc.callbacks.count(tracing._on_gc) == 1
    tracing.stop_tracing()
    assert tracing._on_gc not in gc.callbacks

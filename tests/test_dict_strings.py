"""Dictionary-encoded strings on the device lanes (ISSUE 20): utf8
columns ride the int lanes as int32 codes — scan-side stream encoding,
dict-keyed group-bys through the device-resident stage loop, equality /
IN-list predicates on codes, cross-batch dictionary unification — all
bit-identical to the plain utf8 host lane, with lossless degradation on
dictionary overflow and injected faults.  Knob off = byte-identical
seed behaviour."""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from blaze_tpu import config, faults
from blaze_tpu.batch import ColumnBatch, DictColumn
from blaze_tpu.bridge import xla_stats
from blaze_tpu.cache import reset_cache
from blaze_tpu.memory import MemManager
from blaze_tpu.plan.stages import DagScheduler

# the hostile key domain every sweep draws from: empty string, repeated
# keys, multi-byte utf8 (2-, 3- and 4-byte sequences), and NULLs mixed
# in by the callers
HOSTILE = ["", "a", "aa", "véhicule", "北京市", "zäh-🚀", "ключ",
           "nul\x00byte", " lead", "trail "]


@pytest.fixture(autouse=True)
def clean_slate():
    faults.clear()
    MemManager.init(4 << 30)
    reset_cache()
    try:
        yield
    finally:
        faults.clear()
        reset_cache()


@pytest.fixture
def dict_on():
    config.conf.set(config.ENCODING_DICT_ENABLE.key, True)
    try:
        yield
    finally:
        config.conf.unset(config.ENCODING_DICT_ENABLE.key)


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


def _utf8_table(n=4000, n_keys=40, seed=5, null_rate=0.06):
    rng = np.random.default_rng(seed)
    domain = HOSTILE + [f"sku-{i:04d}" for i in range(n_keys)]
    keys = [domain[i] if rng.random() > null_rate else None
            for i in rng.integers(0, len(domain), n)]
    return pa.table({"k": pa.array(keys, type=pa.string()),
                     "v": pa.array(rng.random(n))})


_UTF8_SCHEMA = {"fields": [
    {"name": "k", "type": {"id": "utf8"}, "nullable": True},
    {"name": "v", "type": {"id": "float64"}, "nullable": True}]}


def _group_by_plan(tmp_path, t, tag="", n_reduce=3):
    paths = []
    half = t.num_rows // 2
    for i in range(2):
        p = str(tmp_path / f"in{tag}-{i}.parquet")
        pq.write_table(t.slice(i * half, half), p)
        paths.append(p)
    return {
        "kind": "hash_agg",
        "groupings": [{"expr": {"kind": "column", "index": 0},
                       "name": "k"}],
        "aggs": [{"fn": "sum", "mode": "final", "name": "s",
                  "args": [{"kind": "column", "index": 1}]},
                 {"fn": "count", "mode": "final", "name": "c",
                  "args": [{"kind": "column", "index": 2}]}],
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
                          "args": [{"kind": "column", "name": "v"}]},
                         {"fn": "count", "mode": "partial", "name": "c",
                          "args": [{"kind": "column", "name": "v"}]}],
                "input": {"kind": "parquet_scan",
                          "schema": _UTF8_SCHEMA,
                          "file_groups": [[paths[0]], [paths[1]]]}}}}


def _sorted_df(tbl):
    return (tbl.to_pandas().sort_values("k", na_position="first")
            .reset_index(drop=True))


# -- scan-side encoding -----------------------------------------------------

def test_scan_decode_parity_hostile_data(tmp_path, dict_on):
    """The device-lane scan stream (execute(), where the encoder lives —
    the Arrow-resident collect path stays plain) round-trips every
    hostile utf8 value and NULL exactly through the dictionary
    encoding."""
    from blaze_tpu.bridge.context import TaskContext, task_scope
    from blaze_tpu.plan.planner import create_plan
    t = _utf8_table(n=1500, seed=9, null_rate=0.15)
    p = str(tmp_path / "scan.parquet")
    pq.write_table(t, p)
    config.conf.set(config.BATCH_SIZE.key, 256)
    try:
        pl = create_plan({"kind": "parquet_scan", "schema": _UTF8_SCHEMA,
                          "file_groups": [[p]]})
        before = xla_stats.encoding_stats()
        with task_scope(TaskContext(stage_id=0, partition_id=0)):
            batches = list(pl.execute(0))
    finally:
        config.conf.unset(config.BATCH_SIZE.key)
    after = xla_stats.encoding_stats()
    assert after["dict_encoded_columns"] > before["dict_encoded_columns"]
    assert any(isinstance(cb.columns[0], DictColumn) for cb in batches)
    got = pa.Table.from_batches([cb.to_arrow() for cb in batches])
    assert got.column("k").combine_chunks().equals(
        t.column("k").combine_chunks())
    assert got.column("v").combine_chunks().equals(
        t.column("v").combine_chunks())


def test_disabled_path_is_plain(tmp_path):
    """Knob off (the default): no column is dict-encoded anywhere and
    the encoding counters stay zero — byte-identical seed behaviour."""
    t = _utf8_table(n=500)
    before = xla_stats.encoding_stats()
    cb = ColumnBatch.from_arrow(t)
    for c in cb.columns:
        assert not isinstance(c, DictColumn)
    assert xla_stats.encoding_stats() == before


def test_stream_encoder_prefix_growth():
    """The per-stream encoder only ever APPENDS to its dictionary, so
    the last snapshot decodes every earlier batch's codes (the property
    the stage loop's drain depends on)."""
    from blaze_tpu.ops.scan import _StreamDictEncoder
    from blaze_tpu.plan.types import schema_from_dict
    schema = schema_from_dict(_UTF8_SCHEMA)
    enc = _StreamDictEncoder(schema, max_entries=1 << 16)
    t = _utf8_table(n=3000, seed=13)
    dicts = []
    for rb in t.to_batches(max_chunksize=256):
        out = enc(rb)
        assert pa.types.is_dictionary(out.column(0).type)
        dicts.append(out.column(0).dictionary)
        # decode parity per batch
        assert out.column(0).cast(pa.string()).equals(
            rb.column(0).cast(pa.string()))
    for a, b in zip(dicts, dicts[1:]):
        assert b.slice(0, len(a)).equals(a)  # prefix property


# -- group-by through the stage loop ----------------------------------------

def test_string_group_by_rides_stage_loop(tmp_path, staged_path,
                                          loop_on, dict_on):
    t = _utf8_table(n=6000)
    plan = _group_by_plan(tmp_path, t)
    config.conf.set(config.ENCODING_DICT_ENABLE.key, False)
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "off")
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-off")).run_collect(plan))
    config.conf.set(config.ENCODING_DICT_ENABLE.key, True)
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")

    before = xla_stats.snapshot()
    got = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-on")).run_collect(plan))
    d = xla_stats.delta(before)
    assert got.equals(clean)  # bit-identical, not approximately
    assert d["stage_loop_tasks"] >= 2  # both map tasks folded on codes
    assert d["stage_loop_fallbacks"] == 0
    assert d["dict_encoded_columns"] >= 1


def test_string_keys_without_dict_still_evict(tmp_path, staged_path,
                                              loop_on):
    """Knob off: utf8 group keys keep rejecting the loop, and the
    rejection is accounted as a STRING eviction (satellite 2)."""
    config.conf.set(config.ENCODING_DICT_ENABLE.key, False)
    try:
        plan = _group_by_plan(tmp_path, _utf8_table(n=2000), tag="ev")
        before = xla_stats.snapshot()
        DagScheduler(work_dir=str(tmp_path / "dag")).run_collect(plan)
        d = xla_stats.delta(before)
    finally:
        config.conf.unset(config.ENCODING_DICT_ENABLE.key)
    assert d["stage_loop_tasks"] == 0
    assert d["host_evictions_string"] >= 1


def test_dictionary_overflow_falls_back_lossless(tmp_path, staged_path,
                                                 loop_on, dict_on):
    """More distinct keys than maxEntries: the stream encoder retires
    the column mid-stream, the loop's guard falls back WHOLESALE, and
    the result is still exact."""
    rng = np.random.default_rng(3)
    n = 4000
    keys = [f"key-{i:05d}" for i in rng.integers(0, 500, n)]
    t = pa.table({"k": pa.array(keys, type=pa.string()),
                  "v": pa.array(rng.random(n))})
    plan = _group_by_plan(tmp_path, t, tag="ovf")
    config.conf.set(config.ENCODING_DICT_ENABLE.key, False)
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "off")
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-c")).run_collect(plan))
    config.conf.set(config.ENCODING_DICT_ENABLE.key, True)
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")
    config.conf.set(config.ENCODING_DICT_MAX_ENTRIES.key, 64)
    config.conf.set(config.BATCH_SIZE.key, 256)
    try:
        before = xla_stats.snapshot()
        got = _sorted_df(DagScheduler(
            work_dir=str(tmp_path / "dag-o")).run_collect(plan))
        d = xla_stats.delta(before)
    finally:
        config.conf.unset(config.ENCODING_DICT_MAX_ENTRIES.key)
        config.conf.unset(config.BATCH_SIZE.key)
    assert got.equals(clean)
    assert d["stage_loop_fallbacks"] >= 1


def test_injected_fault_mid_stream_falls_back(tmp_path, staged_path,
                                              loop_on, dict_on):
    plan = _group_by_plan(tmp_path, _utf8_table(n=4000), tag="flt")
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "off")
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-c")).run_collect(plan))
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")
    before = xla_stats.snapshot()
    with faults.scoped(("device-loop", dict(p=1.0))):
        got = _sorted_df(DagScheduler(
            work_dir=str(tmp_path / "dag-f")).run_collect(plan))
    d = xla_stats.delta(before)
    assert got.equals(clean)
    assert d["stage_loop_fallbacks"] >= 1
    assert d["stage_loop_tasks"] == 0


# -- predicates on codes ----------------------------------------------------

def _dict_batch(values, extra=None):
    arrs = {"k": pc.dictionary_encode(pa.array(values, type=pa.string()))}
    if extra is not None:
        arrs["v"] = extra
    return ColumnBatch.from_arrow(pa.table(arrs))


def _plain_batch(values, extra=None):
    arrs = {"k": pa.array(values, type=pa.string())}
    if extra is not None:
        arrs["v"] = extra
    return ColumnBatch.from_arrow(pa.table(arrs))


@pytest.mark.parametrize("needle", ["véhicule", "", "absent-key"])
def test_equality_on_codes_matches_host(needle):
    from blaze_tpu.exprs.base import Literal, col
    from blaze_tpu.exprs.binary import BinaryExpr
    from blaze_tpu.schema import UTF8
    vals = HOSTILE * 3 + [None, None]
    e = BinaryExpr("==", col(0), Literal(needle, UTF8))
    got = e.evaluate(_dict_batch(vals))
    want = e.evaluate(_plain_batch(vals))
    n = len(vals)
    assert got.to_host(n).equals(want.to_host(n))


def test_in_list_on_codes_matches_host():
    from blaze_tpu.exprs.base import col
    from blaze_tpu.exprs.conditional import InList
    vals = HOSTILE * 3 + [None]
    for members in (("véhicule", "北京市", "missing"),
                    ("a", None), ("nope",)):
        for negated in (False, True):
            e = InList(col(0), tuple(members), negated)
            got = e.evaluate(_dict_batch(vals))
            want = e.evaluate(_plain_batch(vals))
            n = len(vals)
            assert got.to_host(n).equals(want.to_host(n)), \
                (members, negated)


def test_dict_vs_dict_equality_across_dictionaries():
    """Two dict columns with DIFFERENT dictionaries must not compare
    raw codes."""
    from blaze_tpu.exprs.base import col
    from blaze_tpu.exprs.binary import BinaryExpr
    a = pa.array(["x", "y", "z", "x", None], type=pa.string())
    b = pa.array(["z", "y", "x", "x", "x"], type=pa.string())
    t_dict = pa.table({"a": pc.dictionary_encode(a),
                       "b": pc.dictionary_encode(b)})
    t_plain = pa.table({"a": a, "b": b})
    e = BinaryExpr("==", col(0), col(1))
    got = e.evaluate(ColumnBatch.from_arrow(t_dict))
    want = e.evaluate(ColumnBatch.from_arrow(t_plain))
    assert got.to_host(5).equals(want.to_host(5))


# -- concat / dictionary unification ----------------------------------------

def test_concat_unifies_disjoint_dictionaries():
    """Batches whose dictionaries DON'T share a prefix merge through the
    remap path, counted in dict_exchange_remaps."""
    t1 = pa.table({"k": pc.dictionary_encode(
        pa.array(["a", "b", "a"], type=pa.string()))})
    t2 = pa.table({"k": pc.dictionary_encode(
        pa.array(["c", "b", None, "d"], type=pa.string()))})
    b1 = ColumnBatch.from_arrow(t1)
    b2 = ColumnBatch.from_arrow(t2)
    assert isinstance(b1.columns[0], DictColumn)
    before = xla_stats.encoding_stats()["dict_exchange_remaps"]
    out = ColumnBatch.concat([b1, b2])
    assert xla_stats.encoding_stats()["dict_exchange_remaps"] > before
    got = out.to_arrow().column(0)
    assert got.cast(pa.string()).to_pylist() == \
        ["a", "b", "a", "c", "b", None, "d"]


# -- hash parity ------------------------------------------------------------

def test_decoded_codes_hash_like_raw_strings():
    """The file-exchange wire decodes codes back to utf8 before
    hashing; the decode must reproduce the exact bytes, so partition
    ids are unchanged by the encoding."""
    from blaze_tpu.kernels import hashing as H
    vals = (HOSTILE * 7)[:64] + [None] * 3
    arr = pa.array(vals, type=pa.string())
    enc = pc.dictionary_encode(arr)
    cb = ColumnBatch.from_arrow(pa.table({"k": enc}))
    decoded = cb.columns[0].to_arrow(cb.num_rows)

    def pids(a, p):
        (mat, lengths), valid = H.string_column_to_padded_bytes(a)
        return H.spark_partition_ids([((mat, lengths), valid)],
                                     ["utf8"], p, xp=np).tolist()

    for p in (3, 8):
        assert pids(arr, p) == pids(decoded, p)


# -- recompile guard + subplan cache ----------------------------------------

def test_dict_stage_zero_steady_state_recompiles(tmp_path, staged_path,
                                                 loop_on, dict_on):
    """The dict-keyed program fingerprints like any other: the first
    run builds it, every later run (same shape) reuses it with ZERO
    XLA recompiles."""
    plan = _group_by_plan(tmp_path, _utf8_table(n=4000), tag="rc")
    first = xla_stats.snapshot()
    DagScheduler(work_dir=str(tmp_path / "d0")).run_collect(plan)
    d0 = xla_stats.delta(first)
    # built on first-ever sight; an earlier test with the same shape may
    # have built it already, in which case this run is pure cache hits
    assert (d0["stage_loop_programs_built"]
            + d0["stage_loop_program_cache_hits"]) >= 1
    before = xla_stats.snapshot()
    DagScheduler(work_dir=str(tmp_path / "d1")).run_collect(plan)
    d = xla_stats.delta(before)
    assert d["stage_loop_programs_built"] == 0
    assert d["total_compiles"] == 0, \
        f"steady-state recompiles: {d['total_compiles']}"


def test_encoding_knobs_ride_program_keys(tmp_path, staged_path, loop_on):
    """Flipping the dict knob must select a DIFFERENT program (the
    fingerprint carries the encoding), never silently reuse one traced
    for the other representation."""
    from blaze_tpu.plan import stage_compiler
    from blaze_tpu.plan.column_pruning import prune_columns
    from blaze_tpu.plan.fused import fuse_plan
    from blaze_tpu.plan.planner import collapse_filter_project, create_plan
    t = _utf8_table(n=500)
    p = str(tmp_path / "fp.parquet")
    pq.write_table(t, p)
    plan = {"kind": "hash_agg",
            "groupings": [{"expr": {"kind": "column", "index": 0},
                           "name": "k"}],
            "aggs": [{"fn": "sum", "mode": "partial", "name": "s",
                      "args": [{"kind": "column", "index": 1}]}],
            "input": {"kind": "parquet_scan", "schema": _UTF8_SCHEMA,
                      "file_groups": [[p]]}}

    def compile_under(dict_enable):
        config.conf.set(config.ENCODING_DICT_ENABLE.key, dict_enable)
        try:
            agg = fuse_plan(prune_columns(collapse_filter_project(
                create_plan(plan))))
            return stage_compiler.try_compile(agg)
        finally:
            config.conf.unset(config.ENCODING_DICT_ENABLE.key)

    off = compile_under(False)
    on = compile_under(True)
    assert off is None  # utf8 keys are loop-ineligible without codes
    assert on is not None
    assert any(s is not None for s in on.dict_keys)


def test_subplan_cache_hits_dict_stage(tmp_path, staged_path, dict_on,
                                       loop_on):
    config.conf.set(config.CACHE_ENABLE.key, True)
    try:
        plan = _group_by_plan(tmp_path, _utf8_table(n=3000), tag="sc")
        before = xla_stats.cache_stats()
        r1 = DagScheduler(work_dir=str(tmp_path / "c0")).run_collect(plan)
        d1 = {k: xla_stats.cache_stats()[k] - before[k] for k in before}
        assert d1.get("subplan_cache_puts", 0) >= 1
        before = xla_stats.cache_stats()
        r2 = DagScheduler(work_dir=str(tmp_path / "c1")).run_collect(plan)
        d2 = {k: xla_stats.cache_stats()[k] - before[k] for k in before}
        assert d2.get("subplan_cache_hits", 0) >= 1
        assert _sorted_df(r2).equals(_sorted_df(r1))
    finally:
        config.conf.unset(config.CACHE_ENABLE.key)


# -- explain footer ---------------------------------------------------------

def test_explain_encodings_footer(tmp_path, staged_path, loop_on, dict_on):
    from blaze_tpu.plan.explain import format_encodings_footer
    plan = _group_by_plan(tmp_path, _utf8_table(n=1500), tag="xp")
    before = xla_stats.snapshot()
    DagScheduler(work_dir=str(tmp_path / "d")).run_collect(plan)
    footer = format_encodings_footer(xla_stats.delta(before))
    assert footer and "encodings:" in footer
    assert "dict_cols=" in footer

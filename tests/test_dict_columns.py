"""A utf8 column as a `DictColumn` (int32 codes on the chip, one host
dictionary) from where it enters a query to where a row is shown: a
dictionary's identity and order, and every station a rollup runs, coded
against plain utf8, row for row.

  1  batch.py: fingerprint, sortedness, unification, the remap on the
     device, concat;
  2  the broadcast join's string payload as code lanes;
  3  an Expand folded inside the stage loop against `ExpandExec.execute`;
  4  the exchange: partition ids by the string's own hash, IPC blocks of
     codes, two map tasks with two dictionaries unified at the reader;
  5  the resident sort and window over dictionary columns.

The device path is rehearsed as tests/test_sort_device.py does it:
`placement.host_resident` patched to false, batches jax arrays on the CPU
backend."""

import contextlib

import jax
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

import blaze_tpu.bridge.placement as P
from blaze_tpu import batch as B
from blaze_tpu import config
from blaze_tpu.batch import ColumnBatch, DeviceColumn, DictColumn
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.cache import reset_cache
from blaze_tpu.exprs import col
from blaze_tpu.memory import MemManager
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.ops.base import CoalesceStream
from blaze_tpu.ops.sort import SortExec
from blaze_tpu.ops.window import RankFunc, WindowExec, WindowRankType
from blaze_tpu.plan.stages import DagScheduler
from blaze_tpu.schema import Schema

WORDS = ["", "Books", "Home", "Music", "Shoes", "véhicule", "北京市", "a", "aa",
         "zäh-🚀", "Women"]


@pytest.fixture(autouse=True)
def clean_slate():
    MemManager.init(4 << 30)
    reset_cache()
    yield
    reset_cache()


@contextlib.contextmanager
def device_placement():
    with pytest.MonkeyPatch.context() as m:
        m.setattr(P, "host_resident", lambda: False)
        yield


@pytest.fixture
def staged_device_path():
    """Batches on the device, every plan staged, one chip's mesh."""
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    config.conf.set(config.MESH_DEVICES.key, 1)
    try:
        with device_placement():
            yield
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)
        config.conf.unset(config.MESH_DEVICES.key)


def _coded(values, dictionary):
    """A dictionary array of `values` under exactly `dictionary`."""
    d = pa.array(dictionary, type=pa.string())
    return pa.DictionaryArray.from_arrays(
        pc.index_in(pa.array(values, type=pa.string()), value_set=d)
        .cast(pa.int32()), d)


def _strings(n, seed, words=WORDS, nulls=0.1):
    rng = np.random.default_rng(seed)
    return [None if rng.random() < nulls else words[i]
            for i in rng.integers(0, len(words), n)]


def _rows(t: pa.Table):
    df = t.to_pandas()
    return df.sort_values(list(df.columns), na_position="first") \
        .reset_index(drop=True)


# -- 1. a dictionary's identity and order -----------------------------------

@pytest.mark.parametrize("values,ordered", [
    (["a", "b", "c"], True), (["b", "a"], False), ([], True), (["x"], True),
    (["a", "a"], False), (["A", "a", "é", "北"], True),
    (["", "a", "aa", "b"], True)])
def test_a_dictionary_says_whether_code_order_is_string_order(values,
                                                              ordered):
    assert B.dict_info(pa.array(values, pa.string())).sorted is ordered


def test_the_fingerprint_is_of_the_content():
    a = pa.array(["x", "b", "a", "c"]).slice(1)
    b = pa.array(["b", "a", "c"])
    assert a is not b and B.same_dictionary(a, b)
    assert B.dict_info(a).fingerprint == B.dict_info(b).fingerprint
    assert not B.same_dictionary(b, pa.array(["b", "a", "d"]))
    assert not B.same_dictionary(b, pa.array(["b", "ac", ""]))
    assert not B.same_dictionary(b, b.slice(0, 2))
    assert B.dict_info(b) is B.dict_info(b)        # read once an array


@pytest.mark.parametrize("base,other,merged,remap", [
    (["a", "b"], ["a", "b"], ["a", "b"], None),
    (["a", "b"], ["a", "b", "c"], ["a", "b", "c"], None),    # prefix growth
    (["a", "b", "c"], ["a", "b"], ["a", "b", "c"], None),    # its earlier state
    (["a", "b"], ["b", "z", "a"], ["a", "b", "z"], [1, 2, 0]),
    (["a"], ["q", "r"], ["a", "q", "r"], [1, 2]),
    (["a", "b", "c"], ["c"], ["a", "b", "c"], [2])])
def test_unification_keeps_the_bases_codes(base, other, merged, remap):
    got, lane = B.unify_dictionary(pa.array(base), pa.array(other))
    assert got.to_pylist() == merged
    assert (lane is None) == (remap is None)
    if remap is not None:
        assert lane.tolist() == remap and lane.dtype == np.int32


@pytest.mark.parametrize("placed", [False, True])
def test_concat_joins_codes_under_one_dictionary(placed):
    """Equal fingerprints: the code lanes are joined as they are.  Two
    dictionaries: unified on the host, the second batch's codes remapped
    where they lie, and counted."""
    a = _coded(["x", None, "y", "x"], ["x", "y"])
    same = _coded(["y", "y", None], ["x", "y"])
    other = _coded(["z", "x", None], ["z", "x"])
    ctx = device_placement() if placed else contextlib.nullcontext()
    with ctx:
        batches = [ColumnBatch.from_arrow(pa.table({"s": arr, "i": pa.array(
            np.arange(len(arr)))})) for arr in (a, same, other)]
        before = xla_stats.snapshot()
        one = ColumnBatch.concat(batches[:2])
        moved = xla_stats.delta(before)
        assert moved["dict_unified"] == moved["dict_remap_rows"] == 0
        assert isinstance(one.columns[0], DictColumn)
        assert isinstance(one.columns[0].data, jax.Array) == placed
        assert one.to_arrow().column(0).to_pylist() == [
            "x", None, "y", "x", "y", "y", None]
        before = xla_stats.snapshot()
        two = ColumnBatch.concat(batches)
        moved = xla_stats.delta(before)
        assert moved["dict_unified"] == 1 and moved["dict_remap_rows"] == 3
        assert two.columns[0].dictionary.to_pylist() == ["x", "y", "z"]
        assert two.to_arrow().column(0).to_pylist() == [
            "x", None, "y", "x", "y", "y", None, "z", "x", None]


def test_a_sorted_dictionary_is_built_from_strings_or_from_codes():
    values = ["m", None, "b", "m", "zz", "b"]
    plain = B.encode_sorted(pa.array(values))
    coded = B.encode_sorted(_coded(values, ["zz", "m", "b", "unused"]))
    for got in (plain, coded):
        assert got.cast(pa.string()).to_pylist() == values
        assert B.dict_info(got.dictionary).sorted
    assert plain.dictionary.to_pylist() == ["b", "m", "zz"]
    assert plain.indices.to_pylist() == [1, None, 0, 1, 2, 0]
    assert B.dict_order_ranks(pa.array(["zz", "m", "b"])).tolist() \
        == [2, 1, 0]


def test_decoding_is_counted_and_an_arrow_dictionary_array_is_not():
    cb = ColumnBatch.from_arrow(pa.table({
        "s": _coded(["x", None, "y"], ["x", "y"]), "i": pa.array([1, 2, 3])}))
    tracing.start_tracing()
    try:
        before = xla_stats.snapshot()
        kept = cb.to_arrow(keep_dict=True)
        assert xla_stats.delta(before)["dict_rows_decoded"] == 0
        plain = cb.to_arrow()
        assert xla_stats.delta(before)["dict_rows_decoded"] == 3
    finally:
        spans = tracing.stop_tracing()
    assert pa.types.is_dictionary(kept.column(0).type)
    assert kept.column(0).cast(pa.string()).to_pylist() == ["x", None, "y"]
    assert plain.column(0).to_pylist() == ["x", None, "y"]
    assert [s["attrs"]["rows"] for s in spans
            if s["name"] == "dict_decode"] == [3]
    xla_stats.reset()
    assert all(v == 0 for v in xla_stats.dict_stats().values())


# -- 2. the broadcast join's string payload ----------------------------------

def _join(build_t, probe_batches):
    from blaze_tpu.ops.joins import BroadcastJoinExec, JoinType
    probe = MemoryScanExec(Schema.from_arrow(probe_batches[0].schema),
                           [[ColumnBatch.from_arrow(b)
                             for b in probe_batches]])
    return BroadcastJoinExec(probe, MemoryScanExec.from_arrow(build_t),
                             [col(0)], [col(0)], JoinType.INNER)


def test_a_string_payload_rides_the_device_probe_as_codes():
    rng = np.random.default_rng(3)
    build_t = pa.table({
        "bk": pa.array(np.arange(100, 160)),
        "cat": pa.array([WORDS[i % len(WORDS)] for i in range(60)]),
        "name": pa.array([None if i % 9 == 0 else f"n{(i * 37) % 60:02d}"
                          for i in range(60)])})
    probes = [pa.table({
        "pk": pa.array(rng.integers(90, 170, 900)),
        "store": _coded([f"s{j}" for j in rng.integers(0, 4, 900)],
                        ["s3", "s0", "s2", "s1"])}) for _ in range(3)]
    want = _rows(pa.Table.from_batches(
        [b.to_arrow() for b in _join(build_t, probes).execute(0)]))
    with device_placement():
        plan = _join(build_t, probes)
        list(plan.execute(0))                     # the map is built once
        before = xla_stats.snapshot()
        out = list(plan.execute(0))
        moved = xla_stats.delta(before)
        assert moved["join_probe_host_rows"] == 0
        assert moved["join_probe_device_rows"] == 2700
        assert moved["dict_rows_decoded"] == 0
        assert moved["dict_rows_coded"] == 3 * sum(b.num_rows for b in out)
        for b in out:
            store, cat, name = (b.columns[i] for i in (1, 3, 4))
            assert all(isinstance(c, DictColumn)
                       and isinstance(c.data, jax.Array)
                       for c in (store, cat, name))
            # the build side's dictionaries are sorted, built once; the
            # probe side's column keeps its own
            assert B.dict_info(cat.dictionary).sorted
            assert B.dict_info(name.dictionary).sorted
            assert store.dictionary.to_pylist() == ["s3", "s0", "s2", "s1"]
        got = _rows(pa.Table.from_batches([b.to_arrow() for b in out]))
    assert got.equals(want) and len(got) > 1500


def test_the_plan_says_whether_a_join_probes_on_the_device():
    from blaze_tpu.exprs import BinaryExpr
    from blaze_tpu.ops.joins import BroadcastJoinExec, JoinType
    build = MemoryScanExec.from_arrow(pa.table({
        "bk": pa.array([1, 2]), "name": pa.array(["a", "b"])}))
    probe = MemoryScanExec.from_arrow(pa.table({
        "pk": pa.array([1, 2]), "v": pa.array([1.0, 2.0])}))

    def planned(**kw):
        kw = dict(dict(lk=[col(0)], rk=[col(0)], how=JoinType.INNER,
                       flt=None), **kw)
        return BroadcastJoinExec(probe, build, kw["lk"], kw["rk"], kw["how"],
                                 join_filter=kw["flt"]).device_probe_planned()

    assert planned()
    assert not planned(how=JoinType.LEFT)
    assert not planned(flt=BinaryExpr("<", col(0), col(2)))
    assert not planned(rk=[col(1)], lk=[col(0)])      # a utf8 key


# -- 3. an Expand inside the stage --------------------------------------------

_SALES = {"fields": [
    {"name": "cat", "type": {"id": "utf8"}, "nullable": True},
    {"name": "cls", "type": {"id": "utf8"}, "nullable": True},
    {"name": "yr", "type": {"id": "int32"}, "nullable": True},
    {"name": "v", "type": {"id": "float64"}, "nullable": True}]}


def _sales(n=6000, seed=11):
    rng = np.random.default_rng(seed)
    return pa.table({
        "cat": pa.array(_strings(n, seed, WORDS[:5])),
        "cls": pa.array(_strings(n, seed + 1, [f"c{i}" for i in range(30)])),
        "yr": pa.array(rng.integers(1998, 2003, n).astype(np.int32),
                       mask=rng.random(n) < 0.05),
        "v": pa.array(np.round(rng.random(n) * 100, 2))})


def _c(i):
    return {"kind": "column", "index": i}


def _rollup_plan(tmp_path, t, tag, files=2, n_reduce=3):
    """rollup(cat, cls, yr) of sum(v): scan -> Expand (four lists, NULL
    literals of the key's type, the grouping id) -> partial sum ->
    exchange on the four keys -> final sum."""
    paths, per = [], -(-t.num_rows // files)
    for i in range(files):
        p = str(tmp_path / f"sales{tag}-{i}.parquet")
        pq.write_table(t.slice(i * per, per), p, row_group_size=1024)
        paths.append([p])

    def null(tid):
        return {"kind": "literal", "value": None, "type": {"id": tid}}

    lists = [[_c(0) if kept > 0 else null("utf8"),
              _c(1) if kept > 1 else null("utf8"),
              _c(2) if kept > 2 else null("int32"),
              {"kind": "literal", "value": gid, "type": {"id": "int64"}},
              _c(3)] for kept, gid in ((3, 0), (2, 1), (1, 3), (0, 7))]
    names = ["cat", "cls", "yr", "gid"]

    def agg(mode, inp, arg):
        return {"kind": "hash_agg", "input": inp,
                "groupings": [{"expr": _c(i), "name": n}
                              for i, n in enumerate(names)],
                "aggs": [{"fn": "sum", "mode": mode, "name": "s",
                          "args": [_c(arg)]}]}

    partial = agg("partial", {
        "kind": "expand", "names": names + ["v"], "projections": lists,
        "input": {"kind": "parquet_scan", "schema": _SALES,
                  "file_groups": paths}}, 4)
    return agg("final", {
        "kind": "local_exchange", "input": partial,
        "partitioning": {"kind": "hash", "num_partitions": n_reduce,
                         "exprs": [_c(i) for i in range(4)]}}, 4)


def _rollup_oracle(t: pa.Table):
    df = t.to_pandas()
    frames = []
    for kept, gid in ((3, 0), (2, 1), (1, 3), (0, 7)):
        keys = ["cat", "cls", "yr"][:kept]
        g = (df.groupby(keys, dropna=False, as_index=False).v.sum()
             if keys else df[["v"]].sum().to_frame().T)
        for k in ["cat", "cls", "yr"][kept:]:
            g[k] = None
        g["gid"] = gid
        frames.append(g[["cat", "cls", "yr", "gid", "v"]])
    import pandas as pd
    return pd.concat(frames, ignore_index=True)


def _as_frame(t):
    """Rows in one order on both sides: NULL strings as "<NULL>", a NULL year
    as -1, sorted by the grouping id and the keys."""
    df = t.to_pandas() if isinstance(t, pa.Table) else t.copy()
    df.columns = ["cat", "cls", "yr", "gid", "v"]
    for k in ("cat", "cls"):
        df[k] = df[k].astype(object).where(df[k].notna(), "<NULL>")
    df["yr"] = df.yr.astype("float64").fillna(-1).astype("int64")
    df["gid"] = df.gid.astype("int64")
    df["v"] = df.v.astype("float64")
    return df.sort_values(["gid", "cat", "cls", "yr"]).reset_index(drop=True)


def test_an_expand_folds_inside_the_stage_loop(tmp_path, staged_device_path):
    """The K projection lists are evaluated inside the fold's program: the
    expanded rows are counted, never made; both aggregations are stage-loop
    tasks with five key lanes, two of them code lanes; the answer is
    `ExpandExec.execute`'s under host placement and pandas'."""
    t = _sales()
    plan = _rollup_plan(tmp_path, t, "loop")
    before = xla_stats.snapshot()
    got = DagScheduler(work_dir=str(tmp_path / "dag")).run_collect(plan)
    moved = xla_stats.delta(before)
    assert moved["expand_rows_out"] == 4 * t.num_rows
    assert moved["stage_loop_tasks"] == 5 and moved["stage_loop_fallbacks"] == 0
    assert moved["agg_eager_rows"] == 0
    assert moved["stage_loop_rows"] > moved["expand_rows_out"]
    assert moved["dict_rows_coded"] > 0
    names = xla_stats.compile_report()["kernels"]
    assert "runtime.stage_loop_fold_expand" in names
    want, got = _as_frame(_rollup_oracle(t)), _as_frame(got)
    assert len(got) == len(want)
    assert got.drop(columns="v").equals(want.drop(columns="v"))
    assert np.allclose(got.v, want.v, rtol=1e-12)
    # a NULL of the data and a NULL of the rollup stand apart by the
    # grouping id alone, as Spark's do
    null_cls = got[got.cat.eq("Books") & got.cls.eq("<NULL>") & got.yr.eq(-1)]
    assert sorted(null_cls.gid) == [1, 3]
    data_null, rolled = (null_cls[null_cls.gid == g].v.iloc[0]
                         for g in (1, 3))
    assert 0 < data_null < rolled


def test_the_stage_loops_expand_is_expand_execs(tmp_path):
    """The same plan where the loop does not run (host placement): the
    Expand stays the operator it was, and the rows are the same."""
    t = _sales(3000, seed=4)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    try:
        before = xla_stats.snapshot()
        host = DagScheduler(work_dir=str(tmp_path / "h")).run_collect(
            _rollup_plan(tmp_path, t, "host"))
        assert xla_stats.delta(before)["expand_rows_out"] == 0
        config.conf.set(config.MESH_DEVICES.key, 1)
        with device_placement():
            before = xla_stats.snapshot()
            loop = DagScheduler(work_dir=str(tmp_path / "l")).run_collect(
                _rollup_plan(tmp_path, t, "dev"))
            assert xla_stats.delta(before)["expand_rows_out"] == 4 * 3000
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)
        config.conf.unset(config.MESH_DEVICES.key)
    a, b = _as_frame(host), _as_frame(loop)
    assert a.drop(columns="v").equals(b.drop(columns="v"))
    assert np.allclose(a.v, b.v, rtol=1e-12)


def test_a_fallen_back_expand_stage_runs_the_unfused_aggregation(
        tmp_path, staged_device_path, monkeypatch):
    """Whatever the loop cannot take falls to `ExpandExec.execute` under
    the eager aggregation, which stays correct."""
    from blaze_tpu.runtime import loop as device_loop

    def declined(*a, **kw):
        raise device_loop.StageLoopFallback("declined for the test")
        yield

    monkeypatch.setattr(device_loop, "execute_loop", declined)
    t = _sales(2000, seed=9)
    before = xla_stats.snapshot()
    got = DagScheduler(work_dir=str(tmp_path / "dag")).run_collect(
        _rollup_plan(tmp_path, t, "fb"))
    moved = xla_stats.delta(before)
    assert moved["stage_loop_fallbacks"] > 0 and moved["agg_eager_rows"] > 0
    want, got = _as_frame(_rollup_oracle(t)), _as_frame(got)
    assert got.drop(columns="v").equals(want.drop(columns="v"))
    assert np.allclose(got.v, want.v, rtol=1e-12)


# -- 4. the exchange carries codes -----------------------------------------------

@pytest.mark.parametrize("placed", [False, True])
@pytest.mark.parametrize("keys", [("s",), ("i", "s"), ("s", "t", "i")])
def test_a_coded_and_a_plain_batch_go_to_the_same_partitions(placed, keys):
    """The partition id of a string key is the string's own murmur3 hash,
    chained as Spark chains it, whether the batch holds strings or codes."""
    from blaze_tpu.shuffle.partitioning import HashPartitioning
    n = 700
    s, t = _strings(n, 1), _strings(n, 2, ["p", "q", "", "rrrrrrrrr"])
    i = pa.array(np.random.default_rng(3).integers(0, 50, n))
    plain = pa.table({"s": pa.array(s), "t": pa.array(t), "i": i})
    coded = pa.table({"s": _coded(s, WORDS[::-1]),
                      "t": _coded(t, ["q", "rrrrrrrrr", "p", ""]), "i": i})
    part = HashPartitioning([col(plain.column_names.index(k))
                             for k in keys], 7)
    want = part.partition_ids(ColumnBatch.from_arrow(plain))
    with device_placement() if placed else contextlib.nullcontext():
        before = xla_stats.snapshot()
        got = part.partition_ids(ColumnBatch.from_arrow(coded))
        assert xla_stats.delta(before)["dict_rows_decoded"] == 0
    assert np.array_equal(got, want) and len(set(want.tolist())) > 3


def test_an_ipc_block_holds_codes_and_the_reader_hands_them_on(tmp_path):
    from blaze_tpu.shuffle.partitioning import HashPartitioning
    from blaze_tpu.shuffle.reader import FileSegmentBlock, read_block
    from blaze_tpu.shuffle.writer import ShuffleRepartitioner
    s = _strings(500, 8)
    cb = ColumnBatch.from_arrow(pa.table({
        "s": _coded(s, WORDS), "v": pa.array(np.arange(500.0))}))
    rep = ShuffleRepartitioner(HashPartitioning([col(0)], 3), cb.schema)
    before = xla_stats.snapshot()
    rep.insert_batch(cb)
    data, index = str(tmp_path / "m.data"), str(tmp_path / "m.index")
    lengths = rep.write(data, index)
    moved = xla_stats.delta(before)
    assert moved["dict_rows_decoded"] == 0
    assert moved["dict_rows_coded"] == 500
    rows, offset = [], 0
    for length in lengths:
        for rb in read_block(FileSegmentBlock(data, offset, length)):
            assert pa.types.is_dictionary(rb.column(0).type)
            rows += rb.column(0).cast(pa.string()).to_pylist()
        offset += length
    assert sorted(rows, key=str) == sorted(s, key=str)


def _two_dictionary_tables(n=12000):
    """Two files whose scans meet their keys in different orders, so each
    map task's encoder builds another dictionary."""
    rng = np.random.default_rng(21)
    domain = WORDS + [f"sku-{i:03d}" for i in range(900)]
    halves = []
    for words in (domain, domain[::-1][5:] + ["only-in-the-second"]):
        halves.append(pa.table({
            "k": pa.array([None if rng.random() < 0.05 else words[i]
                           for i in rng.integers(0, len(words), n // 2)]),
            "v": pa.array(np.round(rng.random(n // 2) * 10, 2))}))
    return halves


def _sum_by_key_plan(tmp_path, halves, tag):
    from tests.test_dict_strings import _group_by_plan
    return _group_by_plan(tmp_path, pa.concat_tables(halves), tag=tag)


def test_two_map_tasks_two_dictionaries_one_answer(tmp_path,
                                                   staged_device_path):
    """Each map task hands on its own dictionary; a reduce task's reader
    unifies them on the host and remaps the second's codes on the device;
    the final fold's keys are code lanes; the rows are the plain run's."""
    halves = _two_dictionary_tables()
    # tiles of 128 rows: a reduce task reads several, the later ones under
    # the second map task's dictionary alone
    config.conf.set(config.BATCH_SIZE.key, 128)
    config.conf.set(config.ENCODING_DICT_ENABLE.key, False)
    try:
        plain = DagScheduler(work_dir=str(tmp_path / "p")).run_collect(
            _sum_by_key_plan(tmp_path, halves, "plain"))
        config.conf.unset(config.ENCODING_DICT_ENABLE.key)
        before = xla_stats.snapshot()
        coded = DagScheduler(work_dir=str(tmp_path / "c")).run_collect(
            _sum_by_key_plan(tmp_path, halves, "coded"))
        moved = xla_stats.delta(before)
    finally:
        config.conf.unset(config.ENCODING_DICT_ENABLE.key)
        config.conf.unset(config.BATCH_SIZE.key)
    assert moved["stage_loop_tasks"] == 5 and moved["stage_loop_fallbacks"] == 0
    assert moved["dict_unified"] >= 3          # once a reduce task at least
    assert moved["dict_remap_rows"] > 0
    a, b = (t.to_pandas().sort_values("k", na_position="first")
            .reset_index(drop=True) for t in (plain, coded))
    assert a.k.fillna("<NULL>").tolist() == b.k.fillna("<NULL>").tolist()
    assert np.allclose(a.s, b.s, rtol=1e-12) and a.c.equals(b.c)
    assert "only-in-the-second" in set(b.k) and len(b) > 900


def _sum_over_union_plan(tmp_path, halves, tag):
    """A PARTIAL sum by `k` straight over a Union of two scans (the shape of
    the goldens' q71 / q75 / q76), an exchange on `k`, the final sum."""
    from tests.test_dict_strings import _UTF8_SCHEMA, _group_by_plan
    plan = _group_by_plan(tmp_path, pa.concat_tables(halves), tag=tag)
    scans = []
    for i, t in enumerate(halves):
        path = str(tmp_path / f"union{tag}-{i}.parquet")
        pq.write_table(t, path, row_group_size=512)
        scans.append({"kind": "parquet_scan", "schema": _UTF8_SCHEMA,
                      "file_groups": [[path]]})
    plan["input"]["input"]["input"] = {"kind": "union", "inputs": scans}
    return plan


def test_a_fold_over_a_union_holds_its_keys_under_one_dictionary(
        tmp_path, staged_device_path):
    """Each child of a Union has its own encoder, so ONE map task's fold is
    handed batches under two unrelated dictionaries: code 3 of the first is
    not code 3 of the second.  The fold's source unifies them on the host
    and remaps the later child's codes on the device before they reach the
    table; the rows are the plain run's, with the stage loop on."""
    halves = _two_dictionary_tables()
    config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, "on")
    config.conf.set(config.ENCODING_DICT_ENABLE.key, False)
    try:
        plain = DagScheduler(work_dir=str(tmp_path / "p")).run_collect(
            _sum_over_union_plan(tmp_path, halves, "plain"))
        config.conf.unset(config.ENCODING_DICT_ENABLE.key)
        before = xla_stats.snapshot()
        coded = DagScheduler(work_dir=str(tmp_path / "c")).run_collect(
            _sum_over_union_plan(tmp_path, halves, "coded"))
        moved = xla_stats.delta(before)
    finally:
        config.conf.unset(config.ENCODING_DICT_ENABLE.key)
        config.conf.unset(config.STAGE_DEVICE_LOOP_ENABLE.key)
    # the map task and the three reduce tasks all folded code lanes
    assert moved["stage_loop_tasks"] == 4 and moved["stage_loop_fallbacks"] == 0
    assert moved["dict_unified"] >= 1 and moved["dict_remap_rows"] > 0
    a, b = (t.to_pandas().sort_values("k", na_position="first")
            .reset_index(drop=True) for t in (plain, coded))
    assert a.k.fillna("<NULL>").tolist() == b.k.fillna("<NULL>").tolist()
    assert np.allclose(a.s, b.s, rtol=1e-12) and a.c.equals(b.c)
    assert "only-in-the-second" in set(b.k) and len(b) > 900


# -- 5. the resident sort and window ------------------------------------------------

def _scan(table: pa.Table, cuts):
    batches, at = [], 0
    for n in cuts:
        batches.append(ColumnBatch.from_arrow(table.slice(at, n)))
        at += n
    return MemoryScanExec(Schema.from_arrow(table.schema), [batches])


def _collect(plan) -> pa.Table:
    return pa.Table.from_batches([b.compact().to_arrow()
                                  for b in plan.execute(0)],
                                 schema=plan.schema.to_arrow())


def _sort_table(dictionary, n=3000):
    s = _strings(n, 6)
    return pa.table({"name": _coded(s, dictionary),
                     "rid": pa.array(np.arange(n)),
                     "v": pa.array(np.random.default_rng(2).random(n))})


@pytest.mark.parametrize("dictionary", [sorted(WORDS, key=lambda w:
                                               w.encode()), WORDS[::-1]],
                         ids=["sorted", "unsorted"])
@pytest.mark.parametrize("desc,first", [(False, True), (True, False)])
def test_a_string_key_sorts_on_the_chip_in_string_order(dictionary, desc,
                                                        first):
    """A sorted dictionary's codes are the order key; an unsorted one's go
    through their ranks.  Either way the order is the host lane's over the
    strings, ties in arrival order."""
    table = _sort_table(dictionary)
    specs = [(col(0), desc, first)]
    plain = table.set_column(0, "name", table.column(0).cast(pa.string()))
    want = _collect(SortExec(_scan(plain, [1500, 1500]), specs))
    with device_placement():
        before = xla_stats.snapshot()
        out = list(SortExec(_scan(table, [700, 1, 1299, 1000]),
                            specs).execute(0))
        moved = xla_stats.delta(before)
        assert moved["sort_resident_rows"] == 3000
        assert moved["dict_rows_decoded"] == 0
        assert len(out) == 1 and isinstance(out[0].columns[0], DictColumn)
        got = out[0].to_arrow()
    assert got.column("rid").equals(want.column("rid").chunk(0))
    assert got.column("name").to_pylist() == want.column("name").to_pylist()


def test_tiles_of_two_dictionaries_sort_under_one():
    s = _strings(2400, 13)
    first = pa.table({"name": _coded(s[:1200], WORDS),
                      "rid": pa.array(np.arange(1200))})
    second = pa.table({"name": _coded(s[1200:], WORDS[::-1]),
                       "rid": pa.array(np.arange(1200, 2400))})
    want = np.array(sorted(range(2400), key=lambda i: (
        s[i] is not None, (s[i] or "").encode(), i)))
    with device_placement():
        scan = MemoryScanExec(Schema.from_arrow(first.schema), [[
            ColumnBatch.from_arrow(first), ColumnBatch.from_arrow(second)]])
        before = xla_stats.snapshot()
        out, = list(SortExec(scan, [(col(0), False, True)]).execute(0))
        moved = xla_stats.delta(before)
        got = out.to_arrow()
    assert moved["sort_resident_rows"] == 2400
    assert moved["dict_unified"] == 1 and moved["dict_remap_rows"] == 1200
    assert got.column("rid").to_numpy().tolist() == want.tolist()


@pytest.mark.parametrize("dictionary", [sorted(WORDS, key=lambda w:
                                               w.encode()), WORDS[::-1]],
                         ids=["sorted", "unsorted"])
def test_rank_over_a_string_partition_key(dictionary):
    """sort (name ASC NULLS FIRST, v DESC) -> rank() over (partition by
    name order by v desc): the partition key compares by code, NULL is a
    partition and comes first; the answer is the host lane's."""
    table = _sort_table(dictionary)
    specs = [(col(0), False, True), (col(2), True, False)]

    def ranked(scan):
        return WindowExec(SortExec(scan, specs),
                          [RankFunc("rk", WindowRankType.RANK)],
                          [col(0)], [(col(2), True, False)])

    plain = table.set_column(0, "name", table.column(0).cast(pa.string()))
    want = _collect(ranked(_scan(plain, [3000])))
    with device_placement():
        before = xla_stats.snapshot()
        out = list(ranked(_scan(table, [1000, 2000])).execute(0))
        moved = xla_stats.delta(before)
        assert moved["window_resident_rows"] == moved["window_rows"] == 3000
        assert moved["sort_resident_rows"] == 3000
        assert moved["dict_rows_decoded"] == 0
        assert isinstance(out[0].columns[0], DictColumn)
        got = out[0].to_arrow()
    assert got.column("rid").equals(want.column("rid").chunk(0))
    assert got.column("rk").equals(want.column("rk").chunk(0))
    assert got.column("name").to_pylist()[0] is None
    assert max(got.column("rk").to_pylist()) > 100


# -- the coalescer's tile lane -----------------------------------------------------

def test_small_coded_batches_are_laid_into_tiles_under_one_dictionary():
    """Batches of one dictionary are laid end to end as the int32 lanes
    they are; a batch under another dictionary sends the rows held on as
    they are and starts a tile of its own."""
    with device_placement():
        def batch(words, n, seed):
            s = _strings(n, seed, words, nulls=0.0)
            cb = ColumnBatch.from_arrow(pa.table({
                "s": _coded(s, words), "i": pa.array(np.arange(n))}))
            assert isinstance(cb.columns[0].data, jax.Array)
            return cb, s

        parts = [batch(WORDS, 300, 1), batch(WORDS, 200, 2),
                 batch(WORDS[::-1], 100, 3)]
        before = xla_stats.snapshot()
        out = list(CoalesceStream(iter(b for b, _ in parts),
                                  batch_size=4096))
        moved = xla_stats.delta(before)
        assert moved["coalesce_tiled_rows"] == 600
        assert moved["coalesce_concat_rows"] == 0
        assert [b.num_rows for b in out] == [500, 100]
        assert all(isinstance(b.columns[0], DictColumn) for b in out)
        got = [v for b in out for v in b.to_arrow().column(0).to_pylist()]
    assert got == [v for _, s in parts for v in s]

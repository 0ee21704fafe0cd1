"""`CoalesceStream`'s tile lane (ops/base.py `_TileLane`, kernels/tiles.py
`lay_tile`) against `ColumnBatch.concat`, row for row and validity for
validity.

Batches here are what a device probe or a compacted filter leaves: rows
packed to the front of jax arrays, and behind them whatever the program
that packed them left (valid lanes of -1), which no emitted batch may show.
The CPU backend runs the programs; `placement.host_resident` needs no patch
because the lane is chosen from the batch's own column kinds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu import config
from blaze_tpu.batch import (ColumnBatch, DeviceColumn, DictColumn,
                             HostColumn, bucket_capacity)
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.kernels import sort as ksort
from blaze_tpu.kernels import tiles as ktiles
from blaze_tpu.ops import base as ops_base
from blaze_tpu.ops.base import CoalesceStream
from blaze_tpu.schema import DataType, Field, Schema, TypeId

TARGET = config.BATCH_SIZE.get()
KINDS = [("i32", TypeId.INT32), ("i64", TypeId.INT64), ("f64", TypeId.FLOAT64),
         ("flag", TypeId.BOOL), ("day", TypeId.DATE32)]
SCHEMA = Schema([Field(name, DataType(tid)) for name, tid in KINDS])


def _values(dtype: DataType, rng, n: int) -> np.ndarray:
    if dtype.id == TypeId.BOOL:
        return rng.random(n) < 0.5
    if dtype.id == TypeId.FLOAT64:
        return rng.normal(size=n)
    return rng.integers(1, 1 << 20, n).astype(dtype.np_dtype())


def packed(rng, n: int, width: int, schema: Schema = SCHEMA) -> ColumnBatch:
    """`n` rows (a tenth of each column NULL) at the front of `width` lanes,
    the lanes behind them valid and -1 (True in a bool column)."""
    cols = []
    for f in schema:
        data = np.full(width, -1).astype(f.data_type.np_dtype())
        data[:n] = _values(f.data_type, rng, n)
        valid = np.ones(width, bool)
        valid[:n] = rng.random(n) >= 0.1
        cols.append(DeviceColumn(f.data_type, jnp.asarray(data),
                                 jnp.asarray(valid)))
    return ColumnBatch(schema, cols, n, None)


def rows_of(batches):
    """[(data, validity)] a column over the batches' rows, end to end."""
    out = []
    for i in range(len(batches[0].columns)):
        out.append((
            np.concatenate([np.asarray(b.columns[i].data)[:b.num_rows]
                            for b in batches]),
            np.concatenate([np.asarray(b.columns[i].validity)[:b.num_rows]
                            for b in batches])))
    return out


def assert_rows_are_concats(out, batches):
    """`out`'s rows, end to end, are `ColumnBatch.concat(batches)`'s: value
    for value (under a NULL too) and validity for validity."""
    want = ColumnBatch.concat([b for b in batches if b.num_rows])
    for (gd, gv), (wd, wv) in zip(rows_of(out), rows_of([want])):
        assert gd.dtype == wd.dtype
        assert np.array_equal(gv, wv)
        assert np.array_equal(gd, wd)


def assert_clean(batch: ColumnBatch):
    """Behind its rows a batch reads 0 and not valid, as `concat`'s does."""
    assert batch.selection is None
    for c in batch.columns:
        assert c.capacity == batch.capacity
        assert not np.asarray(c.data)[batch.num_rows:].any()
        assert not np.asarray(c.validity)[batch.num_rows:].any()


def lay_calls() -> int:
    return xla_stats.compile_report()["kernels"].get(
        "coalesce.lay", {"calls": 0})["calls"]


def stream_counts(rng, width: int):
    """Row counts of a stream: none, one, the most a first batch may have
    and be held, full width, ragged."""
    pool = [0, 1, TARGET // 2 - 1, width, width - 1, 3, 129]
    each = 6 * (32768 // width)
    counts = [int(min(c, width)) for c in rng.choice(pool, each)]
    counts += [int(c) for c in rng.integers(1, width, each)]
    rng.shuffle(counts)
    first = min(TARGET // 2 - 1, width)
    return [first] + counts        # held from the first batch on


# -- rows, order, shapes -------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("width", [8192, 32768])
def test_the_rows_are_concats_and_every_batch_but_the_last_is_one_tile(
        width, seed):
    rng = np.random.default_rng(seed)
    batches = [packed(rng, n, width) for n in stream_counts(rng, width)]
    total = sum(b.num_rows for b in batches)
    assert total > 2 * TARGET
    before = xla_stats.snapshot()
    out = list(CoalesceStream(iter(batches)))
    d = xla_stats.delta(before)
    assert_rows_are_concats(out, batches)
    assert [b.num_rows for b in out[:-1]] == [TARGET] * (total // TARGET)
    assert {b.capacity for b in out} == {TARGET}     # the tail at the tile's
    assert 0 < out[-1].num_rows <= TARGET
    for b in out:
        assert_clean(b)
        assert b.schema is SCHEMA
        assert all(type(c) is DeviceColumn and isinstance(c.data, jax.Array)
                   for c in b.columns)
    assert d["coalesce_tiled_rows"] == d["chip0_coalesce_tiled_rows"] == total
    assert d["coalesce_concat_rows"] == 0


@pytest.mark.parametrize("counts", [[1], [100, 28], [129], [5000, 3000, 192],
                                    [TARGET // 2 - 1, 1],
                                    [TARGET // 2 - 1, TARGET // 2]])
def test_a_stream_under_one_tile_leaves_at_its_rows_bucket(counts):
    rng = np.random.default_rng(len(counts))
    batches = [packed(rng, n, 8192 if n <= 8192 else 32768) for n in counts]
    (out,) = list(CoalesceStream(iter(batches)))
    total = sum(counts)
    assert out.num_rows == total < TARGET
    assert out.capacity == bucket_capacity(total) \
        == ColumnBatch.concat(batches).capacity
    assert_clean(out)
    assert_rows_are_concats([out], batches)


def test_a_full_tile_to_the_row_leaves_nothing_held():
    rng = np.random.default_rng(3)
    big = packed(rng, 20000, 32768)
    batches = [packed(rng, 10000, 32768), packed(rng, TARGET - 10000, 32768),
               big, packed(rng, 5, 8192)]
    out = list(CoalesceStream(iter(batches)))
    assert [b.num_rows for b in out] == [TARGET, 20000, 5]
    assert out[1] is big                   # nothing was held: it passes whole
    assert out[2].capacity == TARGET       # a tile has left: the tail at its
    assert_clean(out[2])


def test_a_batch_wider_than_a_tile_leaves_as_several():
    """A stream whose batch size shrank under it (the degradation ladder):
    one lay of 3,000 rows behind 100 makes three tiles of 1,024."""
    rng = np.random.default_rng(4)
    batches = [packed(rng, 100, 4096), packed(rng, 3000, 4096),
               packed(rng, 7, 4096)]
    out = list(CoalesceStream(iter(batches), batch_size=1024))
    assert [b.num_rows for b in out] == [1024, 1024, 1024, 35]
    assert {b.capacity for b in out} == {1024}
    assert_rows_are_concats(out, batches)
    for b in out:
        assert_clean(b)


def test_a_batch_size_between_buckets_rides_the_next_bucket():
    """Tiles of exactly 1,000 rows at 1,024 lanes, clean behind them."""
    rng = np.random.default_rng(14)
    batches = [packed(rng, n, 1024) for n in (400, 499, 450, 1024, 3, 300)]
    out = list(CoalesceStream(iter(batches), batch_size=1000))
    assert [b.num_rows for b in out] == [1000, 1000, 676]
    assert {b.capacity for b in out} == {1024}
    assert_rows_are_concats(out, batches)
    for b in out:
        assert_clean(b)


def test_the_batch_size_may_change_under_the_rows_held(monkeypatch):
    """The rows held leave as they are (as `concat` would have emitted them
    with the next batch) and the lane goes on at the new size."""
    sizes = iter([4096, 4096, 1024, 1024, 1024])
    monkeypatch.setattr(ops_base, "effective_batch_size",
                        lambda base=None: next(sizes))
    rng = np.random.default_rng(5)
    batches = [packed(rng, n, 4096) for n in (1500, 1500, 10, 20, 1000)]
    out = list(CoalesceStream(iter(batches)))
    assert [b.num_rows for b in out] == [3000, 1024, 6]
    assert [b.capacity for b in out] == [4096, 1024, 1024]
    assert_rows_are_concats(out, batches)
    for b in out:
        assert_clean(b)


def test_a_batch_with_a_selection_is_compacted_and_laid():
    rng = np.random.default_rng(6)
    first, second = packed(rng, 8000, 8192), packed(rng, 8192, 8192)
    keep = rng.random(8192) < 0.7          # dense enough not to be compacted
    second = second.with_selection(jnp.asarray(keep))
    before = xla_stats.snapshot()
    (out,) = list(CoalesceStream(iter([first, second])))
    assert out.num_rows == 8000 + int(keep.sum())
    assert_rows_are_concats([out], [first, second])
    assert xla_stats.delta(before)["coalesce_tiled_rows"] == out.num_rows


# -- what takes the parent's path -----------------------------------------------

def _with_host_column(rng, n):
    b = packed(rng, n, 8192)
    text = pa.array([f"r{i}" for i in range(n)], type=pa.string())
    return ColumnBatch(
        Schema(list(SCHEMA) + [Field("text", DataType(TypeId.UTF8))]),
        b.columns + [HostColumn(DataType(TypeId.UTF8), text)], n, None)


def _with_dict_column(rng, n):
    b = packed(rng, n, 8192)
    codes = DictColumn.from_codes(
        rng.integers(0, 3, n), None, DataType(TypeId.UTF8), 8192,
        pa.array(["a", "b", "c"]))
    return ColumnBatch(
        Schema(list(SCHEMA) + [Field("text", DataType(TypeId.UTF8))]),
        b.columns + [codes], n, None)


def _numpy_resident(rng, n):
    b = packed(rng, n, 8192)
    return ColumnBatch(SCHEMA, [
        DeviceColumn(c.dtype, np.asarray(c.data), np.asarray(c.validity))
        for c in b.columns], n, None)


@pytest.mark.parametrize("make", [_with_host_column, _with_dict_column,
                                  _numpy_resident])
def test_a_batch_the_program_does_not_take_goes_through_concat(make):
    rng = np.random.default_rng(7)
    batches = [make(rng, n) for n in (3000, 1, 4000)]
    laid, before = lay_calls(), xla_stats.snapshot()
    tracing.start_tracing()
    try:
        (out,) = list(CoalesceStream(iter(batches)))
    finally:
        spans = tracing.stop_tracing()
    d = xla_stats.delta(before)
    want = ColumnBatch.concat(batches)
    assert out.to_arrow().equals(want.to_arrow())
    assert out.capacity == want.capacity == bucket_capacity(7001)
    assert [type(c) for c in out.columns] == [type(c) for c in want.columns]
    assert lay_calls() == laid
    assert d["coalesce_concat_rows"] == 7001 and d["coalesce_tiled_rows"] == 0
    assert [s["attrs"] for s in spans if s["name"] == "coalesce"] == [
        {"batches": 3, "rows": 7001, "lane": "concat"}]


@pytest.mark.parametrize("n", [TARGET // 2, TARGET - 1, TARGET])
def test_a_big_batch_with_nothing_held_passes_whole(n):
    rng = np.random.default_rng(8)
    batches = [packed(rng, n, 32768) for _ in range(3)]
    laid, before = lay_calls(), xla_stats.snapshot()
    out = list(CoalesceStream(iter(batches)))
    assert len(out) == 3 and all(a is b for a, b in zip(out, batches))
    d = xla_stats.delta(before)
    assert lay_calls() == laid
    assert d["coalesce_tiled_rows"] == d["coalesce_concat_rows"] == 0


def test_rows_held_on_the_chip_go_on_through_concat_in_arrival_order():
    """A numpy-resident batch behind rows the tile lane holds: they leave
    the lane as one batch and `concat` joins it with what follows."""
    rng = np.random.default_rng(9)
    batches = [packed(rng, 2000, 8192), packed(rng, 3000, 8192),
               _numpy_resident(rng, 100), packed(rng, 50, 8192),
               packed(rng, 30000, 32768), packed(rng, 70, 8192)]
    before = xla_stats.snapshot()
    out = list(CoalesceStream(iter(batches)))
    d = xla_stats.delta(before)
    assert [b.num_rows for b in out] == [35150, 70]
    assert_rows_are_concats(out, batches)
    assert d["coalesce_concat_rows"] == 35150
    assert d["coalesce_tiled_rows"] == 70
    assert d["coalesce_tiled_rows"] + d["coalesce_concat_rows"] \
        == sum(b.num_rows for b in batches)


# -- programs and counters ------------------------------------------------------

def test_fifty_row_counts_are_two_programs():
    """The offsets and the counts are runtime scalars: a stream's first lay
    and its later ones are the two signatures there are, whatever `n` and
    however many batches a lay takes."""
    schema = Schema([Field("a", DataType(TypeId.INT16)),
                     Field("b", DataType(TypeId.FLOAT32))])
    rng = np.random.default_rng(10)
    counts = [int(n) for n in rng.permutation(np.arange(1, 1025))[:50]]
    assert len(set(counts)) == 50

    def compiles():
        k = xla_stats.compile_report()["kernels"]
        return sum(k.get(name, {"compiles": 0})["compiles"]
                   for name in ("coalesce.lay", "coalesce.tail"))

    start, laid = compiles(), lay_calls()
    batches = [packed(rng, n, 1024, schema) for n in counts]
    out = list(CoalesceStream(iter(batches), batch_size=2048))
    assert sum(b.num_rows for b in out) == sum(counts) > 4 * 2048
    assert 13 <= lay_calls() - laid < 50     # a lay takes up to four batches
    assert compiles() - start == 2
    again = [packed(rng, n, 1024, schema) for n in reversed(counts)]
    list(CoalesceStream(iter(again), batch_size=2048))
    assert compiles() - start == 2


def test_the_counters_add_up_to_the_rows_rebatched_and_reset_to_zero():
    rng = np.random.default_rng(11)
    whole = packed(rng, 20000, 32768)              # passes: in neither
    tiled = [packed(rng, n, 8192) for n in (4000, 8000, 8192, 8192, 8000)]
    other = [_numpy_resident(rng, n) for n in (500, 600)]
    before = xla_stats.snapshot()
    out = list(CoalesceStream(iter([whole] + tiled)))
    out += list(CoalesceStream(iter(other)))
    d = xla_stats.delta(before)
    assert d["coalesce_tiled_rows"] == sum(b.num_rows for b in tiled)
    assert d["coalesce_concat_rows"] == 1100
    assert sum(b.num_rows for b in out) - 20000 \
        == d["coalesce_tiled_rows"] + d["coalesce_concat_rows"]
    chip = xla_stats.chip_stats()[0]
    assert chip["coalesce_tiled_rows"] >= d["coalesce_tiled_rows"]
    assert chip["coalesce_concat_rows"] >= 1100
    xla_stats.reset()
    assert xla_stats.pipeline_stats()["coalesce_tiled_rows"] == 0
    assert xla_stats.pipeline_stats()["coalesce_concat_rows"] == 0
    assert xla_stats.chip_stats() == {}


def test_a_lay_takes_the_batches_that_fill_a_tile_inside_one_coalesce_span():
    rng = np.random.default_rng(12)
    counts = (9000, 9000, 9000, 9000, 100, 200, 300, 400, 500, 600, 700)
    batches = [packed(rng, n, 32768) for n in counts]
    tracing.start_tracing()
    try:
        out = list(CoalesceStream(iter(batches)))
    finally:
        spans = tracing.stop_tracing()
    assert [b.num_rows for b in out] == [TARGET, sum(counts) - TARGET]
    lays = [s for s in spans if s["name"] == "coalesce"]
    assert [s["attrs"] for s in lays] == [
        {"batches": 4, "rows": 36000, "lane": "tile"},   # a tile's rows
        {"batches": 4, "rows": 1000, "lane": "tile"},    # as many as a lay takes
        {"batches": 3, "rows": 1800, "lane": "tile"}]    # the stream's end
    compiled = [s for s in spans if s["name"] == "xla_compile"
                and s.get("attrs", {}).get("kernel", "").startswith("coalesce")]
    assert all(any(l["t0_ns"] <= c["t0_ns"] <= l["t1_ns"] for l in lays)
               for c in compiled)


def test_a_small_streams_tail_is_narrowed_inside_a_span_of_its_own():
    rng = np.random.default_rng(13)
    batches = [packed(rng, n, 8192) for n in (500, 600, 700)]
    tracing.start_tracing()
    try:
        (out,) = list(CoalesceStream(iter(batches)))
    finally:
        spans = tracing.stop_tracing()
    assert out.capacity == 2048
    assert [s["attrs"] for s in spans if s["name"] == "coalesce"] == [
        {"batches": 3, "rows": 1800, "lane": "tile"},
        {"batches": 0, "rows": 1800, "lane": "tile"}]


def test_the_programs_names_and_the_sorts_text():
    """`jit__lay_tile__coalesce_lay` / `jit__narrow_tile__coalesce_tail`;
    and `SortExec`'s assembly, whose body the lane shares, lowers to the
    text of the parent's function word for word."""
    from blaze_tpu.bridge.xla_stats import meter_jit
    assert ktiles.lay_tile._blaze_jitted.__name__ == "_lay_tile__coalesce_lay"
    assert ktiles.narrow_tile._blaze_jitted.__name__ \
        == "_narrow_tile__coalesce_tail"

    def _assemble_tiles(tiles, rows, cap: int):
        width = tiles[0][0][0].shape[0]
        starts = jnp.cumsum(rows) - rows
        total = starts[-1] + rows[-1]

        def laid(*parts):
            buf = jnp.zeros((cap + width,), parts[0].dtype)
            for at, part in zip(starts, parts):
                buf = jax.lax.dynamic_update_slice(buf, part, (at,))
            return buf[:cap]

        cols = jax.tree_util.tree_map(laid, *tiles)
        live = jnp.arange(cap, dtype=jnp.int32) < total
        return tuple((jnp.where(live, d, jnp.zeros_like(d)), v & live)
                     for d, v in cols), total

    parents = meter_jit(_assemble_tiles, name="sort.assemble",
                        static_argnames=("cap",))
    tile = ((jnp.zeros(4096, jnp.int64), jnp.zeros(4096, bool)),
            (jnp.zeros(4096, jnp.float64), jnp.zeros(4096, bool)))
    args = ((tile,) * 4, np.zeros(4, np.int32))

    def text(fn):
        # the location table names the file a line came from: not the program
        return "\n".join(
            line for line in fn._blaze_jitted.lower(*args, cap=8192)
            .as_text().splitlines() if not line.startswith("#loc"))

    mine = text(ksort.assemble_tiles)
    assert mine == text(parents)
    assert "module @jit__assemble_tiles__sort_assemble" in mine

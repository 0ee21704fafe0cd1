"""Shuffle tests: partitioning parity, .data/.index contract, exchange,
two-stage agg through a real shuffle (the spark-local analog, SURVEY.md §4).
"""

import io
import os
import struct

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu import schema as S
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.exprs import col
from blaze_tpu.memory import MemManager
from blaze_tpu.ops import AggExec, AggMode, MemoryScanExec, make_agg
from blaze_tpu.shuffle import (FileSegmentBlock, HashPartitioning,
                               IpcReaderExec, LocalShuffleExchange,
                               RangePartitioning, RoundRobinPartitioning,
                               ShuffleWriterExec, SinglePartitioning,
                               read_index_file, sample_range_bounds)
from blaze_tpu.bridge.resource import put_resource


@pytest.fixture(autouse=True)
def budget():
    MemManager.init(4 << 30)


def test_hash_partition_ids_match_spark_pmod():
    """pmod(murmur3(seed42), n) — golden values from Spark's
    Murmur3_x86_32 via the validated hash kernels (tests/test_hashing.py)."""
    t = pa.table({"k": pa.array([1, 2, 3, 4, 5], type=pa.int64())})
    cb = ColumnBatch.from_arrow(t)
    p = HashPartitioning([col(0)], 4)
    ids = p.partition_ids(cb)
    from blaze_tpu.kernels import hashing as H
    import numpy as np
    want = H.pmod(H.hash_columns(
        [(np.array([1, 2, 3, 4, 5], dtype=np.int64), None, "int64")],
        seed=42, xp=np, algo="murmur3"), 4, xp=np)
    assert ids.tolist() == want.tolist()


def test_round_robin_spreads():
    t = pa.table({"k": pa.array(range(10))})
    p = RoundRobinPartitioning(3)
    cb = ColumnBatch.from_arrow(t)
    ids = p.partition_ids(cb)
    counts = np.bincount(ids, minlength=3)
    assert counts.max() - counts.min() <= 1
    # second batch continues the cursor
    ids2 = p.partition_ids(cb)
    assert ids2[0] == (ids[-1] + 1) % 3


def test_shuffle_writer_data_index_contract(tmp_path):
    rng = np.random.default_rng(0)
    n = 5000
    t = pa.table({"k": pa.array(rng.integers(0, 1000, n)),
                  "v": pa.array(rng.random(n))})
    scan = MemoryScanExec.from_arrow(t, batch_rows=512)
    data = str(tmp_path / "out.data")
    index = str(tmp_path / "out.index")
    w = ShuffleWriterExec(scan, HashPartitioning([col(0)], 8), data, index)
    list(w.execute(0))
    offsets = read_index_file(index)
    assert len(offsets) == 9
    assert offsets[0] == 0
    assert offsets[-1] == os.path.getsize(data)
    # read every partition back through file segments; total rows must match
    total = 0
    seen_keys = set()
    for p in range(8):
        put_resource("t1", [FileSegmentBlock(data, offsets[p],
                                             offsets[p + 1] - offsets[p])])
        reader = IpcReaderExec("t1", S.Schema.from_arrow(t.schema))
        got = reader.execute_collect().to_arrow()
        total += got.num_rows
        seen_keys.update(got.column("k").to_pylist())
    assert total == n
    assert seen_keys == set(t.column("k").to_pylist())


def test_shuffle_writer_spill(tmp_path):
    rng = np.random.default_rng(1)
    n = 40000
    t = pa.table({"k": pa.array(rng.integers(0, 100, n)),
                  "v": pa.array(rng.random(n))})
    MemManager.init(200_000)
    scan = MemoryScanExec.from_arrow(t, batch_rows=4096)
    data = str(tmp_path / "s.data")
    index = str(tmp_path / "s.index")
    w = ShuffleWriterExec(scan, HashPartitioning([col(0)], 4), data, index)
    list(w.execute(0))
    assert w.metrics.get("spill_count") >= 1
    offsets = read_index_file(index)
    total = 0
    for p in range(4):
        put_resource("t2", [FileSegmentBlock(data, offsets[p],
                                             offsets[p + 1] - offsets[p])])
        got = IpcReaderExec("t2", S.Schema.from_arrow(t.schema)) \
            .execute_collect()
        total += got.num_rows
    assert total == n


def test_two_stage_agg_through_exchange():
    """Partial agg -> hash exchange on keys -> final agg == pandas."""
    rng = np.random.default_rng(2)
    n = 30000
    t = pa.table({"k": pa.array(rng.integers(0, 200, n)),
                  "v": pa.array(rng.random(n))})
    scan = MemoryScanExec.from_arrow(t, num_partitions=4, batch_rows=1024)
    schema = S.Schema.from_arrow(t.schema)
    partial = AggExec(scan, [(col(0, "k"), "k")],
                      [(make_agg("sum", [col(1)]), AggMode.PARTIAL, "s"),
                       (make_agg("count", [col(1)]), AggMode.PARTIAL, "c")])
    exchange = LocalShuffleExchange(partial, HashPartitioning([col(0)], 3))
    final = AggExec(exchange, [(col(0, "k"), "k")],
                    [(make_agg("sum", [col(1)]), AggMode.PARTIAL_MERGE, "s"),
                     (make_agg("sum", [col(2)]), AggMode.PARTIAL_MERGE, "c")])
    got = final.execute_collect().to_arrow()
    want = t.to_pandas().groupby("k").agg(s=("v", "sum"), c=("v", "count"))
    assert got.num_rows == len(want)
    gd = dict(zip(got.column("k").to_pylist(), got.column("s.sum").to_pylist()))
    cd = dict(zip(got.column("k").to_pylist(), got.column("c.sum").to_pylist()))
    for k, row in want.iterrows():
        assert gd[k] == pytest.approx(row.s)
        assert cd[k] == row.c
    exchange.cleanup()


def test_range_partitioning_with_sampled_bounds():
    rng = np.random.default_rng(3)
    n = 10000
    t = pa.table({"k": pa.array(rng.integers(0, 10000, n))})
    specs = [(col(0, "k"), False, True)]
    bounds = sample_range_bounds(t, specs, 4, ["k"])
    assert bounds.num_rows == 3
    p = RangePartitioning(specs, 4, bounds)
    cb = ColumnBatch.from_arrow(t)
    ids = p.partition_ids(cb)
    ks = np.asarray(t.column("k"))
    # ranges must be ordered: max of partition p <= min of partition p+1
    for a in range(3):
        if (ids == a).any() and (ids == a + 1).any():
            assert ks[ids == a].max() <= ks[ids == a + 1].min()
    # roughly balanced
    counts = np.bincount(ids, minlength=4)
    assert counts.min() > n // 10


@pytest.mark.parametrize("n_parts", [9, 40],
                         ids=["counting_sort", "argsort"])
@pytest.mark.parametrize("n", [1, 777, 4096, 5000, 0],
                         ids=["1", "777", "4096", "5000", "empty"])
def test_writer_partition_order_is_stable(tmp_path, n, n_parts):
    """Rows leave the writer grouped by partition and, within one, in
    the order they arrived: np.argsort(kind="stable") with its starts
    and ends, on both of the writer's grouping branches.  No row at all
    is an empty .data and a length of 0 for every partition."""
    from blaze_tpu.shuffle import IpcCompressionReader
    from blaze_tpu.shuffle.writer import ShuffleRepartitioner
    rng = np.random.default_rng(n)
    pids = rng.integers(0, n_parts, n).astype(np.int32)
    rep = ShuffleRepartitioner(RoundRobinPartitioning(n_parts), None)
    if n:  # the insert paths never stage an empty batch
        rep._stage(pa.RecordBatch.from_arrays(
            [pa.array(pids), pa.array(np.arange(n, dtype=np.int64))],
            names=["__pid", "row"]))
    data, index = str(tmp_path / "o.data"), str(tmp_path / "o.index")
    lengths = rep.write(data, index)
    offsets = read_index_file(index)
    assert len(offsets) == n_parts + 1
    assert offsets[-1] == os.path.getsize(data) == sum(lengths)
    ref = np.argsort(pids, kind="stable")
    starts = np.searchsorted(pids[ref], np.arange(n_parts), "left")
    ends = np.searchsorted(pids[ref], np.arange(n_parts), "right")
    with open(data, "rb") as f:
        blob = f.read()
    for p in range(n_parts):
        seg = blob[offsets[p]:offsets[p + 1]]
        rows = [r for rb in IpcCompressionReader(io.BytesIO(seg))
                .read_batches() for r in rb.column(0).to_pylist()]
        assert rows == ref[starts[p]:ends[p]].tolist()


def test_single_partitioning_roundtrip(tmp_path):
    t = pa.table({"a": pa.array([1, 2, 3])})
    scan = MemoryScanExec.from_arrow(t)
    data, index = str(tmp_path / "x.data"), str(tmp_path / "x.index")
    w = ShuffleWriterExec(scan, SinglePartitioning(), data, index)
    list(w.execute(0))
    offsets = read_index_file(index)
    put_resource("t3", [FileSegmentBlock(data, 0, offsets[1])])
    got = IpcReaderExec("t3", S.Schema.from_arrow(t.schema)).execute_collect()
    assert got.to_arrow().column(0).to_pylist() == [1, 2, 3]


# -- ISSUE 39: the reader re-tiles in Arrow before it places anything -------

def _ipc_blocks(table, blocks):
    """One IPC block a list of piece lengths, cut from `table` in order."""
    from blaze_tpu.shuffle.ipc import write_batches_to_bytes
    out, off = [], 0
    for pieces in blocks:
        starts = np.cumsum([off] + pieces)
        out.append(write_batches_to_bytes(
            table.slice(int(s), n).to_batches()[0]
            for s, n in zip(starts, pieces)))
        off = int(starts[-1])
    return out


# blocks as lists of piece lengths; the query's capacity_shrink; the
# selected rows a batch of what `execute` yields
_TILE_CASES = {
    # the SF10 pair's reduce partition: two hot map tasks' blocks
    "two_blocks_with_tails": (
        [[32768, 32768, 6200]] * 2, 0, [32768] * 4 + [12400]),
    "one_batch_over_the_tile": ([[40000]], 0, [40000]),
    # the four-chip exchange's one block a reduce task
    "one_large_batch": ([[720000]], 0, [720000]),
    "large_batch_between_tails": (
        [[100], [40000], [100]], 0, [100, 40000, 100]),
    "small_blocks_are_one_tail": ([[100]] * 3, 0, [300]),
    # the tail is a slice of the second block's piece, at an offset
    "tail_is_a_view": ([[20000]] * 2, 0, [32768, 7232]),
    "exact_tiles_leave_no_tail": ([[32768], [16384, 16384]], 0, [32768] * 2),
    "empty_partition": ([], 0, []),
    "degraded_query_tiles_at_half": (
        [[10000] * 5, [4000]], 1, [16384] * 3 + [4848]),
}


@pytest.mark.parametrize("case", list(_TILE_CASES))
def test_ipc_reader_hands_the_chip_batches_of_one_tile(case, monkeypatch):
    from blaze_tpu import batch as batch_mod
    from blaze_tpu.batch import bucket_capacity
    from blaze_tpu.bridge.context import query_scope
    from blaze_tpu.serving import QueryContext
    blocks, shrink, want = _TILE_CASES[case]
    n = sum(sum(b) for b in blocks)
    rng = np.random.default_rng(39)
    v = rng.random(n)
    t = pa.table({"k": pa.array(np.arange(n, dtype=np.int64)),
                  "v": pa.array(v, mask=v < 0.1),
                  "s": pa.array(rng.integers(0, 9, n)).cast(pa.string())})
    put_resource(f"tile-{case}", _ipc_blocks(t, blocks))
    reader = IpcReaderExec(f"tile-{case}", S.Schema.from_arrow(t.schema))
    pieces = [p for b in blocks for p in b]
    # Arrow-resident consumers read the blocks' pieces as they were written
    assert [rb.num_rows for rb in reader.arrow_batches(0)] == pieces
    q = QueryContext(f"tile-{case}")
    for _ in range(shrink + 1 if shrink else 0):
        q.degrade()  # rung 1 is agg pass-through; from rung 2 on halving
    assert q.capacity_shrink == shrink
    # device placement: a batch's capacity is its bucket on the ladder
    monkeypatch.setattr(batch_mod, "_host_resident", lambda: False)
    with query_scope(q):
        got = list(reader.execute(0))
    tile = 32768 >> shrink
    assert [b.selected_count() for b in got] == want
    assert [b.capacity for b in got] == [bucket_capacity(r) for r in want]
    if max(pieces, default=0) <= tile:
        # nothing was over the tile, so nothing is placed above it and
        # every batch but the tail is full
        assert all(b.capacity <= tile for b in got)
        assert all(r == tile for r in want[:-1])
    if got:
        back = pa.Table.from_batches([b.to_arrow() for b in got])
        assert back.equals(t)  # the rows, in the input's order

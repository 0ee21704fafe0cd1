"""Shuffle read + collect operators.

Parity: ipc_reader_exec.rs:47 (pulls BlockObjects registered by the engine's
reader in the resource map — file segments / byte buffers / channels,
:277-359), ipc_writer_exec.rs (collect-to-driver IPC stream), and
ffi_reader_exec.rs (row-to-columnar input imported over Arrow FFI; here the
in-process analog imports an iterator of Arrow batches).
"""

from __future__ import annotations

import io
import itertools
from dataclasses import dataclass
from typing import (Any, BinaryIO, Callable, Iterable, Iterator, List,
                    NamedTuple, Optional, Union)

import pyarrow as pa

from blaze_tpu import faults
from blaze_tpu.batch import (ColumnBatch, DictStream, one_schema,
                             plain_columns)
from blaze_tpu.bridge import xla_stats
from blaze_tpu.bridge.resource import get_resource
from blaze_tpu.faults import (FetchFailedError, InjectedFault,
                              ShuffleChecksumError)
from blaze_tpu.ops.base import (BatchIterator, ExecutionPlan, _TileLane,
                                effective_batch_size)
from blaze_tpu.schema import Schema
from blaze_tpu.shuffle.ipc import IpcCompressionReader, IpcCompressionWriter


@dataclass
class FileSegmentBlock:
    """(path, offset, length) — the FileSegment fast path
    (ref ipc_reader_exec.rs:277).  stage_id/map_id carry the writing
    map task's lineage so a corrupted/truncated segment can be traced
    back to — and re-produced by — exactly that task."""

    path: str
    offset: int
    length: int
    stage_id: int = -1
    map_id: int = -1


class ResidentRun(NamedTuple):
    """`rows` rows of a batch that lies on the chip, from lane `start` on:
    one reduce partition's share of one map batch (`batch` is the writer's
    `ResidentBatch`)."""

    batch: Any
    start: int
    rows: int

    def to_arrow(self) -> pa.RecordBatch:
        return self.batch.to_arrow().slice(self.start, self.rows)


@dataclass
class ResidentBlock:
    """Reduce partition `partition`'s rows of ONE map task's output where
    it lies on the chip (shuffle/writer.py `ResidentMapOutput`): a run of
    every batch the task committed, in the task's batch order.  `batches`
    is None where the output was let go: reading that names the map task,
    as a lost file does."""

    batches: Optional[list]
    partition: int
    stage_id: int = -1
    map_id: int = -1

    def runs(self) -> Iterator[ResidentRun]:
        if self.batches is None:
            raise EOFError("the resident map output was released")
        for b in self.batches:
            start, end = (int(b.offsets[self.partition]),
                          int(b.offsets[self.partition + 1]))
            if end > start:
                yield ResidentRun(b, start, end - start)


Block = Union[FileSegmentBlock, ResidentBlock, bytes, BinaryIO]


def read_block(block: Block) -> Iterator[Union[pa.RecordBatch, ResidentRun]]:
    if isinstance(block, (FileSegmentBlock, ResidentBlock)):
        resident = isinstance(block, ResidentBlock)
        where = (f"resident:{block.stage_id}/{block.map_id}"
                 f"#{block.partition}" if resident else
                 f"{block.path}@{block.offset}+{block.length}")
        if not resident and block.length == 0:
            return
        try:
            faults.maybe_fail("shuffle-read", path=where)
            yield from block.runs() if resident else _read_segment(block)
        except (ShuffleChecksumError, EOFError, OSError,
                InjectedFault) as e:
            # the Spark FetchFailed contract: a block that cannot be
            # read back intact (bit rot, truncation, lost file, injected
            # fetch failure) names its producer so the DAG scheduler can
            # re-run just that map task instead of failing the query
            xla_stats.note_fetch_failure()
            raise FetchFailedError(block.stage_id, block.map_id,
                                   f"{where}: {e}") from e
    elif isinstance(block, (bytes, bytearray, memoryview)):
        yield from IpcCompressionReader(io.BytesIO(block)).read_batches()
    else:  # file-like channel
        yield from IpcCompressionReader(block).read_batches()


def _read_segment(block: FileSegmentBlock) -> Iterator[pa.RecordBatch]:
    # mmap fast path: raw frames decode zero-copy against the page
    # cache (the FileSegment mmap read of ipc_reader_exec.rs:277);
    # the pa.py_buffer keeps the mapping alive as long as any batch
    # references it
    buf = None
    try:
        import mmap
        with open(block.path, "rb") as f:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        buf = pa.py_buffer(mm).slice(block.offset, block.length)
    except (OSError, ValueError):
        buf = None  # exotic FS / zero-length mapping: buffered path
    if buf is not None:
        # decode OUTSIDE the fallback guard: a mid-stream decode
        # error must propagate, not restart the block and hand
        # duplicate batches downstream
        from blaze_tpu.shuffle.ipc import read_frames_from_buffer
        yield from read_frames_from_buffer(buf)
        return
    with open(block.path, "rb") as f:
        f.seek(block.offset)
        yield from IpcCompressionReader(f, limit=block.length).read_batches()


def _tiles(pieces: Iterable[pa.RecordBatch]) -> Iterator[pa.RecordBatch]:
    """The blocks' record batches, in stream order, joined and cut into
    batches of exactly `effective_batch_size()` rows and one tail.  The
    writer cuts each (map, partition) block into pieces of the batch size
    and a short tail, so pieces as they come would pair a tail with the
    next block's full piece: one batch over the tile, placed at twice its
    capacity.  A piece that alone holds more than the tile (the mesh
    exchange's one block a reduce task) is passed whole.  Slices are
    views; a join copies on the host."""
    staged: List[pa.RecordBatch] = []
    rows = 0
    for rb in pieces:
        # asked anew for every piece, so a mid-query degradation rung
        # takes effect at the next boundary: what is staged over a shrunk
        # tile goes first
        tile = effective_batch_size()
        n = rb.num_rows
        if staged and (n > tile or rows >= tile):
            yield _join(staged)
            staged, rows = [], 0
        if n > tile:
            yield rb
            continue
        take = min(n, tile - rows)
        if take:
            staged.append(rb.slice(0, take))
            rows += take
        if rows == tile:
            yield _join(staged)
            staged, rows = [], 0
            if take < n:
                staged, rows = [rb.slice(take)], n - take
    if staged:
        yield _join(staged)


def _join(staged: List[pa.RecordBatch]) -> pa.RecordBatch:
    # one map task's pieces may carry a column as codes and the next
    # one's as plain strings (its scan's encoder hit its cap)
    return staged[0] if len(staged) == 1 \
        else pa.concat_batches(one_schema(staged))


class IpcReaderExec(ExecutionPlan):
    """Reads shuffle blocks for this partition from the resource map.

    The resource value is either an iterator/list of Blocks, or a callable
    `partition -> iterable of Blocks` (the per-reduce-task registration
    pattern of AuronBlockStoreShuffleReaderBase.scala:29-66).
    """

    def __init__(self, resource_id: str, schema: Schema,
                 num_partitions: int = 1):
        super().__init__()
        self.resource_id = resource_id
        self._schema = schema
        self._num_partitions = num_partitions

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return self._num_partitions

    def execute(self, partition: int) -> BatchIterator:
        # blocks of several map tasks bring each its own dictionaries: the
        # task's batches leave under one a column (`batch.DictStream`)
        stream = DictStream()
        for batch in self._tile_batches(partition):
            batch = stream.under_one_dictionary(batch)
            if stream.dicts:
                xla_stats.note_dict(
                    dict_rows_coded=batch.num_rows * len(stream.dicts))
            yield batch

    def _tile_batches(self, partition: int) -> BatchIterator:
        """The blocks' rows, in block order, as batches of exactly
        `effective_batch_size()` rows and a tail.  Record batches (a file
        segment's, a channel's) are re-tiled in Arrow, on the host, BEFORE
        anything is placed, so they reach the chip at the tile's capacity
        and no device program joins them.  Runs that lie on the chip (a
        resident block's) are laid end to end there by the copy-only tile
        program (ops/base.py `_TileLane`, kernels/tiles.py `lay_runs`) and
        never leave it.  Where a list mixes the kinds (a recovered or
        spilled map output among resident ones) each stretch of one kind
        ends in its own tail."""
        pieces = itertools.groupby(
            self._block_batches(partition),
            key=lambda piece: isinstance(piece, ResidentRun))
        for resident, stretch in pieces:
            if not resident:
                for rb in _tiles(stretch):
                    yield ColumnBatch.from_arrow(rb)
                continue
            lane = _TileLane(runs=True)
            for run in stretch:
                # asked anew for every run, as `_tiles` asks
                yield from lane.lay(run.batch.batch, effective_batch_size(),
                                    start=run.start, rows=run.rows)
            if lane.rows:
                yield lane.tail()

    def arrow_batches(self, partition: int):
        """Arrow-resident read: decoded IPC frames go straight to
        Arrow-resident consumers (the reduce-side host agg) without a
        ColumnBatch round trip, a dictionary-encoded column as the plain
        strings those consumers take.  Segment reads + IPC decode run on
        the prefetch worker so reduce-side compute overlaps them
        (kill-switch auron.tpu.io.prefetch)."""
        for rb in self._block_batches(partition):
            if isinstance(rb, ResidentRun):
                rb = rb.to_arrow()   # same rows, same order, read back
            if any(pa.types.is_dictionary(f.type) for f in rb.schema):
                rb = pa.RecordBatch.from_arrays(plain_columns(rb.columns),
                                                names=rb.schema.names)
            yield rb

    def _block_batches(self, partition: int):
        from blaze_tpu.ops.base import prefetch
        return prefetch(self._read_blocks(partition), name="ipc_reader")

    def _read_blocks(self, partition: int):
        from blaze_tpu.bridge.context import current_task
        source = get_resource(self.resource_id)
        if source is None:
            raise KeyError(f"shuffle resource {self.resource_id!r} not found")
        blocks = source(partition) if callable(source) else source
        ctx = current_task()
        for block in blocks:
            # per-block cancellation point: a cancelled query stops
            # fetching mid-shuffle instead of draining every segment
            ctx.check_running()
            for rb in read_block(block):
                if not isinstance(rb, ResidentRun):
                    self.metrics.add("io_bytes", rb.nbytes)
                yield rb


class IpcWriterExec(ExecutionPlan):
    """Writes the child stream as framed IPC into a host sink — the
    collect()-to-driver path (ref ipc_writer_exec.rs)."""

    def __init__(self, child: ExecutionPlan,
                 sink_factory: Callable[[int], BinaryIO]):
        super().__init__([child])
        self._sink_factory = sink_factory

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int) -> BatchIterator:
        sink = self._sink_factory(partition)
        w = IpcCompressionWriter(sink)
        for batch in self.children[0].execute(partition):
            rb = batch.compact().to_arrow()
            if rb.num_rows:
                w.write_batch(rb)
                self.metrics.add("output_rows", rb.num_rows)
                self.metrics.add("io_bytes", rb.nbytes)
        w.finish()
        return iter(())


class FFIReaderExec(ExecutionPlan):
    """Imports host-exported Arrow batches (the ConvertToNative path,
    ref ffi_reader_exec.rs; in-process, 'FFI' is a zero-copy handoff of
    pyarrow batches through the resource map)."""

    def __init__(self, resource_id: str, schema: Schema,
                 num_partitions: int = 1):
        super().__init__()
        self.resource_id = resource_id
        self._schema = schema
        self._num_partitions = num_partitions

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return self._num_partitions

    def execute(self, partition: int) -> BatchIterator:
        source = get_resource(self.resource_id)
        if source is None:
            raise KeyError(f"ffi resource {self.resource_id!r} not found")
        batches = source(partition) if callable(source) else source
        for rb in batches:
            yield ColumnBatch.from_arrow(rb)

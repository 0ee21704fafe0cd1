"""Shuffle write: staged repartitioning + .data/.index files.

Parity: shuffle_writer_exec.rs + shuffle/sort_repartitioner.rs:44
(SortShuffleRepartitioner: BufferedData stages batches, radix-sorts rows by
partition id, writes per-partition framed compressed IPC runs with offsets,
spills under memory pressure and merges spills at shuffle_write;
buffered_data.rs:48) and the file contract consumed by the JVM
(.data + little-endian u64 cumulative-offset .index,
ref AuronShuffleWriterBase.scala:46-85).

TPU-first: partition ids compute ON DEVICE (murmur3+pmod inside the jit'd
stage), then rows group by pid via the same device sort-by-key machinery as
aggregation; the host writes per-partition frames.  Spill files hold the
same per-partition framed layout with an in-memory offset table, so the
final merge is pure sequential IO per partition (no decode).

The resident lane: where the scheduler hands the task a sink
(`RESIDENT_SINK`, plan/stages.py chooses the tier) and every column of
every batch is carried on the chip, a batch is laid partition-major where
it lies (kernels/tiles.py `partition_tile`) and the task commits a
`ResidentMapOutput` in place of files.  The files stay its spill target
and the lane of every other case.
"""

from __future__ import annotations

import io
import os
import re
import struct
import tempfile
import threading
from dataclasses import dataclass, field, replace
from typing import BinaryIO, Callable, Iterator, List, Optional, Sequence, \
    Tuple

import jax
import numpy as np
import pyarrow as pa

from blaze_tpu import config
from blaze_tpu.batch import ColumnBatch, DeviceColumn, one_schema
from blaze_tpu.bridge import xla_stats
from blaze_tpu.bridge.context import current_task
from blaze_tpu.bridge.resource import get_resource
from blaze_tpu.kernels.tiles import partition_tile
from blaze_tpu.memory import MemConsumer, MemManager
from blaze_tpu.ops.base import BatchIterator, ExecutionPlan
from blaze_tpu.schema import Schema, TypeId
from blaze_tpu.shuffle.ipc import IpcCompressionWriter
from blaze_tpu.shuffle.partitioning import Partitioning
from blaze_tpu.shuffle.reader import FileSegmentBlock, ResidentBlock
from blaze_tpu.xputil import to_host

#: resource-map key, before a map task's `.data` path, of the sink the task
#: commits a resident output to: `sink(output) -> bool`, first wins.  The
#: TaskDefinition carries the path and nothing else
RESIDENT_SINK = "exchange-sink://"

# the most reduce partitions the resident lane takes: `partition_tile` ranks
# a row inside its partition by counting, one prefix sum a partition
_RESIDENT_PARTS = 256


#: attempt-suffixed index sidecar: `<base>.a<N>.index` — the speculative
#: execution naming scheme (plan/stages.py _map_task_def allocates the
#: attempt ids; un-suffixed paths take the legacy single-attempt commit)
_ATTEMPT_INDEX_RE = re.compile(r"^(?P<base>.+)\.a(?P<attempt>\d+)\.index$")


def promote_attempt_output(data_file: str, index_file: str
                           ) -> Optional[bool]:
    """First-wins commit arbitration for attempt-suffixed shuffle output.

    Every attempt writes its own private `<base>.a<N>.data/.index` pair,
    so concurrent attempts never race on file CONTENT — only on who gets
    to be the committed output.  The arbitration is a claim file created
    with O_EXCL (atomic on POSIX and on the FUSE/object-store mounts
    that lack hard links) recording the winning attempt id, followed by
    ONE os.replace of the winner's index to the canonical `<base>.index`
    path.  A losing attempt deletes its own files, so a cancelled or
    raced loser can never be read.  Readers resolve the winner through
    the claim (resolve_attempt_data) and the single canonical index.

    Returns True when this attempt won, False when a sibling already
    committed (the loser's output is discarded), None when the paths are
    not attempt-suffixed (speculation off: the caller's tmp+os.replace
    discipline already committed atomically and nothing changes)."""
    m = _ATTEMPT_INDEX_RE.match(index_file)
    if m is None:
        return None
    attempt = int(m.group("attempt"))
    final_index = m.group("base") + ".index"
    claim = final_index + ".owner"
    won = False
    try:
        fd = os.open(claim, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o644)
        try:
            os.write(fd, str(attempt).encode())
        finally:
            os.close(fd)
        won = True
    except FileExistsError:
        # a sibling claimed first; an identical-attempt re-commit (task
        # retry after the result frame was lost) is still the winner
        try:
            with open(claim) as f:
                won = int(f.read().strip() or "-1") == attempt
        except (OSError, ValueError):
            won = False
    from blaze_tpu.bridge import xla_stats
    if won:
        if not os.path.exists(index_file):
            # idempotent re-commit after the first promotion already
            # moved this attempt's index to the canonical path (task
            # retry of the winner after a lost result frame)
            return True
        if os.path.exists(final_index):
            # the claim is supposed to make this impossible; count it so
            # the speculation soak's duplicate_output_blocks check sees
            # any double-accept instead of silently overwriting
            xla_stats.note_speculation(duplicate_commits=1)
        os.replace(index_file, final_index)
        return True
    for p in (index_file, data_file):
        try:
            os.unlink(p)
        except OSError:
            pass
    xla_stats.note_speculation(loser_commits_rejected=1)
    return False


def resolve_attempt_data(data_file: str) -> Tuple[str, int]:
    """Map a canonical `<base>.data` path to the committed attempt's
    actual data file.  Returns (path, attempt): the claim file written
    by promote_attempt_output names the winner; without one the legacy
    un-suffixed path is the single attempt (attempt 0)."""
    base = data_file[:-len(".data")]
    claim = base + ".index.owner"
    try:
        with open(claim) as f:
            attempt = int(f.read().strip())
    except (OSError, ValueError):
        return data_file, 0
    return f"{base}.a{attempt}.data", attempt


def _offsets(counts) -> np.ndarray:
    """Cumulative offsets (n + 1, int64) of per-partition counts."""
    return np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)


def _with_pids(rb: pa.RecordBatch, pids: np.ndarray) -> pa.RecordBatch:
    """A batch as `ShuffleRepartitioner` stages it: `__pid` first."""
    return pa.RecordBatch.from_arrays(
        [pa.array(pids, type=pa.int32())] + list(rb.columns),
        names=["__pid"] + list(rb.schema.names))


@dataclass
class ResidentBatch:
    """One batch of a map task's output on the chip, laid partition-major:
    reduce partition p's rows are lanes [offsets[p], offsets[p + 1]) of
    every column of `batch`, in arrival order."""

    batch: ColumnBatch
    offsets: np.ndarray
    _arrow: Optional[pa.RecordBatch] = None
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def to_arrow(self) -> pa.RecordBatch:
        """The rows, read back ONCE whoever asks and kept: an Arrow
        consumer's reduce tasks each take a slice (a dictionary column as
        its codes)."""
        with self._lock:
            if self._arrow is None:
                self._arrow = self.batch.to_arrow(keep_dict=True)
            return self._arrow

    def staged(self) -> pa.RecordBatch:
        """As the file lane stages a batch, its partition ids in front."""
        return _with_pids(self.to_arrow(), np.repeat(
            np.arange(len(self.offsets) - 1, dtype=np.int32),
            np.diff(self.offsets)))


class ResidentMapOutput(MemConsumer):
    """A map task's committed output that stays on the chip: the exchange's
    resident tier.  It charges its bytes to the memory manager of its
    task's chip; `spill()` writes exactly the `.data` / `.index` pair the
    file lane would have written (through `ShuffleRepartitioner.write`:
    the same rows in the same order, frame for frame) and from then on
    hands out file segments, so under memory pressure the exchange is the
    file tier's.  Readers may ask from any thread."""

    def __init__(self, partitioning: Partitioning, schema: Schema,
                 batches: List[ResidentBatch], data_file: str,
                 index_file: str):
        super().__init__("shuffle_resident")
        self.partitioning = partitioning
        self.schema = schema
        self._batches: Optional[List[ResidentBatch]] = batches
        self._paths = (data_file, index_file)
        self._segments: Optional[tuple] = None   # (data file, offsets)
        self._lock = threading.Lock()
        self.partition_rows = sum(
            (np.diff(b.offsets) for b in batches),
            np.zeros(partitioning.num_partitions, np.int64))
        self.nbytes = sum(b.batch.nbytes_device() for b in batches)
        # bytes of one row as the chip carries it: every column's value
        # and its validity byte
        self.row_bytes = sum(c.data.dtype.itemsize + 1
                             for c in batches[0].batch.columns) \
            if batches else 0

    @property
    def rows(self) -> int:
        return int(self.partition_rows.sum())

    @property
    def on_chip(self) -> bool:
        return self._batches is not None

    def block(self, partition: int, stage_id: int, map_id: int):
        """Reduce partition `partition`'s rows of this output: the runs on
        the chip, the file segment they were spilled to, or None where the
        partition has no row."""
        with self._lock:
            batches, segments = self._batches, self._segments
        if segments is not None:
            data, offsets = segments
            length = int(offsets[partition + 1] - offsets[partition])
            return FileSegmentBlock(data, int(offsets[partition]), length,
                                    stage_id=stage_id, map_id=map_id) \
                if length else None
        if batches is not None and not self.partition_rows[partition]:
            return None
        # a released output (`batches` None) is a lost block: reading it
        # names the map task to run again
        return ResidentBlock(batches, partition, stage_id, map_id)

    def spill(self) -> int:
        with self._lock:
            batches = self._batches
            if batches is None:
                return 0
            rep = ShuffleRepartitioner(self.partitioning, self.schema)
            for b in batches:
                rep._stage(b.staged())
            offsets = _offsets(rep.write(*self._paths))
            self._segments = (self._paths[0], offsets)
            self._batches = None
            released, self._mem_used = self._mem_used, 0
        xla_stats.note_exchange_tier("spilled", self.rows, int(offsets[-1]))
        xla_stats.note_host_exchange(int(offsets[-1]))
        return released

    def release(self) -> None:
        """Let the rows go (the scheduler's cleanup, a commit that lost)."""
        with self._lock:
            self._batches = None
        self.update_mem_used(0)
        self.unregister()


class _PartitionedSpill:
    """Spill file laid out partition-major with an offset table."""

    def __init__(self):
        fd, self.path = tempfile.mkstemp(prefix="blaze-shuffle-",
                                         suffix=".spill")
        os.close(fd)
        self.offsets: List[int] = []

    def release(self):
        try:
            os.unlink(self.path)
        except OSError:
            pass


class ShuffleRepartitioner(MemConsumer):
    """BufferedData + spill management (ref sort_repartitioner.rs:44)."""

    def __init__(self, partitioning: Partitioning, schema: Schema,
                 metrics=None):
        super().__init__("shuffle")
        self.partitioning = partitioning
        self.schema = schema
        self.metrics = metrics
        self._staged: List[pa.RecordBatch] = []  # with __pid lead column
        self._staged_bytes = 0
        self._spills: List[_PartitionedSpill] = []
        self._metrics = metrics
        self._stream_sink: Optional[BinaryIO] = None
        self._stream_writer: Optional[IpcCompressionWriter] = None
        self._stream_file: Optional[str] = None
        self._stream_tmp: Optional[str] = None
        # the resident lane, while it is open: (laid batch, its partition
        # counts on the chip) a batch; None once the task writes files.
        # While it is open nothing is staged and nothing spilled
        self._resident: Optional[List[tuple]] = None
        self._resident_bytes = 0
        self._resident_lock = threading.Lock()
        self.file_rows = 0    # rows the file lane took, staged or streamed

    # -- resident lane -------------------------------------------------------
    def open_resident(self) -> bool:
        """Keep the task's output on the chip for as long as its batches
        are carried there.  Only valid before the first insert."""
        if (1 < self.partitioning.num_partitions <= _RESIDENT_PARTS
                and not self._staged and not self._spills
                and self._stream_sink is None):
            self._resident = []
        return self._resident is not None

    def _insert_resident(self, batch: ColumnBatch) -> bool:
        """Lay `batch` partition-major where it lies and hold it; False
        where the batch has to go through the file lane (the lane is then
        closed for the rest of the task, what it held staged first, in
        arrival order)."""
        carried = bool(batch.columns) and all(
            isinstance(c, DeviceColumn) and isinstance(c.data, jax.Array)
            for c in batch.columns)
        pids = self.partitioning.device_partition_ids(batch) \
            if carried else None
        if pids is None:
            self._stage_resident()
            return False
        laid, counts = partition_tile(
            tuple((c.data, c.validity) for c in batch.columns), pids,
            batch.selection, np.int32(batch.num_rows),
            n_parts=self.partitioning.num_partitions)
        held = ColumnBatch(
            batch.schema,
            [replace(c, data=d, validity=v)
             for c, (d, v) in zip(batch.columns, laid)], 0)
        with self._resident_lock:
            if self._resident is None:   # spilled from another thread
                return False
            self._resident.append((held, counts))
            self._resident_bytes += held.nbytes_device()
        self._charge()
        return True

    def _take_resident(self) -> Optional[List[ResidentBatch]]:
        """Close the lane: what it held, with the partition counts read
        back in ONE transfer; None where it was closed before."""
        with self._resident_lock:
            held, self._resident = self._resident, None
            self._resident_bytes = 0
        if held is None:
            return None
        counts = to_host([c for _b, c in held]) if held else []
        out = []
        for (batch, _c), n in zip(held, counts):
            offsets = _offsets(n)
            out.append(ResidentBatch(
                replace(batch, num_rows=int(offsets[-1])), offsets))
        return out

    def _stage_resident(self) -> None:
        """What the lane holds joins the staged rows, read back, and the
        lane is closed (no charge is declared here: `spill` runs under the
        memory manager's own lock)."""
        for b in self._take_resident() or ():
            staged = b.staged()
            self._staged.append(staged)
            self._staged_bytes += staged.nbytes
            self.file_rows += staged.num_rows

    def commit_resident(self, data_file: str, index_file: str
                        ) -> Optional[ResidentMapOutput]:
        """The task's whole output as it lies on the chip, charged to the
        memory manager under its own name; None where the task left the
        lane (`write` then commits files)."""
        batches = self._take_resident()
        if batches is None:
            return None
        out = ResidentMapOutput(self.partitioning, self.schema, batches,
                                data_file, index_file)
        coded = sum(f.data_type.id == TypeId.UTF8 for f in self.schema)
        if coded:
            # every utf8 column the lane carries is a dictionary's codes
            xla_stats.note_dict(dict_rows_coded=out.rows * coded)
        self._charge()
        out.set_spillable(MemManager.get())
        out.update_mem_used(out.nbytes)
        return out

    def _charge(self) -> None:
        self.update_mem_used(self._staged_bytes + self._resident_bytes)

    # -- streaming single-partition mode -----------------------------------
    def open_stream(self, data_file: str) -> bool:
        """Single-reduce-partition local writes stream frames straight
        into the .data file as batches arrive: no staging buffer, no
        end-of-task serialization hump, and upstream compute overlaps
        shuffle IO.  Only valid before the first insert; multi-partition
        layouts still need the staged pid sort."""
        if (self.partitioning.num_partitions != 1 or self._staged
                or self._spills):
            return False
        # write to a task-private temp path, os.replace at finalize: a
        # failed/speculative attempt can never leave a truncated .data
        # at the final path or truncate a sibling attempt's output
        # (AuronShuffleWriterBase's tmp-file + commit discipline)
        self._stream_tmp = (f"{data_file}.inprogress"
                            f".{os.getpid()}.{id(self):x}")
        self._stream_sink = open(self._stream_tmp, "wb")
        self._stream_file = data_file
        return True

    def _stream_write(self, rb) -> None:
        if self._stream_writer is None:
            self._stream_writer = IpcCompressionWriter(
                self._stream_sink,
                codec_name=config.SHUFFLE_FILE_CODEC.get())
        self.file_rows += rb.num_rows
        if isinstance(rb, pa.Table):
            for piece in rb.to_batches():
                if piece.num_rows:
                    self._stream_writer.write_batch(piece)
        else:
            self._stream_writer.write_batch(rb)

    def close(self) -> None:
        """Abandon an un-finalized write (task failure/cancel path): the
        stream temp file is removed, the final path never existed, and
        any spill files are released — a query cancelled between spill
        and write() must not leak them."""
        with self._resident_lock:
            self._resident, self._resident_bytes = None, 0
        if self._stream_sink is not None:
            try:
                self._stream_sink.close()
            except OSError:
                pass
            try:
                os.unlink(self._stream_tmp)
            except OSError:
                pass
            self._stream_sink = None
            self._stream_writer = None
        if self._spills:
            spills, self._spills = self._spills, []
            for s in spills:
                try:
                    s.release()
                except OSError:
                    pass

    # -- insert (ref ShuffleRepartitioner::insert_batch, shuffle/mod.rs:55)
    def insert_batch(self, batch: ColumnBatch) -> None:
        if self._resident is not None:
            # no `compact()`, so no count read back: the program takes
            # the selection as it is
            current_task().check_running()
            if batch.num_rows == 0 or self._insert_resident(batch):
                return
        batch = batch.compact()
        if batch.num_rows == 0:
            return
        current_task().check_running()
        if self.partitioning.num_partitions == 1:
            if self._stream_sink is not None:
                self._stream_write(batch.to_arrow())
            else:
                self._stage(batch.to_arrow())
            return
        pids = self.partitioning.partition_ids(batch)
        # a dictionary column crosses the exchange as its codes: the IPC
        # block holds a dictionary-encoded array, nothing is decoded
        rb = batch.to_arrow(keep_dict=True)
        coded = sum(pa.types.is_dictionary(c.type) for c in rb.columns)
        if coded:
            from blaze_tpu.bridge import xla_stats
            xla_stats.note_dict(dict_rows_coded=rb.num_rows * coded)
        self._stage(_with_pids(rb, pids))

    def insert_arrow(self, rb) -> None:
        """Arrow-resident insert: with ONE reduce partition no partition
        ids are needed at all — the batch stages as-is (partition-id
        work and the ColumnBatch round trip both vanish); multi-partition
        falls back through ColumnBatch for the device pid kernel."""
        if rb.num_rows == 0:
            return
        if self.partitioning.num_partitions == 1:
            current_task().check_running()
            if self._stream_sink is not None:
                self._stream_write(rb)
            elif isinstance(rb, pa.Table):
                for piece in rb.to_batches():
                    if piece.num_rows:
                        self._stage(piece)
            else:
                self._stage(rb)
            return
        if isinstance(rb, pa.Table):
            rb = rb.combine_chunks().to_batches()[0]
        self.insert_batch(ColumnBatch.from_arrow(rb))

    def _stage(self, staged) -> None:
        self._staged.append(staged)
        self._staged_bytes += staged.nbytes
        self.file_rows += staged.num_rows
        self._charge()

    # -- spill (MemConsumer) -----------------------------------------------
    def spill(self) -> int:
        # an open resident lane is shed first: its rows join the staged
        # ones and the task writes files from here on, as before this lane
        self._stage_resident()
        if not self._staged:
            return 0
        # spills keep the wire codec (not the local-file codec): spilled
        # frames are copied verbatim into whatever sink write()/write_rss
        # merges them into, which for RSS is a network push
        spill = _PartitionedSpill()
        with open(spill.path, "wb") as f:
            spill.offsets = self._write_partitioned(f)
        self._spills.append(spill)
        released = self._staged_bytes
        self._staged = []
        self._staged_bytes = 0
        self._mem_used = 0
        if self._metrics is not None:
            self._metrics.add("spill_count")
            self._metrics.add("spilled_bytes", released)
        return released

    def _write_partitioned(self, sink: BinaryIO,
                           codec_name: Optional[str] = None) -> List[int]:
        """Sort staged rows by pid, write per-partition frames; returns
        cumulative offsets (n+1).

        `codec_name` overrides the frame codec for staged rows headed to
        a LOCAL .data file: page-cache-backed disk where compression
        costs CPU on the critical path and saves nothing, so
        `auron.tpu.shuffle.localFileCodec` (default raw) applies there.
        Frames are self-describing (leading codec byte), so readers —
        including remote fetchers — handle any mix; set the conf to lz4
        for deployments where .data segments ship over the network more
        often than they are read back locally.  Spill frames and RSS
        pushes keep the io.compression.codec wire codec (spills may be
        merged verbatim into an RSS push, shuffle/rss.rs analog)."""
        n_parts = self.partitioning.num_partitions
        if n_parts == 1:
            # single reduce partition: every row is partition 0 — the
            # insert paths stage batches WITHOUT a __pid column here, so
            # they stream out as-is (no pid sort/take, no column strip)
            w = IpcCompressionWriter(sink, codec_name=codec_name)
            for staged in self._staged:
                w.write_batch(staged)
            w.finish()
            return [0, sink.tell()]
        tbl = pa.Table.from_batches(one_schema(self._staged)) \
            .combine_chunks()
        rb = tbl.to_batches()[0]
        pids = np.asarray(rb.column(0))
        if n_parts <= 32:
            # counting sort: one flatnonzero sweep per partition beats a
            # generic argsort ~5x at small reducer counts (pids are a
            # handful of distinct values, the classic radix-1 case);
            # each sweep is a full pass over pids, so high reducer
            # counts stay on the single argsort below
            groups = [np.flatnonzero(pids == p) for p in range(n_parts)]
            order = np.concatenate(groups)
            counts = np.array([len(g) for g in groups])
            ends = counts.cumsum()
            starts = ends - counts
        else:
            order = np.argsort(pids, kind="stable")
            sorted_pids = pids[order]
            starts = np.searchsorted(sorted_pids, np.arange(n_parts),
                                     "left")
            ends = np.searchsorted(sorted_pids, np.arange(n_parts),
                                   "right")
        sorted_rb = rb.take(pa.array(order, type=pa.int64()))
        payload = sorted_rb.select(range(1, sorted_rb.num_columns))
        offsets = [0]
        bs = config.BATCH_SIZE.get()
        for p in range(n_parts):
            s, e = int(starts[p]), int(ends[p])
            if e > s:
                w = IpcCompressionWriter(sink, codec_name=codec_name)
                for off in range(s, e, bs):
                    w.write_batch(payload.slice(off, min(bs, e - off)))
                w.finish()
            offsets.append(sink.tell())
        return offsets

    # -- final write (ref shuffle_write, shuffle/mod.rs:58) ----------------
    def write(self, data_file: str, index_file: str) -> List[int]:
        """Merge spills + staged rows into .data/.index; returns lengths.

        Every mode serializes into a task-private temp file and commits
        with os.replace — a failure mid-write can never leave a
        truncated .data at the final path (the AuronShuffleWriterBase
        tmp-file discipline); the .index is written only after the
        commit, from one shared tail."""
        if self._stream_sink is not None:
            # streaming mode: frames are already on the temp file
            assert data_file == self._stream_file
            if self._stream_writer is not None:
                self._stream_writer.finish()
            end = self._stream_sink.tell()
            self._stream_sink.close()
            self._stream_sink = None
            self._stream_writer = None
            offsets = [0, end]
            os.replace(self._stream_tmp, data_file)
        else:
            tmp = f"{data_file}.inprogress.{os.getpid()}.{id(self):x}"
            try:
                with open(tmp, "wb") as out:
                    if not self._spills:
                        # no spills: partition-major frames stream
                        # straight out — BytesIO staging existed only to
                        # merge with spill segments, and doubled every
                        # shuffle byte
                        if self._staged:
                            offsets = self._write_partitioned(
                                out,
                                codec_name=config.SHUFFLE_FILE_CODEC.get())
                        else:  # empty input: empty .data, zero offsets
                            offsets = [0] * (
                                self.partitioning.num_partitions + 1)
                    else:
                        offsets = self._merge_spills_into(out)
                self._staged = []
                self._staged_bytes = 0
                self.update_mem_used(0)
                os.replace(tmp, data_file)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        with open(index_file, "wb") as idx:
            for off in offsets:
                idx.write(struct.pack("<q", off))
        # attempt-suffixed paths (speculation): first-wins promotion of
        # the index to the canonical path; a losing attempt's files are
        # discarded here and the task still returns normally — the wave
        # loop already took the winner's result
        promote_attempt_output(data_file, index_file)
        return [offsets[i + 1] - offsets[i]
                for i in range(len(offsets) - 1)]

    def _merge_spills_into(self, out: BinaryIO) -> List[int]:
        """Staged rows + spill segments, partition-major, into `out`."""
        mem_offsets: List[int] = []
        mem_buf = io.BytesIO()
        if self._staged:
            mem_offsets = self._write_partitioned(
                mem_buf, codec_name=config.SHUFFLE_FILE_CODEC.get())
        n_parts = self.partitioning.num_partitions
        offsets = [0]
        spill_files = [open(s.path, "rb") for s in self._spills]
        try:
            mem_view = mem_buf.getbuffer()
            for p in range(n_parts):
                if mem_offsets:
                    out.write(mem_view[mem_offsets[p]:mem_offsets[p + 1]])
                for s, f in zip(self._spills, spill_files):
                    seg_len = s.offsets[p + 1] - s.offsets[p]
                    if seg_len:
                        f.seek(s.offsets[p])
                        out.write(f.read(seg_len))
                offsets.append(out.tell())
        finally:
            for f in spill_files:
                f.close()
            for s in self._spills:
                s.release()
            self._spills = []
        return offsets

    def write_rss(self, rss_write: Callable[[int, bytes], None]) -> None:
        """Push per-partition bytes through a host callback
        (ref rss_shuffle_writer_exec.rs + shuffle/rss.rs:45 RssWriter)."""
        mem_offsets: List[int] = []
        mem_buf = io.BytesIO()
        if self._staged:
            mem_offsets = self._write_partitioned(mem_buf)
            self._staged = []
            self._staged_bytes = 0
            self.update_mem_used(0)
        n_parts = self.partitioning.num_partitions
        spill_files = [open(s.path, "rb") for s in self._spills]
        try:
            mem_view = mem_buf.getbuffer()
            for p in range(n_parts):
                chunks = []
                if mem_offsets:
                    chunks.append(bytes(mem_view[mem_offsets[p]:mem_offsets[p + 1]]))
                for s, f in zip(self._spills, spill_files):
                    seg_len = s.offsets[p + 1] - s.offsets[p]
                    if seg_len:
                        f.seek(s.offsets[p])
                        chunks.append(f.read(seg_len))
                data = b"".join(chunks)
                if data:
                    rss_write(p, data)
        finally:
            for f in spill_files:
                f.close()
            for s in self._spills:
                s.release()
            self._spills = []


class ShuffleWriterExec(ExecutionPlan):
    """Map-side shuffle write (ref shuffle_writer_exec.rs).  Consumes the
    child partition, writes `.data`/`.index`, emits nothing — the engine
    reads the index for MapStatus (AuronShuffleWriterBase.scala:68-85)."""

    def __init__(self, child: ExecutionPlan, partitioning: Partitioning,
                 data_file: str, index_file: str):
        super().__init__([child])
        self.partitioning = partitioning
        self.data_file = data_file
        self.index_file = index_file
        self.partition_lengths: Optional[List[int]] = None

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int) -> BatchIterator:
        rep = ShuffleRepartitioner(self.partitioning, self.schema,
                                   self.metrics)
        rep.set_spillable(MemManager.get())
        child = self.children[0]
        # single-partition writes take the Arrow-resident insert (no
        # partition ids needed) when the child natively produces Arrow;
        # multi-partition keeps ColumnBatch — partition ids come from the
        # device pid kernel, and round-tripping Arrow through
        # insert_arrow would ADD conversions for device-resident children
        arrow_native = (self.partitioning.num_partitions == 1
                        and type(child).arrow_batches
                        is not ExecutionPlan.arrow_batches)
        # the tier is the scheduler's choice (plan/stages.py): where it
        # left a sink under this task's .data path, the output stays on
        # the chip for as long as the batches are carried there
        sink = get_resource(RESIDENT_SINK + self.data_file)
        try:
            # single-reduce local writes stream frames to disk as
            # they arrive (compute/IO overlap, no staging hump)
            if sink is None or not rep.open_resident():
                rep.open_stream(self.data_file)
            # sinks yield nothing, so the stream meter never sees rows;
            # count what is written (rows in == rows shuffled out).
            # the child stream pulls on a prefetch worker so upstream
            # compute overlaps this map task's partition/write IO
            from blaze_tpu.ops.base import prefetch
            if arrow_native:
                for rb in prefetch(child.arrow_batches(partition),
                                   name="shuffle_map"):
                    self.metrics.add("output_rows", rb.num_rows)
                    self.metrics.add("output_batches")
                    rep.insert_arrow(rb)
            else:
                for batch in prefetch(child.execute(partition),
                                      name="shuffle_map"):
                    self.metrics.add("output_rows", batch.num_rows)
                    self.metrics.add("output_batches")
                    rep.insert_batch(batch)
            output = rep.commit_resident(self.data_file, self.index_file)
            if output is not None:
                # first wins, as a file commit: a loser's rows are let go
                if sink(output):
                    xla_stats.note_exchange_tier(
                        "resident", output.rows,
                        output.rows * output.row_bytes)
                else:
                    output.release()
                self.partition_lengths = [
                    int(n) * output.row_bytes
                    for n in output.partition_rows]
                self.metrics.add("data_size", sum(self.partition_lengths))
                return iter(())
            self.partition_lengths = rep.write(self.data_file,
                                               self.index_file)
            xla_stats.note_exchange_tier("file", rep.file_rows,
                                         sum(self.partition_lengths))
            self.metrics.add("data_size", sum(self.partition_lengths))
            self.metrics.add("io_bytes", sum(self.partition_lengths))
        finally:
            rep.close()
            rep.unregister()
        return iter(())


class RssShuffleWriterExec(ExecutionPlan):
    """Remote-shuffle-service writer: bytes go through a callback instead of
    local files (ref rss_shuffle_writer_exec.rs)."""

    def __init__(self, child: ExecutionPlan, partitioning: Partitioning,
                 rss_write: Callable[[int, bytes], None]):
        super().__init__([child])
        self.partitioning = partitioning
        self._rss_write = rss_write

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int) -> BatchIterator:
        rep = ShuffleRepartitioner(self.partitioning, self.schema,
                                   self.metrics)
        rep.set_spillable(MemManager.get())
        try:
            for batch in self.children[0].execute(partition):
                self.metrics.add("output_rows", batch.num_rows)
                self.metrics.add("output_batches")
                rep.insert_batch(batch)
            rep.write_rss(self._rss_write)
        finally:
            rep.unregister()
        return iter(())

"""Scan operators: in-memory (tests) and Parquet.

Parity: parquet_exec.rs:70 (DataFusion parquet source through the JVM Hadoop
FS bridge, page filtering + bloom gated by conf) and the TestMemoryExec
pattern used across the reference's Rust unit tests (SURVEY.md §4 tier 1).

TPU-first: parquet decoding is host work (pyarrow's C++ reader), producing
Arrow batches that cross to device as padded columns.  Predicate pushdown =
row-group min/max pruning + pyarrow filter pushdown; the residual predicate
still runs on device in FilterExec (scans never trust pushdown completeness,
matching the reference).
"""

from __future__ import annotations

import operator
from typing import Iterator, List, Optional, Sequence

import pyarrow as pa
import pyarrow.dataset
import pyarrow.parquet as pq

from blaze_tpu import config
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.ops.base import BatchIterator, ExecutionPlan
from blaze_tpu.schema import Schema

def open_source(path: str):
    """Local paths pass through; scheme'd paths (hdfs://, s3://...) open
    through the registered FsProvider — the host-engine FS callback path
    (ref hadoop_fs.rs InternalFileReader)."""
    if "://" in path and not path.startswith("file://"):
        from blaze_tpu.bridge.fs import fs_provider
        return fs_provider.provide(path).open(path)
    return path


class _MetaLru:
    """Bounded LRU for parquet footer metadata, keyed by path with the
    file mtime as validity stamp: a rewritten file refreshes IN PLACE (no
    stale twin lingering under an old (path, mtime) key), touches move
    entries to the MRU end, and inserts evict from the LRU end — a
    long-running session holds at most `metadataCacheSize` footers."""

    def __init__(self):
        import collections
        import threading
        self._lock = threading.Lock()
        self._entries = collections.OrderedDict()  # path -> (mtime, md)

    def get(self, path: str, mtime: float):
        with self._lock:
            entry = self._entries.get(path)
            if entry is None or entry[0] != mtime:
                if entry is not None:
                    del self._entries[path]  # stale: mtime moved
                return None
            self._entries.move_to_end(path)
            return entry[1]

    def put(self, path: str, mtime: float, md) -> None:
        limit = max(1, config.PARQUET_METADATA_CACHE_SIZE.get())
        with self._lock:
            self._entries[path] = (mtime, md)
            self._entries.move_to_end(path)
            while len(self._entries) > limit:
                self._entries.popitem(last=False)

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def clear(self):
        with self._lock:
            self._entries.clear()


_META_CACHE = _MetaLru()


def parquet_metadata(path: str):
    """Footer metadata cached across scans and fused-stage bound discovery
    (ref auron.parquet.metadataCacheSize; validated by mtime so rewritten
    files refresh).  Remote paths have no local mtime to invalidate on, so
    they bypass the cache rather than serve stale footers after an
    in-place rewrite."""
    import os
    if "://" in path and not path.startswith("file://"):
        return pq.ParquetFile(open_source(path)).metadata
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        mtime = 0
    md = _META_CACHE.get(path, mtime)
    if md is None:
        md = pq.ParquetFile(open_source(path)).metadata
        _META_CACHE.put(path, mtime, md)
    return md


class MemoryScanExec(ExecutionPlan):
    """Fixed batches per partition (the TestMemoryExec analog)."""

    def __init__(self, schema: Schema,
                 partitions: Sequence[Sequence[ColumnBatch]]):
        super().__init__()
        self._schema = schema
        self._partitions = [list(p) for p in partitions]

    @staticmethod
    def from_arrow(table, num_partitions: int = 1,
                   batch_rows: Optional[int] = None) -> "MemoryScanExec":
        if isinstance(table, pa.RecordBatch):
            table = pa.Table.from_batches([table])
        if config.ENCODING_DICT_ENABLE.get():
            table = _dict_encode_table(table)
        schema = Schema.from_arrow(table.schema)
        batch_rows = batch_rows or config.BATCH_SIZE.get()
        batches = table.to_batches(max_chunksize=batch_rows)
        parts: List[List[ColumnBatch]] = [[] for _ in range(num_partitions)]
        for i, rb in enumerate(batches):
            parts[i % num_partitions].append(ColumnBatch.from_arrow(rb))
        return MemoryScanExec(schema, parts)

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return len(self._partitions)

    def execute(self, partition: int) -> BatchIterator:
        for b in self._partitions[partition]:
            yield b


class ParquetScanExec(ExecutionPlan):
    """Parquet scan over a list of file splits.

    Each partition owns a list of (path, row_group_range) splits, mirroring
    the FileScanConfig file groups of parquet_exec.rs:70.  `predicate` is a
    PhysicalExpr evaluated twice: statically against row-group min/max stats
    here (pruning, ref conf auron.parquet.enable.pageFiltering), and
    row-wise on device by the FilterExec above this scan.
    """

    accepts_prune = True

    def __init__(self, schema: Schema, file_groups: Sequence[Sequence[str]],
                 projection: Optional[Sequence[str]] = None,
                 predicate=None, batch_rows: Optional[int] = None,
                 partition_schema: Optional[Schema] = None,
                 partition_values: Optional[Sequence[Sequence[Sequence]]]
                 = None):
        super().__init__()
        self._file_schema = schema
        # Hive-style partition-constant columns: the reference's
        # relation.schema is file columns + partition columns, and the
        # projection selects from that COMBINED space in projection order
        # (ref FileScanExecConf, NativeParquetScanBase.scala:55,
        # planner.rs:170-200).  A projected plan emits exactly the
        # projected columns; an unprojected one emits file cols + all
        # partition cols.
        self._partition_schema = partition_schema
        self._partition_values = partition_values  # [group][file][field]
        part_names = ({f.name for f in partition_schema}
                      if partition_schema is not None else set())
        self._projection = list(projection) if projection is not None else None
        if self._projection is not None:
            file_part = Schema([schema.field(n) for n in self._projection
                                if n not in part_names])
            self._out_partition_fields = [
                partition_schema.field(n) for n in self._projection
                if n in part_names] if partition_schema is not None else []
            combined = {f.name: f for f in schema}
            if partition_schema is not None:
                combined.update({f.name: f for f in partition_schema})
            self._schema = Schema([combined[n] for n in self._projection])
        else:
            file_part = schema
            self._out_partition_fields = (list(partition_schema)
                                          if partition_schema is not None
                                          else [])
            self._schema = (Schema(list(schema) + list(partition_schema))
                            if partition_schema is not None else schema)
        self._file_part = file_part
        self._file_groups = [list(g) for g in file_groups]
        self._predicate = predicate
        self._batch_rows = batch_rows or config.BATCH_SIZE.get()

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return len(self._file_groups)

    def execute(self, partition: int, extra_prune=None) -> BatchIterator:
        """`extra_prune`: a condition over THIS scan's output that every
        row the consumer goes on to use meets (the fused aggregation's
        filter, a join's build-key range).  It prunes row groups by their
        statistics for this read alone (see `_decode_batches`); the
        consumer still filters row by row."""
        # decode AND ColumnBatch conversion (incl. device placement) run on
        # the prefetch worker: the next batch's pyarrow decode + H2D
        # overlap downstream compute (double-buffering; kill-switch
        # auron.tpu.io.prefetch)
        from blaze_tpu.ops.base import prefetch
        transform = ColumnBatch.from_arrow
        post = self._post_decode_filter()
        # per-stream incremental dictionary encoder: each execute() call
        # owns one (the running dictionary is stream state — codes are
        # only comparable within a stream, and each batch's dictionary
        # extends the previous batch's, so the LAST dictionary seen
        # decodes every earlier batch of the stream)
        enc = _stream_dict_encoder(self._schema)
        if post is not None or enc is not None:
            def transform(rb, _post=post, _enc=enc):
                if _enc is not None:
                    rb = _enc(rb)
                cb = ColumnBatch.from_arrow(rb)
                return _post(cb) if _post is not None else cb
        # the row groups each pull looked at, for its `produce:*` span
        seen = {}
        return prefetch(self._decode_batches(partition, extra_prune, seen),
                        depth=self._prefetch_depth(),
                        transform=transform,
                        name="parquet_scan", span_attrs=seen)

    @staticmethod
    def _prefetch_depth():
        """Default double-buffering depth, widened to one stage-loop
        chunk when the device-resident loop is active: the loop consumes
        a whole chunk of batches per dispatch, so a depth-2 ring would
        stall it on decode every chunk."""
        from blaze_tpu import config
        if not config.IO_PREFETCH_ENABLE.get():
            return 0
        depth = config.IO_PREFETCH_DEPTH.get()
        from blaze_tpu.plan.stage_compiler import stage_loop_active
        if stage_loop_active():
            depth = max(depth, config.STAGE_DEVICE_LOOP_CHUNK.get())
        return depth

    def _post_decode_filter(self):
        """Scan-embedded filtering: when the pushdown predicate is fully
        traceable, the fused filter program ANDs its exact row mask into
        each decoded batch ON THE PREFETCH WORKER — the mask computation
        overlaps downstream compute, and the Filter operator above (which
        evaluates the same conjuncts) re-ANDs an identical mask.  Only
        applies when the output schema is the file schema (the predicate
        is bound against file-column ordinals; projections / partition
        columns reorder the space)."""
        if self._predicate is None or self._projection is not None \
                or self._partition_schema is not None:
            return None
        from blaze_tpu.exprs.program import fused_filter
        return fused_filter([self._predicate], self._schema)

    def arrow_batches(self, partition: int, extra_prune=None):
        """Prefetched Arrow-resident scan stream (see _decode_batches)."""
        from blaze_tpu.ops.base import prefetch
        return prefetch(self._decode_batches(partition, extra_prune),
                        name="parquet_scan")

    def _decode_batches(self, partition: int, extra_prune=None,
                        seen: Optional[dict] = None):
        """Arrow-resident scan stream.  Files under the eager threshold
        decode with pq.read_row_groups (multithreaded column decode,
        measurably faster than the single-threaded iter_batches slicer);
        batches re-slice zero-copy to the engine batch size.  Larger
        files stream through iter_batches for bounded memory.

        `extra_prune`: a pruning-ONLY predicate scoped to THIS read —
        joins pass the build-side join-key [min, max] runtime filter here
        so row groups provably outside the build range never decode (the
        reference pushes its bloom runtime filters into the probe scan
        the same way, ref bloom_filter_might_contain.rs + parquet page
        filtering).  It prunes via statistics only; exact row filtering
        stays with the caller.  Passing it per-read keeps the shared
        plan node immutable across partitions/executions.  The caller
        speaks of this scan's OUTPUT columns, which a projection numbers
        otherwise than the file, so it is held against the file by
        column name (`pruning.by_name`).

        `seen`: the caller's tally of `row_groups` looked at and `pruned`
        among them (`xla_stats` counts the same, by chip)."""
        import os
        from blaze_tpu.bridge import xla_stats
        from blaze_tpu.bridge.context import current_task
        from blaze_tpu.ops.pruning import by_name, conjunction
        prune_pred = self._predicate
        if extra_prune is not None and not self._out_partition_fields:
            prune_pred = conjunction(
                [p for p in (prune_pred, by_name(extra_prune, self._schema))
                 if p is not None])
        eager_limit = config.SCAN_EAGER_FILE_BYTES.get()
        group = self._file_groups[partition]
        columns = ([f.name for f in self._file_part]
                   if self._projection is not None else None)
        # whole-group fast path: one multithreaded read across all files
        # (parallelism spans files, not just row groups within one)
        if (len(group) > 1 and prune_pred is None
                and not self._out_partition_fields
                and all(isinstance(p, str) and os.path.exists(p)
                        for p in group)
                and sum(os.path.getsize(p) for p in group) <= eager_limit):
            try:
                tbl = pq.read_table(group, columns=columns,
                                    use_threads=True)
            except Exception:
                pass  # schema evolution across files: per-file loop
            else:
                for rb in tbl.to_batches(max_chunksize=self._batch_rows):
                    if rb.num_rows == 0:
                        continue
                    rb = _align_schema(rb, self._file_part)
                    self.metrics.add("io_bytes", rb.nbytes)
                    yield rb
                return
        share_max = (config.CACHE_SCAN_SHARE_MAX_BYTES.get()
                     if config.CACHE_ENABLE.get()
                     and config.CACHE_SCAN_SHARE.get() else 0)
        for fidx, path in enumerate(self._file_groups[partition]):
            try:
                f = pq.ParquetFile(open_source(path))
            except Exception:
                if config.IGNORE_CORRUPTED_FILES.get():
                    continue
                raise
            row_groups = self._prune_row_groups(f, prune_pred)
            total = f.metadata.num_row_groups
            pruned = total - len(row_groups)
            self.metrics.add("pruned_row_groups", pruned)
            xla_stats.note_scan_groups(current_task().device_id, total,
                                       pruned)
            if seen is not None:
                seen["row_groups"] = seen.get("row_groups", 0) + total
                seen["pruned"] = seen.get("pruned", 0) + pruned
            if not row_groups:
                continue
            if (share_max and isinstance(path, str)
                    and os.path.exists(path)
                    and os.path.getsize(path) <= share_max):
                yield from self._share_file(f, path, row_groups, columns,
                                            partition, fidx)
                continue
            if (isinstance(path, str) and os.path.exists(path)
                    and os.path.getsize(path) <= eager_limit):
                tbl = f.read_row_groups(row_groups, columns=columns,
                                        use_threads=True)
                batches = tbl.to_batches(max_chunksize=self._batch_rows)
            else:
                batches = f.iter_batches(batch_size=self._batch_rows,
                                         row_groups=row_groups,
                                         columns=columns)
            for rb in batches:
                if rb.num_rows == 0:
                    continue
                rb = _align_schema(rb, self._file_part)
                self.metrics.add("io_bytes", rb.nbytes)
                yield self._assemble_output(rb, partition, fidx)

    def _share_file(self, f, path, row_groups, columns, partition, fidx):
        """Decode one file through the scan broker: concurrent scans of
        the same (file, row-groups, batch-rows) with a covered column
        set ride one decode pass.  The leader publishes RAW batches —
        alignment and partition-constant assembly stay per consumer, so
        a follower's output is bit-identical to its own decode."""
        from blaze_tpu.bridge import xla_stats
        from blaze_tpu.bridge.context import active_query
        from blaze_tpu.cache import scanshare
        broker = scanshare.get_broker()
        mode, entry = broker.lease(path, row_groups, columns,
                                   self._batch_rows)
        try:
            raw = None
            if mode == "follow":
                q = active_query()
                raw = scanshare.follow_batches(
                    entry, check=q.check if q is not None else None)
            if raw is None:
                # leader — or a follower decoding itself after the
                # leader failed (its error is the leader's to surface)
                tbl = f.read_row_groups(row_groups, columns=columns,
                                        use_threads=True)
                raw = tbl.to_batches(max_chunksize=self._batch_rows)
                if mode == "lead":
                    broker.publish(entry, list(raw))
                    raw = entry.batches
                    xla_stats.note_cache(scan_share_misses=1)
            for rb in raw:
                if rb.num_rows == 0:
                    continue
                rb = _align_schema(rb, self._file_part)
                self.metrics.add("io_bytes", rb.nbytes)
                yield self._assemble_output(rb, partition, fidx)
        except BaseException as e:  # noqa: BLE001 - unblock followers
            if mode == "lead" and not entry.event.is_set():
                broker.publish(entry, None, error=e)
            raise
        finally:
            broker.release(entry)

    def _assemble_output(self, rb: pa.RecordBatch, partition: int,
                         fidx: int) -> pa.RecordBatch:
        """Merge file columns with the projected partition constants into
        self._schema order (projection may interleave the two)."""
        if not self._out_partition_fields:
            return rb
        return assemble_partition_constants(
            rb, self._schema, self._partition_schema,
            self._partition_values, partition, fidx)

    def _prune_row_groups(self, f: pq.ParquetFile,
                          prune_pred=None) -> List[int]:
        md = f.metadata
        all_groups = list(range(md.num_row_groups))
        if (prune_pred is None or
                not config.PARQUET_ENABLE_PAGE_FILTERING.get()):
            return all_groups
        from blaze_tpu.ops.pruning import prune_with_stats
        return prune_with_stats(md, self._file_schema, prune_pred,
                                all_groups)


def assemble_partition_constants(rb: pa.RecordBatch, out_schema: Schema,
                                 partition_schema: Optional[Schema],
                                 partition_values, partition: int,
                                 fidx: int) -> pa.RecordBatch:
    """Merge file columns with Hive partition constants into
    `out_schema` order (FileScanConfig partition_values): missing or
    short per-file value lists null-fill.  ONE implementation for every
    scan format — the parquet and ORC scans must never drift on
    partition-constant semantics (r5 review)."""
    values: dict = {}
    if partition_values is not None and partition < len(partition_values):
        group = partition_values[partition]
        if fidx < len(group):
            values = {f.name: v for f, v in
                      zip(partition_schema, group[fidx])}
    by_name = {rb.schema.field(i).name: rb.column(i)
               for i in range(rb.num_columns)}
    arrays = []
    for fld in out_schema:
        if fld.name in by_name:
            arrays.append(by_name[fld.name])
            continue
        v = values.get(fld.name)
        at = fld.data_type.to_arrow()
        arrays.append(pa.nulls(rb.num_rows, type=at) if v is None
                      else pa.array([v] * rb.num_rows, type=at))
    return pa.RecordBatch.from_arrays(
        arrays, schema=out_schema.to_arrow())


def _stream_dict_encoder(schema: Schema):
    """A fresh per-stream encoder when dictionary encoding is on and the
    scan emits utf8 columns; None otherwise (the disabled path never
    touches the batch — byte-identical to pre-encoding behavior)."""
    from blaze_tpu.schema import TypeId
    if not config.ENCODING_DICT_ENABLE.get():
        return None
    if not any(f.data_type.id == TypeId.UTF8 for f in schema):
        return None
    return _StreamDictEncoder(schema, config.ENCODING_DICT_MAX_ENTRIES.get())


class _StreamDictEncoder:
    """Incremental per-stream dictionary encoding of utf8 scan columns.

    Each utf8 column keeps a running stream-global dictionary in
    first-seen order; every emitted batch's DictionaryArray indexes into
    the CURRENT global, so dictionaries grow by appending only (prefix
    property).  Downstream, a batch's codes therefore remain valid
    against any LATER dictionary of the same stream — the stage loop
    exploits this by decoding final group keys with the last dictionary
    snapshot it saw.

    Overflow past `auron.tpu.encoding.dict.maxEntries` retires the
    column for the rest of the stream: later batches carry plain utf8
    and downstream code (ColumnBatch.concat mixed branch, the stage-loop
    stream guard) degrades losslessly to host strings.
    """

    def __init__(self, schema: Schema, max_entries: int):
        from blaze_tpu.schema import TypeId
        # col index -> running dictionary (None = not started,
        # False = retired by overflow)
        self._cols = {i: None for i, f in enumerate(schema)
                      if f.data_type.id == TypeId.UTF8}
        self._noted: set = set()
        self._max = max(1, max_entries)

    def __call__(self, rb: pa.RecordBatch) -> pa.RecordBatch:
        import pyarrow.compute as pc
        arrays = list(rb.columns)
        changed = False
        for i, vals in list(self._cols.items()):
            if vals is False or i >= rb.num_columns:
                continue
            arr = rb.column(i)
            if pa.types.is_dictionary(arr.type):
                continue  # already encoded upstream
            if not pa.types.is_string(arr.type):
                arr = arr.cast(pa.string())
            if vals is None:
                vals = pa.array([], type=pa.string())
            pos = pc.index_in(arr, value_set=vals)
            missing = pc.and_(pc.is_valid(arr), pc.is_null(pos))
            if len(arr) and pc.any(missing).as_py():
                new_vals = pc.unique(arr.filter(missing)).cast(pa.string())
                if len(vals) + len(new_vals) > self._max:
                    # overflow: stop encoding this column for the stream
                    self._cols[i] = False
                    continue
                vals = pa.concat_arrays([vals, new_vals])
                pos = pc.index_in(arr, value_set=vals)
            self._cols[i] = vals
            if i not in self._noted:
                self._noted.add(i)
                from blaze_tpu.bridge import xla_stats
                xla_stats.note_encoding(dict_encoded_columns=1)
            arrays[i] = pa.DictionaryArray.from_arrays(
                pos.cast(pa.int32()), vals)
            changed = True
        if not changed:
            return rb
        return pa.RecordBatch.from_arrays(arrays, names=list(rb.schema.names))


def _dict_encode_table(table: pa.Table) -> pa.Table:
    """Whole-table dictionary encoding for memory scans: one unified
    dictionary per utf8 column (to_batches then slices it zero-copy, so
    every batch of the scan shares one dictionary — the concat fast
    path).  Columns whose cardinality exceeds maxEntries stay plain."""
    import pyarrow.compute as pc
    cap = max(1, config.ENCODING_DICT_MAX_ENTRIES.get())
    arrays, changed = [], False
    for i, f in enumerate(table.schema):
        col = table.column(i)
        if not pa.types.is_string(f.type):
            arrays.append(col)
            continue
        arr = (col.combine_chunks() if col.num_chunks != 1
               else col.chunk(0))
        if isinstance(arr, pa.ChunkedArray):
            arr = (arr.chunk(0) if arr.num_chunks
                   else pa.array([], type=pa.string()))
        enc = pc.dictionary_encode(arr)
        if len(enc.dictionary) > cap:
            arrays.append(col)
            continue
        from blaze_tpu.bridge import xla_stats
        xla_stats.note_encoding(dict_encoded_columns=1)
        arrays.append(enc)
        changed = True
    if not changed:
        return table
    return pa.Table.from_arrays(arrays, names=list(table.schema.names))


def _align_schema(rb: pa.RecordBatch, schema: Schema) -> pa.RecordBatch:
    """Cast physical file types to the plan's logical schema (schema
    evolution: missing columns -> nulls, widened ints, ts units)."""
    target = schema.to_arrow()
    if rb.schema.equals(target):
        return rb
    arrays = []
    for field in target:
        idx = rb.schema.get_field_index(field.name)
        if idx < 0:
            arrays.append(pa.nulls(rb.num_rows, type=field.type))
        else:
            col = rb.column(idx)
            arrays.append(col if col.type.equals(field.type)
                          else col.cast(field.type, safe=False))
    return pa.RecordBatch.from_arrays(arrays, schema=target)

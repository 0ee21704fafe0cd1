"""Execution operator base + the batch-coalescing stream.

Parity: DataFusion `ExecutionPlan` as used by the reference's 28 operators
(ref: datafusion-ext-plans/src/*, planner.rs:122 create_plan) and the
CoalesceStream auto-wrapped around every plan root
(ref: common/execution_context.rs:146-150, rt.rs:160-166).

Execution model (TPU-first): synchronous pull iterators of ColumnBatch per
partition.  The reference's tokio async streams exist to overlap JVM IO with
native compute; here overlap comes from (a) the host prefetch thread in the
task runtime (bridge/runtime.py) and (b) XLA async dispatch — device work is
enqueued ahead while the host iterates.
"""

from __future__ import annotations

import contextlib
import functools
import queue
import threading
import time
from typing import Callable, Iterator, List, Optional, Sequence

import jax
import numpy as np

from blaze_tpu import config
from blaze_tpu.batch import (ColumnBatch, DeviceColumn, bucket_capacity,
                             column_of, same_dictionary)
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.bridge.context import current_task
from blaze_tpu.bridge.metrics import BASELINE_METRICS, MetricNode
from blaze_tpu.kernels.tiles import lay_runs, lay_tile, narrow_tile
from blaze_tpu.schema import Schema

BatchIterator = Iterator[ColumnBatch]

# Per-thread set of operator-instance ids currently inside a metered
# stream.  Several operators route execute() through their own
# arrow_batches() (or vice versa); the guard makes the inner self-call
# pass through unmetered so rows/time are not double-counted.
_metering = threading.local()


def _active_ids() -> set:
    ids = getattr(_metering, "ids", None)
    if ids is None:
        ids = _metering.ids = set()
    return ids


def _batch_rows(item) -> int:
    sc = getattr(item, "selected_count", None)  # ColumnBatch
    if sc is not None:
        return sc()
    return getattr(item, "num_rows", 0)  # pyarrow RecordBatch


class _MeteredIter:
    """Wraps an operator's batch stream: per-next() wall time goes to
    `elapsed_compute_ns` (INCLUSIVE of child pull; renderers derive
    self-time), rows/batches counted per yield.  Metrics accumulate
    incrementally so a downstream early break (LimitExec) still records
    the partial work.  While tracing is on each pull is one `op:<class>`
    span, a real interval: child pulls nest inside it."""

    __slots__ = ("_it", "_plan", "_key")

    def __init__(self, it, plan, key):
        self._it = iter(it)
        self._plan = plan
        self._key = key

    def __iter__(self):
        return self

    def __next__(self):
        active = _active_ids()
        reenter = self._key in active
        if not reenter:
            # cooperative cancellation at every metered batch step: a
            # cancelled/overdue query stops within one batch no matter
            # which operator is driving (reentrant self-calls skip the
            # check — the outer frame already ran it this step)
            current_task().check_running()
            active.add(self._key)
        # one span an operator a pull, as one meter: none on the
        # re-entrant self-call.  Off, the flag is all that is read
        traced = tracing._enabled and not reenter
        rows = None
        t0 = time.perf_counter_ns()
        try:
            if traced:
                with tracing.span(
                        f"op:{type(self._plan).__name__}") as attrs:
                    item = next(self._it)
                    # the count's readback, if any, is the operator's
                    rows = attrs["rows"] = _batch_rows(item)
            else:
                item = next(self._it)
        finally:
            self._plan.metrics.add("elapsed_compute_ns",
                                   time.perf_counter_ns() - t0)
            if not reenter:
                active.discard(self._key)
        m = self._plan.metrics
        m.add("output_batches")
        m.add("output_rows", _batch_rows(item) if rows is None else rows)
        return item


def _meter_stream(fn):
    """Wrap a subclass execute/arrow_batches with the standard meter."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        active = _active_ids()
        key = id(self)
        if key in active:  # inner self-call (execute <-> arrow_batches)
            return fn(self, *args, **kwargs)
        active.add(key)
        t0 = time.perf_counter_ns()
        try:
            # eager call under the meter: operators like IpcWriterExec do
            # all their work here and return an empty iterator
            if tracing._enabled:
                with tracing.span(f"op:{type(self).__name__}",
                                  phase="open"):
                    it = fn(self, *args, **kwargs)
            else:
                it = fn(self, *args, **kwargs)
        finally:
            setup_ns = time.perf_counter_ns() - t0
            active.discard(key)
        self.metrics.add("elapsed_compute_ns", setup_ns)
        return _MeteredIter(it, self, key)

    wrapper._blaze_metered = True
    wrapper._blaze_wraps = fn
    return wrapper


class _Raised:
    """Worker-side exception in transit to the consumer."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


_DONE = object()


class PrefetchIterator:
    """Bounded-depth background prefetch of a batch stream — the async
    pipelined executor applied at host-IO edges (parquet row-group decode,
    shuffle IPC segment reads, map-side materialization).  The reference
    gets IO/compute overlap from tokio streams + sync_channel (rt.rs:142);
    here a single worker thread pulls `source` (optionally applying
    `transform`, e.g. Arrow decode + device placement, so that work also
    leaves the consumer's critical path) into a bounded queue.

    Contract:
      * ordering preserved (one worker, FIFO queue);
      * a source/transform exception is re-raised at the consumer, in
        position, after every item produced before it;
      * close() stops AND joins the worker — no leaked threads; called on
        early downstream termination and from __del__;
      * depth <= 0, or the `auron.tpu.io.prefetch` kill-switch off,
        degrades to a fully synchronous passthrough (no thread).
    """

    def __init__(self, source, depth: Optional[int] = None,
                 transform: Optional[Callable] = None,
                 name: str = "prefetch",
                 span_attrs: Optional[dict] = None):
        if depth is None:
            depth = (config.IO_PREFETCH_DEPTH.get()
                     if config.IO_PREFETCH_ENABLE.get() else 0)
        self._source = iter(source)
        self._transform = transform
        self._name = name
        # a dict the source fills as it produces an item: what is in it
        # after a pull moves to that pull's `produce:*` span (a scan: the
        # row groups it looked at)
        self._span_attrs = span_attrs
        self._done = False
        if depth <= 0:
            self._queue = None
            self._thread = None
            return
        # the worker re-enters the consumer's TaskContext: cancellation
        # checks and task-scoped state are thread-local
        self._ctx = current_task()
        # ... and so is the tracer's context: the worker's spans carry
        # the consumer's query/stage/partition and enclosing span
        self._trace_ctx = tracing.capture()
        self._queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._work, name=f"blaze-prefetch-{name}", daemon=True)
        self._thread.start()

    # -- worker --------------------------------------------------------------
    def _work(self):
        from blaze_tpu.bridge.context import task_scope
        try:
            with task_scope(self._ctx), tracing.adopt(self._trace_ctx):
                while True:
                    with tracing.span(f"produce:{self._name}",
                                      rows=0) as attrs:
                        try:
                            item = next(self._source)
                        except StopIteration:
                            break
                        finally:
                            # (the last pull too: a file all of whose
                            # row groups were pruned yields nothing)
                            if self._span_attrs:
                                attrs.update(self._span_attrs)
                                self._span_attrs.clear()
                        if self._transform is not None:
                            item = self._transform(item)
                        attrs["rows"] = getattr(item, "num_rows", 0)
                    if not self._put(item):
                        return  # closed under us
            self._put(_DONE)
        except BaseException as exc:
            self._put(_Raised(exc))
        finally:
            close = getattr(self._source, "close", None)
            if close is not None:
                try:
                    close()
                except BaseException:
                    pass

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    # -- consumer ------------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._queue is None:  # synchronous passthrough
            item = next(self._source)
            return (self._transform(item) if self._transform is not None
                    else item)
        if self._done:
            raise StopIteration
        t0 = time.perf_counter_ns()
        with tracing.span("prefetch_wait", source=self._name):
            item = self._queue.get()
        xla_stats.note_prefetch(wait_ns=time.perf_counter_ns() - t0)
        if item is _DONE:
            self._done = True
            self._thread.join(timeout=10)
            raise StopIteration
        if isinstance(item, _Raised):
            self._done = True
            self._thread.join(timeout=10)
            raise item.exc
        xla_stats.note_prefetch(batches=1)
        return item

    def close(self):
        """Stop + join the worker, draining the queue so a blocked put
        unblocks.  Idempotent; safe after exhaustion."""
        if self._queue is None or self._done:
            self._done = True
            return
        self._done = True
        self._stop.set()
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10)

    def __del__(self):
        try:
            self.close()
        except BaseException:
            pass


def prefetch(source, depth: Optional[int] = None,
             transform: Optional[Callable] = None,
             name: str = "prefetch",
             span_attrs: Optional[dict] = None):
    """Wrap a host-IO stream with the bounded background prefetcher (see
    PrefetchIterator); semantics of the stream are unchanged."""
    return PrefetchIterator(source, depth=depth, transform=transform,
                            name=name, span_attrs=span_attrs)


class ExecutionPlan:
    """One physical operator node.

    Every subclass's `execute`/`arrow_batches` override is wrapped at
    class-creation time with the standard meter, so all operators emit
    the BASELINE_METRICS vocabulary (output_rows, output_batches,
    elapsed_compute_ns, spilled_bytes, mem_used, io_bytes) without
    per-operator bookkeeping; operator code only adds extras
    (pruned_row_groups, spill_count, ...).
    """

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        for attr in ("execute", "arrow_batches"):
            fn = cls.__dict__.get(attr)
            if fn is not None and callable(fn) and \
                    not getattr(fn, "_blaze_metered", False):
                setattr(cls, attr, _meter_stream(fn))

    def __init__(self, children: Sequence["ExecutionPlan"] = ()):
        self._children: List[ExecutionPlan] = list(children)
        self.metrics = MetricNode(name=type(self).__name__)
        for m in BASELINE_METRICS:
            self.metrics.values.setdefault(m, 0)

    # -- topology -----------------------------------------------------------
    @property
    def children(self) -> List["ExecutionPlan"]:
        return self._children

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    @property
    def num_partitions(self) -> int:
        """Output partition count (Spark RDD partitions analog)."""
        if self._children:
            return self._children[0].num_partitions
        return 1

    @property
    def reexecutable(self) -> bool:
        """Whether execute(partition) can be called again from scratch
        (file/memory-backed sources: yes).  The device-resident stage
        loop (plan/stage_compiler.py) only admits stages whose source
        is re-executable, because its wholesale fallback re-runs the
        partition through the staged path.  One-shot streams (already-
        consumed resource readers) must override this to False."""
        if self._children:
            return all(c.reexecutable for c in self._children)
        return True

    # -- execution ----------------------------------------------------------
    def execute(self, partition: int) -> BatchIterator:
        """Pull-stream of batches for one partition."""
        raise NotImplementedError

    def arrow_batches(self, partition: int, **kwargs):
        """Pull-stream of Arrow record batches.  Host-resident consumers
        (Acero joins, host-vectorized agg) use this to stay
        Arrow-resident; sources that already hold Arrow data override it
        to skip the ColumnBatch round trip entirely.  Keyword arguments
        are `execute`'s."""
        for cb in self.execute(partition, **kwargs):
            cb = cb.compact()
            if cb.num_rows:
                yield cb.to_arrow()

    # Whether `execute` and `arrow_batches` take `extra_prune`: a condition
    # over this operator's output that every row its consumer goes on to
    # use meets, for a parquet scan beneath to prune its row groups by (a
    # parquet scan; a `FilterExec`, whose output is its child's).
    accepts_prune = False

    def execute_pruned(self, partition: int, conjuncts, arrow: bool = False):
        """`execute(partition)` (`arrow_batches` where `arrow`) for a
        consumer that uses only the rows that meet every one of
        `conjuncts`, conditions over `self.schema`.  An operator that
        `accepts_prune` takes their AND with THIS read as a
        statistics-only pruning predicate, so that a row group none of
        whose rows can meet it is never decoded nor placed on the chip;
        any other ignores them.  The consumer still filters row by row."""
        from blaze_tpu.ops.pruning import conjunction
        open_ = self.arrow_batches if arrow else self.execute
        pred = conjunction(conjuncts) if self.accepts_prune else None
        if pred is None:
            return open_(partition)
        return open_(partition, extra_prune=pred)

    def execute_collect(self) -> "ColumnBatch":
        """All partitions concatenated (test/driver helper)."""
        out = []
        for p in range(self.num_partitions):
            out.extend(self.execute(p))
        if not out:
            from blaze_tpu.batch import ColumnBatch as CB
            import pyarrow as pa
            empty = pa.Table.from_batches([], schema=self.schema.to_arrow())
            return CB.from_arrow(empty)
        return ColumnBatch.concat(out)

    def collect_metrics(self) -> MetricNode:
        node = MetricNode(name=type(self).__name__, values=dict(self.metrics.values))
        node.children = [c.collect_metrics() for c in self._children]
        return node

    def __repr__(self):
        head = type(self).__name__
        if not self._children:
            return head
        inner = ", ".join(repr(c) for c in self._children)
        return f"{head}({inner})"

    def pretty(self, indent: int = 0) -> str:
        lines = ["  " * indent + type(self).__name__]
        for c in self._children:
            lines.append(c.pretty(indent + 1))
        return "\n".join(lines)


def effective_batch_size(base: Optional[int] = None) -> int:
    """Coalesce target honouring the active query's degradation ladder:
    each `shrink-capacity` rung halves the target (floor 256 rows), so a
    quota-breaching query re-batches smaller and retains less state."""
    from blaze_tpu.bridge.context import active_query
    size = base or config.BATCH_SIZE.get()
    q = active_query()
    if q is not None:
        shrink = getattr(q, "capacity_shrink", 0)
        if shrink:
            size = max(256, size >> shrink)
    return size


class CoalesceStream:
    """Re-batches a stream to ~batch_size dense rows.

    The reference coalesces small batches at every plan root and between
    operators (ref execution_context.rs:146 CoalesceStream).  Here it also
    compacts sparse selections: a batch whose surviving-row density is below
    `min_density` is compacted so downstream device work stops paying for
    dead lanes — the static-shape analog of selection vectors.

    A small batch is held and joined with what follows it, by one of two
    lanes that its own columns choose.  Rows packed to the front of
    fixed-width columns held as device arrays (a device probe's output, a
    compacted filter's; a dictionary column is its int32 code lane, laid
    with rows of the same dictionary) are laid end to end on the chip
    (`_TileLane`), one program a few batches, and leave as tiles of exactly
    the batch size.  Any other batch (a host column, numpy buffers under
    host placement) is staged and
    joined by `ColumnBatch.concat` once the staged rows reach the batch
    size.  A batch of at least half the batch size passes whole while
    nothing is held.
    """

    def __init__(self, stream: BatchIterator, batch_size: Optional[int] = None,
                 min_density: float = 0.5, metrics: Optional[MetricNode] = None):
        self._stream = stream
        self._batch_size = batch_size or config.BATCH_SIZE.get()
        self._min_density = min_density
        self._metrics = metrics or MetricNode()

    def __iter__(self) -> BatchIterator:
        staged: List[ColumnBatch] = []
        staged_rows = 0
        tiles = _TileLane()
        ctx = current_task()
        chip = ctx.device_id
        for batch in self._stream:
            ctx.check_running()
            # re-evaluated per batch so a mid-query degradation rung
            # takes effect at the next boundary
            target = effective_batch_size(self._batch_size)
            n = batch.selected_count()
            if n == 0:
                continue
            density = n / max(1, batch.capacity)
            if density < self._min_density:
                batch = batch.compact()
            if n >= target // 2 and not staged and not tiles.rows:
                yield batch
                continue
            batch = batch.compact()
            if not staged and _tileable(batch):
                for tile in tiles.lay(batch, target):
                    xla_stats.note_coalesce(chip, True, tile.num_rows)
                    yield tile
                continue
            if tiles.rows:
                # a batch the tile program does not take, behind rows it
                # holds: they go on through `concat`, in arrival order
                staged_rows = tiles.rows
                staged.append(tiles.tail())
            staged.append(batch)
            staged_rows += n
            if staged_rows >= target:
                yield _concat(staged, staged_rows, chip)
                staged, staged_rows = [], 0
        if tiles.rows:
            xla_stats.note_coalesce(chip, True, tiles.rows)
            yield tiles.tail()
        if staged:
            yield _concat(staged, staged_rows, chip)


def _concat(staged: List[ColumnBatch], rows: int, chip: int) -> ColumnBatch:
    with tracing.span("coalesce", batches=len(staged), rows=rows,
                      lane="concat"):
        out = ColumnBatch.concat(staged, bucket_capacity(rows))
    xla_stats.note_coalesce(chip, False, rows)
    return out


def _tileable(batch: ColumnBatch) -> bool:
    """Whether `_TileLane` takes the batch as it lies: rows packed to the
    front, every column a fixed-width one held as a device array (a
    dictionary column is its int32 code lane)."""
    return (batch.selection is None and bool(batch.columns)
            and all(isinstance(c, DeviceColumn)
                    and isinstance(c.data, jax.Array)
                    for c in batch.columns))


def _dictionaries(batch: ColumnBatch) -> tuple:
    """A column's dictionary where it is a `DictColumn`, else None."""
    return tuple(getattr(c, "dictionary", None) for c in batch.columns)


# the most batches one `lay_tile` program takes: a stream holds them as they
# arrived until their rows reach a tile or there are this many (its
# signature is the count, the widths and the column types; spare places
# take the last batch again, with no row)
_LAY_PARTS = 4


class _TileLane:
    """The rows a `CoalesceStream` holds on the chip: what the last tile
    left, laid, and behind it the batches that arrived since, as they are.
    Once their rows reach the batch size (or `_LAY_PARTS` batches wait) ONE
    program lays them end to end (kernels/tiles.py `lay_tile`: offsets and
    counts are runtime scalars, so a new count is never a new program) and
    a tile of exactly the batch size leaves, at that capacity and with no
    selection; the rest stays as the head of the next.  No per-column op,
    and fewer dispatches than batches.  Row order and values are
    `ColumnBatch.concat`'s.

    `runs`: a batch's rows lie from a lane `start` on (`lay`'s) and not at
    its front, a reduce partition's run of a map task's batch that the
    exchange laid partition-major; the program is kernels/tiles.py
    `lay_runs` and no `coalesce` span is written (shuffle/reader.py)."""

    def __init__(self, runs: bool = False):
        self._runs = runs
        self._starts = []     # where each waiting batch's rows begin
        self.rows = 0         # held: laid and waiting
        self._tile = 0        # the batch size, and its capacity
        self._lanes = 0
        self._schema = None
        self._dicts = ()      # a column's dictionary, or None
        self._parts = []      # the batches that wait, and their rows
        self._counts = []
        self._last = None     # the batch laid last
        self._held = None     # `lay_tile`'s rest
        self._head = None     # its head, while that holds every row held
        self._cut = False     # a full tile has left

    def _lay(self) -> None:
        """Everything that waits goes behind the rows laid (nothing waits:
        `lay_tile` cuts the rows laid once more)."""
        parts, counts = self._parts or [self._last], self._counts or [0]
        spare = _LAY_PARTS - len(parts)
        laid = [] if self._held is None else [self.rows - sum(counts)]
        parts = tuple(parts) + (parts[-1],) * spare
        rows = np.array(laid + counts + [0] * spare, np.int32)
        if self._runs:
            starts = np.array((self._starts or [0]) + [0] * spare, np.int32)
            self._head, self._held = lay_runs(
                self._held, parts, starts, rows,
                tile=self._tile, lanes=self._lanes)
        else:
            with tracing.span("coalesce", batches=len(self._parts),
                              rows=sum(counts), lane="tile"):
                self._head, self._held = lay_tile(
                    self._held, parts, rows,
                    tile=self._tile, lanes=self._lanes)
        self._last = parts[len(counts) - 1]
        self._parts, self._counts, self._starts = [], [], []

    def _batch(self, cols, n: int) -> ColumnBatch:
        return ColumnBatch(
            self._schema,
            [column_of(f.data_type, d, v, codes)
             for f, (d, v), codes in zip(self._schema, cols, self._dicts)],
            n, None)

    def lay(self, batch: ColumnBatch, target: int, start: int = 0,
            rows: Optional[int] = None) -> List[ColumnBatch]:
        """`batch`'s rows (`rows` of them from lane `start` on, where the
        lane takes runs) behind the rows held; the full tiles that makes
        (and first, where the batch size or a column's dictionary changed
        under the rows held, those rows as they are: `lay_tile`'s room is
        one tile's, and its lanes are one dictionary's)."""
        out = []
        rows = batch.num_rows if rows is None else rows
        dicts = _dictionaries(batch)
        if self.rows and not all(same_dictionary(a, b)
                                 for a, b in zip(dicts, self._dicts)):
            # codes lie end to end only under one dictionary: the rows
            # held leave as they are
            out.append(self.tail())
        if target != self._tile:
            if self.rows:
                out.append(self.tail())
            self._tile, self._lanes = target, bucket_capacity(target)
        self._schema, self._dicts = batch.schema, dicts
        self._parts.append(tuple((c.data, c.validity) for c in batch.columns))
        self._counts.append(rows)
        self._starts.append(start)
        self.rows += rows
        if self.rows >= self._tile or len(self._parts) == _LAY_PARTS:
            self._lay()
        while self.rows >= self._tile:
            out.append(self._batch(self._head, self._tile))
            self.rows -= self._tile
            self._head, self._cut = None, True
            if self.rows >= self._tile:   # a batch wider than a tile
                self._lay()
        return out

    def tail(self) -> ColumnBatch:
        """The rows held, as one batch; nothing is held afterwards.  At
        the tile's capacity once a full tile has left, so that a consumer
        sees ONE capacity; at the rows' own bucket before that, so that a
        small stream is not widened to a batch size."""
        if self._parts or self._head is None:
            self._lay()
        head, n = self._head, self.rows
        lanes = self._lanes if self._cut else bucket_capacity(n)
        if lanes < self._lanes:
            with (contextlib.nullcontext() if self._runs else
                  tracing.span("coalesce", batches=0, rows=n, lane="tile")):
                head = narrow_tile(head, lanes=lanes)
        self.rows, self._head = 0, None
        return self._batch(head, n)


def coalesce(stream: BatchIterator, batch_size: Optional[int] = None) -> BatchIterator:
    return iter(CoalesceStream(stream, batch_size))

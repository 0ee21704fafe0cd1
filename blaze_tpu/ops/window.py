"""Window operator: rank family, lead/lag, nth_value, agg-over-window.

Parity: window_exec.rs:896 + window/window_context.rs:31 +
window/processors/{row_number,rank,dense_rank,percent_rank,cume_dist,lead,
nth_value,agg}.rs and window-group-limit (proto auron.proto:600).

TPU-first: the input arrives sorted by (partition keys, order keys) —
Spark plans a SortExec under every window — so all processors become
vectorized prefix scans over segment structure: partition boundaries ->
cumsum segment ids, rank = position of the last order-key change, running
aggregates = segmented cumulative sums.  No per-row state machine.  A
partition that arrives on the chip stays there: flags and every function's
scan are one device program (`WindowExec`'s resident lane,
kernels/window.py); the host lane runs the same scans in numpy.
"""

from __future__ import annotations

import enum
import itertools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from blaze_tpu import config
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.memory import MemConsumer, try_new_spill
from blaze_tpu.exprs import BoundReference, PhysicalExpr
from blaze_tpu.exprs.base import ColVal
from blaze_tpu.ops.base import BatchIterator, ExecutionPlan
from blaze_tpu.ops.sort import (_DEVICE_KEY_TYPES, _DEVICE_SORT_ROWS,
                                host_sort_keys)
from blaze_tpu.schema import (DataType, Field, FLOAT64, INT32, INT64, Schema, TypeId)


class WindowRankType(enum.Enum):
    ROW_NUMBER = "row_number"
    RANK = "rank"
    DENSE_RANK = "dense_rank"
    PERCENT_RANK = "percent_rank"
    CUME_DIST = "cume_dist"


@dataclass
class WindowFunc:
    name: str

    def out_field(self, in_schema: Schema) -> Field:
        raise NotImplementedError


@dataclass
class RankFunc(WindowFunc):
    kind: WindowRankType = WindowRankType.ROW_NUMBER

    def out_field(self, in_schema):
        if self.kind in (WindowRankType.PERCENT_RANK, WindowRankType.CUME_DIST):
            return Field(self.name, FLOAT64, False)
        return Field(self.name, INT32, False)


@dataclass
class LeadLagFunc(WindowFunc):
    expr: PhysicalExpr = None
    offset: int = 1          # positive = lead, negative = lag
    default: Optional[object] = None

    def out_field(self, in_schema):
        return Field(self.name, self.expr.data_type(in_schema), True)


@dataclass
class NthValueFunc(WindowFunc):
    expr: PhysicalExpr = None
    n: int = 1               # 1-based
    ignore_nulls: bool = False  # ref processors/nth_value.rs IGNORE NULLS

    def out_field(self, in_schema):
        return Field(self.name, self.expr.data_type(in_schema), True)


@dataclass
class WindowAggFunc(WindowFunc):
    agg: object = None       # AggFunction
    running: bool = True     # unbounded-preceding..current-row vs whole part

    def out_field(self, in_schema):
        return Field(self.name, self.agg.output_type(in_schema), True)


class _WindowBuffer(MemConsumer):
    """Buffered window input rows: a spill-capable MemConsumer (same
    pattern as ops/sort.py _SortState).  Under memory pressure the
    in-memory batches move to the shared Spill tiers (host-RAM -> disk)
    and are read back at the next boundary flush."""

    def __init__(self, op: "WindowExec"):
        super().__init__("WindowExec.buffer")
        self._op = op
        self.metrics = op.metrics
        self._mem: List[pa.RecordBatch] = []
        self._mem_bytes = 0
        self._spills: list = []
        self.rows = 0

    def add(self, rb: pa.RecordBatch) -> None:
        self._mem.append(rb)
        self._mem_bytes += rb.nbytes
        self.rows += rb.num_rows
        self.update_mem_used(self._mem_bytes)

    def spill(self) -> int:
        if not self._mem:
            return 0
        s = try_new_spill()
        s.write_batches(iter(self._mem))
        self._spills.append(s)
        released = self._mem_bytes
        self._mem = []
        self._mem_bytes = 0
        self._mem_used = 0
        self.spill_metrics.spill_count += 1
        self.spill_metrics.spilled_bytes += released
        self._op.metrics.add("spill_count")
        self._op.metrics.add("spilled_bytes", released)
        return released

    def drain(self) -> List[pa.RecordBatch]:
        """All buffered batches in arrival order (spilled runs first, since
        spills always capture the oldest prefix); resets the buffer."""
        out: List[pa.RecordBatch] = []
        for s in self._spills:
            out.extend(s.read_batches())
        self._spills = []
        out.extend(self._mem)
        self._mem = []
        self._mem_bytes = 0
        self.rows = 0
        self.update_mem_used(0)
        return out


class WindowExec(ExecutionPlan):
    """Two lanes, chosen a partition from what arrives (no option):

    resident  under device placement, where every function has a device
              form (the rank family, sum / count / min / max / avg over a
              running or whole-partition frame, a result type the chip
              holds) and the sorted run arrives as ONE batch of plain
              fixed-width device columns with device keys and arguments,
              of 1,024 rows or more (a `SortExec`'s resident run as it
              is): every function by one `segmented_scan` program
              (kernels/window.py), the results appended as device columns,
              `group_limit` as a selection.  One batch in, one batch out:
              nothing is buffered and nothing is read back.
    host      everything else (lead / lag / nth_value, a utf8, dictionary
              or host column, a decimal result past 18 digits, a small
              run or one of several batches, host placement): Arrow on the
              host, numpy scans, partition-aligned chunks from a
              spill-capable buffer.
    """

    def __init__(self, child: ExecutionPlan,
                 funcs: Sequence[WindowFunc],
                 partition_by: Sequence[PhysicalExpr],
                 order_by: Sequence[Tuple[PhysicalExpr, bool, bool]],
                 group_limit: Optional[int] = None):
        super().__init__([child])
        self.funcs = list(funcs)
        self.partition_by = list(partition_by)
        self.order_by = list(order_by)
        self.group_limit = group_limit
        in_schema = child.schema
        for f in self.funcs:
            if isinstance(f, WindowAggFunc):
                f.agg.bind(in_schema)
        self._out_schema = Schema(
            list(in_schema) + [f.out_field(in_schema) for f in self.funcs])
        self._scan_funcs = self._device_forms()

    @property
    def schema(self) -> Schema:
        return self._out_schema

    def execute(self, partition: int) -> BatchIterator:
        source = iter(self.children[0].execute(partition))
        if self._scan_funcs is None:
            yield from self._host_lane(source)
            return
        # the resident lane takes a run that IS one batch; a second batch
        # sends the run through the host lane from its first row
        head = list(itertools.islice(source, 2))
        tile = self._device_tile(head[0]) if len(head) == 1 else None
        if tile is not None and tile[0].num_rows >= _DEVICE_SORT_ROWS:
            yield self._scan_resident(*tile)
        else:
            yield from self._host_lane(itertools.chain(head, source))

    # -- the resident lane ---------------------------------------------------
    def _device_forms(self) -> Optional[tuple]:
        """The node's functions as `kernels/window.segmented_scan` takes
        them, or None where one has no device form or a result the chip
        does not hold: the node then takes the host lane whole."""
        from blaze_tpu.ops.agg.functions import (AvgAgg, CountAgg, MinMaxAgg,
                                                 SumAgg)
        in_schema = self.children[0].schema
        forms = []
        for f in self.funcs:
            if isinstance(f, RankFunc):
                forms.append((f.kind.value,))
                continue
            if not isinstance(f, WindowAggFunc) or not \
                    f.out_field(in_schema).data_type.is_fixed_width:
                return None
            arg = f.agg.children[0].data_type(in_schema) \
                if f.agg.children else None
            if isinstance(f.agg, CountAgg):
                kind = "count"
            elif arg is None or arg.id not in _DEVICE_KEY_TYPES:
                return None
            elif isinstance(f.agg, MinMaxAgg):
                kind = "min" if f.agg.minimum else "max"
            elif arg.id in (TypeId.DATE32, TypeId.TIMESTAMP_MICROS,
                            TypeId.BOOL):
                return None         # no sum of a date
            elif isinstance(f.agg, SumAgg):
                kind = "sum"
            elif isinstance(f.agg, AvgAgg) and arg.id != TypeId.DECIMAL:
                kind = "avg"        # a decimal quotient rounds on the host
            else:
                return None
            forms.append((kind, bool(f.running)))
        return tuple(forms)

    def _device_tile(self, batch: ColumnBatch):
        """(`batch` compacted, its partition keys, order keys, arguments)
        if the device can scan it where it lies, as `ops/sort.py`
        `_SortState._device_tile` asks for a sort: compute placed there,
        every column a fixed-width device column (a dictionary column is
        its code lane), every key and argument a device value.  None for
        anything else."""
        import jax
        from blaze_tpu.batch import DeviceColumn
        from blaze_tpu.bridge.placement import host_resident
        if host_resident() or not batch.columns or not all(
                isinstance(c, DeviceColumn) and isinstance(c.data, jax.Array)
                and c.data.ndim == 1 for c in batch.columns):
            return None
        batch = batch.compact()

        def value(expr, types, codes=None):
            v = expr.evaluate(batch)
            if v.dictionary is not None:
                # a dictionary column is its int32 code lane: a partition
                # key compares by code under the batch's one dictionary,
                # an order key orders by code where that is sorted
                from blaze_tpu.batch import dict_info
                if not isinstance(expr, BoundReference) or codes is None \
                        or (codes == "ordered"
                            and not dict_info(v.dictionary).sorted):
                    return None
                v = ColVal(INT32, data=v.data, validity=v.validity)
            if not (v.is_device and isinstance(v.data, jax.Array)
                    and v.dtype.id in types
                    and v.data.shape == (batch.capacity,)):
                return None
            return v

        part = [value(e, _DEVICE_KEY_TYPES, "equal")
                for e in self.partition_by]
        order = [value(e, _DEVICE_KEY_TYPES, "ordered")
                 for e, _, _ in self.order_by]
        has_arg = [isinstance(f, WindowAggFunc) and bool(f.agg.children)
                   for f in self.funcs]
        args = [value(f.agg.children[0], _DEVICE_KEY_TYPES) if has else None
                for f, has in zip(self.funcs, has_arg)]
        if any(v is None for v in part + order) or any(
                a is None for a, has in zip(args, has_arg) if has):
            return None
        return batch, part, order, args

    def _scan_resident(self, batch: ColumnBatch, part, order,
                       args) -> ColumnBatch:
        """`batch`'s rows with every function's column appended, all on the
        device, at the batch's own capacity."""
        from blaze_tpu.batch import DeviceColumn
        from blaze_tpu.bridge import tracing, xla_stats
        from blaze_tpu.bridge.context import current_task
        from blaze_tpu.kernels import window as kwin
        rows = batch.num_rows
        pairs = [[None if v is None else (v.data, v.validity) for v in vs]
                 for vs in (part, order, args)]
        with tracing.span("window_device", lane="resident", rows=rows,
                          partitions=1, functions=len(self.funcs)):
            out, selection = kwin.segmented_scan(
                *map(tuple, pairs), np.int32(rows),
                part_types=tuple(v.dtype for v in part),
                order_types=tuple(v.dtype for v in order),
                funcs=self._scan_funcs, group_limit=self.group_limit)
        xla_stats.note_window(rows, current_task().device_id, True,
                              kwin.scan_bytes(rows, *pairs, out))
        coded = sum(getattr(c, "dictionary", None) is not None
                    for c in batch.columns)
        if coded:
            xla_stats.note_dict(dict_rows_coded=rows * coded)
        # the run's columns as they lie (a dictionary column stays one),
        # the functions' behind them
        return ColumnBatch(
            self.schema,
            list(batch.columns) + [
                DeviceColumn(f.data_type, d, v) for f, (d, v) in zip(
                    list(self.schema)[len(batch.columns):], out)],
            rows, selection)

    # -- the host lane -------------------------------------------------------
    def _host_lane(self, source) -> BatchIterator:
        # Stream in partition-boundary-aligned chunks: input is sorted by
        # partition_by (the planner places a SortExec below, as Spark does),
        # so once a later partition starts every earlier one is complete and
        # can be processed + emitted.  The buffer is a spill-capable
        # MemConsumer; peak working memory is the largest single partition,
        # not the whole input (ref window_exec.rs streaming processors).
        from blaze_tpu.memory import MemManager

        buf = _WindowBuffer(self)
        buf.set_spillable(MemManager.get())
        flush_rows = 4 * config.BATCH_SIZE.get()
        prev_last: Optional[tuple] = None  # prior batch's last-row part keys
        last_cut: Optional[int] = None  # buffer-relative last partition start
        try:
            for b in source:
                rb = b.compact().to_arrow()
                if rb.num_rows == 0:
                    continue
                if self.partition_by:
                    # incremental boundary scan: only THIS batch's keys are
                    # evaluated; the seam is detected by comparing row 0
                    # against the cached key values of the previous batch's
                    # last row (no batch copy, no buffer rescan, no spill
                    # rehydration just to look)
                    base = buf.rows
                    keys = self._part_keys(rb)
                    n = rb.num_rows
                    seg = np.zeros(n, dtype=bool)
                    for k in keys:
                        seg[1:] |= k[1:] != k[:-1]
                    if prev_last is not None:
                        seg[0] = any(k[0] != pl
                                     for k, pl in zip(keys, prev_last))
                    idx = np.flatnonzero(seg)
                    idx = idx[idx + base > 0]  # buffer row 0 is not a cut
                    if len(idx):
                        last_cut = int(idx[-1]) + base
                    prev_last = tuple(k[-1] for k in keys)
                buf.add(rb)
                if self.partition_by and buf.rows >= flush_rows \
                        and last_cut is not None:
                    whole = pa.Table.from_batches(buf.drain()) \
                        .combine_chunks().to_batches()[0]
                    # take() materializes a copy: a plain slice would pin
                    # every drained buffer while the accounting only sees
                    # the slice's logical bytes
                    tail_idx = pa.array(
                        np.arange(last_cut, whole.num_rows), type=pa.int64())
                    buf.add(whole.take(tail_idx))
                    head = whole.slice(0, last_cut)
                    last_cut = None
                    yield from self._process(head)
            tail = buf.drain()
            if tail:
                tbl = pa.Table.from_batches(tail).combine_chunks()
                if tbl.num_rows:
                    yield from self._process(tbl.to_batches()[0])
        finally:
            buf.unregister()

    # ------------------------------------------------------------------
    def _process(self, rb: pa.RecordBatch) -> List[ColumnBatch]:
        """One partition-aligned chunk through the host lane: numpy scans
        over what Arrow holds, whatever the placement."""
        from blaze_tpu.bridge import tracing, xla_stats
        from blaze_tpu.bridge.context import current_task
        with tracing.span("window_device", lane="host", rows=rb.num_rows,
                          partitions=1, functions=len(self.funcs)):
            out = self._process_host(rb)
        xla_stats.note_window(rb.num_rows, current_task().device_id, False)
        return out

    def _process_host(self, rb: pa.RecordBatch) -> List[ColumnBatch]:
        n = rb.num_rows
        cb = ColumnBatch.from_arrow(rb)

        part_seg, order_change = self._segments(rb, cb)
        # positions & per-partition geometry (prefix scans)
        pos = np.arange(n, dtype=np.int64)
        seg_start = _segment_start(part_seg, pos)
        row_number = (pos - seg_start + 1).astype(np.int32)
        # partition sizes via boundary scatter
        part_size = _segment_size(part_seg, n)

        # rank: position of the last (partition-or-order) change before/at row
        change = part_seg | order_change
        rank_pos = _running_max_where(change, pos)
        rank_val = (rank_pos - seg_start + 1).astype(np.int32)
        dense = _segmented_cumsum(order_change & ~part_seg,
                                  part_seg).astype(np.int32) + 1

        out_cols: List[pa.Array] = list(rb.columns)
        for f in self.funcs:
            if isinstance(f, RankFunc):
                out_cols.append(self._rank_col(f, row_number, rank_val, dense,
                                               part_size, seg_start, change,
                                               pos, n))
            elif isinstance(f, LeadLagFunc):
                out_cols.append(self._lead_lag(f, cb, part_seg, n))
            elif isinstance(f, NthValueFunc):
                out_cols.append(self._nth_value(f, cb, seg_start, part_size, n))
            elif isinstance(f, WindowAggFunc):
                out_cols.append(self._window_agg(f, cb, part_seg,
                                                 order_change, n))
            else:
                raise TypeError(f"unknown window function {f}")

        out_schema = self.schema.to_arrow()
        out_cols = [a.cast(fld.type, safe=False)
                    if not a.type.equals(fld.type) else a
                    for a, fld in zip(out_cols, out_schema)]
        out = pa.RecordBatch.from_arrays(out_cols, schema=out_schema)
        if self.group_limit is not None:
            # window-group-limit: keep rows with rank <= k (proto :600)
            out = out.filter(pa.array(rank_val <= self.group_limit))
        return [ColumnBatch.from_arrow(out)]

    def _part_keys(self, rb: pa.RecordBatch,
                   cb: Optional[ColumnBatch] = None) -> List[np.ndarray]:
        """Order-key-encoded partition_by columns (host arrays)."""
        n = rb.num_rows
        if cb is None:
            cb = ColumnBatch.from_arrow(rb)
        arrays = [e.evaluate(cb).to_host(n) for e in self.partition_by]
        prb = pa.RecordBatch.from_arrays(
            arrays, names=[f"p{i}" for i in range(len(arrays))])
        return host_sort_keys(prb, list(range(len(arrays))),
                              [False] * len(arrays), [True] * len(arrays))

    def _part_boundaries(self, rb: pa.RecordBatch,
                         cb: Optional[ColumnBatch] = None) -> np.ndarray:
        """Bool array marking rows where a new partition starts."""
        n = rb.num_rows
        part_seg = np.zeros(n, dtype=bool)
        part_seg[0] = True
        if self.partition_by:
            for k in self._part_keys(rb, cb):
                part_seg[1:] |= k[1:] != k[:-1]
        return part_seg

    def _segments(self, rb: pa.RecordBatch, cb: ColumnBatch):
        """(partition_boundary, order_change) bool arrays over rows."""
        n = rb.num_rows
        part_seg = self._part_boundaries(rb, cb)
        if self.order_by:
            arrays = [e.evaluate(cb).to_host(n) for e, _, _ in self.order_by]
            orb = pa.RecordBatch.from_arrays(
                arrays, names=[f"o{i}" for i in range(len(arrays))])
            keys = host_sort_keys(orb, list(range(len(arrays))),
                                  [d for _, d, _ in self.order_by],
                                  [f for _, _, f in self.order_by])
            order_change = np.zeros(n, dtype=bool)
            order_change[0] = True
            for k in keys:
                order_change[1:] |= k[1:] != k[:-1]
        else:
            order_change = np.ones(n, dtype=bool)
        return part_seg, order_change

    def _rank_col(self, f: RankFunc, row_number, rank_val, dense, part_size,
                  seg_start, change, pos, n) -> pa.Array:
        k = f.kind
        if k == WindowRankType.ROW_NUMBER:
            return pa.array(row_number, type=pa.int32())
        if k == WindowRankType.RANK:
            return pa.array(rank_val, type=pa.int32())
        if k == WindowRankType.DENSE_RANK:
            return pa.array(dense, type=pa.int32())
        if k == WindowRankType.PERCENT_RANK:
            denom = np.maximum(part_size - 1, 1).astype(np.float64)
            out = (rank_val.astype(np.float64) - 1.0) / denom
            out = np.where(part_size == 1, 0.0, out)
            return pa.array(out, type=pa.float64())
        # CUME_DIST: (last row position with same order value + 1 - start)/size
        last_same = _next_change_pos(change, pos, n)
        out = (last_same - seg_start).astype(np.float64) / \
            part_size.astype(np.float64)
        return pa.array(out, type=pa.float64())

    def _lead_lag(self, f: LeadLagFunc, cb: ColumnBatch, part_seg: np.ndarray,
                  n: int) -> pa.Array:
        vals = f.expr.evaluate(cb).to_host(n)
        off = f.offset
        pid = np.cumsum(part_seg) - 1
        idx = np.arange(n) + off
        ok = (idx >= 0) & (idx < n)
        safe = np.clip(idx, 0, n - 1)
        ok &= pid[safe] == pid  # stay inside the partition
        shifted = vals.take(pa.array(safe, type=pa.int64()))
        default = pa.scalar(f.default, type=vals.type)
        return pc.if_else(pa.array(ok), shifted, default)

    def _nth_value(self, f: NthValueFunc, cb: ColumnBatch, starts,
                   part_size, n: int) -> pa.Array:
        vals = f.expr.evaluate(cb).to_host(n)
        if f.ignore_nulls:
            # nth NON-NULL row of the partition: rank each non-null value
            # within its partition via a prefix count, pick rank == n
            valid = np.asarray(vals.is_valid())
            cum = np.cumsum(valid)
            base = cum[starts] - valid[starts]
            rank = cum - base
            is_nth = valid & (rank == f.n)
            nth_idx = np.full(n, -1, dtype=np.int64)
            rows = np.nonzero(is_nth)[0]
            nth_idx[starts[rows]] = rows
            target = nth_idx[starts]
            ok = target >= 0
        else:
            target = starts + (f.n - 1)
            ok = (f.n - 1) < part_size
        safe = np.clip(target, 0, n - 1)
        taken = vals.take(pa.array(safe, type=pa.int64()))
        return pc.if_else(pa.array(ok), taken,
                          pa.scalar(None, type=vals.type))

    def _window_agg(self, f: WindowAggFunc, cb: ColumnBatch, part_seg,
                    order_change, n) -> pa.Array:
        from blaze_tpu.ops.agg.functions import (AvgAgg, CountAgg, MinMaxAgg,
                                                 SumAgg)
        from blaze_tpu.xputil import to_host
        e = f.agg.children[0] if f.agg.children else None
        decimal = e is not None and \
            e.data_type(cb.schema).id == TypeId.DECIMAL
        if e is None:
            data = np.ones(n, dtype=np.int64)
            valid = np.ones(n, dtype=bool)
        elif decimal:
            # decimals keep the unscaled-int64 device representation (a
            # float/int cast would truncate the fraction); a readback
            # under device placement is a `d2h` like any other
            dv = e.evaluate(cb).to_device(cb.capacity)
            data, valid = (np.asarray(a)[:n]
                           for a in to_host((dv.data, dv.validity)))
            data = data.astype(np.int64)    # p <= 9 may ride as int32
        else:
            arr = e.evaluate(cb).to_host(n)
            data = np.asarray(arr.cast(
                pa.float64() if pa.types.is_floating(arr.type)
                else pa.int64(), safe=False).fill_null(0))
            valid = np.asarray(arr.is_valid())
        running = f.running and bool(self.order_by)
        seen = _segmented_cumsum(valid.astype(np.int64), part_seg)
        if isinstance(f.agg, CountAgg):
            out, ovalid = seen, np.ones(n, dtype=bool)
        elif isinstance(f.agg, (SumAgg, AvgAgg)):
            dt = np.float64 if np.issubdtype(data.dtype, np.floating) \
                else np.int64
            out = _segmented_cumsum(np.where(valid, data.astype(dt), 0),
                                    part_seg)
            if isinstance(f.agg, AvgAgg):
                out = out.astype(np.float64) / np.maximum(seen, 1)
            ovalid = seen > 0
        elif isinstance(f.agg, MinMaxAgg):
            floating = np.issubdtype(data.dtype, np.floating)
            big = np.inf if floating else np.iinfo(np.int64).max
            x = np.where(valid, data, np.asarray(
                big if f.agg.minimum else -big, dtype=data.dtype))
            out = _segmented_cummin(x, part_seg) if f.agg.minimum \
                else _segmented_cummax(x, part_seg)
            ovalid = seen > 0
        else:
            raise TypeError(f"window agg {f.agg.name} unsupported")
        if not running:
            # whole-partition frame: broadcast the partition's last value
            out = _partition_last(out, part_seg, n)
            ovalid = _partition_last(ovalid, part_seg, n)
        else:
            # RANGE frame: ties (same order value) share the frame end value
            last_same = _next_change_pos(part_seg | order_change,
                                         np.arange(n, dtype=np.int64), n) - 1
            out = np.take(out, last_same)
            ovalid = np.take(ovalid, last_same)
        if decimal and f.agg.output_type(cb.schema).id == TypeId.DECIMAL:
            from blaze_tpu.batch import decimal_from_unscaled
            return decimal_from_unscaled(
                out, ovalid, f.agg.output_type(cb.schema).to_arrow())
        return pa.array(out, mask=~ovalid)


# -- prefix-scan helpers (the host lane's: numpy) ------------------------------

def _segment_start(part_seg, pos):
    return _running_max_where(part_seg, pos)


def _running_max_where(mask, pos):
    """For each row, the position of the most recent row where mask=True."""
    return np.maximum.accumulate(np.where(mask, pos, np.int64(-1)))


def _segment_size(part_seg, n):
    pos = np.arange(n, dtype=np.int64)
    start = _segment_start(part_seg, pos)
    # size = next_start - start; next start found from the right
    is_last = np.concatenate([part_seg[1:], np.ones(1, dtype=bool)])
    end_pos = _next_true_pos(is_last, pos, n)
    return end_pos - start + 1


def _next_true_pos(mask, pos, n):
    """Position of the next row (>= current) where mask is True."""
    marked = np.where(mask, pos, np.int64(n))
    return np.flip(np.minimum.accumulate(np.flip(marked)))


def _next_change_pos(change, pos, n):
    """Exclusive end of the run of rows equal to this row: position of the
    next change after current, or n."""
    nxt = np.concatenate([change[1:], np.ones(1, dtype=bool)])
    return _next_true_pos(nxt, pos, n) + 1


def _partition_last(values, part_seg, n):
    """Broadcast each partition's LAST row value to all its rows."""
    pos = np.arange(n, dtype=np.int64)
    is_last = np.concatenate([part_seg[1:], np.ones(1, dtype=bool)])
    last_pos = _next_true_pos(is_last, pos, n)
    return np.take(values, np.clip(last_pos, 0, n - 1))


def _by_partition(values, part_seg):
    import pandas as pd
    return pd.Series(values).groupby(np.cumsum(part_seg) - 1)


def _segmented_cumsum(values, part_seg):
    """Cumulative sum that RESTARTS at each partition boundary: every
    partition summed on its own, first row to last, so no other
    partition's total enters its rounding (`cumsum(all) - cumsum at the
    start` is a difference of two sums that do not restart)."""
    values = np.asarray(values)
    if values.dtype == bool:
        values = values.astype(np.int64)
    return _by_partition(values, part_seg).cumsum().to_numpy()


def _segmented_cummax(values, part_seg):
    # skipna=False propagates NaN like the device lane's jnp.maximum (NaN
    # dominates a running max)
    return _by_partition(values, part_seg).cummax(skipna=False).to_numpy()


def _segmented_cummin(values, part_seg):
    return -_segmented_cummax(-values, part_seg)


# -- event-time windows (streaming runtime) ----------------------------------
# Parity: Flink's SliceAssigners / WindowOperator watermark semantics
# (the reference accelerates the operator *body*; window assignment and
# the watermark clock stay host-side, exactly as here).  The streaming
# StreamExecutor (streaming/executor.py) feeds scheduler output batches
# through EventTimeWindowState and fires panes when the watermark passes
# window end; state snapshots ride in the checkpoint manifest.


@dataclass(frozen=True)
class EventTimeWindowSpec:
    """Tumbling (slide_ms None) or sliding event-time window, epoch ms."""

    size_ms: int
    slide_ms: Optional[int] = None

    def __post_init__(self):
        if self.size_ms <= 0:
            raise ValueError("window size_ms must be > 0")
        if self.slide_ms is not None and self.slide_ms <= 0:
            raise ValueError("window slide_ms must be > 0")

    def assign(self, ts_ms: int) -> List[int]:
        """Window starts containing ts (Flink SlidingEventTimeWindows
        .assignWindows; one start for tumbling)."""
        slide = self.slide_ms or self.size_ms
        last = ts_ms - (ts_ms % slide)
        starts = []
        w = last
        while w > ts_ms - self.size_ms:
            starts.append(w)
            w -= slide
        return starts

    def end(self, start_ms: int) -> int:
        return start_ms + self.size_ms


class WatermarkTracker:
    """Event-time clock: per-partition max record timestamp, watermark =
    min over partitions that have emitted - allowed lateness (Flink's
    per-split watermark combination; never-seen partitions are idle and
    do not hold the clock back).  A record with ts >= watermark is on
    time; the watermark only moves forward."""

    def __init__(self, lateness_ms: int = 0):
        self.lateness_ms = int(lateness_ms)
        self._max_ts: dict = {}
        self._wm: Optional[int] = None

    def observe(self, partition: int, ts_ms: int) -> None:
        cur = self._max_ts.get(partition)
        if cur is None or ts_ms > cur:
            self._max_ts[partition] = int(ts_ms)

    def watermark(self) -> Optional[int]:
        if not self._max_ts:
            return self._wm
        wm = min(self._max_ts.values()) - self.lateness_ms
        if self._wm is None or wm > self._wm:
            self._wm = wm
        return self._wm

    def snapshot(self) -> dict:
        return {"max_ts": {str(p): t for p, t in self._max_ts.items()},
                "wm": self._wm}

    def restore(self, state: dict) -> None:
        self._max_ts = {int(p): int(t)
                        for p, t in (state.get("max_ts") or {}).items()}
        self._wm = state.get("wm")


_ETW_AGGS = ("count", "sum", "min", "max", "avg")


class EventTimeWindowState(MemConsumer):
    """Keyed windowed-aggregation state for the streaming runtime.

    Folds scheduler output rows into per-(window, key) accumulators;
    `advance(wm)` fires every pane whose window end <= watermark.  Late
    rows (ts < watermark at arrival) follow the late-side policy:
    `drop` counts them, `side` buffers them for `take_late()`, `accept`
    folds them into the pane's RETAINED accumulator — a fired pane
    re-opens with the state it fired with, so the re-emitted pane
    carries corrected cumulative values (valid for min/max/avg, not
    just count/sum deltas) and downstream treats it as an update.
    Accept therefore keeps fired accumulators for the life of the query
    (counted in `state_bytes()`, so memory quotas see them); drop/side
    retain nothing after a fire.  The whole
    state is JSON-snapshotable so it rides in the checkpoint manifest,
    and the object is a MemConsumer so per-query memory quotas see the
    retained bytes (there is no cheaper tier than firing: spill()
    releases nothing, so quota pressure climbs the degrade ladder)."""

    def __init__(self, spec: EventTimeWindowSpec, in_schema: pa.Schema,
                 ts_field: str, key_fields: Sequence[str],
                 aggs: Sequence[Tuple[str, Optional[str]]],
                 late_policy: str = "drop"):
        MemConsumer.__init__(self, "EventTimeWindowState")
        self.spec = spec
        self.ts_field = ts_field
        self.key_fields = list(key_fields)
        for fn, _col in aggs:
            if fn not in _ETW_AGGS:
                raise ValueError(f"unsupported window agg {fn!r}")
        self.aggs = [(fn, col) for fn, col in aggs]
        self.late_policy = late_policy
        if late_policy not in ("drop", "side", "accept"):
            raise ValueError(f"unknown late-side policy {late_policy!r}")
        self._in_schema = in_schema
        # (window_start, key tuple) -> [acc per agg]
        self._state: dict = {}
        self.late_records = 0
        self._late_rows: List[dict] = []
        # accept policy: accumulators of already-fired panes, kept so a
        # late row re-opens its pane with cumulative state
        self._fired: dict = {}
        from blaze_tpu.memory import MemManager
        self.set_spillable(MemManager.get())

    # -- accumulators ---------------------------------------------------
    @staticmethod
    def _acc_init(fn: str):
        if fn == "count":
            return 0
        if fn == "avg":
            return [0.0, 0]
        return None  # sum/min/max start empty (null on no input)

    @staticmethod
    def _acc_fold(fn: str, acc, v):
        if fn == "count":
            return acc + (1 if v is not None else 0)
        if v is None:
            return acc
        if fn == "sum":
            return v if acc is None else acc + v
        if fn == "min":
            return v if acc is None or v < acc else acc
        if fn == "max":
            return v if acc is None or v > acc else acc
        if fn == "avg":
            return [acc[0] + v, acc[1] + 1]
        raise ValueError(fn)

    @staticmethod
    def _acc_result(fn: str, acc):
        if fn == "avg":
            return acc[0] / acc[1] if acc[1] else None
        return acc

    # -- folding --------------------------------------------------------
    def add_batch(self, rb, partition: Optional[int] = None,
                  watermark: Optional[int] = None) -> int:
        """Fold one RecordBatch/Table; returns the late-record count for
        this batch (already routed per policy)."""
        cols = {name: rb.column(i).to_pylist()
                for i, name in enumerate(rb.schema.names)}
        ts_col = cols[self.ts_field]
        keys = [cols[k] for k in self.key_fields]
        vals = [cols[c] if c is not None else None for _fn, c in self.aggs]
        late = 0
        for r in range(len(ts_col)):
            ts = ts_col[r]
            key = tuple(k[r] for k in keys)
            if (watermark is not None and ts is not None
                    and ts < watermark):
                late += 1
                if self.late_policy == "drop":
                    continue
                if self.late_policy == "side":
                    self._late_rows.append(
                        {n: cols[n][r] for n in rb.schema.names})
                    continue
                # accept: fall through and fold (pane may re-fire)
            for w in self.spec.assign(int(ts)):
                slot = self._state.get((w, key))
                if slot is None:
                    # re-open a fired pane with the accumulators it
                    # fired with (accept policy), else start fresh
                    slot = self._fired.pop((w, key), None)
                    if slot is None:
                        slot = [self._acc_init(fn) for fn, _ in self.aggs]
                    self._state[(w, key)] = slot
                for i, (fn, _col) in enumerate(self.aggs):
                    # col None = count(*): every row counts
                    v = vals[i][r] if vals[i] is not None else 1
                    slot[i] = self._acc_fold(fn, slot[i], v)
        self.late_records += late
        self.update_mem_used(self.state_bytes())
        return late

    # -- firing ---------------------------------------------------------
    def _out_schema(self) -> pa.Schema:
        fields = [self._in_schema.field(k) for k in self.key_fields]
        fields += [pa.field("window_start", pa.int64()),
                   pa.field("window_end", pa.int64())]
        for i, (fn, col) in enumerate(self.aggs):
            name = f"{fn}_{col}" if col else fn
            if fn == "count":
                t = pa.int64()
            elif fn == "avg":
                t = pa.float64()
            else:
                t = self._in_schema.field(col).type
            fields.append(pa.field(name, t))
        return pa.schema(fields)

    def advance(self, watermark: Optional[int]) -> pa.Table:
        """Fire every pane whose window end <= watermark (all panes when
        watermark is None at end-of-stream flush); deterministic order
        (window_start, key)."""
        due = [wk for wk in self._state
               if watermark is None or self.spec.end(wk[0]) <= watermark]
        due.sort(key=lambda wk: (wk[0], tuple(str(k) for k in wk[1])))
        schema = self._out_schema()
        rows: List[list] = [[] for _ in schema]
        for w, key in due:
            accs = self._state.pop((w, key))
            c = 0
            for k in key:
                rows[c].append(k)
                c += 1
            rows[c].append(w)
            rows[c + 1].append(self.spec.end(w))
            c += 2
            for i, (fn, _col) in enumerate(self.aggs):
                rows[c + i].append(self._acc_result(fn, accs[i]))
            if self.late_policy == "accept":
                self._fired[(w, key)] = accs
        self.update_mem_used(self.state_bytes())
        arrays = [pa.array(v, type=f.type)
                  for v, f in zip(rows, schema)]
        return pa.Table.from_arrays(arrays, schema=schema)

    def flush(self) -> pa.Table:
        """End-of-stream: fire everything still buffered."""
        return self.advance(None)

    def take_late(self) -> List[dict]:
        out, self._late_rows = self._late_rows, []
        return out

    # -- checkpoint snapshot --------------------------------------------
    def state_bytes(self) -> int:
        # rough retained-bytes model: dict entry + key tuple + accs
        per = 96 + 24 * (len(self.key_fields) + len(self.aggs))
        return ((len(self._state) + len(self._fired)) * per
                + 48 * len(self._late_rows))

    @staticmethod
    def _panes_out(panes: dict) -> list:
        return [[w, list(key), accs]
                for (w, key), accs in
                sorted(panes.items(),
                       key=lambda kv: (kv[0][0], str(kv[0][1])))]

    def snapshot(self) -> dict:
        return {"windows": self._panes_out(self._state),
                "fired": self._panes_out(self._fired),
                "late_records": self.late_records}

    def restore(self, state: dict) -> None:
        self._state = {(int(w), tuple(key)): list(accs)
                       for w, key, accs in (state.get("windows") or [])}
        self._fired = {(int(w), tuple(key)): list(accs)
                       for w, key, accs in (state.get("fired") or [])}
        self.late_records = int(state.get("late_records", 0))
        self.update_mem_used(self.state_bytes())

    def spill(self) -> int:
        # window accumulators have no colder tier (firing early would
        # break event-time semantics); report nothing released so quota
        # arbitration escalates to the degrade ladder instead
        return 0

    def close(self) -> None:
        self.unregister()

"""Columnar batches bridging Arrow (host) and statically-shaped device arrays.

The reference streams Arrow `RecordBatch`es between operators
(ref: native-engine/auron/src/rt.rs:156-192, Arrow C-Data FFI at the JVM
boundary).  XLA wants static shapes, so the TPU-native equivalent is:

  * every device buffer is padded to a static `capacity` (rounded to the TPU
    lane width, 128); real row count is host-side metadata;
  * nullability is a separate bool `validity` array per column (Arrow's
    validity bitmap, unpacked — TPU ops are masked, not branchy);
  * filters do NOT compact: they AND a row `selection` mask (the
    CoalesceStream analog, ref common/execution_context.rs:146-150, compacts
    lazily at operator boundaries that need packed rows);
  * variable-width columns (utf8/binary/nested) stay host-resident as Arrow
    arrays and join the device columns only through dedicated kernels
    (offsets+bytes form) — TPU has no pointers.

Residency: when compute placement pins to host (placement.host_resident),
"device" column buffers are plain numpy arrays — the glue ops here dispatch
through xputil.xp_of so padding/masking/compaction run as numpy (no eager
XLA program launches), while jit'd stage kernels consume the numpy operands
directly.  With a locally-attached accelerator the buffers are jax arrays
and every path routes through jnp exactly as before.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu import config
from blaze_tpu.schema import DataType, Field, Schema, TypeId
from blaze_tpu.xputil import asnp, to_device, to_host, xp_of

LANE = 128  # TPU lane width; device buffers are padded to a multiple of this


def _host_resident() -> bool:
    from blaze_tpu.bridge.placement import host_resident
    return host_resident()


def round_capacity(n: int) -> int:
    return max(LANE, -(-n // LANE) * LANE)


def _bucket_policy() -> tuple:
    """(base rung, growth factor) of the capacity ladder, both sanitized:
    the base lane-rounds, the factor floors at 9/8 so the ladder always
    terminates and stays geometric."""
    base = max(LANE, round_capacity(config.BATCH_BUCKET_MIN.get()))
    growth = max(1.125, config.BATCH_BUCKET_GROWTH.get())
    return base, growth


def _next_rung(cap: int, growth: float) -> int:
    return max(round_capacity(int(cap * growth)), cap + LANE)


def bucket_ladder(limit: int) -> List[int]:
    """The ladder rungs `bucket_capacity` can return, ascending, up to the
    first rung >= limit (docs/tests; the default config yields 128*2^k)."""
    base, growth = _bucket_policy()
    rungs = [base]
    while rungs[-1] < limit:
        rungs.append(_next_rung(rungs[-1], growth))
    return rungs


def bucket_capacity(n: int) -> int:
    """Quantize a requested row capacity onto the geometric bucket ladder.

    Every jit boundary keyed by buffer capacity then sees a bounded set
    of static shapes — at most one XLA compile per (kernel, rung) instead
    of one per distinct ragged tail size (the recompilation storm
    `meter_jit` flags as shape churn).  Memory overhead is bounded by the
    growth factor.  With bucketing disabled this degrades to plain lane
    rounding."""
    if not config.BATCH_BUCKETING_ENABLE.get():
        cap = round_capacity(n)
    else:
        cap, growth = _bucket_policy()
        while cap < n:
            cap = _next_rung(cap, growth)
    from blaze_tpu.bridge import xla_stats
    xla_stats.note_bucket(cap, cap - min(int(n), cap))
    return cap


def _unpack_validity(arr: pa.Array) -> np.ndarray:
    """Arrow validity bitmap -> bool array of len(arr)."""
    if arr.null_count == 0:
        return np.ones(len(arr), dtype=bool)
    buf = arr.buffers()[0]
    bits = np.unpackbits(np.frombuffer(buf, dtype=np.uint8), bitorder="little")
    return bits[arr.offset:arr.offset + len(arr)].astype(bool)


def _arrow_fixed_values(arr: pa.Array, dtype: DataType) -> np.ndarray:
    """Extract the data buffer of a fixed-width Arrow array as numpy."""
    if dtype.id == TypeId.TIMESTAMP_MICROS and pa.types.is_timestamp(arr.type) \
            and arr.type.unit != "us":
        # normalize any timestamp unit to microseconds at the host boundary;
        # safe=False truncates sub-microsecond ns components like Spark
        arr = arr.cast(pa.timestamp("us", tz=arr.type.tz), safe=False)
    if dtype.id == TypeId.BOOL:
        buf = arr.buffers()[1]
        bits = np.unpackbits(np.frombuffer(buf, dtype=np.uint8), bitorder="little")
        return bits[arr.offset:arr.offset + len(arr)].astype(bool)
    if dtype.id == TypeId.DECIMAL:
        buf = arr.buffers()[1]
        if pa.types.is_decimal(arr.type):
            pairs = decimal_limb_pairs(arr)
            if dtype.precision > 18 or arr.type.precision > 18:
                # a wider TYPE takes the int64 lane only where every VALUE
                # fits it (the high limb is the low one's sign); a NULL's
                # bytes are not looked at
                fits = pairs[:, 1] == pairs[:, 0] >> 63
                if arr.null_count:
                    fits = fits | ~_unpack_validity(arr)
                if not fits.all():
                    raise TypeError(
                        f"a value of {arr.type} does not fit the int64 "
                        f"device representation; keep the column host-"
                        f"resident")
            return pairs[:, 0].copy()
        # unscaled-int64 storage (buffered partial acc columns keep the
        # device representation)
        vals = np.frombuffer(buf, dtype=np.int64)
        return vals[arr.offset:arr.offset + len(arr)]
    np_dtype = dtype.np_dtype()
    buf = arr.buffers()[1]
    vals = np.frombuffer(buf, dtype=np_dtype)
    return vals[arr.offset:arr.offset + len(arr)]


def decimal_limb_pairs(arr: pa.Array) -> np.ndarray:
    """A decimal128 arrow array's values as an (n, 2) int64 view: the
    little-endian (low, high) limbs of each."""
    return np.frombuffer(arr.buffers()[1], dtype=np.int64).reshape(-1, 2)[
        arr.offset:arr.offset + len(arr)]


def decimal_from_unscaled(values: np.ndarray, valid: Optional[np.ndarray],
                          t: pa.DataType) -> pa.Array:
    """Unscaled int64/int32 values -> decimal128 arrow array WITHOUT an
    arrow cast (a cast would rescale; the ints already ARE the scaled
    representation).  Builds the 16-byte little-endian limbs directly:
    vectorized, unlike a per-value python-Decimal loop."""
    v = np.ascontiguousarray(values).astype(np.int64, copy=False)
    return decimal_from_limbs(v, v >> 63, valid, t)  # sign extension


def decimal_from_limbs(lo: np.ndarray, hi: np.ndarray,
                       valid: Optional[np.ndarray], t: pa.DataType
                       ) -> pa.Array:
    """(low, high) int64 limbs of two's-complement int128 unscaled values
    -> decimal128 arrow array of type `t`, no cast."""
    limbs = np.empty((len(lo), 2), dtype=np.int64)
    limbs[:, 0] = lo
    limbs[:, 1] = hi
    data_buf = pa.py_buffer(limbs.tobytes())
    if valid is None or bool(np.asarray(valid).all()):
        validity_buf, null_count = None, 0
    else:
        valid = np.asarray(valid, dtype=bool)
        bits = np.packbits(valid.astype(np.uint8), bitorder="little")
        validity_buf = pa.py_buffer(bits.tobytes())
        null_count = int((~valid).sum())
    return pa.Array.from_buffers(t, len(lo), [validity_buf, data_buf],
                                 null_count=null_count)


def bounded_decimal(values: np.ndarray, valid: np.ndarray,
                    t: pa.DataType) -> pa.Array:
    """`decimal_from_unscaled` under Spark's non-ANSI CheckOverflow: a
    value past the bound of `t` is NULL (and counted), never wrapped."""
    v = np.asarray(values).astype(np.int64, copy=False)
    valid = np.asarray(valid, dtype=bool)
    if t.precision <= 18:
        fits = np.abs(v) < 10 ** t.precision
        lost = int((valid & ~fits).sum())
        if lost:
            from blaze_tpu.bridge import xla_stats
            xla_stats.note_decimal(overflow_groups=lost)
            valid = valid & fits
    return decimal_from_unscaled(v, valid, t)


@dataclass
class DeviceColumn:
    """Fixed-width column resident on device: padded data + validity."""

    dtype: DataType
    data: jax.Array      # (capacity,); numpy when host-resident
    validity: jax.Array  # (capacity,) bool; False in padding

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    @staticmethod
    def from_numpy(values: np.ndarray, valid: Optional[np.ndarray],
                   dtype: DataType, capacity: int,
                   stage_host: bool = False) -> "DeviceColumn":
        """`stage_host` keeps the padded buffers as numpy even under device
        placement, so a batch-level caller can issue ONE device_put over
        every column (ColumnBatch.place_device) instead of a transfer per
        column."""
        n = len(values)
        assert capacity >= n
        np_dtype = dtype.np_dtype()
        if dtype.id == TypeId.DECIMAL and values.dtype == np.int32:
            np_dtype = np.int32  # scaled-int32 tier (encoding.decimal.int32)
        data = np.zeros(capacity, dtype=np_dtype)
        data[:n] = values
        v = np.zeros(capacity, dtype=bool)
        v[:n] = True if valid is None else valid
        if stage_host or _host_resident():
            return DeviceColumn(dtype, data, v)
        data, v = to_device((data, v))
        return DeviceColumn(dtype, data, v)

    @staticmethod
    def from_arrow(arr: pa.Array, dtype: DataType, capacity: int,
                   stage_host: bool = False) -> "DeviceColumn":
        arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
        values = _arrow_fixed_values(arr, dtype)
        valid = _unpack_validity(arr)
        store = dtype.np_dtype()
        if dtype.id == TypeId.DECIMAL and config.ENCODING_DECIMAL_ENABLE.get():
            from blaze_tpu.bridge import xla_stats
            if dtype.precision <= 9 and config.ENCODING_DECIMAL_INT32.get():
                # the narrow scaled-int tier: p<=9 unscaled values fit
                # int32, and the single add/sub the device lanes apply
                # before widening cannot overflow it
                store = np.int32
                xla_stats.note_encoding(decimal_scaled_int32_dispatches=1)
            else:
                xla_stats.note_encoding(decimal_scaled_int64_dispatches=1)
        if capacity == len(arr) and _host_resident():
            # zero-copy: numpy views over the Arrow buffers (host-resident
            # batches are unpadded, and nothing mutates column data in
            # place)
            return DeviceColumn(dtype,
                                values.astype(store, copy=False),
                                valid)
        return DeviceColumn.from_numpy(values.astype(store, copy=False),
                                       valid, dtype, capacity,
                                       stage_host=stage_host)

    def to_arrow(self, num_rows: int, selection: Optional[np.ndarray] = None,
                 prefetched: Optional[tuple] = None) -> pa.Array:
        """`prefetched` = (values, validity) numpy arrays already pulled in
        a batched device_get — individual per-column syncs each cost a full
        dispatch round trip."""
        if prefetched is not None:
            values, valid = prefetched
            values = values[:num_rows]
            valid = valid[:num_rows]
        else:
            values = asnp(self.data)[:num_rows]
            valid = asnp(self.validity)[:num_rows]
        if selection is not None:
            values = values[selection[:num_rows]]
            valid = valid[selection[:num_rows]]
        mask = None if valid.all() else ~valid  # no nulls -> zero-copy
        at = self.dtype.to_arrow()
        if self.dtype.id == TypeId.DECIMAL:
            return decimal_from_unscaled(values, valid, at)
        if self.dtype.id == TypeId.BOOL:
            return pa.array(values.astype(bool), type=at, mask=mask)
        return pa.array(values, type=at, mask=mask)

    def take_host(self, indices: np.ndarray) -> "DeviceColumn":
        """Gather rows host-side (compaction boundary)."""
        values = asnp(self.data)[indices]
        valid = asnp(self.validity)[indices]
        return DeviceColumn.from_numpy(values, valid, self.dtype,
                                       bucket_capacity(len(indices)))


@dataclass
class DictColumn(DeviceColumn):
    """utf8 column dictionary-encoded for the device lanes: `data` holds
    int32 codes into `dictionary` (a host pa.Array of utf8 values, no
    null entries), `validity` marks nulls (code 0 at null positions).
    The LOGICAL dtype stays UTF8 and `to_arrow`/`take_host` decode back
    to plain strings, so every generic consumer (sort, joins, shuffle,
    materialization) stays correct without knowing about the encoding —
    only the opt-in fast paths (expr programs, stage loop, hash kernels)
    look at the codes."""

    dictionary: pa.Array = None

    @staticmethod
    def from_codes(codes: np.ndarray, valid: Optional[np.ndarray],
                   dtype: DataType, capacity: int, dictionary: pa.Array,
                   stage_host: bool = False) -> "DictColumn":
        n = len(codes)
        assert capacity >= n
        data = np.zeros(capacity, dtype=np.int32)
        data[:n] = codes
        v = np.zeros(capacity, dtype=bool)
        v[:n] = True if valid is None else valid
        if stage_host or _host_resident():
            return DictColumn(dtype, data, v, dictionary=dictionary)
        data, v = to_device((data, v))
        return DictColumn(dtype, data, v, dictionary=dictionary)

    @staticmethod
    def from_arrow_dict(arr: pa.DictionaryArray, dtype: DataType,
                        capacity: int,
                        stage_host: bool = False) -> "DictColumn":
        arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
        valid = _unpack_validity(arr)
        codes = np.asarray(arr.indices.cast(pa.int32()).fill_null(0))
        d = arr.dictionary
        if isinstance(d, pa.ChunkedArray):
            d = d.combine_chunks()
        if not pa.types.is_string(d.type):
            d = d.cast(pa.string())
        if d.null_count:
            # codes pointing at a null dictionary entry are logically
            # null rows (the scan encoder never emits null entries, but
            # external dictionary arrays may)
            valid = valid & _unpack_validity(d)[codes]
        return DictColumn.from_codes(codes, valid, dtype, capacity, d,
                                     stage_host=stage_host)

    def to_arrow(self, num_rows: int, selection: Optional[np.ndarray] = None,
                 prefetched: Optional[tuple] = None) -> pa.Array:
        """Decode codes back to plain utf8 (host materialization)."""
        if prefetched is not None:
            codes, valid = prefetched
            codes = codes[:num_rows]
            valid = valid[:num_rows]
        else:
            codes = asnp(self.data)[:num_rows]
            valid = asnp(self.validity)[:num_rows]
        if selection is not None:
            codes = codes[selection[:num_rows]]
            valid = valid[selection[:num_rows]]
        idx = pa.array(codes.astype(np.int64),
                       mask=None if valid.all() else ~valid)
        return self.dictionary.take(idx).cast(self.dtype.to_arrow())

    def take_host(self, indices: np.ndarray) -> "DictColumn":
        codes = asnp(self.data)[indices]
        valid = asnp(self.validity)[indices]
        return DictColumn.from_codes(codes, valid, self.dtype,
                                     bucket_capacity(len(indices)),
                                     self.dictionary)


@dataclass
class HostColumn:
    """Variable-width / nested column kept host-side as an Arrow array."""

    dtype: DataType
    array: pa.Array  # exactly num_rows long (never padded)

    @property
    def capacity(self) -> int:
        return len(self.array)

    def to_arrow(self, num_rows: int, selection: Optional[np.ndarray] = None) -> pa.Array:
        arr = self.array.slice(0, num_rows)
        if selection is not None:
            arr = arr.filter(pa.array(selection[:num_rows]))
        return arr

    def take_host(self, indices: np.ndarray) -> "HostColumn":
        return HostColumn(self.dtype, self.array.take(pa.array(indices, type=pa.int64())))


Column = Union[DeviceColumn, HostColumn]


@dataclass
class ColumnBatch:
    """A batch of rows: schema + per-column device/host storage.

    `selection` (device bool array over capacity, or None) marks surviving
    rows after filters; padding rows are always deselected via `row_mask()`.
    """

    schema: Schema
    columns: List[Column]
    num_rows: int
    selection: Optional[jax.Array] = None

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_arrow(rb: Union[pa.RecordBatch, pa.Table],
                   capacity: Optional[int] = None) -> "ColumnBatch":
        if isinstance(rb, pa.Table):
            rb = rb.combine_chunks()
            arrays = [c.combine_chunks() if isinstance(c, pa.ChunkedArray) else c
                      for c in rb.columns]
            arrays = [a.chunk(0) if isinstance(a, pa.ChunkedArray) else a for a in arrays]
        else:
            arrays = list(rb.columns)
        schema = Schema.from_arrow(rb.schema)
        n = rb.num_rows
        if capacity is not None:
            cap = capacity
        elif _host_resident():
            cap = n  # unpadded: numpy needs no static shapes; buffers wrap
            # the Arrow memory zero-copy (jit consumers re-pad on entry)
        else:
            cap = bucket_capacity(n)
        cols: List[Column] = []
        for arr, f in zip(arrays, schema):
            if pa.types.is_dictionary(arr.type) \
                    and f.data_type.id == TypeId.UTF8:
                cols.append(DictColumn.from_arrow_dict(
                    arr, f.data_type, cap, stage_host=True))
            elif f.data_type.is_fixed_width:
                cols.append(DeviceColumn.from_arrow(arr, f.data_type, cap,
                                                    stage_host=True))
            else:
                cols.append(HostColumn(f.data_type, arr))
        return ColumnBatch(schema, cols, n).place_device()

    @staticmethod
    def from_numpy(schema: Schema, arrays: Sequence[np.ndarray],
                   capacity: Optional[int] = None) -> "ColumnBatch":
        n = len(arrays[0]) if arrays else 0
        cap = capacity or bucket_capacity(n)
        cols: List[Column] = []
        for arr, f in zip(arrays, schema):
            if f.data_type.is_fixed_width:
                cols.append(DeviceColumn.from_numpy(np.asarray(arr), None, f.data_type, cap))
            else:
                cols.append(HostColumn(f.data_type, pa.array(arr, type=f.data_type.to_arrow())))
        return ColumnBatch(schema, cols, n)

    # -- properties ---------------------------------------------------------
    @property
    def capacity(self) -> int:
        for c in self.columns:
            if isinstance(c, DeviceColumn):
                return c.capacity
        return round_capacity(self.num_rows)

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, i: int) -> Column:
        return self.columns[i]

    def _xp(self):
        """Array namespace for this batch's buffers (numpy when
        host-resident, jnp for device arrays or inside a jit trace)."""
        probe = [self.selection]
        for c in self.columns:
            if isinstance(c, DeviceColumn):
                probe.append(c.data)
                break
        return xp_of(*probe)

    def row_mask(self) -> jax.Array:
        """Device bool mask over capacity: in-range AND selected."""
        cap = self.capacity
        base = self._xp().arange(cap) < self.num_rows
        if self.selection is not None:
            base = base & self.selection
        return base

    def selected_mask(self, n: Optional[int] = None):
        """HOST bool mask over the first `n` (default num_rows) rows:
        True where the row survives the selection.  The one sanctioned
        way for row-level raise paths (ANSI casts, element_at(…, 0)) to
        skip rows a filter already deselected — filters only set
        `selection` without compacting, so expression evaluators still
        see deselected rows' values (see Cast._ansi_check_device)."""
        n = self.num_rows if n is None else n
        return asnp(self.row_mask())[:n]

    def is_selected(self, row: int) -> bool:
        """Row-level selection probe for raise-gating paths (ANSI casts,
        element_at, decimal ANSI): lazily caches the host mask — one
        device sync per batch at most, none when never consulted."""
        m = getattr(self, "_sel_mask_cache", None)
        if m is None:
            m = self.selected_mask()
            self._sel_mask_cache = m
        return row >= len(m) or bool(m[row])

    def selected_count(self) -> int:
        """Host-synced surviving row count (one scalar D2H, cached —
        every sync costs a full dispatch round trip)."""
        if self.selection is None:
            return self.num_rows
        c = getattr(self, "_sel_count", None)
        if c is None:
            c = int(to_host(self._xp().sum(self.row_mask())))
            self._sel_count = c  # dataclasses.replace drops the cache
        return c

    def place_device(self) -> "ColumnBatch":
        """Issue ONE batched async device placement for every numpy-backed
        device column (jax.device_put over the flat buffer list — a
        transfer per column would serialize the round trips).
        Run from the IO prefetch worker, the NEXT batch's H2D overlaps the
        current batch's compute: double-buffered placement.  No-op under
        host residency or when everything is already placed."""
        if _host_resident():
            return self
        idx = [i for i, c in enumerate(self.columns)
               if isinstance(c, DeviceColumn)
               and isinstance(c.data, np.ndarray)]
        if not idx:
            return self
        bufs: List[np.ndarray] = []
        for i in idx:
            bufs.append(self.columns[i].data)
            bufs.append(np.asarray(self.columns[i].validity))
        placed = to_device(bufs)
        cols = list(self.columns)
        for j, i in enumerate(idx):
            # replace() preserves the column subclass (DictColumn keeps
            # its dictionary across placement)
            cols[i] = replace(cols[i], data=placed[2 * j],
                              validity=placed[2 * j + 1])
        return replace(self, columns=cols)

    # -- transformations ----------------------------------------------------
    def with_selection(self, sel: jax.Array) -> "ColumnBatch":
        new = sel if self.selection is None else (self.selection & sel)
        return replace(self, selection=new)

    def compact(self) -> "ColumnBatch":
        """Pack surviving rows to the front; drops the selection mask.

        Device-resident columns compact ON DEVICE (stable argsort of the
        mask = order-preserving partition) with only the one scalar count
        sync — a full per-column D2H round trip here would dominate every
        filter.  Host (string) columns still need the
        mask host-side."""
        if self.selection is None:
            return self
        count = self.selected_count()
        if count == self.num_rows:
            return replace(self, selection=None)
        if self._xp() is np or any(isinstance(c, HostColumn)
                                   for c in self.columns):
            # host-resident (or string-bearing) batches compact with one
            # numpy fancy-index pass — no XLA program launches
            sel_np = asnp(self.row_mask())
            indices = np.nonzero(sel_np)[0]
            cols = [c.take_host(indices) for c in self.columns]
            return ColumnBatch(self.schema, cols, len(indices), None)
        mask = self.row_mask()
        perm = jnp.argsort(~mask, stable=True)  # selected first, in order
        cols = [replace(c, data=jnp.take(c.data, perm),
                        validity=jnp.take(c.validity, perm))
                for c in self.columns]
        return ColumnBatch(self.schema, cols, count, None)

    def take(self, indices: np.ndarray) -> "ColumnBatch":
        indices = np.asarray(indices)
        cols = [c.take_host(indices) for c in self.columns]
        return ColumnBatch(self.schema, cols, len(indices), None)

    def select_columns(self, indices: Sequence[int]) -> "ColumnBatch":
        return ColumnBatch(Schema([self.schema[i] for i in indices]),
                           [self.columns[i] for i in indices],
                           self.num_rows, self.selection)

    def to_arrow(self) -> pa.RecordBatch:
        # batch ALL device reads (mask + every column) into one device_get:
        # the round trip dominates, and device_get overlaps transfers
        to_fetch = []
        if self.selection is not None:
            to_fetch.append(self.row_mask())
        dev_idx = [i for i, c in enumerate(self.columns)
                   if isinstance(c, DeviceColumn)]
        for i in dev_idx:
            to_fetch.append(self.columns[i].data)
            to_fetch.append(self.columns[i].validity)
        if to_fetch and all(isinstance(x, np.ndarray) for x in to_fetch):
            fetched = to_fetch  # host-resident: nothing to sync
        else:
            fetched = to_host(to_fetch) if to_fetch else []
        pos = 0
        sel = None
        if self.selection is not None:
            sel = fetched[0]
            pos = 1
        pre = {}
        for i in dev_idx:
            pre[i] = (fetched[pos], fetched[pos + 1])
            pos += 2
        arrays = [c.to_arrow(self.num_rows, sel, prefetched=pre[i])
                  if i in pre else c.to_arrow(self.num_rows, sel)
                  for i, c in enumerate(self.columns)]
        return pa.RecordBatch.from_arrays(arrays, schema=self.schema.to_arrow())

    @staticmethod
    def concat(batches: Sequence["ColumnBatch"],
               capacity: Optional[int] = None) -> "ColumnBatch":
        """Concatenate after compacting each batch.  Device columns stay on
        device (slice bounds are host metadata, so shapes remain static);
        host columns concatenate via Arrow."""
        assert batches
        batches = [b.compact() for b in batches]
        schema = batches[0].schema
        total = sum(b.num_rows for b in batches)
        cap = capacity or bucket_capacity(total)
        cols: List[Column] = []
        for i, f in enumerate(schema):
            if f.data_type.is_fixed_width:
                xp = xp_of(*[b.columns[i].data for b in batches])
                vals = xp.concatenate(
                    [b.columns[i].data[:b.num_rows] for b in batches])
                valid = xp.concatenate(
                    [b.columns[i].validity[:b.num_rows] for b in batches])
                pad = cap - total
                if pad > 0:
                    vals = xp.pad(vals, (0, pad))
                    valid = xp.pad(valid, (0, pad))
                cols.append(DeviceColumn(f.data_type, vals, valid))
            elif all(isinstance(b.columns[i], DictColumn) for b in batches):
                cols.append(_concat_dict_columns(
                    [(b.columns[i], b.num_rows) for b in batches],
                    f.data_type, cap))
            elif any(isinstance(b.columns[i], DictColumn) for b in batches):
                # mixed encoded/plain (encoder hit its cardinality cap
                # mid-stream): decode losslessly to a host column
                arrs = [b.columns[i].to_arrow(b.num_rows) for b in batches]
                combined = pa.concat_arrays(
                    [a.cast(f.data_type.to_arrow()) for a in arrs])
                cols.append(HostColumn(f.data_type, combined))
            else:
                arrs = [b.columns[i].array for b in batches]
                combined = pa.concat_arrays([a.cast(f.data_type.to_arrow()) for a in arrs])
                cols.append(HostColumn(f.data_type, combined))
        return ColumnBatch(schema, cols, total, None)

    def nbytes_device(self) -> int:
        total = 0
        for c in self.columns:
            if isinstance(c, DeviceColumn):
                total += c.data.nbytes + c.validity.nbytes
        return total

    def __repr__(self):
        return (f"ColumnBatch(rows={self.num_rows}, cap={self.capacity}, "
                f"cols={[f.name for f in self.schema]})")


def _concat_dict_columns(parts, dtype: DataType, cap: int) -> DictColumn:
    """Concatenate dict-encoded columns by unifying their dictionaries:
    codes remap onto a merged first-seen dictionary (merge order = batch
    order, so cross-partition unification is deterministic).  The common
    case — one stream's incremental encoder, where each batch's
    dictionary is a prefix of the next — costs zero remaps."""
    import pyarrow.compute as pc
    merged = None
    datas, valids = [], []
    remaps = 0
    for c, n in parts:
        codes = asnp(c.data)[:n].astype(np.int64)
        valid = asnp(c.validity)[:n]
        d = c.dictionary
        if merged is None or d is merged or merged.equals(d):
            merged = d
        elif len(d) >= len(merged) and d.slice(0, len(merged)).equals(merged):
            # incremental-encoder prefix growth: old codes stay valid
            merged = d
        else:
            pos = pc.index_in(d, value_set=merged)
            missing = np.asarray(pc.is_null(pos))
            remap = np.asarray(pos.fill_null(0)).astype(np.int64)
            if missing.any():
                base = len(merged)
                merged = pa.concat_arrays(
                    [merged, d.filter(pa.array(missing))])
                remap[missing] = base + np.cumsum(missing)[missing] - 1
            codes = remap[codes]
            remaps += 1
        datas.append(codes)
        valids.append(valid)
    if remaps:
        from blaze_tpu.bridge import xla_stats
        xla_stats.note_encoding(dict_exchange_remaps=remaps)
    return DictColumn.from_codes(
        np.concatenate(datas) if datas else np.zeros(0, np.int64),
        np.concatenate(valids) if valids else np.zeros(0, bool),
        dtype, cap, merged if merged is not None
        else pa.array([], type=pa.string()))

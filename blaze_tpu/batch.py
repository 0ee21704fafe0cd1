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

import collections
import functools
import hashlib
import threading
from dataclasses import dataclass, replace
from typing import List, NamedTuple, Optional, Sequence, Tuple, Union

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



# ---------------------------------------------------------------------------
# A dictionary's identity and order
# ---------------------------------------------------------------------------
# Codes of two columns compare only under the same dictionary.  A
# dictionary is a host `pa.Array` of utf8 values; what says whether two of
# them are the same is a fingerprint of their content, and what says
# whether code order is string order is `sorted` (strictly ascending,
# byte-wise: Spark's UTF8String order).  Both are read once an array and
# kept beside it.

class DictInfo(NamedTuple):
    fingerprint: bytes
    sorted: bool


_DICT_INFO: "collections.OrderedDict" = collections.OrderedDict()
_DICT_INFO_LOCK = threading.Lock()
_DICT_INFO_LIMIT = 512


def dict_info(d: pa.Array) -> DictInfo:
    """`d`'s fingerprint and whether it is sorted, computed once an array
    (the array is held with its entry, so its id is not used again)."""
    key, held = id(d), d
    with _DICT_INFO_LOCK:
        hit = _DICT_INFO.get(key)
        if hit is not None and hit[0] is d:
            _DICT_INFO.move_to_end(key)
            return hit[1]
    h = hashlib.blake2b(digest_size=16)
    h.update(len(d).to_bytes(8, "little"))
    if len(d):
        if not pa.types.is_string(d.type):
            d = d.cast(pa.string())
        bufs = d.buffers()
        offsets = np.frombuffer(bufs[1], dtype=np.int32)[
            d.offset:d.offset + len(d) + 1]
        h.update((offsets - offsets[0]).tobytes())
        if bufs[2] is not None:
            h.update(memoryview(bufs[2])[int(offsets[0]):int(offsets[-1])])
    import pyarrow.compute as pc
    ordered = len(d) < 2 or bool(
        pc.all(pc.less(d.slice(0, len(d) - 1), d.slice(1))).as_py())
    info = DictInfo(h.digest(), ordered)
    with _DICT_INFO_LOCK:
        _DICT_INFO[key] = (held, info)
        while len(_DICT_INFO) > _DICT_INFO_LIMIT:
            _DICT_INFO.popitem(last=False)
    return info


def same_dictionary(a: Optional[pa.Array], b: Optional[pa.Array]) -> bool:
    if a is b:
        return True
    if a is None or b is None or len(a) != len(b):
        return False
    return dict_info(a).fingerprint == dict_info(b).fingerprint


def encode_sorted(arr: pa.Array) -> pa.DictionaryArray:
    """`arr` (utf8) against the SORTED dictionary of its distinct values:
    code order is string order.  For a table that is collected once (a
    broadcast build side), never a fact batch at a time."""
    import pyarrow.compute as pc
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        # already codes (a scan's encoder): the ENTRIES are sorted and the
        # codes moved through their ranks, no row is decoded
        entries = arr.dictionary.cast(pa.string())
        ranks = pa.array(dict_order_ranks(entries))
        return pa.DictionaryArray.from_arrays(
            ranks.take(arr.indices), entries.take(pc.sort_indices(entries)))
    values = pc.unique(arr.drop_null()).cast(pa.string())
    values = values.take(pc.sort_indices(values))
    codes = pc.index_in(arr, value_set=values).cast(pa.int32())
    return pa.DictionaryArray.from_arrays(codes, values)


def one_schema(batches: List[pa.RecordBatch]) -> List[pa.RecordBatch]:
    """Record batches under one schema: where a column arrived
    dictionary-encoded in some and plain in others (an encoder that hit
    its cap mid-stream), its dictionary batches are decoded."""
    if all(b.schema.equals(batches[0].schema) for b in batches[1:]):
        return batches
    plain = {i for b in batches for i, f in enumerate(b.schema)
             if not pa.types.is_dictionary(f.type)}
    return [pa.RecordBatch.from_arrays(
        [d if i in plain else c
         for i, (c, d) in enumerate(zip(b.columns,
                                        plain_columns(b.columns)))],
        names=b.schema.names) for b in batches]


def plain_columns(columns) -> list:
    """Arrow columns with every dictionary-encoded one decoded to its
    value type."""
    return [c.cast(c.type.value_type) if pa.types.is_dictionary(c.type)
            else c for c in columns]


def dict_order_ranks(d: pa.Array) -> np.ndarray:
    """rank[code]: the place of each entry in string order (int32), so
    that an unsorted dictionary's codes become an order key by one gather."""
    import pyarrow.compute as pc
    order = np.asarray(pc.sort_indices(d))
    ranks = np.empty(len(d), dtype=np.int32)
    ranks[order] = np.arange(len(d), dtype=np.int32)
    return ranks


def unify_dictionary(base: pa.Array, other: pa.Array
                     ) -> Tuple[pa.Array, Optional[np.ndarray]]:
    """(the dictionary both sets of codes are valid under, the remap lane
    for `other`'s codes or None where they hold as they are).  `base`'s
    codes always hold: the result is `base` with what it lacks appended
    (merge order = arrival order, so unification is deterministic), which
    is why a stream's LAST dictionary decodes every earlier batch."""
    import pyarrow.compute as pc
    if same_dictionary(base, other):
        return base, None
    if len(other) >= len(base) and same_dictionary(
            other.slice(0, len(base)), base):
        return other, None    # an incremental encoder's prefix growth
    if len(other) < len(base) and same_dictionary(
            base.slice(0, len(other)), other):
        return base, None     # an earlier state of that encoder, late
    pos = pc.index_in(other, value_set=base)
    missing = np.asarray(pc.is_null(pos))
    remap = np.asarray(pos.fill_null(0)).astype(np.int32)
    merged = base
    if missing.any():
        remap[missing] = len(base) + np.cumsum(missing)[missing] - 1
        merged = pa.concat_arrays([base, other.filter(pa.array(missing))])
    return merged, remap


def _remap_lane(remap: np.ndarray):
    """`remap` padded to a power of two of entries, so that a new
    dictionary size is rarely a new program."""
    size = max(LANE, 1 << max(0, len(remap) - 1).bit_length())
    out = np.zeros(size, dtype=np.int32)
    out[:len(remap)] = remap
    return out


def _lane_at_codes(lane, codes):
    return jnp.take(lane, codes, mode="clip")


@functools.lru_cache(maxsize=1)
def _dict_remap_jit():
    from blaze_tpu.bridge.xla_stats import meter_jit
    return meter_jit(_lane_at_codes, name="batch.dict_remap")


def gather_by_code(lane: np.ndarray, codes):
    """lane[codes], where `codes` lies: one device program
    (`jit__lane_at_codes__batch_dict_remap`) for a jax array, numpy otherwise."""
    if isinstance(codes, np.ndarray):
        return lane[np.clip(codes, 0, max(0, len(lane) - 1))] \
            if len(lane) else np.zeros_like(codes)
    return _dict_remap_jit()(to_device(_remap_lane(lane)), codes)


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

    def _host_codes(self, num_rows: int, selection, prefetched):
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
        return codes, valid

    def to_arrow(self, num_rows: int, selection: Optional[np.ndarray] = None,
                 prefetched: Optional[tuple] = None) -> pa.Array:
        """Decode codes back to plain utf8 (host materialization)."""
        codes, valid = self._host_codes(num_rows, selection, prefetched)
        idx = pa.array(codes.astype(np.int64),
                       mask=None if valid.all() else ~valid)
        if len(codes):
            from blaze_tpu.bridge import tracing, xla_stats
            xla_stats.note_dict(dict_rows_decoded=len(codes))
            tracing.instant("dict_decode", rows=len(codes))
        return self.dictionary.take(idx).cast(self.dtype.to_arrow())

    def to_arrow_coded(self, num_rows: int,
                       selection: Optional[np.ndarray] = None,
                       prefetched: Optional[tuple] = None
                       ) -> pa.DictionaryArray:
        """The column as Arrow holds a dictionary column: the codes as
        they are, under this dictionary; nothing is decoded."""
        codes, valid = self._host_codes(num_rows, selection, prefetched)
        idx = pa.array(codes.astype(np.int32, copy=False),
                       mask=None if valid.all() else ~valid)
        return pa.DictionaryArray.from_arrays(idx, self.dictionary)

    def remapped(self, merged: pa.Array,
                 remap: Optional[np.ndarray]) -> "DictColumn":
        """The same values under `merged`; the codes go through `remap`
        (`unify_dictionary`'s) where they lie."""
        if remap is None:
            return self if merged is self.dictionary \
                else replace(self, dictionary=merged)
        data = gather_by_code(remap, self.data)
        return replace(self, data=data, dictionary=merged)

    def take_host(self, indices: np.ndarray) -> "DictColumn":
        codes = asnp(self.data)[indices]
        valid = asnp(self.validity)[indices]
        return DictColumn.from_codes(codes, valid, self.dtype,
                                     bucket_capacity(len(indices)),
                                     self.dictionary)


def column_of(dtype: DataType, data, validity,
              dictionary: Optional[pa.Array] = None) -> DeviceColumn:
    """A device column over `data` / `validity`: a `DictColumn` where the
    lane holds codes under `dictionary`."""
    if dictionary is None:
        return DeviceColumn(dtype, data, validity)
    return DictColumn(dtype, data, validity, dictionary=dictionary)


class DictStream:
    """A stream's dictionary columns under ONE dictionary a column.

    Batches of one stream (a reduce task's blocks of several map tasks, a
    sort's tiles) may bring each its own dictionary.  Where the
    fingerprints agree a batch passes as it is.  Where they do not, the
    stream's dictionary grows by what the batch's has and it lacks
    (`unify_dictionary`: on the host, over the ENTRIES) and the batch's
    codes are remapped where they lie, one gather through the remap lane
    (span `dict_remap`; counters `dict_unified`, `dict_remap_rows`).  The
    stream's dictionary only ever grows at its end, so its LAST state
    (`dicts`, by column) decodes every batch handed on before.  `only`
    names the columns to hold (all of them where it is None)."""

    def __init__(self, only=None):
        self.dicts: dict = {}
        self._only = only

    def under_one_dictionary(self, batch: "ColumnBatch") -> "ColumnBatch":
        from blaze_tpu.bridge import tracing, xla_stats
        cols = None
        for i, c in enumerate(batch.columns):
            if not isinstance(c, DictColumn) or (
                    self._only is not None and i not in self._only):
                continue
            merged, remap = unify_dictionary(
                self.dicts.get(i, c.dictionary), c.dictionary)
            self.dicts[i] = merged
            if remap is None and merged is c.dictionary:
                continue
            cols = cols if cols is not None else list(batch.columns)
            if remap is None:
                cols[i] = c.remapped(merged, None)
                continue
            with tracing.span("dict_remap", rows=batch.num_rows,
                              entries=len(remap)):
                cols[i] = c.remapped(merged, remap)
            xla_stats.note_dict(dict_unified=1,
                                dict_remap_rows=batch.num_rows)
        return batch if cols is None else replace(batch, columns=cols)


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

    def to_arrow(self, keep_dict: bool = False) -> pa.RecordBatch:
        """`keep_dict`: a dictionary column leaves as an Arrow dictionary
        array (its codes, nothing decoded), for a consumer that carries
        codes on: the exchange's writer."""
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
        coded = [keep_dict and isinstance(c, DictColumn)
                 for c in self.columns]
        arrays = [c.to_arrow_coded(self.num_rows, sel, prefetched=pre[i])
                  if k else c.to_arrow(self.num_rows, sel, prefetched=pre[i])
                  if i in pre else c.to_arrow(self.num_rows, sel)
                  for i, (c, k) in enumerate(zip(self.columns, coded))]
        if any(coded):
            return pa.RecordBatch.from_arrays(arrays,
                                              names=self.schema.names)
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
                    [b.columns[i] for b in batches],
                    [b.num_rows for b in batches], f.data_type, cap))
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


def _concat_dict_columns(parts: Sequence[DictColumn], rows: Sequence[int],
                         dtype: DataType, cap: int) -> DictColumn:
    """Concatenate dict-encoded columns under ONE dictionary: where their
    fingerprints agree the codes are joined as the int32 lanes they are;
    where they do not, the dictionaries are unified on the host
    (`unify_dictionary`: merge order = batch order, an incremental
    encoder's prefix growth costs nothing) and the codes of a part whose
    dictionary differs are remapped where they lie (`dict_remap_rows`)."""
    merged = parts[0].dictionary if parts else None
    remaps = []
    for c in parts:
        merged, remap = unify_dictionary(merged, c.dictionary)
        remaps.append(remap)
    unified = sum(r is not None for r in remaps)
    if unified:
        from blaze_tpu.bridge import xla_stats
        xla_stats.note_encoding(dict_exchange_remaps=unified)
        xla_stats.note_dict(
            dict_unified=unified,
            dict_remap_rows=sum(n for n, r in zip(rows, remaps)
                                if r is not None))
    xp = xp_of(*[c.data for c in parts]) if parts else np
    datas, valids = [], []
    for c, n, remap in zip(parts, rows, remaps):
        data = c.data[:n]
        if remap is not None:
            data = gather_by_code(remap, data)
        datas.append(data)
        valids.append(c.validity[:n])
    total = sum(rows)
    vals = xp.concatenate(datas) if datas else np.zeros(0, np.int32)
    valid = xp.concatenate(valids) if valids else np.zeros(0, bool)
    if cap > total:
        vals = xp.pad(vals, (0, cap - total))
        valid = xp.pad(valid, (0, cap - total))
    return DictColumn(dtype, vals.astype(xp.int32), valid,
                      dictionary=merged if merged is not None
                      else pa.array([], type=pa.string()))

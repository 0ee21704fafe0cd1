"""Device sort + segmented-reduce kernels.

Replaces the reference's radix sort / loser-tree merge
(ref: datafusion-ext-commons/src/algorithm/rdx_sort.rs, loser_tree.rs) with
XLA's fused lexicographic sort (`lax.sort`, num_keys) and
`jax.ops.segment_*` reductions — the TPU-idiomatic external-sort building
blocks.  K-way merging of spilled runs happens host-side in the Sort
operator; the device is responsible for fast in-memory runs.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.bridge.xla_stats import meter_jit
from blaze_tpu.kernels import compare
from blaze_tpu.kernels.tiles import _assemble_tiles
from blaze_tpu.schema import DataType
from blaze_tpu.xputil import xp_of


# -- numpy fallbacks for the segment reductions (host-resident batches) ----
# np.bincount covers sums exactly for floats; integer sums use add.at to
# keep int64 exactness; min/max use the ufunc.at scatter form.

def _in_range(v, gids, num_segments):
    """XLA scatter drops out-of-range segment ids (mode=drop); match it."""
    gids = np.asarray(gids)
    ok = (gids >= 0) & (gids < num_segments)
    if bool(ok.all()):
        return v, gids
    return np.asarray(v)[ok], gids[ok]


def _np_segment_sum(v, gids, num_segments):
    v, gids = _in_range(np.asarray(v), gids, num_segments)
    if np.issubdtype(v.dtype, np.floating):
        return np.bincount(gids, weights=v, minlength=num_segments
                           )[:num_segments].astype(v.dtype)
    out = np.zeros(num_segments, dtype=np.int64)
    np.add.at(out, gids, v.astype(np.int64))
    return out


def _np_segment_reduce(v, gids, num_segments, ufunc, identity):
    v, gids = _in_range(np.asarray(v), gids, num_segments)
    out = np.full(num_segments, identity, dtype=v.dtype)
    with np.errstate(invalid="ignore"):  # NaN propagates, like XLA min/max
        ufunc.at(out, gids, v)
    return out


def sort_indices(columns: Sequence[Tuple[jax.Array, Optional[jax.Array], DataType]],
                 descending: Sequence[bool], nulls_first: Sequence[bool],
                 valid_mask: Optional[jax.Array] = None) -> jax.Array:
    """Stable row permutation sorting by the given key columns.

    Masked-out rows (padding / filtered) sink to the end of the permutation.
    """
    keys = compare.order_keys(columns, descending, nulls_first)
    return compare.lexsort_indices(keys, valid_mask)


def lsd_pass(digit: jax.Array, perm: jax.Array) -> jax.Array:
    """One pass of a least-significant-digit-first sort: `perm` reordered,
    stably, by `digit[perm]`.  Two operands of 32 bits whatever the keys:
    the TPU compiler's time for a sort grows with the operands and their
    width (2 x 32 bits: half a minute; 6 operands with 64-bit keys: seven
    minutes, at any length), so a sort by many wide keys is many runs of
    this one program rather than one program of its own."""
    return jax.lax.sort((jnp.take(digit, perm), perm), num_keys=1,
                        is_stable=True)[1]


sort_pass = meter_jit(lsd_pass, name="sort.pass")


# -- a partition sorted where it lies (ops/sort.py, the resident lane) ------
# Tiles are ((data, validity), ...) a staged batch, one pair a column, every
# tile of a call at one width; `rows` says how many lanes of each are rows.

PAD_DIGIT = 0xFFFFFFFF  # what a padding lane carries in every digit


def _widen_tile(tile, width: int):
    """A tile of a narrower bucket at the partition's width."""
    return jax.tree_util.tree_map(
        lambda a: jnp.pad(a, (0, width - a.shape[0])), tile)


widen_tile = meter_jit(_widen_tile, name="sort.widen",
                       static_argnames=("width",))

# the laying itself is kernels/tiles.py's, which the coalescer shares
assemble_tiles = meter_jit(_assemble_tiles, name="sort.assemble",
                           static_argnames=("cap",))


def _f32_digit(x):
    """A float32 as the uint32 whose `<` is the float's (-0.0 as 0.0,
    whatever the compiler made of `order_key`'s `+ 0.0`)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(x < 0, ~bits, bits | jnp.uint32(0x80000000))


def _halves(key):
    return [(key >> jnp.uint64(32)).astype(jnp.uint32),
            key.astype(jnp.uint32)]


def _value_digits(key, float_pair: bool):
    """An order key's value (kernels/compare.order_key: no NaN, no -0.0) as
    uint32 digits, most significant first.  A float64 is cut through its
    bits where the backend has them; where it is a pair of float32
    (`float_pair`: the TPU, which has no 64-bit bitcast) into the pair's
    two halves, which order as their sum does."""
    if key.dtype == jnp.uint64:
        return _halves(key)
    if key.dtype == jnp.float32:
        return [_f32_digit(key)]
    if key.dtype != jnp.float64:
        return [key.astype(jnp.uint32)]
    if not float_pair:
        bits = jax.lax.bitcast_convert_type(key, jnp.uint64)
        return _halves(jnp.where(key < 0, ~bits,
                                 bits | jnp.uint64(0x8000000000000000)))
    hi = key.astype(jnp.float32)
    lo = jnp.where(jnp.isinf(hi), jnp.float32(0),
                   (key - hi.astype(jnp.float64)).astype(jnp.float32))
    return [_f32_digit(hi), _f32_digit(lo)]


def _key_digits(keys, total, dtypes, descending, nulls_first,
                float_pair: bool):
    """The sort keys ((data, validity) a key, `total` rows in front) as
    32-bit digits, most significant first: (digits, varies, lanes).  Their
    joint lexicographic order is the SQL order, as `ops/sort.py`
    `host_sort_keys` orders: a key's bucket (NULLs first or last, NaN
    beyond the values), then its value.  Padding lanes carry `PAD_DIGIT`;
    `varies[i]` says if any two rows differ in digit i (one that does not
    cannot move a row); `lanes` is the identity permutation."""
    cap = keys[0][0].shape[0]
    lanes = jnp.arange(cap, dtype=jnp.int32)
    live = lanes < total
    digits = []
    for (data, validity), dtype, desc, first in zip(keys, dtypes, descending,
                                                    nulls_first):
        bucket, value = compare.order_key(data, validity, dtype, desc, first)
        digits.append(bucket.astype(jnp.uint32))
        digits.extend(_value_digits(value, float_pair))
    padded = tuple(jnp.where(live, d, jnp.uint32(PAD_DIGIT)) for d in digits)
    varies = jnp.stack([
        jnp.min(p) != jnp.max(jnp.where(live, d, jnp.uint32(0)))
        for p, d in zip(padded, digits)])
    return padded, varies, lanes


key_digits = meter_jit(
    _key_digits, name="sort.digits",
    static_argnames=("dtypes", "descending", "nulls_first", "float_pair"))

_WORD = 32  # validity bits gathered as one word


def _gather_sorted(cols, perm, rows, out_cap: int):
    """The first `rows` rows in the order `perm` gives, in `out_cap` lanes:
    every column's data by one gather, the validity of up to 32 columns by
    one gather of their bits."""
    idx = perm[:out_cap]
    keep = jnp.arange(out_cap, dtype=jnp.int32) < rows
    valid = []
    for at in range(0, len(cols), _WORD):
        group = cols[at:at + _WORD]
        word = sum(v.astype(jnp.uint32) << i
                   for i, (_d, v) in enumerate(group))
        word = jnp.take(word, idx)
        valid.extend(((word >> i) & 1).astype(bool) & keep
                     for i in range(len(group)))
    return tuple((jnp.where(keep, jnp.take(d, idx), jnp.zeros((), d.dtype)),
                  v) for (d, _v), v in zip(cols, valid))


gather_sorted = meter_jit(_gather_sorted, name="sort.gather",
                          static_argnames=("out_cap",))


def group_ids_from_sorted(keys: Sequence[jax.Array], valid_mask: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
    """Dense group ids for rows already sorted by `keys`.

    Returns (group_ids, num_groups).  Invalid rows get group id = capacity-1
    bucket beyond num_groups (callers slice by num_groups)."""
    jnp = xp_of(*keys, valid_mask)
    n = keys[0].shape[0]
    boundary = compare.rows_differ_from_prev(keys) & valid_mask
    # first valid row must open a group even if equal to an invalid row 0
    first_valid = jnp.argmax(valid_mask)
    boundary = boundary | (jnp.arange(n) == first_valid) & valid_mask
    gids = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    gids = jnp.where(valid_mask, gids, n - 1)
    return gids, num_groups


def segment_sum(values: jax.Array, gids: jax.Array, num_segments: int,
                valid: Optional[jax.Array] = None) -> jax.Array:
    xp = xp_of(values, gids, valid)
    v = values if valid is None else xp.where(valid, values, 0)
    if xp is np:
        return _np_segment_sum(v, gids, num_segments)
    return jax.ops.segment_sum(v, gids, num_segments=num_segments)


def segment_count(valid: jax.Array, gids: jax.Array, num_segments: int) -> jax.Array:
    if xp_of(valid, gids) is np:
        return _np_segment_sum(np.asarray(valid, dtype=np.int64), gids,
                               num_segments)
    return jax.ops.segment_sum(valid.astype(jnp.int64), gids,
                               num_segments=num_segments)


def segment_min(values: jax.Array, gids: jax.Array, num_segments: int,
                valid: Optional[jax.Array] = None) -> jax.Array:
    xp = xp_of(values, gids, valid)
    if valid is not None:
        big = _identity_for(values.dtype, minimum=False, xp=xp)
        values = xp.where(valid, values, big)
    if xp is np:
        return _np_segment_reduce(values, gids, num_segments, np.minimum,
                                  _identity_for(values.dtype, False, np))
    return jax.ops.segment_min(values, gids, num_segments=num_segments)


def segment_max(values: jax.Array, gids: jax.Array, num_segments: int,
                valid: Optional[jax.Array] = None) -> jax.Array:
    xp = xp_of(values, gids, valid)
    if valid is not None:
        small = _identity_for(values.dtype, minimum=True, xp=xp)
        values = xp.where(valid, values, small)
    if xp is np:
        return _np_segment_reduce(values, gids, num_segments, np.maximum,
                                  _identity_for(values.dtype, True, np))
    return jax.ops.segment_max(values, gids, num_segments=num_segments)


def segment_first(values: jax.Array, valid: jax.Array, gids: jax.Array,
                  num_segments: int) -> Tuple[jax.Array, jax.Array]:
    """First row's value per segment, null or not — Spark
    first(ignoreNulls=false) semantics; rows pre-sorted => deterministic.
    Empty segments (segment_min identity = int64 max) come back invalid."""
    xp = xp_of(values, valid, gids)
    n = values.shape[0]
    pos = xp.arange(n, dtype=xp.int64)
    if xp is np:
        first_pos = _np_segment_reduce(pos, gids, num_segments, np.minimum,
                                       np.int64(n))
    else:
        first_pos = jax.ops.segment_min(pos, gids,
                                        num_segments=num_segments)
    has_rows = first_pos < n
    idx = xp.clip(first_pos, 0, n - 1)
    return xp.take(values, idx), xp.take(valid, idx) & has_rows


def segment_first_ignores_null(values: jax.Array, valid: jax.Array,
                               gids: jax.Array, num_segments: int
                               ) -> Tuple[jax.Array, jax.Array]:
    """First NON-NULL value per segment — Spark first(ignoreNulls=true)
    (ref agg/first_ignores_null.rs)."""
    xp = xp_of(values, valid, gids)
    n = values.shape[0]
    pos = xp.where(valid, xp.arange(n, dtype=xp.int64), xp.int64(n))
    if xp is np:
        first_pos = _np_segment_reduce(pos, gids, num_segments, np.minimum,
                                       np.int64(n))
    else:
        first_pos = jax.ops.segment_min(pos, gids,
                                        num_segments=num_segments)
    has_valid = first_pos < n
    idx = xp.clip(first_pos, 0, n - 1)
    return xp.take(values, idx), has_valid


def _identity_for(dtype, minimum: bool, xp=jnp):
    if jnp.issubdtype(dtype, jnp.floating):
        return xp.array(-jnp.inf if minimum else jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return xp.array(minimum is True and False or True, dtype=dtype)
    info = jnp.iinfo(dtype)
    return xp.array(info.min if minimum else info.max, dtype=dtype)


def segment_boundaries_to_offsets(gids: jax.Array, num_groups: jax.Array,
                                  capacity: int) -> jax.Array:
    """Per-group start offsets (int32[capacity+1]) from dense sorted gids."""
    xp = xp_of(gids, num_groups)
    if xp is np:
        counts = np.bincount(np.where(gids < capacity, gids, capacity),
                             minlength=capacity + 1)[:capacity]
        return np.concatenate([np.zeros(1, counts.dtype),
                               np.cumsum(counts)])
    counts = jnp.bincount(jnp.where(gids < capacity, gids, capacity),
                          length=capacity + 1)[:capacity]
    return jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)])


def merge_sorted_host(runs, key_fn):
    """Host-side k-way merge of sorted run iterators (loser-tree analog).

    `runs`: list of iterators yielding (key_tuple, payload) in sorted order.
    Python heapq replaces the tournament tree (ref algorithm/loser_tree.rs) —
    the host merge is IO-bound, not compute-bound."""
    import heapq
    heap = []
    for i, it in enumerate(runs):
        try:
            k, p = next(it)
            heap.append((k, i, p, it))
        except StopIteration:
            pass
    heapq.heapify(heap)
    while heap:
        k, i, p, it = heapq.heappop(heap)
        yield k, p
        try:
            k2, p2 = next(it)
            heapq.heappush(heap, (k2, i, p2, it))
        except StopIteration:
            pass

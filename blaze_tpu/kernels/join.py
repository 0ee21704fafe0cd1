"""Device join-probe kernels: match counting + bounded pair expansion.

Parity target: joins/join_hash_map.rs:277 (JoinHashMap probe) and
bhj/semi_join.rs — the reference probes a pointer-linked hash map row by
row.  The TPU-native form keeps the build side as a HASH-SORTED table
(hashes ascending, with a unique-hash run-length index) and probes with
two jit'd programs:

  1. `probe_counts`: vectorized binary search of every probe hash into the
     unique build hashes -> (start, count) per probe row.  One XLA program,
     no data-dependent shapes.
  2. `expand_pairs`: two-pass expansion — exclusive-scan of counts gives
     each probe row its output offset; a bounded gather materializes
     (probe_idx, build_idx) pair arrays of STATIC size `cap`.  Rows past a
     probe's count are masked invalid.  The true total comes back with the
     pairs; if it exceeds `cap` the caller re-invokes with the next
     power-of-two bucket (bounded recompiles, same overflow-chunking
     discipline as the fused agg table).

Hash collisions are verified by the caller against the real key columns,
so a colliding pair can never produce a wrong join row.

The sort-merge join's device path (ops/joins/merge.py) uses the same shape
over real keys instead of hashes: the searched side is key-SORTED already,
so `merge_bounds` is `probe_counts` with a lexicographic comparison over
the order keys of kernels/compare.py, and the pairs come out of the same
`expand_pairs` (metered apart, so the trace tells the two joins' time
apart).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def build_runs(sorted_hashes: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(unique_hashes, run_start, run_count) for an ascending hash array.

    Positional arrays are int32 whenever they can be (build side below
    2^31 rows): TPU v5e emulates every 64-bit op as a multi-instruction
    sequence (~10x), and these arrays ride the probe hot path."""
    uh, start, count = np.unique(sorted_hashes, return_index=True,
                                 return_counts=True)
    idt = np.int32 if sorted_hashes.shape[0] < (1 << 31) else np.int64
    return uh, start.astype(idt), count.astype(idt)


from blaze_tpu.bridge.xla_stats import meter_jit
from blaze_tpu.kernels import hashing as H
from blaze_tpu.xputil import to_host


@functools.partial(meter_jit, name="join.probe_counts")
def probe_counts(unique_hashes: jax.Array, run_start: jax.Array,
                 run_count: jax.Array, probe_hashes: jax.Array,
                 probe_null: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-probe-row (start, count) into the sorted build table.

    Null-key probe rows count 0 (SQL equi-join semantics)."""
    pos = jnp.searchsorted(unique_hashes, probe_hashes)
    n_unique = unique_hashes.shape[0]
    pos_c = jnp.clip(pos, 0, max(n_unique - 1, 0))
    hit = (pos < n_unique) & (jnp.take(unique_hashes, pos_c) == probe_hashes)
    hit = hit & ~probe_null
    start = jnp.where(hit, jnp.take(run_start, pos_c), 0)
    count = jnp.where(hit, jnp.take(run_count, pos_c), 0)
    return start, count


@functools.partial(meter_jit, name="join.expand_pairs",
                   static_argnames=("cap",))
def expand_pairs(start: jax.Array, count: jax.Array, cap: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Bounded two-pass expansion of (start, count) runs into pair arrays.

    Returns (probe_idx[cap], sorted_pos[cap], valid[cap], total).
    `sorted_pos` indexes the hash-sorted build order; the caller maps it
    through the build permutation.  Entries at output offset >= cap are
    dropped (caller grows `cap` and retries when total > cap).

    Pair arrays are int32 when `cap` fits (the 64-bit-emulation rule
    from build_runs); `total` is always computed in int64 because the
    TRUE pair count can exceed the current bucket."""
    n = start.shape[0]
    idt = jnp.int32 if cap < (1 << 31) else jnp.int64
    offsets = jnp.cumsum(count.astype(jnp.int64)) - count
    total = offsets[-1] + count[-1] if n else jnp.int64(0)
    off32 = offsets.astype(idt)
    # scatter probe-row boundaries into the output domain, then a
    # max-scan assigns each output slot its probe row (vectorized
    # "which run am I in": standard scan-based expansion)
    slot_probe = jnp.zeros(cap, dtype=idt).at[
        jnp.where(count > 0, offsets, cap)].max(
        jnp.arange(n, dtype=idt), mode="drop")
    slot_probe = jax.lax.associative_scan(jnp.maximum, slot_probe)
    out_pos = jnp.arange(cap, dtype=idt)
    valid = out_pos < jnp.minimum(total, cap).astype(idt)
    p = jnp.clip(slot_probe, 0, max(n - 1, 0))
    within = out_pos - jnp.take(off32, p)
    sorted_pos = jnp.take(start, p).astype(idt) + within
    return p, sorted_pos, valid, total


def _pow2_at_least(n: int) -> int:
    return max(1024, 1 << int(max(n, 1) - 1).bit_length())


def probe_expand_device(unique_hashes, run_start, run_count, sorted_idx,
                        probe_hashes, probe_null
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Full device probe: counts + expansion entirely as XLA programs,
    ONE scalar sync for the total, one D2H for the final pair arrays.
    Overflow grows the static output bucket and re-runs (cached compile
    per bucket)."""
    start, count = probe_counts(unique_hashes, run_start, run_count,
                                probe_hashes, probe_null)
    total = int(to_host(jnp.sum(count)))
    if total == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    cap = _pow2_at_least(total)
    p, sorted_pos, valid, _t = expand_pairs(start, count, cap)
    # regression guard: the pair arrays must stay narrow — a silent
    # promotion back to i64 would re-enter TPU 64-bit emulation
    want = jnp.int32 if cap < (1 << 31) else jnp.int64
    assert p.dtype == want and sorted_pos.dtype == want, (
        f"join pair arrays widened: {p.dtype}/{sorted_pos.dtype}, "
        f"expected {want} at cap={cap}")
    p_np, sp_np, v_np = to_host((p, sorted_pos, valid))
    p_np = p_np[v_np[: len(p_np)]][:total]
    sp_np = sp_np[v_np[: len(sp_np)]][:total]
    b_np = np.asarray(sorted_idx)[sp_np]
    return p_np, b_np


# ---------------------------------------------------------------------------
# broadcast join on a unique build key: the rows stay on the chip
# ---------------------------------------------------------------------------

def hash_valid(flat_cols, tids):
    """(xxhash64 of the key columns int64[cap], any key NULL bool[cap]):
    the join's hash of a row, build side and probe side alike.
    flat_cols: ((data, validity), ...) aligned with the type ids `tids`;
    float keys are normalised first (-0.0 joins 0.0, NaN joins NaN)."""
    flat_cols = H.norm_float_keys(flat_cols, tids, jnp)
    cols = [(v, val, tid) for (v, val), tid in zip(flat_cols, tids)]
    h = H.hash_columns(cols, seed=42, xp=jnp, algo="xxhash64")
    anyn = None
    for (_v, val) in flat_cols:
        nv = ~val
        anyn = nv if anyn is None else (anyn | nv)
    return h, anyn


def _shift_front(a: jax.Array, s: int) -> jax.Array:
    """a[i + s] at i; what falls off the end is filled with zeros."""
    return jnp.concatenate([a[s:], jnp.zeros((s,), a.dtype)])


def pack_front(keep: jax.Array, arrays):
    """Every array's kept lanes moved to the front, in order: lane i goes
    to (number of kept lanes before i).  No gather and no sort: a kept
    lane has to move left by d = (dropped lanes before it), and moves by
    d's bits, lowest first, one static shift and one select a bit and an
    array.  Two kept lanes never meet: between kept lanes i < j lie at
    least d_j - d_i dropped ones, so after the bits below 2^b the lanes'
    places still differ by at least one (d mod 2^b differs by no more
    than d does).  Lanes from the count on hold leftovers."""
    cap = keep.shape[0]
    idt = jnp.int32
    rank = jnp.cumsum(keep, dtype=idt) - 1
    d = jnp.where(keep, jnp.arange(cap, dtype=idt) - rank, 0)
    live = keep
    arrays = list(arrays)
    for b in range((cap - 1).bit_length()):  # 1 << b stays under cap
        s = 1 << b
        moves = live & (((d >> b) & 1) == 1)
        arrives = _shift_front(moves, s)
        arrays = [jnp.where(arrives, _shift_front(a, s), a) for a in arrays]
        d = jnp.where(arrives, _shift_front(d, s), d)
        live = arrives | (live & ~moves)
    return arrays


def _keys_equal(a: jax.Array, b: jax.Array, tid: str) -> jax.Array:
    if tid in ("float32", "float64"):
        return (a == b) | (jnp.isnan(a) & jnp.isnan(b))
    return a == b


def _row_mask(lanes: int, rows, selection):
    mask = jnp.arange(lanes, dtype=jnp.int32) < rows
    return mask if selection is None else mask & selection


def _search_rows(uh, urow, build_keys, probe_keys, tids, rows, selection):
    """(candidate build row, hit) a lane through the hash-sorted index:
    the probe keys' hash searched in `uh`, the hash and then the REAL
    keys compared at the candidate (a hash collision joins nothing)."""
    h, any_null = hash_valid(probe_keys, tids)
    mask = _row_mask(h.shape[0], rows, selection)
    pos = jnp.searchsorted(uh, h)
    pos = jnp.clip(pos, 0, uh.shape[0] - 1)
    row = jnp.take(urow, pos)
    hit = (jnp.take(uh, pos) == h) & (row >= 0) & ~any_null & mask
    row = jnp.maximum(row, 0)
    for (pk, _pv), bk, tid in zip(probe_keys, build_keys, tids):
        (bk, _), = H.norm_float_keys([(jnp.take(bk, row), None)], (tid,),
                                     jnp)
        hit = hit & _keys_equal(pk, bk, tid)
    return row, hit


def _direct_rows(drow, kmin, probe_key, rows, selection):
    """(candidate build row, hit) a lane through the direct-address
    index of ONE dense integer key: `drow[key - kmin]`.  The address is
    the key, so there is nothing to hash, search or compare.  The
    difference wraps in the key's own width (an int8 or int16 key is
    widened to 32 bits first, since the index may have more entries than
    such a key has positive values), and a wrapped difference that lands
    inside the index is still the true one: key and `kmin` + offset are
    both representable, so equal modulo the width is equal."""
    key, valid = probe_key
    if key.dtype.itemsize < 4:
        key = key.astype(jnp.int32)
    off = key - kmin.astype(key.dtype)
    size = drow.shape[0]
    inside = (off >= 0) & (off < size)
    row = jnp.take(drow, jnp.clip(off, 0, size - 1).astype(jnp.int32))
    hit = (inside & (row >= 0) & valid
           & _row_mask(key.shape[0], rows, selection))
    return jnp.maximum(row, 0), hit


@functools.partial(meter_jit, name="join.probe_gather",
                   static_argnames=("tids",))
def probe_gather(uh, urow, build_keys, build_cols, probe_keys, probe_cols,
                 rows, selection, tids, direct=None):
    """One probe batch of an inner join on a UNIQUE build key, whole.

    build_cols / probe_cols: ((data, validity), ...) of the columns the
    join puts out; probe_keys: ((data, validity), ...) of the batch's
    keys, of the types `tids`; rows, selection: the batch's row count and
    selection mask (or None), its `row_mask()`.

    The build side's index comes in one of two forms, two traces of this
    one program (`JoinMap.direct_key` says which a map has):

      * searched (`direct` None).  uh: the build side's distinct hashes,
        ascending, padded with the largest int64; urow: the build row of
        each, -1 for padding and for a build row with a NULL key (NULL
        joins nothing); build_keys: the build side's key data, one array
        a key.  Hashes the probe keys, searches `uh` (as `probe_counts`
        does), takes the candidate's build row and compares the REAL
        keys there;
      * direct (`direct` = (drow, kmin); uh, urow and build_keys None):
        one dense integer key.  drow[k - kmin] is the build row of key
        k, -1 where no build row has that key (and in the padding to a
        power of two); the candidate is read at the key's own offset,
        with no hash, no search and no comparison.

    From the candidate on the two are one: gathers the build columns
    there and packs the matched rows of both sides to the front.
    Returns (probe columns, build columns, count), columns as
    (data, validity) with validity false from `count` on."""
    probe_keys = H.norm_float_keys(probe_keys, tids, jnp)
    if direct is None:
        row, hit = _search_rows(uh, urow, build_keys, probe_keys, tids,
                                rows, selection)
    else:
        row, hit = _direct_rows(*direct, probe_keys[0], rows, selection)
    cols = list(probe_cols) + [(jnp.take(d, row), jnp.take(v, row))
                               for d, v in build_cols]
    packed = pack_front(hit, [a for dv in cols for a in dv])
    count = jnp.sum(hit, dtype=jnp.int32)
    inside = jnp.arange(hit.shape[0], dtype=jnp.int32) < count
    out = [(packed[2 * i], packed[2 * i + 1] & inside)
           for i in range(len(cols))]
    n_probe = len(probe_cols)
    return tuple(out[:n_probe]), tuple(out[n_probe:]), count


# ---------------------------------------------------------------------------
# sort-merge join: the same probe over key-sorted sides
# ---------------------------------------------------------------------------

def _order_operands(cols, dtypes):
    """Flat (bucket, key) operands of kernels/compare.order_key, ascending
    and NULLs first: how both children of a merge join are sorted."""
    from blaze_tpu.kernels import compare
    ops = []
    for (data, valid), dt in zip(cols, dtypes):
        ops.extend(compare.order_key(data, valid, dt))
    return ops


def _lex_less(a_ops, b_ops):
    """a < b, lexicographically over operand lists, elementwise."""
    less = jnp.zeros(a_ops[0].shape, dtype=bool)
    for a, b in reversed(list(zip(a_ops, b_ops))):
        less = (a < b) | ((a == b) & less)
    return less


def _merge_bounds(probe_cols, build_cols, probe_rows, build_rows, dtypes):
    """Per probe row, its equal-key run in the key-sorted build side.

    probe_cols / build_cols: ((data, validity), ...) of the join keys, both
    sides of one type per key (`promote_join_key_exprs`), the build side
    sorted ascending, NULLs first, over its first `build_rows` rows.
    Returns (lo, count, total): `lo` is the lower bound of the probe row's
    key in the build side (where its run starts, or would), `count` the
    run's length, 0 for a probe row past `probe_rows`, one with a NULL key
    (SQL: NULL joins nothing; NaN joins NaN, as order_key encodes it) or
    one without a partner; `total` their int64 sum.

    One vectorised binary search (log2(capacity) rounds of one gather per
    operand), then the run's end from the build side's own run boundaries:
    no second search for the upper bound."""
    p_ops = _order_operands(probe_cols, dtypes)
    b_ops = _order_operands(build_cols, dtypes)
    cap_p, cap_b = p_ops[0].shape[0], b_ops[0].shape[0]
    idt = jnp.int32
    n_b = jnp.asarray(build_rows, idt)

    def step(_, lo_hi):
        lo, hi = lo_hi
        mid = (lo + hi) >> 1
        at = [jnp.take(o, mid, mode="clip") for o in b_ops]
        go_right = (lo < hi) & _lex_less(at, p_ops)
        return (jnp.where(go_right, mid + 1, lo),
                jnp.where((lo < hi) & ~go_right, mid, hi))

    lo, _hi = jax.lax.fori_loop(
        0, max(1, cap_b.bit_length()), step,
        (jnp.zeros(cap_p, idt), jnp.full(cap_p, n_b, idt)))
    # where each build row's run ends: the next row that differs from its
    # predecessor, found by a reverse running minimum
    pos_b = jnp.arange(cap_b, dtype=idt)
    differs = jnp.zeros(cap_b, dtype=bool)
    for o in b_ops:
        differs = differs | jnp.concatenate(
            [jnp.ones(1, bool), o[1:] != o[:-1]])
    starts = jnp.where(differs & (pos_b < n_b), pos_b, n_b)
    run_end = jnp.concatenate([jax.lax.cummin(starts, reverse=True)[1:],
                               n_b[None]])
    at = [jnp.take(o, lo, mode="clip") for o in b_ops]
    hit = lo < n_b
    for a, p in zip(at, p_ops):
        hit = hit & (a == p)
    for _data, valid in probe_cols:
        hit = hit & valid
    hit = hit & (jnp.arange(cap_p, dtype=idt) < probe_rows)
    count = jnp.where(hit, jnp.take(run_end, lo, mode="clip") - lo, 0)
    return lo, count.astype(idt), jnp.sum(count.astype(jnp.int64))


merge_bounds = meter_jit(_merge_bounds, name="smj.bounds",
                         static_argnames=("dtypes",))
# The same expansion as a program of the merge join's own name,
# `jit_expand_pairs__smj_expand_pairs`.  A program has one name, and the
# trace is read by it: under `join.expand_pairs` the merge join's device
# time (`__smj_`) would leave this part of its work out, and its share of
# the roofline read too high; q01 runs both joins in one query.
merge_expand_pairs = meter_jit(expand_pairs.__wrapped__,
                               name="smj.expand_pairs",
                               static_argnames=("cap",))

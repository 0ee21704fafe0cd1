"""Fused, fully-jittable stage kernels (static shapes end-to-end).

The eager operator layer (ops/) favors generality: it syncs group counts to
the host per batch.  For the hot TPC-DS shapes the stage compiler fuses
scan-side filter + project + partial aggregation into ONE jit'd function
with a FIXED-capacity group table — no host sync inside the stage, so XLA
fuses the whole pipeline (hash, sort, segmented reduce) into one program.
This mirrors how the reference keeps its whole operator chain inside one
tokio task (rt.rs:156): here the chain lives inside one XLA computation.

Key building block: `partial_agg_table` — sort-based grouping into a
static `num_slots` table (key cols + acc cols + slot validity).  Overflow
slots (more distinct groups than num_slots) spill into a "overflowed"
count the host can check — the AGG_TRIGGER_PARTIAL_SKIPPING analog
(agg_table.rs:108-122): the host reruns the batch through the general
path when it overflows.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.kernels import compare
from blaze_tpu.xputil import to_device, to_host


class AggTable(NamedTuple):
    """Fixed-capacity columnar group table (the device AccTable)."""

    keys: Tuple[jax.Array, ...]        # each (num_slots,)
    key_valid: Tuple[jax.Array, ...]   # per-key null flags
    accs: Tuple[jax.Array, ...]        # accumulator columns (num_slots,)
    acc_valid: Tuple[jax.Array, ...]
    slot_valid: jax.Array              # (num_slots,) bool
    num_groups: jax.Array              # scalar int32 (may exceed num_slots!)


def sort_by_keys(key_cols: Sequence[Tuple[jax.Array, jax.Array]],
                 valid_mask: jax.Array):
    """Sort rows by (encoded) grouping keys; returns (perm, sorted ops,
    sorted validity)."""
    operands = []
    for data, kvalid in key_cols:
        from blaze_tpu.schema import DataType, TypeId
        bucket, key = compare.order_key(
            data, kvalid, _dtype_of(data), False, True)
        operands.append(bucket)
        operands.append(key)
    perm = compare.lexsort_indices(operands, valid_mask)
    sorted_ops = [jnp.take(o, perm) for o in operands]
    sorted_valid = jnp.take(valid_mask, perm)
    return perm, sorted_ops, sorted_valid


def _dtype_of(data: jax.Array):
    from blaze_tpu import schema as S
    m = {"bool": S.BOOL, "int8": S.INT8, "int16": S.INT16, "int32": S.INT32,
         "int64": S.INT64, "float32": S.FLOAT32, "float64": S.FLOAT64}
    return m[jnp.dtype(data.dtype).name]


def partial_agg_table(key_cols: Sequence[Tuple[jax.Array, jax.Array]],
                      agg_specs: Sequence[Tuple[str, jax.Array, jax.Array]],
                      valid_mask: jax.Array, num_slots: int) -> AggTable:
    """One fused pass: sort rows by key, segment-reduce into a static table.

    agg_specs: (kind, values, validity) with kind in sum/count/min/max.
    Fully traceable — `num_slots` is the only static parameter.
    """
    n = valid_mask.shape[0]
    perm, sorted_ops, sorted_valid = sort_by_keys(key_cols, valid_mask)
    boundary = compare.rows_differ_from_prev(sorted_ops) & sorted_valid
    first_valid = jnp.argmax(sorted_valid)
    boundary = boundary | ((jnp.arange(n) == first_valid) & sorted_valid)
    gids = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    # rows of groups beyond num_slots scatter out of range (dropped)
    gids = jnp.where(sorted_valid, gids, num_slots)

    keys_out: List[jax.Array] = []
    kvalid_out: List[jax.Array] = []
    for data, kvalid in key_cols:
        sd = jnp.take(data, perm)
        sv = jnp.take(kvalid, perm) & sorted_valid
        # first row of each segment carries the key
        kd = jnp.zeros(num_slots, dtype=data.dtype).at[
            jnp.where(boundary, gids, num_slots)].set(sd, mode="drop")
        kv = jnp.zeros(num_slots, dtype=bool).at[
            jnp.where(boundary, gids, num_slots)].set(sv, mode="drop")
        keys_out.append(kd)
        kvalid_out.append(kv)

    accs_out: List[jax.Array] = []
    avalid_out: List[jax.Array] = []
    for kind, values, avalid in agg_specs:
        sv = jnp.take(values, perm) if values is not None else None
        sav = (jnp.take(avalid, perm) if avalid is not None
               else jnp.ones(n, dtype=bool)) & sorted_valid
        if kind == "count":
            acc = jax.ops.segment_sum(sav.astype(jnp.int64), gids,
                                      num_segments=num_slots)
            accs_out.append(acc)
            avalid_out.append(jnp.ones(num_slots, dtype=bool))
            continue
        if kind == "sum":
            dt = (jnp.float64 if jnp.issubdtype(sv.dtype, jnp.floating)
                  else jnp.int64)
            masked = jnp.where(sav, sv.astype(dt), 0)
            acc = jax.ops.segment_sum(masked, gids, num_segments=num_slots)
        elif kind == "min":
            big = _identity(sv.dtype, False)
            acc = jax.ops.segment_min(jnp.where(sav, sv, big), gids,
                                      num_segments=num_slots)
        elif kind == "max":
            small = _identity(sv.dtype, True)
            acc = jax.ops.segment_max(jnp.where(sav, sv, small), gids,
                                      num_segments=num_slots)
        else:
            raise ValueError(f"unsupported fused agg kind {kind}")
        has = jax.ops.segment_sum(sav.astype(jnp.int32), gids,
                                  num_segments=num_slots) > 0
        acc = jnp.where(has, acc, jnp.zeros_like(acc))
        accs_out.append(acc)
        avalid_out.append(has)

    slot_valid = jnp.arange(num_slots) < jnp.minimum(num_groups, num_slots)
    return AggTable(tuple(keys_out), tuple(kvalid_out), tuple(accs_out),
                    tuple(avalid_out), slot_valid, num_groups)


def pack_dense_keys(key_cols: Sequence[Tuple[jax.Array, jax.Array]],
                    ranges: Sequence[Tuple[int, int]]
                    ) -> Tuple[jax.Array, int]:
    """Pack bounded-range keys into ONE dense group id (row-major strides).

    The TPU fast path: when every grouping key has a known bound — int keys
    with parquet min/max stats, or dictionary codes (always dense) — the
    group id is pure arithmetic and aggregation needs NO SORT, just
    scatter-adds.  Null gets the extra slot per key (range + 1 values).
    Returns (gid array, total_slots)."""
    total = 1
    strides = []
    for lo, hi in ranges:
        strides.append(total)
        total *= (hi - lo + 2)  # +1 for the null slot
    gid = None
    for (data, valid), (lo, hi), stride in zip(key_cols, ranges, strides):
        k = jnp.clip(data.astype(jnp.int64) - lo, 0, hi - lo)
        k = jnp.where(valid, k, hi - lo + 1)
        contrib = k * stride
        gid = contrib if gid is None else gid + contrib
    return gid, total


def pack_dense_keys_i32(key_cols: Sequence[Tuple[jax.Array, jax.Array]],
                        ranges: Sequence[Tuple[int, int]]
                        ) -> Tuple[jax.Array, int]:
    """pack_dense_keys in the 32-bit compute tier: same stride layout,
    all arithmetic in int32 (TPU v5e emulates every 64-bit op as a
    multi-instruction sequence; dense tables are capped far below 2^31
    so the id math never needs the width).  Only the initial `data - lo`
    shift touches the stored key dtype."""
    total = 1
    strides = []
    for lo, hi in ranges:
        strides.append(total)
        total *= (hi - lo + 2)
    assert total < (1 << 31), "dense table exceeds the i32 tier"
    gid = None
    for (data, valid), (lo, hi), stride in zip(key_cols, ranges, strides):
        span = hi - lo
        k = jnp.clip(data - jnp.asarray(lo, dtype=data.dtype),
                     0, span).astype(jnp.int32)
        k = jnp.where(valid, k, jnp.int32(span + 1))
        contrib = k * jnp.int32(stride)
        gid = contrib if gid is None else gid + contrib
    return gid, total


def unpack_dense_keys(slots, ranges: Sequence[Tuple[int, int]], xp=jnp
                      ) -> List[Tuple[jax.Array, jax.Array]]:
    """Inverse of pack_dense_keys for slot indices -> (key, validity).
    Pure stride arithmetic: pass xp=numpy to decode host-side without a
    device round trip."""
    out = []
    rem = slots.astype(xp.int64)
    for lo, hi in ranges:
        size = hi - lo + 2
        k = rem % size
        rem = rem // size
        valid = k < (hi - lo + 1)
        out.append((xp.where(valid, k + lo, 0), valid))
    return out


def dense_partial_agg(gid: jax.Array, num_slots: int,
                      agg_specs: Sequence[Tuple[str, Optional[jax.Array],
                                                Optional[jax.Array]]],
                      valid_mask: jax.Array):
    """Sort-free aggregation: one segment-reduce per accumulator, keyed by
    a precomputed dense group id.  Rows with valid_mask False scatter out
    of range.  Returns (accs, acc_valid, slot_occupied)."""
    g = jnp.where(valid_mask, gid, num_slots)
    accs: List[jax.Array] = []
    avalid: List[jax.Array] = []
    occupied = jax.ops.segment_sum(
        valid_mask.astype(jnp.int32), g, num_segments=num_slots) > 0
    for kind, values, vvalid in agg_specs:
        vv = (vvalid if vvalid is not None
              else jnp.ones_like(valid_mask)) & valid_mask
        if kind == "count":
            acc = jax.ops.segment_sum(vv.astype(jnp.int64), g,
                                      num_segments=num_slots)
            accs.append(acc)
            avalid.append(jnp.ones(num_slots, dtype=bool))
            continue
        if kind == "sum":
            dt = (jnp.float64 if jnp.issubdtype(values.dtype, jnp.floating)
                  else jnp.int64)
            acc = jax.ops.segment_sum(jnp.where(vv, values.astype(dt), 0),
                                      g, num_segments=num_slots)
        elif kind == "min":
            big = _identity(values.dtype, False)
            acc = jax.ops.segment_min(
                jnp.where(vv, values, big),
                jnp.where(vv, g, num_slots), num_segments=num_slots)
        elif kind == "max":
            small = _identity(values.dtype, True)
            acc = jax.ops.segment_max(
                jnp.where(vv, values, small),
                jnp.where(vv, g, num_slots), num_segments=num_slots)
        else:
            raise ValueError(f"unsupported dense agg kind {kind}")
        has = jax.ops.segment_sum(vv.astype(jnp.int32), g,
                                  num_segments=num_slots) > 0
        accs.append(jnp.where(has, acc, jnp.zeros_like(acc)))
        avalid.append(has)
    return accs, avalid, occupied


# `owner` of a slot nothing holds.  Every other value it takes is smaller:
# a claim made inside a step (zero or more) and, between steps, the
# negative that says which keys of the stored group are NULL.
FREE = int(np.iinfo(np.int32).max)

# One null bit a key column in an int32 `owner`: `-1 - nullbits` has to
# stay negative.  A grouping of more columns is declined where the table
# is planned (plan/fused.py `_try_fuse_agg`).
MAX_KEY_COLUMNS = 31


def key_lane_dtypes(dtype) -> Tuple:
    """The dtypes of the lanes `split_key` makes of a key column."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.int64:
        return (jnp.dtype(jnp.uint32), jnp.dtype(jnp.int32))
    if dtype == jnp.uint64:
        return (jnp.dtype(jnp.uint32), jnp.dtype(jnp.uint32))
    return (dtype,)


def split_key(data: jax.Array) -> Tuple[jax.Array, ...]:
    """A key column as the table stores it: lanes of at most 32 bits.  A
    64-bit integer is its low word (uint32) and its high word (int32, or
    uint32 for an unsigned key: the high word says which).  The chip
    holds a 64-bit lane as two 32-bit ones and scatters the pair through
    a path of its own, 3.0 ms over 32,768 lanes where two scatters of a
    word each take 0.41 together (PERF.md section 6, PR 47).  Anything
    else is one lane as it is (a float64 too: its bits cannot be viewed
    on the chip)."""
    lanes = key_lane_dtypes(data.dtype)
    if len(lanes) == 1:
        return (data,)
    return data.astype(lanes[0]), (data >> 32).astype(lanes[1])


def key_dtype(lanes: Sequence) -> np.dtype:
    """The dtype of the key column these lanes store."""
    if len(lanes) == 1:
        return np.dtype(lanes[0].dtype)
    return np.dtype(np.int64 if lanes[1].dtype == np.int32 else np.uint64)


def join_key(lanes: Sequence[jax.Array]) -> jax.Array:
    """The key column `split_key` made these lanes of.  Elementwise, on
    device arrays or on numpy's after a readback: over the slots a drain
    or a rehash has gathered, never over a step."""
    if len(lanes) == 1:
        return lanes[0]
    low, high = lanes
    wide = key_dtype(lanes)
    return (high.astype(wide) << 32) | low.astype(wide)


def key_valid_lanes(owner: jax.Array, num_keys: int) -> Tuple[jax.Array, ...]:
    """Per key column, whether the group `owner` stands for holds a value
    there (bit i of `-1 - owner` clear).  Elementwise over `owner`, of
    whatever shape, on the device or on numpy's copy: the used slots a
    drain has gathered, or the whole lane.  A FREE slot reads as
    anything."""
    nullbits = ~owner   # -1 - owner
    return tuple(((nullbits >> i) & 1) == 0 for i in range(num_keys))


class HashAggCarry(NamedTuple):
    """Device open-addressing group table (the agg_hash_map.rs analog,
    ref agg_hash_map.rs open-addressing map keyed by grouping bytes).

    TPU-first redesign: linear-probe insertion is expressed as a BOUNDED
    number of scatter/gather rounds — no sort, no per-row loop, no
    data-dependent shapes.  Rounds run over the whole batch while many
    rows are unplaced and over a fixed narrow buffer of the rest
    afterwards (hash_agg_step), and stop when every row is placed.  A
    multi-operand `lax.sort` grouping program takes minutes to compile
    on TPU; this compiles in seconds.  Its cost is its rounds: each op
    of a round costs by the lanes it runs over (PERF.md section 6), and
    nothing in a step runs over the slots.

    What a slot holds is said by ONE int32 lane, `owner`: FREE, or
    `-1 - nullbits` once a group has it (bit i set when key i of the
    stored group is NULL).  Between steps it holds nothing else; what it
    holds inside one is hash_agg_step's business.  `keys` holds each
    key column as `split_key` lays it out, lanes of at most 32 bits (an
    int64 key is two); at a free slot they are whatever was there.
    `groups` counts the used slots.  A consumer derives `used`,
    `key_valid` and the key columns once a drain or a rehash, over the
    slots it gathered (`key_valid_lanes`, `join_key`), never a step."""

    keys: Tuple[Tuple[jax.Array, ...], ...]   # per key its lanes, each (S,)
    accs: Tuple[jax.Array, ...]
    acc_valid: Tuple[jax.Array, ...]
    owner: jax.Array                   # (S,) int32
    groups: jax.Array                  # scalar int32

    @property
    def used(self) -> jax.Array:
        """(S,) bool, derived over every slot."""
        return self.owner < 0

    @property
    def key_valid(self) -> Tuple[jax.Array, ...]:
        """Per key (S,) bool, derived over every slot."""
        return key_valid_lanes(self.owner, len(self.keys))

    @property
    def key_columns(self) -> Tuple[jax.Array, ...]:
        """Per key its (S,) column, joined over every slot."""
        return tuple(join_key(lanes) for lanes in self.keys)


def init_hash_carry(key_dtypes: Sequence, acc_kinds: Sequence[str],
                    acc_dtypes: Sequence, num_slots: int) -> HashAggCarry:
    # slots, row numbers and claims are int32: a table has at most 2^30
    # slots (the stage loop stops at 2^24, runtime/loop.py `_MAX_SLOTS`)
    assert num_slots <= 1 << 30 and len(key_dtypes) <= MAX_KEY_COLUMNS
    keys = tuple(tuple(jnp.zeros(num_slots, dtype=lane)
                       for lane in key_lane_dtypes(dt)) for dt in key_dtypes)
    accs, avalid = init_accumulators(acc_kinds, acc_dtypes, num_slots)
    return HashAggCarry(keys, accs, avalid,
                        jnp.full(num_slots, FREE, dtype=jnp.int32),
                        jnp.int32(0))


def normalize_float_keys(key_cols):
    """Grouping normalizes -0.0 to 0.0 AND NaN to one canonical bit
    pattern BEFORE hashing (Spark's NormalizeFloatingNumbers does both
    upstream of the hash, so the raw-bits hash kernel itself stays
    bit-exact with Spark).  Without the NaN leg, differently-encoded
    NaNs hash to different slots while the slot-match treats any
    NaN == NaN — keys could land in two groups.  The table stores the
    normalized keys, so rows that leave a partial aggregation without
    entering one (runtime/loop.py's pass-through) take the same form."""
    def _norm(d):
        d = jnp.where(d == 0, jnp.abs(d), d)
        return jnp.where(jnp.isnan(d), jnp.array(jnp.nan, dtype=d.dtype), d)

    return [(_norm(d), v)
            if jnp.issubdtype(d.dtype, jnp.floating) else (d, v)
            for d, v in key_cols]


# A probe round costs by the lanes it runs over, not by the rows still
# unplaced: on a v5e 19.5 ms at 65,536 lanes, 5.8 at 16,384, 2.9 at 8,192,
# 1.5 at 4,096 (tools/fold_grid.py; PERF.md section 6, PR 29), and after
# round one few rows are left: a first round leaves about the table's
# load of a batch's live rows (the stage loop keeps that under 1/4, and
# a reduce task of the benchmark under 7%), the second under 1%.  So
# once the unplaced rows fit 1/_NARROW_SHARE of the batch's lanes they
# are compacted (1.8 ms for 65,536 lanes, gathers included) and probed at
# that width.  An eighth holds the first round's leftover of a batch with
# 41% of its lanes live up to a load of 1/4, and of one with every lane
# live up to about 1/10; a smaller share would save 1.5 ms a narrow round
# and cost a second full round (19.5 ms) wherever the leftover no longer
# fitted.  A batch of _NARROW_MIN_LANES or fewer compiles no narrow
# phase: its full rounds already cost what a narrow round costs.
_NARROW_SHARE = 8
_NARROW_MIN_LANES = 2048


def narrow_width(n: int) -> int:
    """Lanes of the narrow probe of an `n`-lane batch; 0 when the batch
    has no narrow phase."""
    return n // _NARROW_SHARE if n > _NARROW_MIN_LANES else 0


def _compact_lanes(unplaced: jax.Array, width: int) -> jax.Array:
    """Positions of the first `width` set lanes of `unplaced`, in lane
    order, padded with the lane count (out of range: gathers clamp it
    and scatters drop it).  A cumulative sum and ONE scatter over the
    lanes: `jnp.nonzero(size=...)` is a scatter-add that costs 65 ns a
    lane on a v5e (PERF.md section 6, PR 25)."""
    n = unplaced.shape[0]
    rank = jnp.cumsum(unplaced.astype(jnp.int32)) - 1
    return jnp.full(width, n, dtype=jnp.int32).at[
        jnp.where(unplaced, rank, width)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")


def hash_agg_step(carry: HashAggCarry,
                  key_cols: Sequence[Tuple[jax.Array, jax.Array]],
                  agg_specs: Sequence[Tuple[str, Optional[jax.Array],
                                            Optional[jax.Array]]],
                  mask: jax.Array, probe_rounds: int = 16):
    """Insert one batch into the table.  Returns (new_carry, overflow,
    num_groups, rounds).  When any row fails to place within
    probe_rounds (overflow > 0) the table handed back is LOGICALLY the
    one given: the same used slots, and the same keys, null bits and
    accumulators at them (key data at a free slot is nobody's), so the
    host can grow/degrade and retry the whole batch losslessly.

    The probe has two widths.  Rounds run over all `n` lanes while more
    than `narrow_width(n)` rows are unplaced (round one always does);
    then the rows still unplaced are compacted, in row order and under
    their original row numbers, and the remaining rounds run over that
    many lanes against the same table.  The winner of a slot is the
    lowest row number either way, so every group lands in the slot it
    would land in at full width.  `rounds` is int32[2]: the rounds run
    at full and at narrow width; together at most `probe_rounds`.

    A step costs by its lanes alone.  A round is 3 + 2k indexed
    operations for k key lanes (`split_key`: one a key column, two for a
    64-bit integer), each over the round's lanes and none wider than 32
    bits: the claim (`owner.at[slot].min`), the read of what came of it,
    the stored group's null bits where a row of this batch holds the
    slot, and a scatter and a gather of each key lane.  The step ends in one
    scatter over the batch's lanes, which either writes the winners'
    null bits into `owner` or, after an overflow, takes their claims
    back; the accumulators of an overflowing step are not touched
    (every row scatters out of range).  Nothing is allocated, selected
    or summed over the slots, and nothing reads the table given after
    the first write, so a donated table is updated where it lies.

    Inside a step `owner` holds a third kind of value: the claim of the
    row that won a free slot, `round << bits(n) | row`.  A used slot is
    negative and never yields to a claim; a slot won in an earlier round
    holds a lower claim than any this round makes; among this round's
    claims the lowest row wins."""
    from blaze_tpu.kernels import hashing as H
    S = carry.owner.shape[0]
    n = mask.shape[0]
    W = narrow_width(n)
    row_bits = max(1, (n - 1).bit_length())
    assert probe_rounds << row_bits <= FREE, "claims must stay under FREE"

    key_cols = normalize_float_keys(key_cols)

    cols = [(d, v, _dtype_of(d).id.value) for d, v in key_cols]
    h = H.hash_columns(cols, seed=42, xp=jnp, algo="xxhash64")
    h = h.astype(jnp.int32) & (S - 1)  # S is a power of two
    # per key column its lanes, as the table lays them out
    key_data = [split_key(d) for d, _v in key_cols]
    # what `owner` says of a slot that holds this row's group
    nullbits = jnp.zeros(n, jnp.int32)
    for i, (_d, v) in enumerate(key_cols):
        nullbits |= (~v).astype(jnp.int32) << i
    mark = ~nullbits  # -1 - nullbits

    def probe(h, key_data, row_idx, my_mark, state, wide: bool):
        """Probe rounds over the lanes given (all of the batch, or its
        compacted unplaced rows) until every lane is placed, the rounds
        are spent or, at full width, the rest fits the narrow width.
        `placed` takes the slot a row matched, `-1 - slot` where the row
        also won it, and keeps S for a row not placed."""
        key_valid = key_valid_lanes(my_mark, len(key_data))

        def round_body(state):
            r, owner, tkeys, placed, unplaced, _left = state
            slot = (h + r) & (S - 1)
            # deterministic winner per slot: the lowest row index
            claim = (r << row_bits) | row_idx
            owner = owner.at[jnp.where(unplaced, slot, S)].min(
                claim, mode="drop")
            o = owner.at[slot].get(mode="promise_in_bounds")
            winner = unplaced & (o == claim)
            wslot = jnp.where(winner, slot, S)
            tkeys = tuple(
                tuple(tl.at[wslot].set(kl, mode="drop")
                      for tl, kl in zip(tk, kd))
                for tk, kd in zip(tkeys, key_data))
            # match AFTER claims so same-key rows placed this round
            # unify.  The slot's group: one from before this step says
            # its null bits itself; one a row of this batch holds (this
            # round or an earlier one) has that row's
            eq = jnp.where(o < 0, o, jnp.take(
                mark, o & ((1 << row_bits) - 1), mode="clip")) == my_mark
            for tk, kd, kv in zip(tkeys, key_data, key_valid):
                same = True
                for tl, kl in zip(tk, kd):
                    sl = tl.at[slot].get(mode="promise_in_bounds")
                    if jnp.issubdtype(kl.dtype, jnp.floating):
                        # grouping treats NaN as equal to NaN (Spark
                        # normalizes)
                        same &= (sl == kl) | (jnp.isnan(sl) & jnp.isnan(kl))
                    else:
                        same &= sl == kl
                # SQL grouping: null == null (the null bits above); valid
                # keys compare by value
                eq &= jnp.where(kv, same, True)
            ok = unplaced & eq
            placed = jnp.where(ok, jnp.where(winner, ~slot, slot), placed)
            unplaced = unplaced & ~ok
            return (r + 1, owner, tkeys, placed, unplaced,
                    jnp.sum(unplaced, dtype=jnp.int32))

        def round_cond(state):
            r, _owner, _tk, _placed, _unplaced, left = state
            # early exit: most batches place everything in 1-2 rounds
            more = (r < probe_rounds) & (left > 0)
            if wide and W:
                more &= (r == 0) | (left > W)
            return more

        return jax.lax.while_loop(round_cond, round_body, state)

    def narrow(r, owner, tkeys, placed, unplaced, left):
        lanes = _compact_lanes(unplaced, W)
        r, owner, tkeys, nplaced, _unplaced, left = probe(
            jnp.take(h, lanes, mode="clip"),
            [tuple(jnp.take(kl, lanes, mode="clip") for kl in kd)
             for kd in key_data], lanes,
            jnp.take(mark, lanes, mode="clip"),
            (r, owner, tkeys, jnp.full(W, S, dtype=jnp.int32),
             jnp.arange(W, dtype=jnp.int32) < left, left), wide=False)
        placed = placed.at[lanes].set(nplaced, mode="drop")
        return r, owner, tkeys, placed, left

    def settled(r, owner, tkeys, placed, _unplaced, left):
        return r, owner, tkeys, placed, left

    state = probe(
        h, key_data, jnp.arange(n, dtype=jnp.int32), mark,
        (jnp.int32(0), carry.owner, tuple(tuple(k) for k in carry.keys),
         jnp.full(n, S, dtype=jnp.int32),  # S == unplaced sentinel
         mask, jnp.sum(mask, dtype=jnp.int32)), wide=True)
    full_rounds, left = state[0], state[5]
    if W:
        # the rows still unplaced number W or fewer, unless the rounds
        # are spent; a batch that placed in its full rounds pays the
        # count and this branch
        r, owner, tkeys, placed, overflow = jax.lax.cond(
            (left > 0) & (full_rounds < probe_rounds), narrow, settled,
            *state)
    else:
        r, owner, tkeys, placed, overflow = settled(*state)
    rounds = jnp.stack([full_rounds, r - full_rounds])

    # every slot claimed in this step has one winner.  Their slots get
    # their groups' null bits; after an overflow they are FREE again,
    # and every row's accumulation drops out like an unplaced row's (the
    # S sentinel)
    failed = overflow > 0
    won = placed < 0
    g = jnp.where(won, ~placed, placed)
    owner = owner.at[jnp.where(won, g, S)].set(
        jnp.where(failed, FREE, mark), mode="drop")
    new_accs, new_avalid = scatter_accumulate(
        jnp.where(failed, S, g), agg_specs, mask, carry.accs,
        carry.acc_valid)
    num_groups = carry.groups + jnp.where(
        failed, 0, jnp.sum(won, dtype=jnp.int32))
    return (HashAggCarry(tkeys, tuple(new_accs), tuple(new_avalid), owner,
                         num_groups),
            overflow, num_groups, rounds)


def row_contribution(kind: str, vd: Optional[jax.Array],
                     vv: Optional[jax.Array], mask: jax.Array, dtype):
    """(value, valid) one row brings to its group's accumulator of
    `kind`: the count of its valid argument (of the row, for count(*)),
    its value, or the identity where the argument is null or the row is
    masked.  It is also the accumulator of a group that holds this ONE
    row, which is how rows leave a partial aggregation that stopped
    grouping (runtime/loop.py).  Null semantics live here alone."""
    cv = (vv if vv is not None else jnp.ones_like(mask)) & mask
    if kind == "count":
        return cv.astype(dtype), cv
    if kind == "sum":
        return jnp.where(cv, vd.astype(dtype), 0), cv
    if kind == "min":
        return jnp.where(cv, vd.astype(dtype), _identity(dtype, False)), cv
    if kind == "max":
        return jnp.where(cv, vd.astype(dtype), _identity(dtype, True)), cv
    raise ValueError(f"unsupported agg kind {kind}")


def scatter_accumulate(g: jax.Array,
                       agg_specs: Sequence[Tuple[str, Optional[jax.Array],
                                                 Optional[jax.Array]]],
                       mask: jax.Array, accs: Sequence[jax.Array],
                       avalid: Sequence[jax.Array]):
    """Shared in-place accumulate switch for the dense-gid and hash-table
    carries: rows scatter into slot `g` (out-of-range drops).  Kept in one
    place so null/identity semantics cannot diverge between paths."""
    new_accs, new_avalid = [], []
    for (kind, vd, vv), a, av in zip(agg_specs, accs, avalid):
        val, cv = row_contribution(kind, vd, vv, mask, a.dtype)
        if kind == "min":
            a = a.at[g].min(val, mode="drop")
        elif kind == "max":
            a = a.at[g].max(val, mode="drop")
        else:
            a = a.at[g].add(val, mode="drop")
        if kind != "count":
            av = av.at[g].max(cv, mode="drop")
        new_accs.append(a)
        new_avalid.append(av)
    return new_accs, new_avalid


def init_accumulators(kinds: Sequence[str], acc_dtypes: Sequence,
                      num_slots: int):
    """Identity-initialized accumulator columns (shared by both carries)."""
    accs, avalid = [], []
    for kind, dt in zip(kinds, acc_dtypes):
        if kind == "count":
            accs.append(jnp.zeros(num_slots, dtype=jnp.int64))
            avalid.append(jnp.ones(num_slots, dtype=bool))
            continue
        if kind == "min":
            accs.append(jnp.full(num_slots, _identity(dt, False), dtype=dt))
        elif kind == "max":
            accs.append(jnp.full(num_slots, _identity(dt, True), dtype=dt))
        else:
            accs.append(jnp.zeros(num_slots, dtype=dt))
        avalid.append(jnp.zeros(num_slots, dtype=bool))
    return tuple(accs), tuple(avalid)


def rehash_width(groups: int, old_slots: int) -> int:
    """Lanes over which a table of `old_slots` slots holding `groups`
    groups is re-inserted: the power of two that holds the groups, no
    fewer than a batch without a narrow phase and no more than the
    table."""
    return min(old_slots, max(_NARROW_MIN_LANES,
                              1 << (int(groups) - 1).bit_length()))


def rehash_carry(old: HashAggCarry, kinds: Sequence[str],
                 new_slots: int, lanes: Optional[int] = None,
                 probe_rounds: int = 16):
    """Re-insert an existing table into a larger one (the grow path).
    `kinds` are the ORIGINAL accumulator kinds; stored accumulators
    re-merge with merge semantics (count -> sum of counts).

    The old table's slots are the batch.  A step costs by its lanes
    (PERF.md section 6), so a caller that knows the table's group count
    gives `lanes` (`rehash_width`, never fewer than the groups): the
    used slots are compacted to the front of that many lanes, in slot
    order, and the step runs over those.  The lowest lane wins a
    contested slot at either width and compaction keeps the order, so
    the new table is slot for slot the uncompacted one.  Groups beyond
    `lanes` would be dropped: the count is the caller's to hold.

    The old table's mask, its keys' validity (from its `owner` lane) and
    its key columns (from their lanes) are read once, here: over the
    compacted lanes where there are any, over the old slots otherwise."""
    key_dtypes = [key_dtype(k) for k in old.keys]
    acc_dtypes = [a.dtype for a in old.accs]
    fresh = init_hash_carry(key_dtypes, kinds, acc_dtypes, new_slots)
    cols = (old.keys, old.accs, old.acc_valid, old.owner)
    mask = old.used
    if lanes is not None and lanes < mask.shape[0]:
        live = _compact_lanes(mask, lanes)
        cols = jax.tree_util.tree_map(
            lambda a: jnp.take(a, live, mode="clip"), cols)
        mask = jnp.arange(lanes, dtype=jnp.int32) < old.groups
    keys, accs, acc_valid, owner = cols
    specs = [("sum" if k == "count" else k, a, av)
             for k, a, av in zip(kinds, accs, acc_valid)]
    key_cols = list(zip([join_key(k) for k in keys],
                        key_valid_lanes(owner, len(keys))))
    return hash_agg_step(fresh, key_cols, specs, mask, probe_rounds)


def merge_agg_tables(table: AggTable,
                     merge_kinds: Sequence[str], num_slots: int) -> AggTable:
    """Re-aggregate a (possibly duplicated-key) table — the partial_merge
    phase as a fused kernel.  Input slots act as rows."""
    key_cols = list(zip(table.keys, table.key_valid))
    specs = []
    for kind, acc, av in zip(merge_kinds, table.accs, table.acc_valid):
        k = "sum" if kind in ("count", "sum") else kind
        specs.append((k, acc, av))
    return partial_agg_table(key_cols, specs, table.slot_valid, num_slots)


def _identity(dtype, minimum: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf if minimum else jnp.inf, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.min if minimum else info.max, dtype=dtype)


# ---------------------------------------------------------------------------
# Device-resident exchange: the shard_map stage runner behind the
# DagScheduler's device shuffle.  The reference repartitions map output
# through shuffle files + BlockManager RPC; on a mesh the same
# repartition is ONE collective program — every device hash-partitions
# its local rows with the Spark-compatible pid, stages them into
# bucket-ladder-padded per-destination buffers, and `lax.all_to_all`
# moves every partition simultaneously over ICI.  The file shuffle
# (shuffle/writer.py) stays behind it as the spill + fault-tolerance
# fallback: any failure here raises and the scheduler re-runs the stage
# through the file path, where PR 4's lineage recovery applies.


class DeviceExchangeError(RuntimeError):
    """The device-resident exchange declined or failed.  The scheduler
    catches this (and any other exchange-side error), bumps
    `shuffle_device_fallbacks`, and re-runs the stage through the host
    file shuffle — device shuffle is an optimization, never a new
    failure mode."""


@functools.lru_cache(maxsize=64)
def _exchange_program(mesh, n_out: int, capacity: int,
                      key_idx: Tuple[int, ...], dtypes: Tuple[str, ...]):
    """Build + cache the jit'd shard_map exchange for one static shape.

    Cache key = (mesh, reduce partition count, bucket-ladder rung, key
    column positions, column dtype signature): the collective compiles
    once per rung and is reused by every batch that lands on it.
    """
    from jax.sharding import PartitionSpec as PS

    from blaze_tpu.bridge.xla_stats import meter_jit
    from blaze_tpu.parallel.collective import (all_to_all_rows,
                                               partition_ids_for_keys)
    from blaze_tpu.parallel.mesh import DP_AXIS

    n_dev = mesh.shape[DP_AXIS]
    ncols = len(dtypes)

    def stage(row_valid, *cols):
        datas = cols[:ncols]
        valids = cols[ncols:]
        keys = [(datas[i], valids[i]) for i in key_idx]
        pid = partition_ids_for_keys(keys, n_out).astype(jnp.int32)
        # reduce partition r is served by device r % n_dev; the pid
        # column rides the exchange so the host can split received rows
        # back into exact reduce partitions
        dev = pid % n_dev
        out_cols, out_valid, overflow = all_to_all_rows(
            list(datas) + list(valids) + [pid],
            row_valid, dev, DP_AXIS, n_dev, capacity)
        return tuple(out_cols) + (out_valid, overflow.reshape(1))

    sharded = jax.shard_map(stage, mesh=mesh, in_specs=PS(DP_AXIS),
                            out_specs=PS(DP_AXIS), check_vma=False)
    return meter_jit(sharded, name="mesh.exchange_rows")


def _pad_rows(a: np.ndarray, total: int, dtype=None) -> np.ndarray:
    """One host column, zero-padded to `total` rows."""
    buf = np.zeros(total, dtype=dtype or a.dtype)
    buf[:int(a.shape[0])] = a
    return buf


class ExchangeTicket:
    """One in-flight device exchange: the UNAWAITED outputs of the
    first-rung dispatch plus everything `DeviceExchange.drain` needs to
    finish the job — the remaining capacity-ladder rungs (with the
    padded send buffers kept alive for an overflow re-dispatch), the
    per-rung accounting accumulated so far, and the host-split
    metadata.  Produced by `dispatch`, consumed exactly once by
    `drain` (which says in `read_bytes` what it read back, padding
    included); between the two the collective and the D2D partition
    routing are free to run while the host folds the next chunk."""

    __slots__ = ("out", "rungs", "row_valid", "datas", "vbufs",
                 "key_idx", "dtypes", "n", "ncols", "n_out",
                 "n_dev", "rows_per_dev", "ctx", "moved_bytes",
                 "collectives", "dispatch_ns", "parts", "settled",
                 "read_bytes")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))


class DeviceExchange:
    """Host-side driver for the on-device repartition.

    Pads the map output to a static per-device row count (so sharding
    splits evenly), dispatches the cached `_exchange_program` at a
    bucket-ladder capacity rung sized for `auron.tpu.mesh.exchangeSkew`,
    climbs to the next rung when a destination bucket overflows (the
    final rung = per-device row count can never overflow), and splits
    the received rows back into per-reduce-partition columns in a
    deterministic (destination, source, slot) order.

    The driver is split into `dispatch` (everything through issuing the
    first rung's shard_map call — returns an ExchangeTicket holding the
    unawaited device futures), `settle` (the overflow host sync, the
    rung climb, accounting) and `drain` (`settle` where the caller has
    not, then the readback and the host split).  `exchange` composes
    them back-to-back, which IS the synchronous path byte-for-byte;
    the overlapped scheduler (plan/stages.py) instead drains ticket k
    on a background thread while task k+1 is still folding.
    """

    def __init__(self, mesh=None):
        if mesh is None:
            from blaze_tpu.parallel.mesh import current_mesh
            mesh = current_mesh()
        self.mesh = mesh

    def exchange(self, columns: Sequence[np.ndarray],
                 valids: Sequence[np.ndarray],
                 key_indices: Sequence[int], n_out: int, ctx: str = ""):
        """columns/valids: per-column (data, bool validity) arrays of
        one common length n — numpy from the staged collect, or device
        (jax) arrays straight from the stage loop's drain (runtime/
        loop.py), which stay on device through padding and sharding
        (D2D, no host round trip).  Returns `parts`: n_out entries of
        ([data...], [valid...]) holding that reduce partition's rows."""
        return self.drain(self.dispatch(columns, valids, key_indices,
                                        n_out, ctx=ctx))

    def dispatch(self, columns: Sequence[np.ndarray],
                 valids: Sequence[np.ndarray],
                 key_indices: Sequence[int], n_out: int,
                 ctx: str = "") -> ExchangeTicket:
        """Issue the all-to-all WITHOUT awaiting it: pad, pick the
        ladder rungs, fire the per-shard fault sites, and dispatch the
        first rung's cached program.  Returns immediately — jax
        dispatch is async, so the returned ticket's `out` arrays are
        device futures the collective is still filling.

        `columns` of one common length are cut evenly over the mesh
        (host columns: the staged collect).  Device columns of map
        tasks go through `dispatch_placed`, which leaves them on the
        chips they lie on."""
        from jax.sharding import NamedSharding, PartitionSpec as PS

        from blaze_tpu.batch import bucket_capacity
        from blaze_tpu.bridge import xla_stats
        from blaze_tpu.parallel.mesh import DP_AXIS

        ncols = len(columns)
        if ncols == 0:
            raise DeviceExchangeError("no columns to exchange")
        n = int(len(columns[0]))
        n_dev = int(self.mesh.shape[DP_AXIS])
        dtypes = tuple(np.dtype(c.dtype).name for c in columns)
        if n == 0:
            return self._empty_ticket(dtypes, n_out, ctx)

        # pad to n_dev * rows_per_dev so NamedSharding splits evenly;
        # padding rows carry row_valid=False and are never sent
        rows_per_dev = bucket_capacity(-(-n // n_dev))
        total = n_dev * rows_per_dev
        row_valid = np.zeros(total, dtype=bool)
        row_valid[:n] = True
        datas = [_pad_rows(c, total) for c in columns]
        vbufs = [_pad_rows(v, total, dtype=bool) for v in valids]
        # the one H2D of a staged wave, counted as a task's is
        row_valid, *rest = to_device(
            (row_valid, *datas, *vbufs),
            NamedSharding(self.mesh, PS(DP_AXIS)))
        xla_stats.note_exchange_source(staged=n)
        return self._fire(row_valid, rest[:ncols], rest[ncols:], n,
                          rows_per_dev, dtypes, key_indices, n_out, ctx)

    def dispatch_placed(self, tasks, key_indices: Sequence[int],
                        n_out: int, ctx: str = "") -> ExchangeTicket:
        """`dispatch` for map output that lies on the mesh already:
        `tasks` are (datas, valids, n) device columns, one entry a map
        task, each on the chip its task ran on.  The exchange's operands
        are assembled from one shard a chip, where the rows lie: tasks
        that share a chip are concatenated there in the order given,
        every chip is padded there to the common `rows_per_dev` (a chip
        that ran no task holds padding only), and the shards are joined
        into global arrays without a copy.  The collective is then the
        only place a row changes chip.  A reduce partition's rows come
        out by (source chip, task on that chip, row)."""
        from jax.sharding import NamedSharding, PartitionSpec as PS

        from blaze_tpu.batch import bucket_capacity
        from blaze_tpu.bridge import xla_stats
        from blaze_tpu.parallel.mesh import DP_AXIS
        from blaze_tpu.xputil import on_task_chip

        tasks = [t for t in tasks if t[2] > 0]
        if not tasks:
            raise DeviceExchangeError("no columns to exchange")
        ncols = len(tasks[0][0])
        dtypes = tuple(np.dtype(c.dtype).name for c in tasks[0][0])
        devices = list(self.mesh.devices.reshape(-1))
        by_chip = {d: [] for d in devices}
        for task in tasks:
            by_chip[devices[self.chip_of(task[0])]].append(task)
        rows = {d: sum(t[2] for t in ts) for d, ts in by_chip.items()}
        rows_per_dev = bucket_capacity(max(rows.values()))
        dtype_of = [np.dtype(d) for d in dtypes]
        shards = []   # per chip: [row_valid, datas..., valids...]
        for dev in devices:
            with jax.default_device(dev):
                held = by_chip[dev]
                if not held:
                    shards.append(
                        [jnp.zeros(rows_per_dev, bool)]
                        + [jnp.zeros(rows_per_dev, dt) for dt in dtype_of]
                        + [jnp.zeros(rows_per_dev, bool)] * ncols)
                    continue
                # a column found on another chip is moved, and counted
                held = on_task_chip(held, dev)
                cols = [[t[0][i] for t in held] for i in range(ncols)]
                vals = [[t[1][i] for t in held] for i in range(ncols)]
                pad = rows_per_dev - rows[dev]
                shards.append(
                    [jnp.arange(rows_per_dev) < rows[dev]]
                    + [jnp.pad(c[0] if len(c) == 1 else jnp.concatenate(c),
                               (0, pad)) for c in cols]
                    + [jnp.pad(v[0] if len(v) == 1 else jnp.concatenate(v),
                               (0, pad)).astype(bool) for v in vals])
        sharding = NamedSharding(self.mesh, PS(DP_AXIS))
        total = len(devices) * rows_per_dev
        row_valid, *rest = [
            jax.make_array_from_single_device_arrays(
                (total,), sharding, [sh[k] for sh in shards])
            for k in range(1 + 2 * ncols)]
        xla_stats.note_exchange_source(placed=sum(rows.values()))
        return self._fire(row_valid, rest[:ncols], rest[ncols:],
                          sum(rows.values()), rows_per_dev, dtypes,
                          key_indices, n_out, ctx)

    def chip_of(self, datas) -> int:
        """Where on the mesh a task's device columns lie: the position
        of their chip, by the first column.  Columns that lie on no chip
        of the mesh count for the first, which takes them (and
        `dispatch_placed` counts the move)."""
        where = next(iter(datas[0].sharding.device_set))
        devices = list(self.mesh.devices.reshape(-1))
        return devices.index(where) if where in devices else 0

    def _empty_ticket(self, dtypes, n_out: int, ctx: str) -> ExchangeTicket:
        import time as _time

        from blaze_tpu.parallel.mesh import DP_AXIS
        parts = [([np.zeros(0, d) for d in dtypes],
                  [np.zeros(0, dtype=bool) for _ in dtypes])
                 for _ in range(n_out)]
        return ExchangeTicket(parts=parts, n=0, ncols=len(dtypes),
                              n_out=int(n_out),
                              n_dev=int(self.mesh.shape[DP_AXIS]),
                              ctx=ctx, rungs=[], moved_bytes=0,
                              collectives=0,
                              dispatch_ns=_time.perf_counter_ns())

    def _fire(self, row_valid, datas, vbufs, n: int, rows_per_dev: int,
              dtypes, key_indices: Sequence[int], n_out: int,
              ctx: str) -> ExchangeTicket:
        """The capacity ladder, the fault sites and the first rung's
        dispatch, over operands that lie sharded on the mesh."""
        import time as _time

        from blaze_tpu import config, faults
        from blaze_tpu.batch import bucket_capacity, bucket_ladder
        from blaze_tpu.parallel.collective import exchange_wire_cost
        from blaze_tpu.parallel.mesh import DP_AXIS

        n_dev = int(self.mesh.shape[DP_AXIS])
        # capacity ladder: start at skew * expected rows/destination,
        # retry the next rung on overflow; rows_per_dev (= every local
        # row routed to ONE destination) is the guaranteed-fit ceiling.
        # Partition r goes to device r % n_dev, so an exchange to fewer
        # partitions than devices has that many destinations: a gather
        # to ONE partition starts at the ceiling, not a rung below it
        skew = max(1.0, config.MESH_EXCHANGE_SKEW.get())
        expect = -(-rows_per_dev // min(int(n_out), n_dev))
        start = min(bucket_capacity(max(int(expect * skew), 1)),
                    bucket_capacity(rows_per_dev))
        rungs = [c for c in bucket_ladder(rows_per_dev) if c >= start]
        if not rungs:
            rungs = [start]
        if rungs[-1] < rows_per_dev:
            rungs.append(bucket_capacity(rows_per_dev))

        key_idx = tuple(int(i) for i in key_indices)
        cap = rungs[0]
        # the scripted mid-collective kill: one decision per shard
        # per dispatch, so `device-collective@k` targets shard k-1
        for d in range(n_dev):
            faults.maybe_fail("device-collective", shard=d, stage=ctx)
        fn = _exchange_program(self.mesh, int(n_out), int(cap),
                               key_idx, dtypes)
        out = fn(row_valid, *datas, *vbufs)
        moved_bytes, collectives = exchange_wire_cost(n_dev, cap, dtypes)
        return ExchangeTicket(
            out=out, rungs=list(rungs[1:]), row_valid=row_valid,
            datas=list(datas), vbufs=list(vbufs), key_idx=key_idx,
            dtypes=dtypes, n=n, ncols=len(dtypes), n_out=int(n_out),
            n_dev=n_dev, rows_per_dev=rows_per_dev, ctx=ctx,
            moved_bytes=moved_bytes, collectives=collectives,
            dispatch_ns=_time.perf_counter_ns())

    def settle(self, ticket: ExchangeTicket) -> None:
        """Wait for a dispatched exchange: block on the overflow scalar
        (the one host sync), climb the remaining ladder rungs when a
        destination bucket overflowed (re-firing the per-shard fault
        sites per re-dispatch, exactly like the synchronous loop), and
        count what rode.  The received rows stay on the mesh until
        `drain` reads them back; a ticket is settled once."""
        from blaze_tpu import faults
        from blaze_tpu.bridge import xla_stats
        from blaze_tpu.parallel.collective import exchange_wire_cost

        if ticket.settled or ticket.parts is not None:
            return
        out = ticket.out
        result = None
        while True:
            overflow = int(np.sum(to_host(out[-1])))
            if overflow == 0:
                result = out
                break
            if not ticket.rungs:
                break
            cap = ticket.rungs.pop(0)
            for d in range(ticket.n_dev):
                faults.maybe_fail("device-collective", shard=d,
                                  stage=ticket.ctx)
            fn = _exchange_program(self.mesh, ticket.n_out, int(cap),
                                   ticket.key_idx, ticket.dtypes)
            out = fn(ticket.row_valid, *ticket.datas, *ticket.vbufs)
            xla_stats.note_exchange_redispatch()
            mb, cc = exchange_wire_cost(ticket.n_dev, cap, ticket.dtypes)
            ticket.moved_bytes += mb
            ticket.collectives += cc
        if result is None:
            raise DeviceExchangeError(
                f"destination bucket overflow persisted through the "
                f"ladder (rows_per_dev={ticket.rows_per_dev})")
        # a row as it rides: one device, one slot
        row_bytes, _ = exchange_wire_cost(1, 1, ticket.dtypes)
        xla_stats.note_device_exchange(ticket.n, ticket.moved_bytes,
                                       ticket.collectives,
                                       ticket.n * row_bytes)
        ticket.out = result
        ticket.settled = True
        ticket.row_valid = ticket.datas = ticket.vbufs = None  # free buffers

    def drain(self, ticket: ExchangeTicket):
        """Await a dispatched exchange (`settle`, where the caller has
        not), read every receive buffer back whole, padding and all
        (`ticket.read_bytes`), and split the received rows into
        per-partition numpy columns."""
        if ticket.parts is not None:
            return ticket.parts
        self.settle(ticket)
        ncols, n_out = ticket.ncols, ticket.n_out
        result = to_host(list(ticket.out[:2 * ncols + 2]))
        ticket.read_bytes = sum(int(a.nbytes) for a in result)
        out_cols = result[:ncols]
        out_vals = [a.astype(bool) for a in result[ncols:2 * ncols]]
        pid_r = result[2 * ncols]
        valid_r = result[2 * ncols + 1].astype(bool)

        # received layout is already (dest device, source device, slot)
        # deterministic; a stable sort by pid keeps it reproducible
        pids = pid_r[valid_r]
        order = np.argsort(pids, kind="stable")
        bounds = np.searchsorted(pids[order], np.arange(n_out + 1))
        datas_live = [c[valid_r][order] for c in out_cols]
        vals_live = [v[valid_r][order] for v in out_vals]
        parts = []
        for r in range(n_out):
            lo, hi = int(bounds[r]), int(bounds[r + 1])
            parts.append(([d[lo:hi] for d in datas_live],
                          [v[lo:hi] for v in vals_live]))
        ticket.parts = parts
        ticket.out = None  # free buffers
        return parts

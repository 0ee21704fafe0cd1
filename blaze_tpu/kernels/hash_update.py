"""Pallas open-addressing hash-table UPDATE kernel (ISSUE 9 tentpole a).

`parallel/stage.py:hash_agg_step`'s scatter formulation expresses linear
probing as whole-batch rounds: every round builds an S-sized claim array
(`.at[slot].min(row_idx)`) plus a gather/scatter volley per key column —
O(rounds * (S + n)) HBM traffic that XLA serializes on TPU.  This kernel
keeps the probe state IN VMEM across the whole grid and does the claim/
match walk directly:

  * grid = (probe_rounds,); every BlockSpec uses a constant index_map so
    the hash table limbs, the used flags, and the pending-row list are
    VMEM-resident for all rounds (the consecutive-revisit rule — same
    placement as mxu_agg's output table).
  * Rows still pending are kept in a COMPACTED index list (VMEM scratch
    + an SMEM remaining-count scalar).  Round r walks only the pending
    rows — total serial work is n + collisions, not rounds * n — and a
    `@pl.when(rem > 0)` gate turns post-convergence rounds into no-ops.
  * Within a round, rows are processed serially IN ROW ORDER.  That is
    exactly the scatter formulation's conflict rule: its per-round claim
    array awards a contested empty slot to the LOWEST row index, then
    matches every row against the post-claim table.  Serial in-order
    processing awards the first (= lowest-index) claimant and matches
    later rows against the already-updated table — the same fixpoint,
    which is what makes the two lanes bit-identical (tests assert it).

The kernel is PLACEMENT-ONLY.  It emits `placed` (slot per row, S =
unplaced sentinel) and `wslot` (slot a row claimed as NEW, S = none);
the caller replays the exact legacy tail — key/validity scatters via
`wslot`, `scatter_accumulate` via `placed`, the atomic keep-new select —
so accumulator math, null semantics and the overflow contract are the
SAME CODE on every lane, not a reimplementation.

Key matching runs on int32 LIMBS of the (already normalized) key bits:
hash_agg_step canonicalizes -0.0 and NaN before hashing, so bitwise
limb equality == the legacy `eq` semantics (NaN == NaN included), and
SQL null grouping falls out of zeroing data limbs where the key is
invalid and carrying the validity bit as one more limb.  All kernel
arithmetic is int32 (Mosaic rejects i64 scalars; traced under an
x64-off scope like mxu_agg).

STATUS (PR 21, jax 0.9.0 / libtpu 0.0.34, v5e): the kernel runs in
interpret mode only.  Mosaic refuses the per-row walk — scalar loads and
stores at data-dependent lane indices of VMEM refs — with "Cannot store
scalars to VMEM", so kernels/lane.py keeps it out of `auto` on TPU
(MOSAIC_REFUSED) until it is redesigned as a vector walk (ROADMAP S4).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# limb encoding
# ---------------------------------------------------------------------------

def limbs_per_column(dtype) -> int:
    """int32 limbs for one key column: its data limbs + 1 validity limb."""
    return (2 if jnp.dtype(dtype).itemsize == 8 else 1) + 1


def _data_limbs(data):
    dt = jnp.dtype(data.dtype)
    if dt.itemsize == 8:
        # 64-bit value -> two u32 halves (bitcast appends the half axis)
        halves = jax.lax.bitcast_convert_type(data, jnp.uint32)
        return [jax.lax.bitcast_convert_type(halves[..., 0], jnp.int32),
                jax.lax.bitcast_convert_type(halves[..., 1], jnp.int32)]
    if dt.itemsize == 4:
        return [jax.lax.bitcast_convert_type(data, jnp.int32)]
    # sub-32-bit ints and bool: widening preserves distinctness
    return [data.astype(jnp.int32)]


def encode_limbs(key_cols: Sequence[Tuple[jax.Array, jax.Array]]):
    """(L, n) int32 limb matrix for rows OR table slots.  Data limbs are
    zeroed where the key is invalid (legacy match ignores invalid data:
    `where(kv, same, True)`), and each column contributes its validity
    bit as a limb, so AND-over-limb-equality == the legacy `eq`."""
    rows = []
    for data, valid in key_cols:
        for limb in _data_limbs(data):
            rows.append(jnp.where(valid, limb, jnp.int32(0)))
        rows.append(valid.astype(jnp.int32))
    return jnp.stack(rows, axis=0)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _make_kernel(n: int, S: int, L: int):
    from jax.experimental import pallas as pl

    def kernel(npend_ref, h_ref, limbs_ref, pend0_ref, used0_ref, tab0_ref,
               placed_ref, wslot_ref, pend_ref, used_ref, tab_ref, rem_ref):
        # NOTE every scalar literal below is an explicit jnp.int32: a
        # weak-typed literal in the kernel jaxpr is re-canonicalized to
        # i64 when the interpret-mode call is discharged inside an outer
        # x64 jit, and the resulting mixed-width swap/compare fails to
        # lower.  Mosaic needs i32 anyway.
        step = pl.program_id(0)

        @pl.when(step == jnp.int32(0))
        def _init():
            placed_ref[...] = jnp.full_like(placed_ref, S)
            wslot_ref[...] = jnp.full_like(wslot_ref, S)
            pend_ref[...] = pend0_ref[...]
            used_ref[...] = used0_ref[...]
            tab_ref[...] = tab0_ref[...]
            rem_ref[0] = npend_ref[0, 0]

        rem = rem_ref[0]

        @pl.when(rem > jnp.int32(0))
        def _round():
            def row(k, wpos):
                i = pend_ref[0, k]
                s = (h_ref[0, i] + step) & jnp.int32(S - 1)
                u = used_ref[0, s]
                claim = u == jnp.int32(0)
                eq = u == jnp.int32(1)
                for l in range(L):
                    eq = jnp.logical_and(
                        eq, tab_ref[l, s] == limbs_ref[l, i])
                hit = jnp.logical_or(claim, eq)

                @pl.when(claim)
                def _():
                    used_ref[0, s] = jnp.int32(1)
                    for l in range(L):
                        tab_ref[l, s] = limbs_ref[l, i]
                    wslot_ref[0, i] = s

                @pl.when(hit)
                def _():
                    placed_ref[0, i] = s

                # compaction is in-place-safe: wpos <= k always, so the
                # write never clobbers a not-yet-read pending entry
                @pl.when(jnp.logical_not(hit))
                def _():
                    pend_ref[0, wpos] = i

                return wpos + jnp.where(hit, jnp.int32(0), jnp.int32(1))

            # explicit i32 bounds: a weak-typed literal here would be
            # re-canonicalized to i64 when the interpret-mode kernel is
            # discharged inside an outer x64 jit (mixed-width while cond)
            rem_ref[0] = jax.lax.fori_loop(jnp.int32(0), rem, row,
                                           jnp.int32(0))

    return kernel


def _sublane_pad(rows: int) -> int:
    return -(-rows // 8) * 8


def vmem_estimate(n: int, S: int, L: int) -> int:
    """Bytes of VMEM the placement kernel occupies as Mosaic lays it
    out: every (r, c) int32 block pads r up to 8 sublanes (a (1, n) row
    costs 8n words), pipelined inputs and outputs are double-buffered,
    scratch is single-buffered."""
    Lp = _sublane_pad(L)
    n_rows = (2 * (8 + Lp + 8)   # h, limbs, pend0 (inputs)
              + 2 * (8 + 8)      # placed, wslot (outputs)
              + 8)               # pend (scratch)
    s_rows = (2 * (8 + Lp)       # used0, tab0 (inputs)
              + 8 + Lp)          # used, tab (scratch)
    return 4 * (n_rows * n + s_rows * S)


def placement(h, limbs, pend0, npend, used0, tab0, probe_rounds: int,
              interpret: bool = False):
    """Run the placement walk.  All operands int32: h (n,) pre-masked to
    [0, S); limbs (L, n); pend0 (n,) initial pending row list (row order,
    sentinel-padded); npend scalar count; used0 (S,) 0/1; tab0 (L, S)
    stored-key limbs.  Returns (placed (n,), wslot (n,)) with sentinel S.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = h.shape[0]
    L, S = tab0.shape
    kernel = _make_kernel(n, S, L)
    const = lambda *_: (0, 0)  # noqa: E731
    with jax.enable_x64(False):
        placed, wslot = pl.pallas_call(
            kernel,
            grid=(probe_rounds,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((1, n), const),
                      pl.BlockSpec((L, n), const),
                      pl.BlockSpec((1, n), const),
                      pl.BlockSpec((1, S), const),
                      pl.BlockSpec((L, S), const)],
            out_specs=[pl.BlockSpec((1, n), const),
                       pl.BlockSpec((1, n), const)],
            out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                       jax.ShapeDtypeStruct((1, n), jnp.int32)],
            scratch_shapes=[pltpu.VMEM((1, n), jnp.int32),
                            pltpu.VMEM((1, S), jnp.int32),
                            pltpu.VMEM((L, S), jnp.int32),
                            pltpu.SMEM((1,), jnp.int32)],
            interpret=interpret,
        )(npend.reshape(1, 1), h.reshape(1, n), limbs,
          pend0.reshape(1, n), used0.reshape(1, S), tab0)
    return placed.reshape(n), wslot.reshape(n)


# ---------------------------------------------------------------------------
# hash_agg_step integration
# ---------------------------------------------------------------------------

def place_rows(h, key_cols, mask, carry, probe_rounds: int,
               interpret: bool = False
               ) -> Optional[Tuple[jax.Array, jax.Array]]:
    """Placement for one hash_agg_step batch, or None when the footprint
    falls outside the VMEM envelope (caller degrades to the scatter
    formulation).  `h` already masked to [0, S); key_cols already
    normalized.  Returns (placed, wslot) int32 with sentinel S."""
    S = carry.used.shape[0]
    n = mask.shape[0]
    L = sum(limbs_per_column(d.dtype) for d, _v in key_cols)
    from blaze_tpu.kernels import lane as lane_mod
    if vmem_estimate(n, S, L) > lane_mod.vmem_budget():
        return None

    limbs = encode_limbs(key_cols)
    tab0 = encode_limbs(list(zip(carry.keys, carry.key_valid)))
    used0 = carry.used.astype(jnp.int32)
    # pending list = masked row indices, compacted IN ROW ORDER (the
    # serial walk's conflict rule depends on this ordering)
    idx = jnp.arange(n, dtype=jnp.int32)
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    pend0 = jnp.full(n, n, dtype=jnp.int32).at[
        jnp.where(mask, pos, n)].set(idx, mode="drop")
    npend = jnp.sum(mask.astype(jnp.int32)).astype(jnp.int32)
    return placement(h.astype(jnp.int32), limbs, pend0, npend, used0,
                     tab0, probe_rounds, interpret=interpret)

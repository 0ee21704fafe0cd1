"""Exact grouped aggregation on the MXU: histogram-as-matmul.

TPU-first redesign of the grouped-aggregation hot loop.  The reference's
native engine aggregates through an open-addressing hash table
(ref: native-engine/datafusion-ext-plans/src/agg/agg_hash_map.rs) — a
scatter-shaped algorithm.  TPUs have no scatter unit: XLA lowers scatter
to a serialized update stream that measures ~80M rows/s on v5e, while the
systolic array sits idle at ~200 TFLOP/s.  This module turns the table
update into matrix multiplies:

    table[hi, lo] += sum_r one_hot_hi[r, hi] * w[r] * one_hot_lo[r, lo]

i.e. the group id is split into two digits (hi = gid >> log2(SL),
lo = gid & (SL-1)) and the update becomes a rank-`rows` outer-product
accumulation `(one_hot_hi)^T @ (w * one_hot_lo)` — one dot_general per
row-chunk, executed on the MXU.  One-hot operands are generated on the
VPU inside the kernel (they never touch HBM), and the output table stays
resident in VMEM across the whole grid (constant out index_map).
On a v5e (jax 0.9.0 / libtpu 0.0.34) the kernel compiles under Mosaic at
every layout `plan_layout` admits and is bit-identical to the scatter
table (chip_smoke.py, `kernels`); its rows/s on the directly attached chip: not
measured.

Exactness without f64 (TPU v5e emulates all 64-bit types, ~10x slower):
values are aggregated as 8-bit LIMBS of a non-negative integer
representation (see plan metadata in plan/fused.py: ints shift by their
parquet-stats minimum; decimal-like doubles scale to integral cents).
Each limb is exactly representable in bfloat16 (0..255); the MXU
accumulates in f32, exact while a chunk partial stays below 2^24
(bounded: 255 * 16384 rows per grid step = 4.2M); chunk partials
accumulate into an int32 table, exact while `255 * rows <= 2^31 - 1`
(the caller drains the table into an int64/f64 host accumulator at
least every `MAX_ROWS_PER_TABLE` rows).  Every arithmetic step is
integer-exact — the final sum is the mathematically exact sum, unlike
any floating accumulation order.

The same window function runs on non-TPU backends via an equivalent
scatter formulation (`_window_table_ref`) so tests and the host engine
exercise identical semantics.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# 255 * MAX_ROWS_PER_TABLE must stay below 2^31 (int32 table exactness)
MAX_ROWS_PER_TABLE = 8_000_000
_LIMB_BITS = 8
_LIMB_MASK = (1 << _LIMB_BITS) - 1
_CHUNK = 2048          # rows per sublane-row; 8 * _CHUNK rows per grid step
_ROWS_PER_STEP = 8 * _CHUNK
# Scoped-VMEM ceiling handed to Mosaic.  `_chunk_for` sizes the one-hot
# operands only; the compiler also keeps the iota/compare temporaries
# and the concatenated f32 partials live, so the real footprint is a few
# times that estimate (22.6 MB measured at sh=256, sl=256, 6 blocks,
# chunk=4096 — over the 16 MB default limit, well under a v5e core's
# 128 MiB of VMEM).
_VMEM_LIMIT_BYTES = 64 << 20


class MxuAggLayout(NamedTuple):
    """Static kernel layout (hashable: keys jit caches).

    `limbs[i]` is the limb count of input array i; array values must be
    non-negative and < 2^(8*limbs[i]).  Block order in the output table:
    [presence?] + arrays in order, limbs little-endian within an array.
    """

    sh: int                  # hi-digit extent (multiple of 8)
    sl: int                  # lo-digit extent (power of two, 128 or 256)
    limbs: Tuple[int, ...]   # limb count per input array
    presence: bool = True    # emit a leading all-ones block (group counts)

    @property
    def num_slots(self) -> int:
        return self.sh * self.sl

    @property
    def n_blocks(self) -> int:
        return (1 if self.presence else 0) + sum(self.limbs)


def plan_layout(num_slots: int, value_bits: Sequence[int],
                presence: bool = True) -> "MxuAggLayout | None":
    """Choose (sh, sl) digits and limb counts, or None when the shape
    falls outside the kernel's efficient/VMEM-safe envelope."""
    limbs = tuple(max(1, -(-int(b) // _LIMB_BITS)) for b in value_bits)
    nb = (1 if presence else 0) + sum(limbs)
    sl = 128 if num_slots <= (1 << 14) else 256
    sh = -(-num_slots // sl)
    sh += (-sh) % 8
    if sh > 512 or sl * nb > 2048 or any(l > 4 for l in limbs):
        return None
    return MxuAggLayout(sh, sl, limbs, presence)


def max_rows_per_table(layout: MxuAggLayout) -> int:
    return MAX_ROWS_PER_TABLE


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _chunk_for(layout: "MxuAggLayout") -> int:
    """Largest row-chunk whose working set fits a conservative VMEM
    budget: oh_hi + oh_lo + one weighted lo + the f32 accumulator and
    i32 output (both sh x sl*nb)."""
    budget = 10 << 20
    table = layout.sh * layout.sl * layout.n_blocks * 8
    for chunk in (8192, 4096, 2048):
        per_row = (layout.sh + 2 * layout.sl) * 2  # bf16 one-hots
        if table + chunk * per_row <= budget:
            return chunk
    return 1024


def _make_kernel(layout: MxuAggLayout, chunk: int):
    sh, sl, limbs, presence = (layout.sh, layout.sl, layout.limbs,
                               layout.presence)
    lo_bits = sl.bit_length() - 1
    nb = layout.n_blocks

    def kernel(*refs):
        from jax.experimental import pallas as pl
        gid_ref = refs[0]
        arr_refs = refs[1:-1]
        out_ref = refs[-1]
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        ih = jax.lax.broadcasted_iota(jnp.int32, (chunk, sh), 1)
        il = jax.lax.broadcasted_iota(jnp.int32, (chunk, sl), 1)

        def row(r, acc):
            gid = gid_ref[0, r, :]
            hi = jax.lax.shift_right_logical(gid, lo_bits)
            lo = jax.lax.bitwise_and(gid, sl - 1)
            # sentinel rows (gid >= sh*sl) yield hi >= sh: all-zero one-hot
            oh_hi = (hi[:, None] == ih).astype(jnp.bfloat16)
            oh_lo = (lo[:, None] == il).astype(jnp.bfloat16)
            # one dot per block, sharing both one-hots: keeps live VMEM
            # to one weighted operand at a time (bigger chunks -> better
            # MXU utilization than a single wide concatenated dot)
            parts = []
            if presence:
                parts.append(jax.lax.dot_general(
                    oh_hi, oh_lo, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            for a_ref, nl in zip(arr_refs, limbs):
                v = a_ref[0, r, :]
                for li in range(nl):
                    w = jax.lax.bitwise_and(
                        jax.lax.shift_right_logical(v, _LIMB_BITS * li),
                        _LIMB_MASK)
                    # minor-dim insertion must happen at 32 bits
                    # (Mosaic restriction), then cast: limb <= 255 is
                    # exact in bf16 and the product stays exact
                    wcol = w.astype(jnp.float32)[:, None] \
                        .astype(jnp.bfloat16)
                    wlo = oh_lo * wcol
                    parts.append(jax.lax.dot_general(
                        oh_hi, wlo, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
            # f32 accumulation is exact: chunk partial <= 255 * 65536 < 2^24
            return acc + jnp.concatenate(parts, axis=1)

        acc = jax.lax.fori_loop(0, 8, row,
                                jnp.zeros((sh, sl * nb), jnp.float32))
        out_ref[:] += acc.astype(jnp.int32)

    return kernel


def _pallas_window_table(gid, arrays, layout: MxuAggLayout,
                         interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk = _chunk_for(layout)
    rows_per_step = 8 * chunk
    n = gid.shape[0]
    pad = (-n) % rows_per_step
    gid = jnp.pad(gid.astype(jnp.int32), (0, pad),
                  constant_values=layout.num_slots)
    arrays = [jnp.pad(a.astype(jnp.int32), (0, pad)) for a in arrays]
    nblk = (n + pad) // rows_per_step
    gid3 = gid.reshape(nblk, 8, chunk)
    arrs3 = [a.reshape(nblk, 8, chunk) for a in arrays]

    kernel = _make_kernel(layout, chunk)
    nb = layout.n_blocks
    # Mosaic lowering rejects i64-typed scalars; the kernel is pure
    # i32/bf16/f32, so trace it with x64 semantics scoped off (the global
    # x64 flag exists for Arrow i64/f64 columns, not for kernel innards).
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid=(nblk,),
            in_specs=[pl.BlockSpec((1, 8, chunk), lambda i: (i, 0, 0))
                      for _ in range(1 + len(arrs3))],
            out_specs=pl.BlockSpec((layout.sh, layout.sl * nb),
                                   lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((layout.sh, layout.sl * nb),
                                           jnp.int32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(gid3, *arrs3)


def _window_table_ref(gid, arrays, layout: MxuAggLayout):
    """Scatter formulation of the same table — non-TPU backends and the
    parity oracle for tests.  Bit-identical output by construction (all
    arithmetic is integer-exact on both paths)."""
    S = layout.num_slots
    gid = gid.astype(jnp.int32)
    blocks: List[jax.Array] = []
    if layout.presence:
        ones = jnp.ones(gid.shape[0], dtype=jnp.int32)
        blocks.append(jnp.zeros(S, jnp.int32).at[gid].add(ones,
                                                          mode="drop"))
    for a, nl in zip(arrays, layout.limbs):
        a = a.astype(jnp.int32)
        for li in range(nl):
            w = (a >> (_LIMB_BITS * li)) & _LIMB_MASK
            blocks.append(jnp.zeros(S, jnp.int32).at[gid].add(
                w, mode="drop"))
    # match the pallas layout: (sh, sl * nb) with block-major columns
    tab = jnp.stack([b.reshape(layout.sh, layout.sl) for b in blocks],
                    axis=1)
    return tab.reshape(layout.sh, layout.sl * len(blocks))


def window_table(gid, arrays, layout: MxuAggLayout, force_ref=False,
                 interpret=False):
    """One window's aggregation table.

    gid: (n,) int32 group ids in [0, sh*sl); rows to drop (filtered out)
    carry gid == sh*sl (the sentinel).  arrays: one (n,) int32 per layout
    entry, non-negative, < 2^(8*limbs[i]), zeroed where the value is
    null.  Returns an int32 (sh, sl * n_blocks) table; block b occupies
    columns [b*sl, (b+1)*sl).  Traceable under jit on any backend.
    """
    if interpret:
        return _pallas_window_table(gid, arrays, layout, interpret=True)
    if not force_ref and jax.default_backend() == "tpu":
        return _pallas_window_table(gid, arrays, layout)
    return _window_table_ref(gid, arrays, layout)


# ---------------------------------------------------------------------------
# host-side recombination
# ---------------------------------------------------------------------------

def split_blocks(table_np: np.ndarray, layout: MxuAggLayout
                 ) -> Tuple[np.ndarray, List[np.ndarray]]:
    """(presence (S,) int64, per-array recombined int64 (S,) values)."""
    sh, sl = layout.sh, layout.sl
    nb = layout.n_blocks
    t = table_np.reshape(sh, nb, sl).astype(np.int64)
    b = 0
    presence = None
    if layout.presence:
        presence = t[:, 0, :].reshape(-1)
        b = 1
    out = []
    for nl in layout.limbs:
        acc = np.zeros(sh * sl, dtype=np.int64)
        for li in range(nl):
            acc += t[:, b, :].reshape(-1) << (_LIMB_BITS * li)
            b += 1
        out.append(acc)
    return presence, out


def limb_bits_for(lo: int, hi: int) -> int:
    """Bits needed for the shifted non-negative value range [0, hi-lo]."""
    span = max(0, int(hi) - int(lo))
    return max(1, span.bit_length())

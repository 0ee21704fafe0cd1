"""Pallas radix-style partition kernel (ISSUE 9 tentpole b).

The scatter formulation behind `spark_partition_ids` binning —
`parallel/collective.py:_dest_slots` and the shuffle writer's
`np.argsort(pids)` — pays an O(n log n) multi-pass sort to recover what
is really a counting problem.  This kernel does the classic two-pass
radix partition with the cursors resident in VMEM:

  pass 1 (vectorized): chunked broadcast-compare histogram over the pid
          column -> per-partition counts;
  offsets: exclusive prefix over the counts -> per-partition starts;
  pass 2 (serial, row order): walk rows once, assign each its
          within-partition rank from the partition's cursor and emit the
          per-partition CONTIGUOUS output order (order[starts[p]+rank]).

Row-order rank assignment is exactly what `argsort(pid, stable=True)`
computes for rows of equal pid, so `(dest_part, dest_slot)` scatters
build bit-identical per-destination buffers and `order` is bit-identical
to the stable argsort — the parity tests assert both.  Rows with
pid >= num_partitions (parked/invalid) route to (num_partitions,
capacity), out of every buffer's range, matching the legacy drop path;
rank >= capacity routes the same way and the caller derives overflow
from the counts (sum of max(0, count - capacity)).

STATUS (PR 21, jax 0.9.0 / libtpu 0.0.34, v5e): interpret mode only.
Mosaic refuses pass 2's scalar stores into VMEM ("Cannot store scalars
to VMEM"); with all walk state in SMEM the kernel compiled and matched
the stable argsort up to 32,768 rows, ran out of the 1 MiB SMEM at
65,536 and was 3x slower than XLA's argsort.  kernels/lane.py keeps it
out of `auto` on TPU (MOSAIC_REFUSED) until it is redesigned."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


_CHUNK = 2048  # histogram rows per vectorized compare block


def _make_kernel(n: int, P: int, Pp: int, capacity: int, chunk: int):
    from jax.experimental import pallas as pl

    nchunks = -(-n // chunk)

    def kernel(pid_ref, part_ref, slot_ref, order_ref, counts_ref,
               starts_ref, cur_ref):
        # pass 1: vectorized histogram, one broadcast-compare per chunk
        lanes = jax.lax.broadcasted_iota(jnp.int32, (chunk, Pp), 1)

        def hist(k, c):
            seg = pid_ref[0, pl.ds(k * chunk, chunk)]
            oh = (seg[:, None] == lanes).astype(jnp.int32)
            return c + jnp.sum(oh, axis=0, keepdims=True)

        # every fori bound is explicit i32: weak-typed literals would be
        # re-canonicalized to i64 when the interpret-mode kernel is
        # discharged inside an outer x64 jit (mixed-width while cond)
        counts = jax.lax.fori_loop(jnp.int32(0), jnp.int32(nchunks), hist,
                                   jnp.zeros((1, Pp), jnp.int32))
        counts_ref[...] = counts

        # offsets: exclusive prefix over the sendable partitions
        def offs(p, acc):
            starts_ref[0, p] = acc
            cur_ref[0, p] = acc
            return acc + counts_ref[0, p]

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(P), offs, jnp.int32(0))

        part_ref[...] = jnp.full_like(part_ref, P)
        slot_ref[...] = jnp.full_like(slot_ref, capacity)
        order_ref[...] = jnp.full_like(order_ref, n)

        # pass 2: serial rank walk in row order (== stable argsort rank).
        # Explicit i32 scalars throughout — see the bound note above.
        def row(i, carry):
            p = pid_ref[0, i]

            @pl.when(p < jnp.int32(P))
            def _():
                c = cur_ref[0, p]
                r = c - starts_ref[0, p]
                ok = r < jnp.int32(capacity)
                part_ref[0, i] = jnp.where(ok, p, jnp.int32(P))
                slot_ref[0, i] = jnp.where(ok, r, jnp.int32(capacity))
                order_ref[0, c] = i
                cur_ref[0, p] = c + jnp.int32(1)

            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), row, jnp.int32(0))

    return kernel


def vmem_estimate(n: int, num_partitions: int) -> int:
    """Bytes of VMEM as Mosaic lays the kernel out: each (1, c) int32
    row pads to 8 sublanes, the pipelined pid input and the four outputs
    are double-buffered, the cursor scratch is single-buffered, and the
    histogram holds one iota and one compare block."""
    Pp = -(-(num_partitions + 1) // 128) * 128
    return 4 * (2 * 8 * 4 * n          # pid + part + slot + order
                + 2 * _CHUNK * Pp      # histogram iota + one-hot
                + (2 * 8 + 2 * 8) * Pp)  # counts (out) + starts/cur


@functools.lru_cache(maxsize=64)
def _ranks_call(n: int, num_partitions: int, capacity: int,
                interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P = num_partitions
    Pp = -(-(P + 1) // 128) * 128
    chunk = min(_CHUNK, max(8, n))
    npad = -(-n // chunk) * chunk
    kernel = _make_kernel(n, P, Pp, capacity, chunk)
    const = lambda *_: (0, 0)  # noqa: E731

    def call(pid):
        pid = jnp.clip(pid, 0, P).astype(jnp.int32)
        pid = jnp.pad(pid, (0, npad - n), constant_values=P)
        with jax.enable_x64(False):
            part, slot, order, counts = pl.pallas_call(
                kernel,
                grid=(1,),
                in_specs=[pl.BlockSpec((1, npad), const)],
                out_specs=[pl.BlockSpec((1, npad), const),
                           pl.BlockSpec((1, npad), const),
                           pl.BlockSpec((1, npad), const),
                           pl.BlockSpec((1, Pp), const)],
                out_shape=[jax.ShapeDtypeStruct((1, npad), jnp.int32),
                           jax.ShapeDtypeStruct((1, npad), jnp.int32),
                           jax.ShapeDtypeStruct((1, npad), jnp.int32),
                           jax.ShapeDtypeStruct((1, Pp), jnp.int32)],
                scratch_shapes=[pltpu.VMEM((1, Pp), jnp.int32),
                                pltpu.VMEM((1, Pp), jnp.int32)],
                interpret=interpret,
            )(pid.reshape(1, npad))
        return (part.reshape(npad)[:n], slot.reshape(npad)[:n],
                order.reshape(npad)[:n], counts.reshape(Pp)[:P])

    return call


def partition_ranks(pid, num_partitions: int, capacity: int,
                    interpret: bool = False):
    """Per-row (dest_part, dest_slot), the contiguous `order`, and the
    per-partition `counts` for one pid column.  Traceable; pid values
    outside [0, num_partitions) are parked out of range."""
    n = pid.shape[0]
    return _ranks_call(int(n), int(num_partitions), int(capacity),
                       bool(interpret))(pid)


def dest_slots(pid, num_partitions: int, capacity: int,
               interpret: bool = False):
    """Kernel-lane drop-in for collective._dest_slots: returns
    (None, (dest_part, dest_slot), overflow) — order is None because the
    dest pair is already per ORIGINAL row (callers skip the take)."""
    part, slot, _order, counts = partition_ranks(
        pid, num_partitions, capacity, interpret=interpret)
    overflow = jnp.sum(jnp.maximum(
        counts - jnp.int32(capacity), 0)).astype(jnp.int32)
    return None, (part, slot), overflow


def partition_order(pids: np.ndarray, n_parts: int,
                    interpret: bool = True):
    """Shuffle-writer lane: stable partition grouping for a host pid
    column.  Returns (order, starts, ends) — bit-identical to
    np.argsort(pids, kind='stable') + searchsorted.

    The pid column is padded up to a power-of-two bucket with PARKED
    rows (pid == n_parts, never written into `order`), so the kernel
    compiles once per bucket rung instead of once per batch length."""
    n = int(pids.shape[0])
    if n == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, np.zeros(n_parts, np.int64), np.zeros(n_parts, np.int64)
    bucket = max(1024, 1 << int(n - 1).bit_length())
    padded = np.full(bucket, n_parts, dtype=np.int32)
    padded[:n] = pids.astype(np.int32)
    _part, _slot, order, counts = partition_ranks(
        jnp.asarray(padded), int(n_parts), bucket,
        interpret=interpret)
    counts = np.asarray(counts).astype(np.int64)
    ends = np.cumsum(counts)
    starts = ends - counts
    # every real row lands in [0, sum(counts)); the bucket tail is all
    # parked sentinels
    return np.asarray(order)[:n].astype(np.int64), starts, ends

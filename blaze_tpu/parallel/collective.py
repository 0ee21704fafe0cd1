"""Mesh collectives: the TPU-native exchange (shuffle-over-ICI).

The reference's all-to-all exchange is shuffle files + BlockManager RPC
(SURVEY.md §2.7).  On a TPU slice, the same repartitioning rides ICI as an
XLA `all_to_all` INSIDE the jit'd stage: every device hash-partitions its
local group table by key, scatters slots into per-destination buffers, and
one collective moves all partitions simultaneously.  Global (ungrouped)
aggregates merge with a single `psum`.  Host shuffle files remain the
cross-slice / cross-host fallback (DCN), exactly how the reference keeps
RSS as the wide-area transport.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from blaze_tpu.kernels import hashing as H
from blaze_tpu.parallel.stage import AggTable, merge_agg_tables


def partition_ids_for_keys(keys: Sequence[Tuple[jax.Array, jax.Array]],
                           num_partitions: int) -> jax.Array:
    """Spark-compatible pid = pmod(murmur3(normalize(keys), 42), P) on
    device (ref shuffle/mod.rs:164-189) — traceable under jit/shard_map.
    Delegates to the ONE shared definition (H.spark_partition_ids) so
    the device lane and the host file-shuffle path agree bit-for-bit on
    where every key lives (incl. -0.0/NaN float normalization)."""
    from blaze_tpu.parallel.stage import _dtype_of
    flat_cols = []
    tids = []
    for data, valid in keys:
        flat_cols.append((data, valid))
        tids.append(_dtype_of(data).id.value)
    return H.spark_partition_ids(flat_cols, tids, num_partitions, xp=jnp)


def _dest_slots(pid: jax.Array, num_partitions: int, capacity: int):
    """Dense within-destination slot assignment for per-destination
    buffers of `capacity` rows.

    Returns (order, dest, overflow): `order` sorts rows by destination;
    `dest` = (partition, slot) per sorted row, routed OUT of bounds for
    rows with pid >= num_partitions or past capacity, so scatters with
    mode="drop" discard them instead of clobbering a live slot;
    `overflow` counts in-range rows dropped by the capacity limit."""
    R = pid.shape[0]
    order = jnp.argsort(pid, stable=True)
    sorted_pid = jnp.take(pid, order)
    counts = jnp.bincount(jnp.clip(pid, 0, num_partitions),
                          length=num_partitions + 1)[:num_partitions]
    starts = jnp.cumsum(counts) - counts
    idx_within = jnp.arange(R) - jnp.take(
        jnp.concatenate([starts, jnp.zeros(1, starts.dtype)]),
        jnp.clip(sorted_pid, 0, num_partitions))
    sendable = sorted_pid < num_partitions
    in_range = sendable & (idx_within < capacity)
    overflow = jnp.sum((sendable & ~in_range).astype(jnp.int32))
    dest = (jnp.where(in_range, sorted_pid, num_partitions),
            jnp.where(in_range, idx_within, capacity))
    return order, dest, overflow


def all_to_all_regroup(table: AggTable, axis_name: str,
                       num_partitions: int, out_slots: int) -> AggTable:
    """Exchange group-table slots so equal keys land on one device, then
    merge — the on-ICI shuffle+final-agg.  Callable only inside shard_map
    over `axis_name`."""
    G = table.slot_valid.shape[0]
    pid = partition_ids_for_keys(
        list(zip(table.keys, table.key_valid)), num_partitions)
    pid = jnp.where(table.slot_valid, pid, num_partitions)  # park empties

    # per-destination capacity G: a device's slots can never overflow it
    order, dest, _overflow = _dest_slots(pid, num_partitions, G)

    def scatter(col):
        sc = jnp.take(col, order)
        buf = jnp.zeros((num_partitions, G), dtype=col.dtype)
        return buf.at[dest].set(sc, mode="drop")

    def scatter_valid(col):
        sc = jnp.take(col, order)
        buf = jnp.zeros((num_partitions, G), dtype=bool)
        return buf.at[dest].set(sc, mode="drop")

    keys_b = [scatter(k) for k in table.keys]
    kval_b = [scatter_valid(v) for v in table.key_valid]
    accs_b = [scatter(a) for a in table.accs]
    aval_b = [scatter_valid(v) for v in table.acc_valid]
    slot_b = scatter_valid(table.slot_valid)

    def exchange(buf):
        return jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                  concat_axis=0, tiled=False)

    keys_r = [exchange(b).reshape(num_partitions * G) for b in keys_b]
    kval_r = [exchange(b).reshape(num_partitions * G) for b in kval_b]
    accs_r = [exchange(b).reshape(num_partitions * G) for b in accs_b]
    aval_r = [exchange(b).reshape(num_partitions * G) for b in aval_b]
    slot_r = exchange(slot_b).reshape(num_partitions * G)

    received = AggTable(tuple(keys_r), tuple(kval_r), tuple(accs_r),
                        tuple(aval_r), slot_r,
                        jnp.sum(slot_r.astype(jnp.int32)))
    # kinds: sum-merge semantics chosen by caller via merge_agg_tables
    return received


def all_to_all_rows(columns: Sequence[jax.Array], valid: jax.Array,
                    pid: jax.Array, axis_name: str, num_partitions: int,
                    capacity: int):
    """Operator-agnostic raw-row exchange over ICI.

    The reference's repartitioner moves arbitrary operator output rows
    (shuffle/mod.rs:55-123) — not just agg tables.  This is the on-mesh
    analog: every device routes each of its local rows to the device
    `pid[r]` names, staging them into per-destination buffers of static
    `capacity`, and ONE `lax.all_to_all` moves every partition
    simultaneously.  Callable only inside shard_map over `axis_name`.

    columns: per-row data arrays, each shape (rows,).
    valid:   (rows,) bool — invalid rows are not sent.
    pid:     (rows,) int destination in [0, num_partitions).

    Returns (columns', valid', overflow):
      columns' each (num_partitions * capacity,) — received rows, padded;
      valid' marks the real ones; overflow counts LOCAL rows dropped
      because a destination bucket exceeded `capacity` (callers re-run
      with a bigger bucket when nonzero — the same bounded-overflow
      discipline as the fused agg table)."""
    pid = jnp.where(valid, pid, num_partitions)  # park unsent rows
    order, dest, overflow = _dest_slots(pid, num_partitions, capacity)

    def exchange(buf):
        return jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                  concat_axis=0, tiled=False)

    out_cols = []
    for col in columns:
        sc = jnp.take(col, order)
        buf = jnp.zeros((num_partitions, capacity), dtype=col.dtype)
        buf = buf.at[dest].set(sc, mode="drop")
        out_cols.append(exchange(buf).reshape(num_partitions * capacity))
    vbuf = jnp.zeros((num_partitions, capacity), dtype=bool)
    vbuf = vbuf.at[dest].set(True, mode="drop")
    out_valid = exchange(vbuf).reshape(num_partitions * capacity)
    return out_cols, out_valid, overflow


def exchange_wire_cost(n_dev: int, capacity: int,
                       dtypes: Sequence[str]) -> Tuple[int, int]:
    """Accounting for ONE all_to_all_rows dispatch at `capacity`: every
    device stages (n_dev dests x capacity) send buffers per exchanged
    column — the data columns + their bool validity columns + the int32
    pid rider + the bool row mask — and the program issues one
    collective per buffer.  Returns (moved_bytes, collectives);
    DeviceExchange sums these per ladder rung for
    xla_stats.note_device_exchange, identically for the synchronous
    exchange and the overlapped dispatch/drain split."""
    import numpy as np
    ncols = len(dtypes)
    per_slot = sum(np.dtype(d).itemsize for d in dtypes) + ncols + 4 + 1
    return n_dev * n_dev * capacity * per_slot, 2 * ncols + 2


def psum_table_accs(table: AggTable, axis_name: str) -> AggTable:
    """Global (ungrouped) aggregate merge: one psum over acc columns."""
    accs = tuple(jax.lax.psum(jnp.where(v, a, jnp.zeros_like(a)), axis_name)
                 for a, v in zip(table.accs, table.acc_valid))
    any_valid = tuple(jax.lax.psum(v.astype(jnp.int32), axis_name) > 0
                      for v in table.acc_valid)
    return table._replace(accs=accs, acc_valid=any_valid)

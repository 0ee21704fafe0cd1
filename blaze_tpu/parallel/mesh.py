"""Device mesh construction + the distributed stage runner.

TPU-native scaling model (SURVEY.md §7 step 7): data parallelism over a 1-D
`dp` mesh axis (each device = one partition worth of rows, the Spark-task
analog), with exchanges as in-jit collectives over ICI.  Multi-host slices
extend the same mesh across hosts (jax.distributed); the host shuffle
service (shuffle/) carries cross-slice DCN traffic.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from blaze_tpu.bridge.xla_stats import meter_jit

DP_AXIS = "dp"

# Every shard_map here passes check_vma=False: the collective programs
# intentionally mix per-device and replicated intermediates.


def make_mesh(num_devices: Optional[int] = None,
              axis: str = DP_AXIS) -> Mesh:
    devs = jax.devices()
    n = num_devices or len(devs)
    return Mesh(np.array(devs[:n]), (axis,))


_mesh_cache: dict = {}


def current_mesh() -> Mesh:
    """The process-wide dp mesh, sized by `auron.tpu.mesh.devices`
    (0 = every visible device).  Cached per size: Mesh construction is
    cheap but mesh IDENTITY keys the jit cache, so handing out a fresh
    Mesh per exchange would recompile every collective program."""
    from blaze_tpu import config
    visible = len(jax.devices())
    n = config.MESH_DEVICES.get() or visible
    n = max(1, min(int(n), visible))
    m = _mesh_cache.get(n)
    if m is None:
        m = _mesh_cache[n] = make_mesh(n)
    return m


def task_device(partition_id: int):
    """The chip that task `partition_id` of any stage runs on: device
    p mod n of the dp mesh, so `auron.tpu.mesh.devices` bounds task
    placement as it sizes the exchange, and reduce partition r runs
    where the exchange's collective left it (`_exchange_program`: r mod
    n).  None where nothing is to be pinned: a mesh of one device, or
    compute pinned to the host's XLA backend beside an accelerator
    (bridge/placement), whose default device is not in the mesh.  Where
    tasks do get chips, the chips share each program's compilation
    (bridge/compile_share)."""
    from blaze_tpu.bridge import compile_share
    from blaze_tpu.bridge.placement import placement_info
    devices = current_mesh().devices.reshape(-1)
    info = placement_info()
    if len(devices) == 1 or (info is not None and info.device_kind
                             != devices[0].platform):
        return None
    compile_share.install()
    return devices[int(partition_id) % len(devices)]


def shard_rows(mesh: Mesh, *arrays: jax.Array):
    """Shard row-dimension arrays across the dp axis."""
    sharding = NamedSharding(mesh, P(DP_AXIS))
    return tuple(jax.device_put(a, sharding) for a in arrays)


def distributed_grouped_agg(mesh: Mesh, key_specs, agg_specs,
                            num_slots: int, out_slots: int,
                            merge_kinds: Sequence[str]):
    """Build the jit'd two-phase distributed aggregation step.

    Returns fn(valid_mask, *key_and_value_arrays) -> final AggTable slots
    per device.  The whole pipeline — partial agg, on-device hash
    partition, ICI all-to-all, final merge — is ONE compiled XLA program:
    the TPU-native equivalent of map-side agg + shuffle + reduce-side agg.

    key_specs / agg_specs describe argument positions:
      key_specs: number of key columns (each contributes data+valid args)
      agg_specs: list of kinds ('sum'|'count'|'min'|'max'); each non-count
                 contributes data+valid args.
    """
    from blaze_tpu.parallel.collective import all_to_all_regroup
    from blaze_tpu.parallel.stage import merge_agg_tables, partial_agg_table

    num_keys = key_specs if isinstance(key_specs, int) else len(key_specs)
    P_ = mesh.shape[DP_AXIS]

    def stage(valid_mask, *cols):
        i = 0
        keys = []
        for _ in range(num_keys):
            keys.append((cols[i], cols[i + 1]))
            i += 2
        specs = []
        for kind in agg_specs:
            if kind == "count":
                specs.append((kind, None, None))
            else:
                specs.append((kind, cols[i], cols[i + 1]))
                i += 2
        local = partial_agg_table(keys, specs, valid_mask, num_slots)
        received = all_to_all_regroup(local, DP_AXIS, P_, out_slots)
        final = merge_agg_tables(received, merge_kinds, out_slots)
        # scalars can't concatenate across the mesh: give num_groups a
        # (1,)-axis so out_specs P('dp') stacks per-device counts
        return final._replace(num_groups=final.num_groups.reshape(1))

    sharded = jax.shard_map(stage, mesh=mesh, in_specs=P(DP_AXIS),
                            out_specs=P(DP_AXIS), check_vma=False)
    return meter_jit(sharded, name="mesh.grouped_agg")


def distributed_sort(mesh: Mesh, num_payloads: int, capacity: int,
                     samples_per_device: int = 64, descending: bool = False):
    """Globally range-partitioned sort as ONE SPMD program.

    The reference's global sort is range-repartition (driver-sampled
    bounds, NativeShuffleExchangeBase.scala:313) + per-partition external
    sort.  The on-mesh form does all of it inside one jit: each device
    samples its local keys, an `all_gather` shares the samples, every
    device derives identical quantile bounds, rows ride the raw-row
    all-to-all to their range partition, and a local sort finishes.
    After the step, device i's valid rows are all <= device i+1's
    (reversed when `descending`) and each device is locally sorted.

    Returns fn(keys, valid, *payloads) -> (keys', valid', *payloads',
    overflow) with per-device length `num_devices * capacity`.  Keys must
    be a numeric dtype; nulls (valid=False) are not emitted.
    """
    from blaze_tpu.parallel.collective import all_to_all_rows

    P_ = mesh.shape[DP_AXIS]
    S = samples_per_device

    def _encode(keys):
        """(sort_key, nan_rank, is_nan): sort_key ascends in the requested
        order.  Integers/bool invert via bitwise NOT (negation wraps
        INT64_MIN and unsigned dtypes); float NaN zeroes out of the value
        key and rides a separate rank — Spark treats NaN as the LARGEST
        value (last on ASC, first on DESC)."""
        if jnp.issubdtype(keys.dtype, jnp.floating):
            nan = jnp.isnan(keys)
            base = jnp.where(nan, jnp.zeros_like(keys), keys)
            skey = -base if descending else base
            rank_nan = 0 if descending else 1
            nan_rank = jnp.where(nan, rank_nan, 1 - rank_nan) \
                .astype(jnp.int32)
            return skey, nan_rank, nan
        skey = ~keys if descending else keys
        return skey, jnp.zeros(keys.shape, jnp.int32), \
            jnp.zeros(keys.shape, bool)

    def stage(keys, valid, *payloads):
        if len(payloads) != num_payloads:
            raise ValueError(
                f"distributed_sort built for {num_payloads} payload "
                f"columns, got {len(payloads)}")
        R = keys.shape[0]
        sort_key, nan_rank, is_nan = _encode(keys)
        # sample only finite valid keys (NaN routes to a fixed partition
        # below; nulls are never emitted)
        finite = valid & ~is_nan
        not_finite = (~finite).astype(jnp.int32)
        _, key_s = jax.lax.sort((not_finite, sort_key), num_keys=2)
        n_fin = jnp.sum(finite.astype(jnp.int32))
        pos = (jnp.arange(S) * jnp.maximum(n_fin, 1)) // S
        pos = jnp.clip(pos, 0, R - 1)
        samp = jnp.take(key_s, pos)
        samp_valid = jnp.arange(S) < jnp.minimum(n_fin, S)

        all_samp = jax.lax.all_gather(samp, DP_AXIS).reshape(P_ * S)
        all_sv = jax.lax.all_gather(samp_valid, DP_AXIS).reshape(P_ * S)
        sinv, ssort = jax.lax.sort(((~all_sv).astype(jnp.int32), all_samp),
                                   num_keys=2)
        m = jnp.sum(all_sv.astype(jnp.int32))
        bpos = (jnp.arange(1, P_) * jnp.maximum(m, 1)) // P_
        bounds = jnp.take(ssort, jnp.clip(bpos, 0, P_ * S - 1))

        pid = jnp.searchsorted(bounds, sort_key, side="right")
        # NaN = largest: last device on ASC order, first on DESC
        pid = jnp.where(is_nan, 0 if descending else P_ - 1, pid)
        cols, valid_r, overflow = all_to_all_rows(
            [keys] + list(payloads), valid,
            pid.astype(jnp.int32), DP_AXIS, P_, capacity)
        keys_r, payloads_r = cols[0], cols[1:]
        skey_r, nan_rank_r, _ = _encode(keys_r)
        # total order: (invalid-last, NaN rank, value key), carried perm
        _, _, _, perm = jax.lax.sort(
            ((~valid_r).astype(jnp.int32), nan_rank_r, skey_r,
             jnp.arange(valid_r.shape[0], dtype=jnp.int32)), num_keys=3)
        out_keys = jnp.take(keys_r, perm)
        out_valid = jnp.take(valid_r, perm)
        out_payloads = [jnp.take(p, perm) for p in payloads_r]
        return tuple([out_keys, out_valid] + out_payloads +
                     [overflow.reshape(1)])

    sharded = jax.shard_map(stage, mesh=mesh, in_specs=P(DP_AXIS),
                            out_specs=P(DP_AXIS), check_vma=False)
    return meter_jit(sharded, name="mesh.sort")


def distributed_hash_join(mesh: Mesh, num_build_payloads: int,
                          num_probe_payloads: int, capacity: int,
                          pair_cap: int):
    """Shuffled hash join (inner equi-join) as ONE SPMD program.

    Both sides hash-partition by Spark-compatible pmod(murmur3(key, 42))
    on device, ride the raw-row all-to-all so equal keys co-locate, and
    each device runs a local sorted-probe join (sort build side, binary
    search per probe row, bounded pair expansion — the same discipline as
    kernels/join.py, kept inside the SPMD program).

    Returns fn(bkeys, bvalid, *bpayloads, pkeys, pvalid, *ppayloads) ->
    (jkeys, jvalid, *bpayloads', *ppayloads', counts) per device, where
    `counts` = [local pair total, build overflow, probe overflow] lets
    the host detect capacity misses (re-run bigger, never silent).
    """
    from blaze_tpu.kernels.join import expand_pairs
    from blaze_tpu.parallel.collective import (all_to_all_rows,
                                               partition_ids_for_keys)

    P_ = mesh.shape[DP_AXIS]
    NB, NP = num_build_payloads, num_probe_payloads

    def stage(*args):
        bkeys, bvalid = args[0], args[1]
        bpay = list(args[2:2 + NB])
        pkeys, pvalid = args[2 + NB], args[3 + NB]
        ppay = list(args[4 + NB:4 + NB + NP])

        # float NaN keys are treated as null HERE: NaN sorts after the
        # +inf padding sentinel and would break the valid-prefix
        # invariant below.  Spark's NaN == NaN join semantics belong to
        # the caller: canonicalize NaN keys to one bit pattern (the
        # planner's key normalization) before the exchange.
        if jnp.issubdtype(bkeys.dtype, jnp.floating):
            bvalid = bvalid & ~jnp.isnan(bkeys)
        if jnp.issubdtype(pkeys.dtype, jnp.floating):
            pvalid = pvalid & ~jnp.isnan(pkeys)

        bpid = partition_ids_for_keys([(bkeys, bvalid)], P_)
        ppid = partition_ids_for_keys([(pkeys, pvalid)], P_)
        bcols, bval_r, bovf = all_to_all_rows(
            [bkeys] + bpay, bvalid, bpid, DP_AXIS, P_, capacity)
        pcols, pval_r, povf = all_to_all_rows(
            [pkeys] + ppay, pvalid, ppid, DP_AXIS, P_, capacity)
        bk, bp = bcols[0], bcols[1:]
        pk, pp = pcols[0], pcols[1:]

        # local sorted-probe join: invalid build keys become a +max
        # sentinel so the sorted array is GLOBALLY ascending (searchsorted
        # needs monotonicity; merely parking invalids last would restart
        # the key order mid-array)
        n = bk.shape[0]
        sentinel = (jnp.inf if jnp.issubdtype(bk.dtype, jnp.floating)
                    else jnp.iinfo(bk.dtype).max)
        bk_masked = jnp.where(bval_r, bk, sentinel)
        # secondary key: invalid-last, so a VALID row whose real key
        # equals the sentinel still sorts before the masked padding and
        # the [0, n_build) prefix is exactly the valid rows
        bk_s, _, bperm = jax.lax.sort(
            (bk_masked, (~bval_r).astype(jnp.int32),
             jnp.arange(n, dtype=jnp.int32)), num_keys=2)
        n_build = jnp.sum(bval_r.astype(jnp.int32))
        lo = jnp.searchsorted(bk_s, pk, side="left")
        hi = jnp.searchsorted(bk_s, pk, side="right")
        # matches beyond the valid prefix are parked invalid rows
        hi = jnp.minimum(hi, n_build)
        count = jnp.where(pval_r, jnp.maximum(hi - lo, 0), 0)
        p_idx, b_sorted_pos, pair_valid, total = expand_pairs(
            lo.astype(jnp.int64), count.astype(jnp.int64), pair_cap)
        b_idx = jnp.take(bperm, jnp.clip(b_sorted_pos, 0, n - 1))

        jkeys = jnp.take(pk, p_idx)
        out_b = [jnp.take(col, b_idx) for col in bp]
        out_p = [jnp.take(col, p_idx) for col in pp]
        # raw total (NOT clamped): total > pair_cap tells the host pairs
        # were dropped — capacity misses must never look like exact fits
        counts = jnp.stack([total.astype(jnp.int64),
                            bovf.astype(jnp.int64),
                            povf.astype(jnp.int64)])
        return tuple([jkeys, pair_valid] + out_b + out_p +
                     [counts.reshape(3)])

    sharded = jax.shard_map(stage, mesh=mesh, in_specs=P(DP_AXIS),
                            out_specs=P(DP_AXIS), check_vma=False)
    return meter_jit(sharded, name="mesh.hash_join")


def distributed_broadcast_join_agg(mesh: Mesh, build_capacity: int):
    """Broadcast-hash-join + grouped aggregation as ONE SPMD program.

    The build side REPLICATES to every device (broadcast = replication,
    SURVEY §2.7; the NativeBroadcastExchangeBase analog) pre-sorted by
    key; probe rows shard across the dp axis.  Each device matches its
    probe shard with a vectorized binary search (the same sorted-build
    discipline as kernels/join), scatter-accumulates sum/count per build
    slot into a local dense table, and a `psum` over ICI merges the
    partials — every device ends with the complete per-build-key
    aggregates, one dispatch, zero host round trips.

    Returns fn(build_keys_sorted, probe_keys, probe_valid, probe_vals)
    -> (sums[build_capacity], counts[build_capacity]), replicated.

    PRECONDITION: build_keys_sorted must be sorted AND unique — the
    binary search credits one slot per key, so duplicate build keys
    would silently undercount (callers dedup with np.unique).
    """
    def stage(build_keys, probe_keys, probe_valid, probe_vals):
        idx = jnp.searchsorted(build_keys, probe_keys)
        idx = jnp.clip(idx, 0, build_capacity - 1)
        matched = probe_valid & (build_keys[idx] == probe_keys)
        slot = jnp.where(matched, idx, build_capacity)
        sums = jnp.zeros(build_capacity, jnp.float64) \
            .at[slot].add(jnp.where(matched, probe_vals, 0.0),
                          mode="drop")
        counts = jnp.zeros(build_capacity, jnp.int64) \
            .at[slot].add(matched.astype(jnp.int64), mode="drop")
        return (jax.lax.psum(sums, DP_AXIS),
                jax.lax.psum(counts, DP_AXIS))

    sharded = jax.shard_map(
        stage, mesh=mesh,
        in_specs=(P(), P(DP_AXIS), P(DP_AXIS), P(DP_AXIS)),
        out_specs=(P(), P()), check_vma=False)
    return meter_jit(sharded, name="mesh.broadcast_join_agg")

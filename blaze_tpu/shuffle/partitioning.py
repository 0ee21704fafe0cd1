"""Partitioning schemes: hash / round-robin / single / range.

Parity: shuffle/mod.rs:113-123 (Partitioning enum) and the Spark-compatible
partition id computation `pmod(murmur3(cols, seed=42), n)`
(ref shuffle/mod.rs:164-189) — bit-exact with Spark's HashPartitioning so a
native map stage can feed vanilla Spark reducers and vice versa.  Range
partitioning uses driver-sampled bounds rows compared via the same host
order-key encoding as sort (ref NativeShuffleExchangeBase.scala:313
rangePartitioningBound + evaluate_range_partition_ids).
"""

from __future__ import annotations

import collections
import threading
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu.batch import ColumnBatch
from blaze_tpu.bridge.context import current_task
from blaze_tpu.bridge.xla_stats import meter_jit
from blaze_tpu.exprs import PhysicalExpr
from blaze_tpu.kernels import hashing as H


class Partitioning:
    num_partitions: int = 1

    def partition_ids(self, batch: ColumnBatch) -> np.ndarray:
        """int32 partition id per (selected) row; batch must be compact."""
        raise NotImplementedError

    def device_partition_ids(self, batch: ColumnBatch):
        """int32 partition id a lane of `batch`'s capacity, left on the
        chip, for the exchange's resident lane (shuffle/writer.py): the
        batch comes with its selection, nothing is compacted and nothing
        read back, and a lane that is no live row may hold any id.  None
        where this partitioning computes its ids on the host."""
        return None


@dataclass
class SinglePartitioning(Partitioning):
    num_partitions: int = 1

    def partition_ids(self, batch: ColumnBatch) -> np.ndarray:
        return np.zeros(batch.num_rows, dtype=np.int32)


# One compiled kernel per (column type signature, partition count): the
# murmur3 chain is ~100 elementwise primitives; dispatched eagerly they
# dominate the whole shuffle write (profiled at ~80% of q01 map wall).
import functools


@functools.lru_cache(maxsize=128)
def _hash_pmod_jit(tids: Tuple[str, ...], n_parts: int):
    def f(flat_cols):
        # the ONE shared pid definition (normalization included) —
        # identical to the device collective lane and the host path
        return H.spark_partition_ids(flat_cols, tids, n_parts, xp=jnp)
    return meter_jit(f, name="shuffle.hash_pmod")


def _native_pmod(flat_cols, tids, n_parts):
    """Fused murmur3+pmod through the native partition kernel
    (partition_kernel.cpp) for all-fixed-width keys; None -> numpy
    chain (strings, unbuilt lib).  Caller has already normalized float
    keys, so every NaN carries the canonical bit pattern the bits-view
    below hashes."""
    import ctypes

    from blaze_tpu.bridge.native import get_partition_kernel
    lib = get_partition_kernel()
    if lib is None:
        return None
    _SUPPORTED = ("bool", "int8", "int16", "int32", "date32", "int64",
                  "timestamp_us", "decimal", "float32", "float64")
    if any(tid not in _SUPPORTED for tid in tids):
        return None  # pre-scan: don't convert columns only to bail
    modes = []
    datas = []      # keeps converted arrays alive across the call
    valid_nps = []
    n = None
    for (v, val), tid in zip(flat_cols, tids):
        if tid in ("bool", "int8", "int16", "int32", "date32"):
            modes.append(0)
            datas.append(np.ascontiguousarray(v, dtype=np.int32))
        elif tid in ("int64", "timestamp_us", "decimal"):
            modes.append(1)
            datas.append(np.ascontiguousarray(v, dtype=np.int64))
        elif tid == "float32":
            modes.append(0)
            datas.append(np.ascontiguousarray(
                v, dtype=np.float32).view(np.int32))
        elif tid == "float64":
            modes.append(1)
            datas.append(np.ascontiguousarray(
                v, dtype=np.float64).view(np.int64))
        else:
            return None  # utf8/binary: numpy byte-matrix path
        n = len(datas[-1]) if n is None else n
        valid_nps.append(
            None if val is None or bool(np.all(val))
            else np.ascontiguousarray(val, dtype=np.uint8))
    if n is None:
        return None
    out = np.empty(n, dtype=np.int32)

    def ptr(a):
        return ctypes.c_void_p(a.ctypes.data) if a is not None else None

    nc = len(modes)
    rc = lib.blaze_murmur3_pmod(
        n, nc, (ctypes.c_int32 * nc)(*modes),
        (ctypes.c_void_p * nc)(*[ptr(a) for a in datas]),
        (ctypes.c_void_p * nc)(*[ptr(a) for a in valid_nps]),
        n_parts, ptr(out))
    return out if rc == 0 else None


# dictionary fingerprint -> (entries' padded bytes, lengths), numpy on the
# host: a dimension table's dictionaries are few and come again with every
# query.  What is placed on a chip is held by the partitioner that placed
# it, for its task alone (`HashPartitioning._placed`)
_DICT_BYTES: "collections.OrderedDict" = collections.OrderedDict()
_DICT_BYTES_LOCK = threading.Lock()
_DICT_BYTES_LIMIT = 64


def _dictionary_bytes(fingerprint: bytes, dictionary: pa.Array):
    """(byte matrix, lengths) of a dictionary's entries, as
    `H.hash_columns` takes a utf8_dict column's: rows padded to a power
    of two of entries and of bytes, so that a new dictionary is rarely a
    new program."""
    with _DICT_BYTES_LOCK:
        entry = _DICT_BYTES.get(fingerprint)
        if entry is not None:
            _DICT_BYTES.move_to_end(fingerprint)
            return entry
    (mat, lengths), _valid = H.string_column_to_padded_bytes(dictionary)
    rows = max(128, 1 << max(0, len(dictionary) - 1).bit_length())
    width = max(4, 1 << max(0, mat.shape[1] - 1).bit_length())
    full = np.zeros((rows, width), dtype=np.uint8)
    full[:mat.shape[0], :mat.shape[1]] = mat
    full_len = np.zeros(rows, dtype=np.int32)
    full_len[:len(lengths)] = lengths
    with _DICT_BYTES_LOCK:
        _DICT_BYTES[fingerprint] = (full, full_len)
        while len(_DICT_BYTES) > _DICT_BYTES_LIMIT:
            _DICT_BYTES.popitem(last=False)
    return full, full_len


class HashPartitioning(Partitioning):
    def __init__(self, exprs: Sequence[PhysicalExpr], num_partitions: int):
        self.exprs = list(exprs)
        self.num_partitions = num_partitions
        # key -> (fingerprint, its dictionary's bytes on this task's
        # chip): the newest dictionary's alone, let go with the plan
        self._placed: dict = {}

    def partition_ids(self, batch: ColumnBatch, keep_on_chip: bool = False):
        """`keep_on_chip`: the device program's ids over every lane of
        the batch's capacity as it left them, or None where the ids are
        computed on the host (host placement, one partition, a key that is
        a host column)."""
        from blaze_tpu.batch import dict_info
        from blaze_tpu.bridge.placement import host_resident
        from blaze_tpu.xputil import asnp, to_device
        n = batch.num_rows
        on_host = host_resident()
        if keep_on_chip and (on_host or self.num_partitions == 1):
            return None
        if self.num_partitions == 1:
            # pmod(h, 1) == 0 for every row: skip the hash chain
            return np.zeros(n, dtype=np.int32)
        # host batches are unpadded; hashing in numpy avoids one jit
        # compile per distinct tail-batch length
        cap = n if on_host else batch.capacity
        flat_cols = []
        tids = []
        for i, e in enumerate(self.exprs):
            v = e.evaluate(batch)
            if v.is_device and v.dictionary is not None:
                # a dictionary column hashes as the strings it stands
                # for: the entries' bytes (laid out once a dictionary,
                # on the chip while this task's batches come under it)
                # gathered by code inside the program
                fp = dict_info(v.dictionary).fingerprint
                mat, lengths = _dictionary_bytes(fp, v.dictionary)
                if not on_host:
                    held = self._placed.get(i)
                    if held is None or held[0] != fp:
                        held = self._placed[i] = (
                            fp, tuple(to_device((mat, lengths))))
                    mat, lengths = held[1]
                codes, valid = ((asnp(v.data)[:cap], asnp(v.validity)[:cap])
                                if on_host else (v.data, v.validity))
                flat_cols.append(((codes, mat, lengths), valid))
                tids.append("utf8_dict")
            elif v.is_device:
                if on_host:
                    flat_cols.append((asnp(v.data)[:cap],
                                      asnp(v.validity)[:cap]))
                else:
                    flat_cols.append((v.data, v.validity))
                tids.append(v.dtype.id.value)
            elif keep_on_chip:
                return None
            else:
                # host (string) columns are exact-length; pad the byte
                # matrix to the batch capacity so mixed string+fixed key
                # hashes line up lane-for-lane
                arr = v.to_host(n)
                (mat, lengths), valid = H.string_column_to_padded_bytes(arr)
                # pow2 width bucket: one compile per bucket, not per batch
                w = max(4, 1 << (mat.shape[1] - 1).bit_length()) \
                    if mat.shape[1] else 4
                full = np.zeros((cap, w), dtype=mat.dtype)
                full[:mat.shape[0], :mat.shape[1]] = mat
                full_len = np.zeros(cap, dtype=lengths.dtype)
                full_len[:len(lengths)] = lengths
                pad_valid = np.zeros(cap, dtype=bool)
                pad_valid[:len(valid)] = valid
                if on_host:
                    flat_cols.append(((full, full_len), pad_valid))
                else:
                    flat_cols.append(to_device(((full, full_len),
                                                pad_valid)))
                tids.append("utf8")
        if on_host:
            # the native kernel hashes raw bit views, so it needs the
            # normalization applied up front; the numpy fallback goes
            # through the shared definition (normalization idempotent)
            flat_cols = H.norm_float_keys(flat_cols, tids, np)
            pids = _native_pmod(flat_cols, tids, self.num_partitions)
            if pids is not None:
                return pids[:n]
            pids = H.spark_partition_ids(flat_cols, tids,
                                         self.num_partitions, xp=np)
            return np.asarray(pids)[:n].astype(np.int32)
        pids = _hash_pmod_jit(tuple(tids), self.num_partitions)(flat_cols)
        if keep_on_chip:
            return pids
        return asnp(pids)[:n].astype(np.int32)

    def device_partition_ids(self, batch: ColumnBatch):
        # a row's id is its keys' alone: deselected lanes are hashed with
        # the rest and never looked at
        return self.partition_ids(batch, keep_on_chip=True)


def _round_robin_ids(selection, rows, cursor, cap: int, n_parts: int):
    """(ids, the cursor behind them): live row k of the batch (one of its
    first `rows` lanes that `selection`, where there is one, keeps) takes
    partition (cursor + k) mod n."""
    mask = jnp.arange(cap, dtype=jnp.int32) < rows
    if selection is not None:
        mask = mask & selection
    rank = jnp.cumsum(mask.astype(jnp.int32))
    cursor = jnp.asarray(cursor, jnp.int32)
    return (rank - 1 + cursor) % n_parts, (cursor + rank[-1]) % n_parts


_round_robin = meter_jit(_round_robin_ids, name="shuffle.round_robin",
                         static_argnames=("cap", "n_parts"))


class RoundRobinPartitioning(Partitioning):
    def __init__(self, num_partitions: int):
        self.num_partitions = num_partitions
        self._next = 0

    def partition_ids(self, batch: ColumnBatch) -> np.ndarray:
        n = batch.num_rows
        # Spark RoundRobin starts at a per-task position; keep a running
        # cursor so rows spread evenly across batches
        ids = (np.arange(n, dtype=np.int64) + self._cursor()) \
            % self.num_partitions
        self._next = int((self._cursor() + n) % self.num_partitions)
        return ids.astype(np.int32)

    def _cursor(self) -> int:
        """The running cursor on the host (read back once, if the resident
        lane left it on the chip)."""
        if isinstance(self._next, jax.Array):
            from blaze_tpu.xputil import asnp
            self._next = int(asnp(self._next))
        return self._next

    def device_partition_ids(self, batch: ColumnBatch):
        # the k-th LIVE row of the task takes partition k mod n: a row's
        # rank among the live lanes, behind a cursor that stays on the chip
        # (the count it advances by is never read back)
        from blaze_tpu.bridge.placement import host_resident
        if host_resident():
            return None
        pids, self._next = _round_robin(
            batch.selection, np.int32(batch.num_rows),
            np.int32(self._next) if isinstance(self._next, int)
            else self._next,
            cap=batch.capacity, n_parts=self.num_partitions)
        return pids


class RangePartitioning(Partitioning):
    """Bounds rows (one per cut, sorted) decide the partition id via
    binary search on host order keys."""

    def __init__(self, sort_exprs: Sequence[Tuple[PhysicalExpr, bool, bool]],
                 num_partitions: int, bounds: pa.RecordBatch):
        self.sort_exprs = list(sort_exprs)
        self.num_partitions = num_partitions
        self.bounds = bounds  # num_partitions-1 rows, columns match sort keys
        from blaze_tpu.ops.sort import host_sort_keys
        self._bound_keys = host_sort_keys(
            bounds, list(range(bounds.num_columns)),
            [d for _, d, _ in self.sort_exprs],
            [f for _, _, f in self.sort_exprs])

    def partition_ids(self, batch: ColumnBatch) -> np.ndarray:
        from blaze_tpu.ops.sort import host_sort_keys
        n = batch.num_rows
        arrays = [e.evaluate(batch).to_host(n)
                  for e, _, _ in self.sort_exprs]
        rb = pa.RecordBatch.from_arrays(
            arrays, names=[f"k{i}" for i in range(len(arrays))])
        row_keys = host_sort_keys(rb, list(range(len(arrays))),
                                  [d for _, d, _ in self.sort_exprs],
                                  [f for _, _, f in self.sort_exprs])
        # id = count of bounds STRICTLY below the row (ties stay in the
        # bound's own partition, matching Spark RangePartitioner)
        nb = len(self._bound_keys[0])
        ids = np.zeros(n, dtype=np.int32)
        from blaze_tpu.ops.sort import compare_scalar
        for b in range(nb):
            gt = np.zeros(n, dtype=bool)
            for j in range(len(row_keys) - 1, -1, -1):
                rk = row_keys[j]
                bk = compare_scalar(rk, self._bound_keys[j][b])
                gt = (rk > bk) | ((rk == bk) & gt)
            ids += gt.astype(np.int32)
        return ids


def sample_range_bounds(sample: pa.Table,
                        sort_exprs: Sequence[Tuple[PhysicalExpr, bool, bool]],
                        num_partitions: int,
                        key_names: Sequence[str]) -> pa.RecordBatch:
    """Driver-side bounds sampling (the rangePartitioningBound analog):
    sort the sample, pick num_partitions-1 evenly spaced rows."""
    from blaze_tpu.ops import MemoryScanExec, SortExec
    scan = MemoryScanExec.from_arrow(sample)
    plan = SortExec(scan, sort_exprs)
    sorted_rb = plan.execute_collect().to_arrow()
    n = sorted_rb.num_rows
    cuts = [int(n * (i + 1) / num_partitions) for i in range(num_partitions - 1)]
    cuts = [min(c, n - 1) for c in cuts]
    idx = pa.array(cuts, type=pa.int64())
    cols = [sorted_rb.column(sorted_rb.schema.get_field_index(k)).take(idx)
            for k in key_names]
    return pa.RecordBatch.from_arrays(cols, names=list(key_names))

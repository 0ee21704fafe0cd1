"""Spark-compatible murmur3 (seed 42) and xxhash64 as vectorized array kernels.

Behavioral parity with the reference kernels
(ref: datafusion-ext-commons/src/spark_hash.rs:28 `create_murmur3_hashes`,
`:34` create_xxhash64_hashes; test vectors at spark_hash.rs:415-520) which are
themselves validated against Spark's `Murmur3_x86_32` / `XXH64`.

Design notes (TPU-first):
  * All kernels are written against either numpy or jax.numpy via the `xp`
    parameter — one implementation serves the host path (string columns,
    shuffle-file bookkeeping) and the device path (shuffle partition ids
    computed inside the jit'd stage function).
  * Hash chaining across columns matches Spark: the running hash of row i is
    the seed for the next column; NULL leaves the running hash unchanged.
  * Variable-width (utf8/binary) hashing takes a padded (rows, max_len) byte
    matrix + per-row lengths — the pointer-free representation (offsets are
    resolved when building the matrix).  Word loops unroll over the static
    max_len, vectorized across rows.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# murmur3_x86_32 (Spark Murmur3_x86_32), 32-bit lanes
# ---------------------------------------------------------------------------

_C1 = 0xCC9E2D51
_C2 = 0x1B873593


def _u32(xp, v):
    return xp.uint32(v) if xp is np else jnp.uint32(v)


def _rotl32(xp, x, r: int):
    return (x << _u32(xp, r)) | (x >> _u32(xp, 32 - r))


def _mix_k1(xp, k1):
    k1 = k1 * _u32(xp, _C1)
    k1 = _rotl32(xp, k1, 15)
    return k1 * _u32(xp, _C2)


def _mix_h1(xp, h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl32(xp, h1, 13)
    return h1 * _u32(xp, 5) + _u32(xp, 0xE6546B64)


def _fmix(xp, h1, length):
    h1 = h1 ^ xp.uint32(length) if isinstance(length, int) else h1 ^ length
    h1 = h1 ^ (h1 >> _u32(xp, 16))
    h1 = h1 * _u32(xp, 0x85EBCA6B)
    h1 = h1 ^ (h1 >> _u32(xp, 13))
    h1 = h1 * _u32(xp, 0xC2B2AE35)
    return h1 ^ (h1 >> _u32(xp, 16))


def murmur3_hash_int(values, seeds, xp=jnp):
    """Spark Murmur3_x86_32.hashInt: values int32-like, seeds uint32."""
    k = values.astype(xp.int32).view(xp.uint32) if xp is np else \
        jnp.asarray(values, dtype=jnp.int32).view(jnp.uint32)
    h1 = _mix_h1(xp, seeds.astype(xp.uint32), _mix_k1(xp, k))
    return _fmix(xp, h1, 4)


def murmur3_hash_long(values, seeds, xp=jnp):
    """Spark Murmur3_x86_32.hashLong: low 32-bit word then high word."""
    v = values.astype(xp.int64) if xp is np else jnp.asarray(values, dtype=jnp.int64)
    u = v.view(xp.uint64)
    lo = (u & xp.uint64(0xFFFFFFFF)).astype(xp.uint32)
    hi = (u >> xp.uint64(32)).astype(xp.uint32)
    h1 = seeds.astype(xp.uint32)
    h1 = _mix_h1(xp, h1, _mix_k1(xp, lo))
    h1 = _mix_h1(xp, h1, _mix_k1(xp, hi))
    return _fmix(xp, h1, 8)


def murmur3_hash_bytes(byte_mat, lengths, seeds, xp=np):
    """Spark Murmur3_x86_32.hashUnsafeBytes over padded byte rows.

    byte_mat: (rows, max_len) uint8, zero-padded; lengths: (rows,) int32.
    Matches Spark: little-endian 4-byte words for the aligned prefix, then
    per-byte tail mixed as SIGNED bytes (Spark's halfWord = getByte()).
    """
    rows, max_len = byte_mat.shape
    pad = (-max_len) % 4
    if pad:
        byte_mat = xp.concatenate(
            [byte_mat, xp.zeros((rows, pad), dtype=xp.uint8)], axis=1)
    n_words = byte_mat.shape[1] // 4
    words = byte_mat.reshape(rows, n_words, 4).astype(xp.uint32)
    # little-endian word assembly
    w = (words[:, :, 0] | (words[:, :, 1] << _u32(xp, 8))
         | (words[:, :, 2] << _u32(xp, 16)) | (words[:, :, 3] << _u32(xp, 24)))
    lengths = lengths.astype(xp.int32)
    aligned_words = lengths // 4
    h1 = seeds.astype(xp.uint32)
    for j in range(n_words):
        mixed = _mix_h1(xp, h1, _mix_k1(xp, w[:, j]))
        h1 = xp.where(j < aligned_words, mixed, h1)
    # tail: bytes [aligned, length) one at a time, sign-extended
    tail_start = aligned_words * 4
    for t in range(3):
        idx = tail_start + t
        in_tail = idx < lengths
        gathered = xp.take_along_axis(
            byte_mat, xp.clip(idx, 0, byte_mat.shape[1] - 1)[:, None], axis=1)[:, 0]
        signed = gathered.astype(xp.int8).astype(xp.int32).view(xp.uint32) if xp is np \
            else gathered.astype(jnp.int8).astype(jnp.int32).view(jnp.uint32)
        mixed = _mix_h1(xp, h1, _mix_k1(xp, signed))
        h1 = xp.where(in_tail, mixed, h1)
    return _fmix(xp, h1, lengths.view(xp.uint32) if xp is np
                 else lengths.view(jnp.uint32))


# ---------------------------------------------------------------------------
# xxhash64 (Spark XXH64), 64-bit lanes (requires jax x64, enabled at import)
# ---------------------------------------------------------------------------

_P1 = 0x9E3779B185EBCA87
_P2 = 0xC2B2AE3D27D4EB4F
_P3 = 0x165667B19E3779F9
_P4 = 0x85EBCA77C2B2AE63
_P5 = 0x27D4EB2F165667C5


def _u64(xp, v):
    return xp.uint64(v)


def _rotl64(xp, x, r: int):
    return (x << _u64(xp, r)) | (x >> _u64(xp, 64 - r))


def _fmix64(xp, h):
    h = h ^ (h >> _u64(xp, 33))
    h = h * _u64(xp, _P2)
    h = h ^ (h >> _u64(xp, 29))
    h = h * _u64(xp, _P3)
    return h ^ (h >> _u64(xp, 32))


def xxhash64_long(values, seeds, xp=jnp):
    """Spark XXH64.hashLong (8-byte input)."""
    v = values.astype(xp.int64).view(xp.uint64) if xp is np else \
        jnp.asarray(values, dtype=jnp.int64).view(jnp.uint64)
    h = seeds.astype(xp.uint64) + _u64(xp, _P5) + _u64(xp, 8)
    k1 = _rotl64(xp, v * _u64(xp, _P2), 31) * _u64(xp, _P1)
    h = h ^ k1
    h = _rotl64(xp, h, 27) * _u64(xp, _P1) + _u64(xp, _P4)
    return _fmix64(xp, h)


def xxhash64_int(values, seeds, xp=jnp):
    """Spark XXH64.hashInt (4-byte input, zero-extended)."""
    v = values.astype(xp.int32).view(xp.uint32) if xp is np else \
        jnp.asarray(values, dtype=jnp.int32).view(jnp.uint32)
    v = v.astype(xp.uint64)
    h = seeds.astype(xp.uint64) + _u64(xp, _P5) + _u64(xp, 4)
    h = h ^ (v * _u64(xp, _P1))
    h = _rotl64(xp, h, 23) * _u64(xp, _P2) + _u64(xp, _P3)
    return _fmix64(xp, h)


def xxhash64_bytes(byte_mat, lengths, seeds, xp=np):
    """Spark XXH64.hashUnsafeBytes over padded byte rows (vectorized).

    Mirrors Spark's stripe(32B) + 8B + 4B + 1B structure with per-row masks.
    """
    rows, max_len = byte_mat.shape
    pad = (-max_len) % 32
    if pad:
        byte_mat = xp.concatenate(
            [byte_mat, xp.zeros((rows, pad), dtype=xp.uint8)], axis=1)
    padded_len = byte_mat.shape[1]
    lengths = lengths.astype(xp.int64)
    seeds = seeds.astype(xp.uint64)

    # assemble little-endian u64 words: (rows, padded_len//8)
    b = byte_mat.astype(xp.uint64)
    w64 = b.reshape(rows, -1, 8)
    longs = w64[:, :, 0]
    for i in range(1, 8):
        longs = longs | (w64[:, :, i] << _u64(xp, 8 * i))
    w32 = b.reshape(rows, -1, 4)
    ints = w32[:, :, 0]
    for i in range(1, 4):
        ints = ints | (w32[:, :, i] << _u64(xp, 8 * i))

    n_stripes_per_row = lengths // 32
    has_stripes = lengths >= 32

    v1 = seeds + _u64(xp, _P1) + _u64(xp, _P2)
    v2 = seeds + _u64(xp, _P2)
    v3 = seeds + _u64(xp, 0)
    v4 = seeds - _u64(xp, _P1)
    max_stripes = padded_len // 32
    for s in range(max_stripes):
        active = s < n_stripes_per_row
        base = 4 * s

        def _round(v, k):
            return _rotl64(xp, v + k * _u64(xp, _P2), 31) * _u64(xp, _P1)
        v1 = xp.where(active, _round(v1, longs[:, base + 0]), v1)
        v2 = xp.where(active, _round(v2, longs[:, base + 1]), v2)
        v3 = xp.where(active, _round(v3, longs[:, base + 2]), v3)
        v4 = xp.where(active, _round(v4, longs[:, base + 3]), v4)

    merged = (_rotl64(xp, v1, 1) + _rotl64(xp, v2, 7)
              + _rotl64(xp, v3, 12) + _rotl64(xp, v4, 18))
    for v in (v1, v2, v3, v4):
        merged = merged ^ (_rotl64(xp, v * _u64(xp, _P2), 31) * _u64(xp, _P1))
        merged = merged * _u64(xp, _P1) + _u64(xp, _P4)
    h = xp.where(has_stripes, merged, seeds + _u64(xp, _P5))
    h = h + lengths.view(xp.uint64)

    # remaining 8-byte chunks after the stripes
    offset = n_stripes_per_row * 32  # in bytes
    n_longs_total = lengths // 8
    max_longs = padded_len // 8
    for j in range(max_longs):
        pos = xp.int64(j * 8)
        active = (pos >= offset) & (j < n_longs_total)
        k1 = _rotl64(xp, longs[:, j] * _u64(xp, _P2), 31) * _u64(xp, _P1)
        nh = _rotl64(xp, h ^ k1, 27) * _u64(xp, _P1) + _u64(xp, _P4)
        h = xp.where(active, nh, h)
    offset = n_longs_total * 8

    # one 4-byte chunk
    has_int = (lengths - offset) >= 4
    int_idx = xp.clip(offset // 4, 0, ints.shape[1] - 1)
    k = xp.take_along_axis(ints, int_idx[:, None], axis=1)[:, 0]
    nh = _rotl64(xp, h ^ (k * _u64(xp, _P1)), 23) * _u64(xp, _P2) + _u64(xp, _P3)
    h = xp.where(has_int, nh, h)
    offset = offset + xp.where(has_int, xp.int64(4), xp.int64(0))

    # trailing single bytes (unsigned)
    for t in range(7):
        idx = offset + t
        in_tail = idx < lengths
        gathered = xp.take_along_axis(
            byte_mat, xp.clip(idx, 0, padded_len - 1)[:, None].astype(xp.int64),
            axis=1)[:, 0].astype(xp.uint64)
        nh = _rotl64(xp, h ^ (gathered * _u64(xp, _P5)), 11) * _u64(xp, _P1)
        h = xp.where(in_tail, nh, h)
    return _fmix64(xp, h)


# ---------------------------------------------------------------------------
# Column-level drivers (null skipping + cross-column chaining, Spark style)
# ---------------------------------------------------------------------------

def _hash_fixed_column(values, validity, dtype_id: str, seeds, xp, algo: str):
    """One column's contribution; NULL rows keep their incoming seed."""
    int_fn = murmur3_hash_int if algo == "murmur3" else xxhash64_int
    long_fn = murmur3_hash_long if algo == "murmur3" else xxhash64_long
    if dtype_id in ("bool",):
        v = values.astype(xp.int32)
        h = int_fn(v, seeds, xp)
    elif dtype_id in ("int8", "int16", "int32", "date32"):
        h = int_fn(values.astype(xp.int32), seeds, xp)
    elif dtype_id in ("int64", "timestamp_us", "decimal"):
        h = long_fn(values.astype(xp.int64), seeds, xp)
    elif dtype_id == "float32":
        f = values.astype(xp.float32)
        # Spark: hashInt(floatToIntBits(f)); java canonicalizes NaN
        bits = f.view(xp.int32) if xp is np else jnp.asarray(f).view(jnp.int32)
        canonical_nan = xp.int32(0x7FC00000)
        bits = xp.where(xp.isnan(f), canonical_nan, bits)
        h = int_fn(bits, seeds, xp)
    elif dtype_id == "float64":
        f = values.astype(xp.float64)
        bits = f.view(xp.int64) if xp is np else jnp.asarray(f).view(jnp.int64)
        canonical_nan = xp.int64(0x7FF8000000000000)
        bits = xp.where(xp.isnan(f), canonical_nan, bits)
        h = long_fn(bits, seeds, xp)
    else:
        raise TypeError(f"unsupported fixed-width type for hashing: {dtype_id}")
    if validity is None:
        return h
    return xp.where(validity, h, seeds)


def hash_columns(columns: Sequence[Tuple], seed: int = 42, xp=jnp,
                 algo: str = "murmur3", num_rows: Optional[int] = None):
    """Spark-chained multi-column hash.

    columns: sequence of (values, validity_or_None, type_id_str) where values
    for utf8/binary are (byte_mat, lengths) tuples and for utf8_dict
    (codes, the dictionary entries' byte_mat, their lengths).
    Returns int32 array (murmur3) or int64 array (xxhash64).
    """
    assert columns, "need at least one column"
    if num_rows is None:
        first = columns[0][0]
        num_rows = first[0].shape[0] if isinstance(first, tuple) else first.shape[0]
        # (a utf8_dict column's first array is its codes: a row each)
    if algo == "murmur3":
        seeds = xp.full(num_rows, seed, dtype=xp.uint32)
    else:
        seeds = (xp.full(num_rows, seed, dtype=xp.int64)).view(xp.uint64) if xp is np \
            else jnp.full(num_rows, seed, dtype=jnp.int64).view(jnp.uint64)
    for values, validity, tid in columns:
        if tid in ("utf8", "binary", "utf8_dict"):
            if tid == "utf8_dict":
                # a dictionary column: the ENTRIES' bytes, laid out once a
                # dictionary, gathered by code; the hash is the string's
                # own, so a coded and a plain batch of one exchange agree
                codes, entry_mat, entry_len = values
                byte_mat = xp.take(entry_mat, codes, axis=0)
                lengths = xp.take(entry_len, codes)
            else:
                byte_mat, lengths = values
            fn = murmur3_hash_bytes if algo == "murmur3" else xxhash64_bytes
            h = fn(byte_mat, lengths, seeds, xp)
            seeds = xp.where(validity, h, seeds) if validity is not None else h
        else:
            seeds = _hash_fixed_column(values, validity, tid, seeds, xp, algo)
    if algo == "murmur3":
        return seeds.view(xp.int32)
    return seeds.view(xp.int64)


def norm_float_keys(flat_cols, tids, xp):
    """Normalize -0.0 -> 0.0 and NaN -> one canonical pattern in float
    key columns before hashing.  Spark inserts NormalizeFloatingNumbers
    upstream of HashPartitioning, grouping and join-key hashing — the
    hash kernels themselves stay raw/bit-exact (the hash() SQL function
    does NOT normalize)."""
    import numpy as _np
    out = []
    for (v, val), tid in zip(flat_cols, tids):
        if tid in ("float32", "float64"):
            v = xp.where(v == 0, xp.abs(v), v)
            v = xp.where(xp.isnan(v), xp.array(_np.nan, dtype=v.dtype), v)
        out.append((v, val))
    return out


def pmod(hashes, n: int, xp=jnp):
    """Spark's non-negative modulo for partition ids
    (ref shuffle/mod.rs:164-189: pmod(murmur3(cols, 42), num_partitions))."""
    h = hashes.astype(xp.int32)
    m = h % xp.int32(n)
    return xp.where(m < 0, m + xp.int32(n), m)


def spark_partition_ids(flat_cols, tids, num_partitions: int, xp=jnp):
    """THE Spark-compatible partition id: pmod(murmur3(normalize(keys),
    seed=42), P).

    Single source of truth shared by the host hash-partition path
    (shuffle/partitioning.py) and the device collective lane
    (parallel/collective.partition_ids_for_keys): both MUST route the
    same row to the same reducer or a device exchange and its file-path
    fallback would disagree about where a key lives.  Normalization
    (NormalizeFloatingNumbers: -0.0 -> 0.0, NaN -> one canonical
    pattern) is part of the definition, not the caller's problem — it
    is idempotent, so pre-normalized host columns pass through
    unchanged.

    flat_cols: [(values, validity_or_None)] aligned with `tids`
    (type-id strings; utf8/binary values are (byte_mat, lengths)).
    Traceable under jit/shard_map with xp=jnp; pure numpy with xp=np.
    """
    flat_cols = norm_float_keys(flat_cols, tids, xp)
    cols = [(v, val, tid) for (v, val), tid in zip(flat_cols, tids)]
    h = hash_columns(cols, seed=42, xp=xp, algo="murmur3")
    return pmod(h, num_partitions, xp=xp)


def string_column_to_padded_bytes(arr, xp=np) -> Tuple:
    """pyarrow string/binary array -> (byte_mat uint8 (n, max_len), lengths).

    The pointer-free device form: offsets resolved on host, bytes padded."""
    import pyarrow as pa
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_large_string(arr.type) or pa.types.is_large_binary(arr.type):
        arr = arr.cast(pa.binary())
    n = len(arr)
    if n == 0:
        mat = np.zeros((0, 4), dtype=np.uint8)
        lengths = np.zeros(0, dtype=np.int32)
        valid = np.ones(0, dtype=bool)
    else:
        # vectorized from the Arrow offsets/data buffers — no per-row Python
        validity_buf = arr.buffers()[0]
        if validity_buf is None or arr.null_count == 0:
            valid = np.ones(n, dtype=bool)
        else:
            bits = np.unpackbits(np.frombuffer(validity_buf, dtype=np.uint8),
                                 bitorder="little")
            valid = bits[arr.offset:arr.offset + n].astype(bool)
        offsets = np.frombuffer(arr.buffers()[1], dtype=np.int32)[
            arr.offset:arr.offset + n + 1].astype(np.int64)
        data_buf = arr.buffers()[2]
        data = (np.frombuffer(data_buf, dtype=np.uint8) if data_buf is not None
                else np.zeros(0, dtype=np.uint8))
        lengths = np.diff(offsets).astype(np.int32)
        max_len = max(int(lengths.max()), 4)
        if len(data) == 0:
            # all rows empty or null: no data buffer to gather from
            mat = np.zeros((n, max_len), dtype=np.uint8)
        else:
            idx = offsets[:-1, None] + np.arange(max_len)[None, :]
            in_range = np.arange(max_len)[None, :] < lengths[:, None]
            safe = np.clip(idx, 0, len(data) - 1)
            mat = np.where(in_range, data[safe], np.uint8(0))
        lengths = np.where(valid, lengths, 0).astype(np.int32)
    if xp is not np:
        return (xp.asarray(mat), xp.asarray(lengths)), xp.asarray(valid)
    return (mat, lengths), valid

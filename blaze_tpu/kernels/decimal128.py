"""Two-limb int128 primitives for decimal128 on the device lanes.

Decimals of precision <= 18 live on device as unscaled int64 (or int32
for p <= 9 under the narrow tier) — see `batch.DeviceColumn.from_arrow`.
Same-scale comparisons and +/- are exact on those ints directly; what
this module adds is the UNEQUAL-scale case: rescaling one side by
10^(scale delta) can overflow int64 (10^18 * 10^2 > 2^63), so both
sides widen to a two-limb (hi int64, lo int64-as-unsigned) int128 pair
first.  10^18 * 10^20 < 2^127, so rescaled compares can never overflow
the pair — no rounding, no wrap, bit-identical to host Arrow decimal
comparison semantics (ANSI and non-ANSI agree on compares).

Everything is element-wise int64 vector math in the repo's xp-agnostic
kernel idiom (xp = numpy on host residency, jnp under jit — XLA lowers
these to plain VPU vector ops; no custom grid is needed for
element-wise work).  The unsigned-low-limb arithmetic uses the classic
signed-int tricks so the same code runs on backends without native
uint64:

  * unsigned compare:  u_lt(a, b) == (a ^ INT64_MIN) <_signed (b ^ INT64_MIN)
  * add carry-out:     carry = u_lt(a + b, a)
  * 64x32 multiply:    split the low limb into 32-bit halves; every
    partial product fits in a signed int64.

`spark_decimal128_hash` covers the hash side of the limb lane: Spark
hashes precision > 18 decimals as murmur3 over the MINIMAL big-endian
two's-complement byte form of the unscaled value (p <= 18 hash as
plain longs — kernels/hashing._hash_fixed_column).  It is a host-side
(numpy) utility: wide decimals are host-resident by construction, the
kernel exists so the exchange partitioner can stay bit-equal to
`spark_partition_ids` if wide keys ever cross it.
"""

from __future__ import annotations

import numpy as np

from blaze_tpu.schema import BOOL, DataType
from blaze_tpu.xputil import xp_of

_MIN64 = -0x8000000000000000  # 1 << 63 as signed int64 bit pattern
_MASK32 = 0xFFFFFFFF


def _i64(xp, v):
    return xp.asarray(np.int64(v))


def u_lt(xp, a, b):
    """Unsigned < over int64 bit patterns."""
    bias = _i64(xp, _MIN64)
    return (a ^ bias) < (b ^ bias)


def from_int64(xp, v):
    """Sign-extend an int64 vector to an (hi, lo) int128 pair."""
    v = v.astype(xp.int64)
    return v >> 63, v  # arithmetic shift: hi is 0 or -1


def add128(xp, ah, al, bh, bl):
    """(ah, al) + (bh, bl) with carry between limbs (wrapping int128)."""
    rl = (al + bl)  # int64 wrap IS the unsigned low-limb add
    carry = u_lt(xp, rl, al).astype(xp.int64)
    rh = ah + bh + carry
    return rh, rl


def neg128(xp, h, l):
    """Two's-complement negate."""
    nl = -l  # wraps for INT64_MIN, as two's complement requires
    nh = ~h + (l == 0).astype(xp.int64)
    return nh, nl


def sub128(xp, ah, al, bh, bl):
    nh, nl = neg128(xp, bh, bl)
    return add128(xp, ah, al, nh, nl)


def mul_small(xp, h, l, m: int):
    """(h, l) * m for a static 0 <= m < 2^31 — every partial product
    fits a signed int64.  Wrapping int128 (callers keep |result| within
    int128 by construction: 10^18 * 10^20 < 2^127)."""
    assert 0 <= m < (1 << 31)
    mm = _i64(xp, m)
    l0 = l & _i64(xp, _MASK32)            # unsigned low 32 of low limb
    l1 = (l >> 32) & _i64(xp, _MASK32)    # unsigned high 32 of low limb
    p0 = l0 * mm                          # < 2^63, non-negative
    p1 = l1 * mm + ((p0 >> 32) & _i64(xp, _MASK32))
    rl = (p1 << 32) | (p0 & _i64(xp, _MASK32))
    carry = (p1 >> 32) & _i64(xp, _MASK32)
    rh = h * mm + carry
    return rh, rl


def mul_pow10(xp, h, l, k: int):
    """(h, l) * 10^k for static k >= 0, in chunks of 10^9 (< 2^31)."""
    assert k >= 0
    while k > 0:
        step = min(k, 9)
        h, l = mul_small(xp, h, l, 10 ** step)
        k -= step
    return h, l


def eq128(xp, ah, al, bh, bl):
    return (ah == bh) & (al == bl)


def lt128(xp, ah, al, bh, bl):
    """Signed int128 <: signed compare on hi, unsigned on lo."""
    return (ah < bh) | ((ah == bh) & u_lt(xp, al, bl))


def fits_int64(xp, h, l):
    """True where the pair is exactly a sign-extended int64."""
    return h == (l >> 63)


def add_overflows(xp, ah, bh, rh):
    """Signed int128 add overflow: operands share a sign the result
    lost.  Callers promote such rows to the eager host path — never
    silently wrap (the ISSUE's overflow contract)."""
    return ((ah < 0) == (bh < 0)) & ((rh < 0) != (ah < 0))


def rescaled_pair(xp, values, scale: int, target_scale: int):
    """Unscaled int64 decimal values at `scale` -> int128 pair at
    `target_scale` (target >= scale; compares align both sides to
    max(scale))."""
    h, l = from_int64(xp, values)
    return mul_pow10(xp, h, l, target_scale - scale)


# A decimal wider than 18 digits has no int64 lane.  Inside an expression
# it is its two limbs: `data` of shape (capacity, 2), [:, 0] the low limb
# and [:, 1] the high one, which is also how Arrow lays a decimal128 out.

def is_limbs(data) -> bool:
    return getattr(data, "ndim", 1) == 2


def pair_of(xp, data):
    """(hi, lo) of a lane: a limb lane's own, an integer lane's sign-
    extended."""
    if is_limbs(data):
        return data[:, 1], data[:, 0]
    return from_int64(xp, data)


def host_limbs(arr, capacity: int):
    """A decimal128 Arrow array as a limb lane padded to `capacity`:
    (data int64 (capacity, 2), validity bool (capacity,)), numpy."""
    import pyarrow as pa
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    from blaze_tpu.batch import decimal_limb_pairs
    n = len(arr)
    data = np.zeros((capacity, 2), dtype=np.int64)
    data[:n] = decimal_limb_pairs(arr)
    valid = np.zeros(capacity, dtype=bool)
    valid[:n] = np.asarray(arr.is_valid()) if arr.null_count else True
    return data, valid


def limbs_to_arrow(data, validity, num_rows: int, t):
    """A limb lane read back -> decimal128 Arrow array of `num_rows`."""
    from blaze_tpu.batch import decimal_from_limbs
    data = np.asarray(data)[:num_rows]
    return decimal_from_limbs(data[:, 0], data[:, 1],
                              np.asarray(validity)[:num_rows], t)


def multiply_colvals(a, b, m: int, out: DataType):
    """decimal * decimal where one side is the constant `m` (unscaled,
    |m| < 2^31) and `a` is the other: exact at `out` = (p1+p2+1, s1+s2),
    which no product of in-bound operands can pass.  Returns an integer
    lane where `out` has one, a limb lane where it does not."""
    from blaze_tpu.exprs.base import ColVal
    xp = xp_of(a.data, b.data)
    valid = a.validity & b.validity
    if out.precision <= 18:
        data = a.data.astype(xp.int64) * _i64(xp, m)
        return ColVal(out, data=xp.where(valid, data, 0), validity=valid)
    h, l = pair_of(xp, a.data)
    h, l = mul_small(xp, h, l, abs(m))
    if m < 0:
        h, l = neg128(xp, h, l)
    _note_limb_dispatch(a.data)
    zero = _i64(xp, 0)
    data = xp.stack([xp.where(valid, l, zero), xp.where(valid, h, zero)],
                    axis=1)
    return ColVal(out, data=data, validity=valid)


def compare_colvals(op: str, a, b, ldt: DataType, rdt: DataType):
    """Device comparison of two decimal ColVals with unequal scales or
    more than 18 digits, via int128 rescale.  Traceable (pure vector
    math), so predicates using it keep their stage on the device loop.
    Returns a BOOL ColVal with Spark null semantics (<=> is null-safe)."""
    from blaze_tpu.exprs.base import ColVal
    xp = xp_of(a.data, b.data)
    target = max(ldt.scale, rdt.scale)
    xh, xl = mul_pow10(xp, *pair_of(xp, a.data), target - ldt.scale)
    yh, yl = mul_pow10(xp, *pair_of(xp, b.data), target - rdt.scale)
    _note_limb_dispatch(a.data)
    eq = eq128(xp, xh, xl, yh, yl)
    lt = lt128(xp, xh, xl, yh, yl)
    if op == "<=>":
        data = (eq & a.validity & b.validity) | (~a.validity & ~b.validity)
        return ColVal.device(BOOL, data)
    valid = a.validity & b.validity
    data = {"==": eq, "!=": ~eq, "<": lt, "<=": lt | eq,
            ">": ~(lt | eq), ">=": ~lt}[op]
    return ColVal(BOOL, data=data & valid, validity=valid)


def _note_limb_dispatch(probe) -> None:
    import jax
    if isinstance(probe, jax.core.Tracer):
        return  # under trace: the jit caller's metering covers the run
    from blaze_tpu.bridge import xla_stats
    xla_stats.note_encoding(decimal_limb_dispatches=1)


# ---------------------------------------------------------------------------
# Spark hash parity for wide decimals (p > 18): murmur3 over minimal
# big-endian two's-complement bytes of the unscaled value.
# ---------------------------------------------------------------------------

def minimal_be_bytes(hi: np.ndarray, lo: np.ndarray):
    """(byte_mat uint8 (n, 16), lengths int32): the minimal big-endian
    two's-complement encoding of each int128, LEFT-aligned in the
    matrix (the padded-bytes form kernels/hashing expects).  Minimal =
    java.math.BigInteger.toByteArray: strip leading 0x00 while the next
    byte has its high bit clear, leading 0xFF while it is set; at least
    one byte always remains."""
    hi = np.asarray(hi, dtype=np.int64)
    lo = np.asarray(lo, dtype=np.int64)
    n = hi.shape[0]
    # big-endian 16-byte matrix
    be = np.zeros((n, 16), dtype=np.uint8)
    for i in range(8):
        be[:, 7 - i] = ((hi >> (8 * i)) & 0xFF).astype(np.uint8)
        be[:, 15 - i] = ((lo >> (8 * i)) & 0xFF).astype(np.uint8)
    sign_byte = np.where(hi < 0, 0xFF, 0x00).astype(np.uint8)
    # count redundant leading bytes: byte == sign filler AND the next
    # byte's high bit matches the sign
    redundant = np.zeros(n, dtype=np.int64)
    still = np.ones(n, dtype=bool)
    for j in range(15):  # at most 15 strippable; last byte always kept
        hi_bit_next = (be[:, j + 1] & 0x80) != 0
        strip = still & (be[:, j] == sign_byte) & \
            (hi_bit_next == (sign_byte == 0xFF))
        redundant += strip
        still = strip
    lengths = (16 - redundant).astype(np.int32)
    # left-align: shift each row's payload to column 0
    idx = redundant[:, None] + np.arange(16)[None, :]
    take = np.clip(idx, 0, 15)
    mat = np.take_along_axis(be, take, axis=1)
    in_range = np.arange(16)[None, :] < lengths[:, None]
    mat = np.where(in_range, mat, np.uint8(0))
    return mat, lengths


def spark_decimal128_hash(hi, lo, seeds=None, seed: int = 42):
    """Spark-compatible murmur3 hash of wide-decimal unscaled int128s
    (numpy host utility; wide decimals are host-resident).  Bit-equal
    to Spark's Murmur3Hash over BigInteger.toByteArray bytes — the limb
    analog of _hash_fixed_column's hash_long for p <= 18."""
    from blaze_tpu.kernels.hashing import murmur3_hash_bytes
    mat, lengths = minimal_be_bytes(hi, lo)
    if seeds is None:
        seeds = np.full(mat.shape[0], seed, dtype=np.uint32)
    return murmur3_hash_bytes(mat, lengths, seeds, np)

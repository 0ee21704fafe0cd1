"""Aggregate functions over segmented (sort-based) group layouts.

Parity: the reference's Agg trait — partial_update / partial_merge /
final_merge over columnar accumulators (ref: datafusion-ext-plans/src/agg/
agg.rs:41,55,63,71; acc.rs:39 AccColumn; impls sum.rs, avg.rs, count.rs,
maxmin.rs:316, first.rs:346, first_ignores_null.rs, collect.rs:749,
bloom_filter.rs:312).

TPU-first redesign: the reference updates accumulators through a hash map of
group slots; here groups arrive as SORTED SEGMENTS (device lexsort + boundary
cumsum, SURVEY.md §7 hard-part 3), so every accumulator update is one fused
segmented reduction on device.  An agg's accumulator state is a tuple of
fixed-width device arrays indexed by dense group id ("AccTable, columnar not
row-based" — same layout philosophy as acc.rs, but jnp arrays).  Collect and
bloom keep host accumulators (variable width), mirroring the reference's
boxed AccColumn for dynamic types.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu.batch import ColumnBatch
from blaze_tpu.exprs import PhysicalExpr
from blaze_tpu.kernels import sort as K
from blaze_tpu.xputil import xp_of
from blaze_tpu.schema import (BOOL, BINARY, DataType, Field, FLOAT64, INT64,
                              Schema, TypeId)

# Arrays on device per group slot; host accs are lists of python objects.
AccArrays = Tuple


class AggFunction:
    """One aggregate function instance bound to its input expressions."""

    name = "agg"
    # the arguments are partial accumulators (PARTIAL_MERGE / FINAL): set
    # by AggExec, which knows the mode; a sum's or an average's types
    # depend on it
    merging = False

    def __init__(self, children: Sequence[PhysicalExpr]):
        self.children = list(children)
        self.input_type: Optional[DataType] = None

    @property
    def decimal_input(self) -> Optional[DataType]:
        """The first argument's type where it is a decimal (bound)."""
        t = self.input_type
        return t if t is not None and t.id == TypeId.DECIMAL else None

    def bind(self, input_schema: Schema) -> None:
        """Resolve input type once (AggExec calls this at plan time)."""
        if self.children:
            self.input_type = self.children[0].data_type(input_schema)

    # -- schema -------------------------------------------------------------
    def acc_fields(self, input_schema: Schema) -> List[Field]:
        """Accumulator columns as materialized in partial batches."""
        raise NotImplementedError

    def output_type(self, input_schema: Schema) -> DataType:
        raise NotImplementedError

    # -- device phases ------------------------------------------------------
    def partial_update(self, args: List[Tuple[jax.Array, jax.Array]],
                       gids: jax.Array, num_segments: int) -> AccArrays:
        """Raw inputs (sorted by group) -> per-group accumulator arrays.
        `args[i]` = (data, validity) gathered through the sort permutation."""
        raise NotImplementedError

    def partial_merge(self, accs: List[Tuple[jax.Array, jax.Array]],
                      gids: jax.Array, num_segments: int) -> AccArrays:
        """Partial accumulator columns (sorted by group) -> combined accs."""
        raise NotImplementedError

    def final_eval(self, accs: List[Tuple[jax.Array, jax.Array]]
                   ) -> Tuple[jax.Array, jax.Array]:
        """Combined accumulator columns -> (data, validity) result column."""
        raise NotImplementedError

    @property
    def is_host(self) -> bool:
        return False


_MAX_DECIMAL_PRECISION = 38


def _bounded_decimal(precision: int, scale: int) -> DataType:
    """Spark's DecimalType.bounded."""
    return DataType(TypeId.DECIMAL, min(precision, _MAX_DECIMAL_PRECISION),
                    min(scale, _MAX_DECIMAL_PRECISION))


def _out_num_type(dt: DataType) -> DataType:
    """Spark sum result types over RAW input: int sums stay int64, floats
    f64, sum(decimal(p,s)) is decimal(p+10, s)."""
    if dt.id == TypeId.DECIMAL:
        return _bounded_decimal(dt.precision + 10, dt.scale)
    if dt.id in (TypeId.FLOAT32, TypeId.FLOAT64):
        return FLOAT64
    return INT64


def decimal_sum_guard(data, valid) -> None:
    """A sum of unscaled decimals over int64 lanes is exact only while no
    group can pass 64 bits.  The sum of the magnitudes bounds every
    group's sum, so where that stays under 2^62 nothing wraps.  Past it
    this engine has no wider accumulator outside the stage loop: it
    refuses, it never wraps."""
    xp = xp_of(data)
    live = xp.where(valid, data, xp.zeros_like(data))
    mass = float(xp.sum(xp.abs(live.astype(xp.float64))))
    if mass >= float(1 << 62):
        from blaze_tpu.bridge import xla_stats
        xla_stats.note_decimal(overflow_groups=1)
        raise ArithmeticError(
            "a decimal sum may pass 64 bits in this batch (sum of "
            f"magnitudes {mass:.3e}); the eager aggregation has no wider "
            "accumulator and will not wrap")


class SumAgg(AggFunction):
    """`merging`: the argument is a partial sum (PARTIAL_MERGE / FINAL),
    whose type is the result's already: Spark's sum(decimal(p,s)) is
    decimal(p+10, s) in every mode, never widened twice."""

    name = "sum"

    def _sum_type(self, s):
        t = self.children[0].data_type(s)
        if self.merging and t.id == TypeId.DECIMAL:
            return t
        return _out_num_type(t)

    def acc_fields(self, s):
        return [Field("sum", self._sum_type(s))]

    def output_type(self, s):
        return self._sum_type(s)

    def partial_update(self, args, gids, n):
        data, valid = args[0]
        acc_dt = jnp.float64 if jnp.issubdtype(data.dtype, jnp.floating) else jnp.int64
        if self.decimal_input is not None:
            decimal_sum_guard(data, valid)
        s = K.segment_sum(data.astype(acc_dt), gids, n, valid)
        has = K.segment_count(valid, gids, n) > 0
        return ((s, has),)

    def partial_merge(self, accs, gids, n):
        data, valid = accs[0]
        if self.decimal_input is not None:
            decimal_sum_guard(data, valid)
        s = K.segment_sum(data, gids, n, valid)
        has = K.segment_count(valid, gids, n) > 0
        return ((s, has),)

    def final_eval(self, accs):
        return accs[0]


class CountAgg(AggFunction):
    """count(expr) / count(*) when children empty (never-null output)."""

    name = "count"

    def acc_fields(self, s):
        return [Field("count", INT64, nullable=False)]

    def output_type(self, s):
        return INT64

    def partial_update(self, args, gids, n):
        xp = xp_of(gids)
        if self.children:
            _, valid = args[0]
            c = K.segment_count(valid, gids, n)
        else:
            ones = xp.ones(gids.shape[0], dtype=bool)
            c = K.segment_count(ones, gids, n)
        return ((c, xp.ones(n, dtype=bool)),)

    def partial_merge(self, accs, gids, n):
        data, valid = accs[0]
        c = K.segment_sum(data, gids, n, valid)
        return ((c, xp_of(c).ones(c.shape[0], dtype=bool)),)

    def final_eval(self, accs):
        data, _ = accs[0]
        return data, xp_of(data).ones(data.shape[0], dtype=bool)


class AvgAgg(AggFunction):
    """`merging`: the arguments are the partial (sum, count).  Spark's
    avg(decimal(p,s)) sums in decimal(p+10, s), counts in int64 and
    gives decimal(p+4, s+4) of its INPUT, in every mode."""

    name = "avg"

    def _sum_type(self, s):
        t = self.children[0].data_type(s)
        if t.id == TypeId.DECIMAL:
            return t if self.merging else _out_num_type(t)
        if t.id in (TypeId.FLOAT32, TypeId.FLOAT64):
            return FLOAT64
        return INT64  # Spark avg(int) sums as long

    def acc_fields(self, s):
        return [Field("sum", self._sum_type(s)),
                Field("count", INT64, nullable=False)]

    def output_type(self, s):
        t = self._sum_type(s)
        if t.id == TypeId.DECIMAL:
            # of the input decimal(p,s), whose sum is decimal(p+10, s)
            return _bounded_decimal(t.precision - 10 + 4, t.scale + 4)
        return FLOAT64

    def partial_update(self, args, gids, n):
        data, valid = args[0]
        if jnp.issubdtype(data.dtype, jnp.floating):
            s = K.segment_sum(data.astype(jnp.float64), gids, n, valid)
        else:  # int and decimal-unscaled sums stay exact in int64
            if self.decimal_input is not None:
                decimal_sum_guard(data, valid)
            s = K.segment_sum(data.astype(jnp.int64), gids, n, valid)
        c = K.segment_count(valid, gids, n)
        return ((s, c > 0), (c, xp_of(c).ones(n, dtype=bool)))

    def partial_merge(self, accs, gids, n):
        (s_d, s_v), (c_d, c_v) = accs
        if self.decimal_input is not None:
            decimal_sum_guard(s_d, s_v)
        s = K.segment_sum(s_d, gids, n, s_v)
        c = K.segment_sum(c_d, gids, n, c_v)
        return ((s, c > 0), (c, xp_of(c).ones(c.shape[0], dtype=bool)))

    def final_eval(self, accs):
        (s_d, _), (c_d, _) = accs
        xp = xp_of(s_d, c_d)
        valid = c_d > 0
        denom = xp.where(valid, c_d, 1)
        return s_d / denom.astype(xp.float64), valid

    def final_eval_decimal(self, sums: np.ndarray, counts: np.ndarray,
                           out: DataType) -> pa.Array:
        """sum * 10^4 / count rounded HALF_UP, as decimal(p+4, s+4): on
        the host, over the unscaled int64 sums as they were read back.
        The quotient is taken first and the remainder scaled, so
        10^4 * sum is never formed; a row whose average passes 64 bits is
        recomputed in Python integers, and one past its type's bound is
        NULL (non-ANSI)."""
        from blaze_tpu.batch import decimal_from_limbs
        valid = counts > 0
        den = np.where(valid, counts, 1).astype(np.int64)
        with np.errstate(over="ignore"):
            mag = np.abs(sums)  # INT64_MIN stays negative: flagged below
            q0, r0 = np.divmod(mag, den)
            frac = (2 * 10_000 * r0 + den) // (2 * den)
            wide = (mag < 0) | (q0 > ((1 << 63) - 1 - frac) // 10_000)
            lo = np.where(sums < 0, -(q0 * 10_000 + frac),
                          q0 * 10_000 + frac)
        hi = lo >> 63
        bound = 10 ** out.precision
        if out.precision <= 18:
            valid = valid & ~wide & (np.abs(lo) < bound)
        elif wide.any():
            for i in np.flatnonzero(wide & valid):
                total, n = int(sums[i]), int(counts[i])
                q, r = divmod(abs(total) * 10_000, n)
                q += 2 * r >= n
                v = -q if total < 0 else q
                if abs(v) >= bound:
                    valid[i] = False
                else:
                    lo[i] = (v & ((1 << 64) - 1)) - ((v >> 63 & 1) << 64)
                    hi[i] = v >> 64
        lost = int((counts > 0).sum() - valid.sum())
        if lost or wide.any():
            from blaze_tpu.bridge import xla_stats
            xla_stats.note_decimal(overflow_groups=max(lost,
                                                       int(wide.sum())))
        return decimal_from_limbs(lo, hi, valid, out.to_arrow())


class MinMaxAgg(AggFunction):
    def __init__(self, children, minimum: bool):
        super().__init__(children)
        self.minimum = minimum
        self.name = "min" if minimum else "max"

    def acc_fields(self, s):
        return [Field(self.name, self.children[0].data_type(s))]

    def output_type(self, s):
        return self.children[0].data_type(s)

    @property
    def is_host(self) -> bool:
        # min/max over utf8/binary accumulates host-side — there is no
        # device dtype for var-width values (Spark Min/Max on strings)
        return (self.input_type is not None
                and not self.input_type.is_fixed_width)

    def host_update(self, args: List[pa.Array], gids: np.ndarray,
                    num_segments: int) -> List[pa.Array]:
        vals = args[0]
        out: List = [None] * num_segments
        for v, g in zip(vals, gids):
            if g < num_segments and v.is_valid:
                pv = v.as_py()
                cur = out[g]
                if cur is None or (pv < cur if self.minimum
                                   else pv > cur):
                    out[g] = pv
        return [pa.array(out, type=vals.type)]

    def host_merge(self, accs: List[pa.Array], gids: np.ndarray,
                   num_segments: int) -> List[pa.Array]:
        # min of mins / max of maxes: identical fold over the acc column
        return self.host_update(accs, gids, num_segments)

    def host_eval(self, accs: List[pa.Array]) -> pa.Array:
        return accs[0]

    def _reduce(self, data, valid, gids, n):
        xp = xp_of(data, valid)
        vals, nan_mask = data, None
        if self.minimum and xp.issubdtype(
                xp.asarray(data).dtype, xp.floating):
            # Spark total order puts NaN LARGEST: min skips NaN (the
            # NaN-propagating segment_min would return NaN for any
            # group containing one) — unless the group is all-NaN
            nan_mask = xp.isnan(data)
            vals = xp.where(nan_mask, xp.inf, data)
        fn = K.segment_min if self.minimum else K.segment_max
        out = fn(vals, gids, n, valid)
        has = K.segment_count(valid, gids, n) > 0
        if nan_mask is not None:
            has_real = K.segment_count(valid & ~nan_mask, gids, n) > 0
            out = xp.where(has & ~has_real, xp.nan, out)
        xp = xp_of(out, has)
        out = xp.where(has, out, xp.zeros_like(out))
        return ((out, has),)

    def partial_update(self, args, gids, n):
        return self._reduce(args[0][0], args[0][1], gids, n)

    def partial_merge(self, accs, gids, n):
        return self._reduce(accs[0][0], accs[0][1], gids, n)

    def final_eval(self, accs):
        return accs[0]


class FirstAgg(AggFunction):
    def __init__(self, children, ignores_null: bool = False):
        super().__init__(children)
        self.ignores_null = ignores_null
        self.name = "first_ignores_null" if ignores_null else "first"

    def acc_fields(self, s):
        t = self.children[0].data_type(s)
        fields = [Field("first", t)]
        if not self.ignores_null:
            # "value is null" vs "no value yet" need separate tracking
            fields.append(Field("has", BOOL, nullable=False))
        return fields

    def output_type(self, s):
        return self.children[0].data_type(s)

    def partial_update(self, args, gids, n):
        data, valid = args[0]
        if self.ignores_null:
            v, has = K.segment_first_ignores_null(data, valid, gids, n)
            return ((v, has),)
        xp = xp_of(data, valid)
        v, vvalid = K.segment_first(data, valid, gids, n)
        has_rows = K.segment_count(xp.ones_like(valid), gids, n) > 0
        return ((v, vvalid), (has_rows, xp.ones(n, dtype=bool)))

    def partial_merge(self, accs, gids, n):
        if self.ignores_null:
            data, valid = accs[0]
            v, has = K.segment_first_ignores_null(data, valid, gids, n)
            return ((v, has),)
        (data, valid), (has, _) = accs
        # first among partials that HAVE a value (has flag), not non-null
        v, _ = K.segment_first_ignores_null(
            data, has.astype(bool), gids, n)
        vv, _ = K.segment_first_ignores_null(
            valid, has.astype(bool), gids, n)
        any_has = K.segment_count(has.astype(bool), gids, n) > 0
        return ((v, vv.astype(bool) & any_has),
                (any_has, xp_of(any_has).ones(n, dtype=bool)))

    def final_eval(self, accs):
        return accs[0]


class CollectAgg(AggFunction):
    """collect_list / collect_set — host accumulators (variable width),
    ref collect.rs:749."""

    def __init__(self, children, distinct: bool):
        super().__init__(children)
        self.distinct = distinct
        self.name = "collect_set" if distinct else "collect_list"

    @property
    def is_host(self) -> bool:
        return True

    def acc_fields(self, s):
        item = self.children[0].data_type(s)
        return [Field("items", DataType(TypeId.LIST,
                                        children=(Field("item", item),)))]

    def output_type(self, s):
        item = self.children[0].data_type(s)
        return DataType(TypeId.LIST, children=(Field("item", item),))

    # host phases operate on pa arrays + numpy gids
    def host_update(self, args: List[pa.Array], gids: np.ndarray,
                    num_segments: int) -> List[pa.Array]:
        vals = args[0]
        out: List[List] = [[] for _ in range(num_segments)]
        for v, g in zip(vals, gids):
            if g < num_segments and v.is_valid:
                out[g].append(v.as_py())
        if self.distinct:
            out = [list(dict.fromkeys(x)) for x in out]
        item_t = vals.type
        return [pa.array(out, type=pa.list_(item_t))]

    def host_merge(self, accs: List[pa.Array], gids: np.ndarray,
                   num_segments: int) -> List[pa.Array]:
        lists = accs[0]
        out: List[List] = [[] for _ in range(num_segments)]
        for v, g in zip(lists, gids):
            if g < num_segments and v.is_valid:
                out[g].extend(v.as_py())
        if self.distinct:
            out = [list(dict.fromkeys(x)) for x in out]
        return [pa.array(out, type=lists.type)]

    def host_eval(self, accs: List[pa.Array]) -> pa.Array:
        return accs[0]


class CombineUniqueAgg(CollectAgg):
    """brickhouse.combine_unique (ref agg/brickhouse/combine_unique.rs):
    collect_set over the FLATTENED elements of a list-typed input —
    merges arrays across rows into one deduplicated array."""

    def __init__(self, children):
        super().__init__(children, distinct=True)
        self.name = "combine_unique"

    def acc_fields(self, s):
        return [Field("items", self.output_type(s))]

    def output_type(self, s):
        # validated here (not acc_fields) so COMPLETE/FINAL planning,
        # which only consults output_type, rejects non-array input at
        # plan time instead of crashing mid-update
        t = self.children[0].data_type(s)
        if t.id != TypeId.LIST:
            raise TypeError("combine_unique expects an array input")
        return t

    def host_update(self, args, gids, num_segments):
        lists = args[0]
        out = [[] for _ in range(num_segments)]
        for v, g in zip(lists, gids):
            if g < num_segments and v.is_valid:
                out[g].extend(e for e in v.as_py() if e is not None)
        out = [list(dict.fromkeys(x)) for x in out]
        return [pa.array(out, type=lists.type)]


class BloomFilterAgg(AggFunction):
    """bloom_filter_agg for runtime-filter joins (ref agg/bloom_filter.rs:312):
    global (ungrouped) Spark-compatible bloom built from int64 hashes."""

    name = "bloom_filter"

    def __init__(self, children, expected_items: int = 1_000_000,
                 num_bits: Optional[int] = None):
        super().__init__(children)
        from blaze_tpu.kernels import bloom
        self.num_bits = num_bits or bloom.optimal_num_bits(expected_items, 0.03)
        self.num_hashes = bloom.optimal_num_hashes(expected_items, self.num_bits)

    @property
    def is_host(self) -> bool:
        return True

    def acc_fields(self, s):
        return [Field("bloom", BINARY)]

    def output_type(self, s):
        return BINARY

    def host_update(self, args, gids, num_segments):
        from blaze_tpu.kernels.bloom import SparkBloomFilter
        vals = args[0].cast(pa.int64())
        out = []
        npg = np.asarray(gids)
        npv = np.asarray(vals.fill_null(0), dtype=np.int64)
        valid = np.asarray(vals.is_valid())
        for g in range(num_segments):
            f = SparkBloomFilter(self.num_bits, self.num_hashes)
            f.put_longs(npv[(npg == g) & valid])
            out.append(f.to_bytes())
        return [pa.array(out, type=pa.binary())]

    def host_merge(self, accs, gids, num_segments):
        from blaze_tpu.kernels.bloom import SparkBloomFilter
        out = []
        npg = np.asarray(gids)
        for g in range(num_segments):
            f: Optional[SparkBloomFilter] = None
            for i in np.nonzero(npg == g)[0]:
                v = accs[0][int(i)]
                if not v.is_valid:
                    continue
                other = SparkBloomFilter.from_bytes(v.as_py())
                if f is None:
                    f = other
                else:
                    f.merge(other)
            out.append(f.to_bytes() if f is not None else None)
        return [pa.array(out, type=pa.binary())]

    def host_eval(self, accs):
        return accs[0]


class HostUDAF(AggFunction):
    """Engine-side UDAF fallback (ref agg/spark_udaf_wrapper.rs:451 — the
    JVM round-trip with SparkUDAFMemTracker).  The host registers four
    callables; accumulator state serializes as binary per group so partial
    batches spill/shuffle like any other column."""

    def __init__(self, name: str, children,
                 init_fn, update_fn, merge_fn, eval_fn,
                 out_type: DataType = FLOAT64):
        super().__init__(children)
        self.name = name
        self._init = init_fn      # () -> state
        self._update = update_fn  # (state, *values) -> state
        self._merge = merge_fn    # (state, state) -> state
        self._eval = eval_fn      # (state) -> python value
        self._out = out_type

    @property
    def is_host(self) -> bool:
        return True

    def acc_fields(self, s):
        return [Field("state", BINARY)]

    def output_type(self, s):
        return self._out

    def _serialize(self, state) -> bytes:
        import pickle
        return pickle.dumps(state)

    def _deserialize(self, b: bytes):
        import pickle
        return pickle.loads(b)

    def host_update(self, args: List[pa.Array], gids: np.ndarray,
                    num_segments: int) -> List[pa.Array]:
        states = [self._init() for _ in range(num_segments)]
        n = len(gids)
        pyargs = [a.to_pylist() for a in args]
        for i in range(n):
            g = int(gids[i])
            if g < num_segments:
                states[g] = self._update(states[g],
                                         *(col[i] for col in pyargs))
        return [pa.array([self._serialize(s) for s in states],
                         type=pa.binary())]

    def host_merge(self, accs: List[pa.Array], gids: np.ndarray,
                   num_segments: int) -> List[pa.Array]:
        states = [None] * num_segments
        for i, g in enumerate(gids):
            g = int(g)
            if g >= num_segments:
                continue
            v = accs[0][i]
            if not v.is_valid:
                continue
            s = self._deserialize(v.as_py())
            states[g] = s if states[g] is None else self._merge(states[g], s)
        return [pa.array([self._serialize(s if s is not None
                                          else self._init())
                          for s in states], type=pa.binary())]

    def host_eval(self, accs: List[pa.Array]) -> pa.Array:
        py = []
        for v in accs[0]:
            if not v.is_valid:
                py.append(None)
            else:
                py.append(self._eval(self._deserialize(v.as_py())))
        return pa.array(py, type=self._out.to_arrow())


# -- registry (proto AggFunction enum, auron.proto:143) ----------------------

def make_agg(name: str, children: Sequence[PhysicalExpr], **kw) -> AggFunction:
    name = name.lower()
    if name == "sum":
        return SumAgg(children)
    if name == "count":
        return CountAgg(children)
    if name == "avg":
        return AvgAgg(children)
    if name == "min":
        return MinMaxAgg(children, minimum=True)
    if name == "max":
        return MinMaxAgg(children, minimum=False)
    if name == "first":
        return FirstAgg(children, ignores_null=False)
    if name == "first_ignores_null":
        return FirstAgg(children, ignores_null=True)
    if name == "collect_list":
        return CollectAgg(children, distinct=False)
    if name == "collect_set":
        return CollectAgg(children, distinct=True)
    if name == "brickhouse.collect":
        # ref agg/brickhouse/collect.rs: delegates to AggCollectSet —
        # the Hive brickhouse collect UDAF materialized as a set
        return CollectAgg(children, distinct=True)
    if name in ("combine_unique", "brickhouse.combine_unique"):
        return CombineUniqueAgg(children)
    if name == "bloom_filter":
        return BloomFilterAgg(children, **kw)
    if name == "udaf":
        from blaze_tpu import config
        from blaze_tpu.bridge.resource import get_resource
        if not config.UDAF_FALLBACK_ENABLE.get():
            raise ValueError("UDAF host fallback disabled "
                             "(auron.udafFallback.enable=false)")
        impl = get_resource(f"udaf://{kw['udaf_name']}")
        if impl is None:
            raise KeyError(f"UDAF {kw['udaf_name']!r} not registered "
                           f"(udaf://{kw['udaf_name']})")
        return HostUDAF(kw["udaf_name"], children, *impl,
                        out_type=kw.get("out_type", FLOAT64))
    raise KeyError(f"unknown aggregate function {name}")

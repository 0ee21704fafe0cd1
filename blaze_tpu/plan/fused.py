"""Fused-stage compiler: planner trees -> single-XLA-program aggregation.

The eager AggExec (ops/agg/exec.py) materializes an Arrow partial batch per
input batch, with a host sync for the group count — general, but it leaves
the device idle between batches.  This pass rewrites eligible
scan→filter→project→partial-agg subtrees so the aggregation loop body is
ONE jit'd XLA program per batch with a persistent on-device group table and
no host syncs (the rt.rs:156 whole-chain-in-one-task analog; SURVEY §7
step 5).

Two fused strategies, chosen at plan time:

  * DENSE (pack_dense_keys + in-place scatter carry): every grouping key
    is an integer column whose global [min, max] bounds are known — from
    parquet row-group statistics or an in-memory table scan.  Group ids
    are pure arithmetic; the loop body scatter-accumulates into a donated
    carry (O(batch) per step).  Zero host syncs until the final decode.
  * HASH (hash_agg_step, parallel/stage.py): fixed-width keys without
    usable bounds.  A device open-addressing table (linear-probe rounds
    of scatter/gather — no lax.sort, which takes minutes to compile on
    TPU) carries across batches; one scalar overflow check per batch.
    On overflow exact modes grow+rehash; PARTIAL degrades to batch-local
    dedup pass-through (the AGG_TRIGGER_PARTIAL_SKIPPING analog,
    ref agg_table.rs:108-122) because the final stage re-merges.

A utf8 group key is an int32 code lane of the HASH table where it arrives
as a `DictColumn` (the stage loop, runtime/loop.py); an `ExpandExec` under
a partial aggregation is absorbed into the chain where the stage loop runs
the stage, which folds each batch once a projection list.  Anything else
(host aggs, avg/collect, merge modes) stays on the eager path.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu import config
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.bridge.context import current_task
from blaze_tpu.bridge.xla_stats import meter_jit
from blaze_tpu.exprs import BoundReference, PhysicalExpr
from blaze_tpu.ops.agg.exec import AggExec, AggMode
from blaze_tpu.ops.agg.functions import (AvgAgg, CountAgg, MinMaxAgg,
                                          SumAgg)
from blaze_tpu.ops.base import BatchIterator, ExecutionPlan
from blaze_tpu.ops.basic import (DebugExec, ExpandExec, FilterExec,
                                 FilterProjectExec, ProjectExec)
from blaze_tpu.ops.scan import MemoryScanExec, ParquetScanExec
from blaze_tpu.parallel.stage import (MAX_KEY_COLUMNS, hash_agg_step,
                                      init_accumulators, init_hash_carry,
                                      join_key, key_valid_lanes,
                                      pack_dense_keys,
                                      rehash_carry, rehash_width,
                                      scatter_accumulate, unpack_dense_keys)
from blaze_tpu.schema import Field, Schema, TypeId
from blaze_tpu.xputil import asnp, to_device, to_host


def fuse_plan(plan: ExecutionPlan) -> ExecutionPlan:
    """Rewrite eligible AggExec nodes into FusedPartialAggExec, in place
    for inner nodes (children lists are mutable; schemas are identical by
    construction)."""
    if not config.FUSED_STAGE_ENABLE.get():
        return plan
    replaced = _try_fuse_agg(plan)
    if replaced is not None:
        plan = replaced
    for i, child in enumerate(plan.children):
        plan.children[i] = fuse_plan(child)
    return plan


# ---------------------------------------------------------------------------
# eligibility + bounds discovery
# ---------------------------------------------------------------------------

_FUSABLE_CHAIN = (FilterExec, ProjectExec, FilterProjectExec, DebugExec)


def _try_fuse_agg(node: ExecutionPlan) -> Optional["FusedPartialAggExec"]:
    if not isinstance(node, AggExec) or isinstance(node,
                                                   FusedPartialAggExec):
        return None
    groups = node._group_exprs
    aggs = node._aggs
    if not groups or not aggs:
        return None
    if len(groups) > MAX_KEY_COLUMNS:
        return None  # the table keeps one null bit a key in an int32 lane
    child = node.children[0]
    in_schema = child.schema

    modes = {m for _, m, _ in aggs}
    if len(modes) != 1:
        return None
    mode = next(iter(modes))
    complete = mode in (AggMode.COMPLETE, AggMode.FINAL)
    merging = mode in (AggMode.PARTIAL_MERGE, AggMode.FINAL)

    specs: List[Tuple[str, str, Optional[PhysicalExpr]]] = []
    for fn, _m, _name in aggs:
        if isinstance(fn, AvgAgg):
            # a PARTIAL average is its (sum, count) accumulators, in that
            # order (AvgAgg.acc_fields): two lanes of the same argument.
            # Only over a decimal: the float fold sums in another order
            # than AggExec's segments, which an average shows
            arg = fn.children[0]
            if mode != AggMode.PARTIAL or not _decimal_lane(
                    arg.data_type(in_schema)):
                return None
            specs.append(("sum", "sum", arg))
            specs.append(("count", "count", arg))
            continue
        if isinstance(fn, SumAgg):
            out_kind = "sum"
        elif isinstance(fn, CountAgg):
            out_kind = "count"
        elif isinstance(fn, MinMaxAgg):
            out_kind = fn.name  # "min" | "max"
        else:
            return None
        arg = fn.children[0] if fn.children else None
        if merging and arg is None:
            return None  # merge modes must reference their acc column
        if arg is not None and not arg.data_type(in_schema).is_fixed_width:
            return None
        if out_kind in ("sum", "min", "max"):
            t = arg.data_type(in_schema) if arg is not None else None
            if t is None or not (t.is_integer or t.is_floating
                                 or _decimal_lane(t)):
                return None
        # merging counts SUMS the partial counts
        reduce_kind = "sum" if (merging and out_kind == "count") \
            else out_kind
        specs.append((reduce_kind, out_kind, arg))

    key_types = [e.data_type(in_schema) for e, _ in groups]
    fixed_keys = all(t.is_fixed_width for t in key_types)
    if not fixed_keys:
        # utf8 group keys can't reach the device strategies, but Arrow's
        # hash aggregation handles them natively — admit them when the
        # host-vectorized path will actually run (placement is decided
        # before plans build, so this is stable for the task).  The
        # eager fallback re-lexsorts buffered partials per combine,
        # which dominated string-keyed queries (q79 at SF1: 10.5s -> the
        # acero path).
        from blaze_tpu.bridge.placement import host_resident
        if not all(t.is_fixed_width or t.id == TypeId.UTF8
                   for t in key_types):
            return None
        host_ok = (host_resident()
                   and config.FUSED_HOST_VECTORIZED_ENABLE.get()
                   and _host_vectorized_eligible(groups, specs, in_schema))
        # device placement: utf8 keys ride the dict-code strategy —
        # dictionary-encode to dense i32 codes, group on device
        # (_execute_dict_device), decode at emit.  min/max over float
        # args are excluded: the step's jnp.minimum folding propagates
        # NaN where Spark's total order skips it (AggExec handles that;
        # see MinMaxAgg._reduce)
        dict_ok = (config.FUSED_DICT_DEVICE_ENABLE.get() and
                   not any(rk in ("min", "max") and arg is not None
                           and arg.data_type(in_schema).is_floating
                           for rk, _ok, arg in specs))
        if not host_ok and not dict_ok:
            return None

    # dense needs integer keys with discoverable bounds
    ranges = None
    decimal_args = any(a is not None and _decimal_lane(a.data_type(in_schema))
                       for _rk, _ok, a in specs)
    # (a decimal value lane folds in the stage loop, whose table is the
    # hash lane's: its guard against 64-bit wrap lives there alone)
    if fixed_keys and all(t.is_integer for t in key_types) \
            and not decimal_args:
        ranges = _discover_ranges(child, groups)
        if ranges is not None:
            total = 1
            for lo, hi in ranges:
                total *= (hi - lo + 2)
            if total > config.FUSED_STAGE_CAPACITY.get():
                ranges = None
            elif total > (1 << 20):
                # sparsity heuristic: a table much larger than the input
                # can't be dense — the O(slots) carry traffic loses to
                # the hash table (distinct groups <= rows by definition)
                rows = _source_row_count(child)
                if rows is not None and total > 4 * rows:
                    ranges = None
    # the sorted path handles overflow two ways: PARTIAL degrades to
    # pass-through (downstream re-merges); exact modes GROW the table
    grow = complete or merging
    # absorb the filter/project chain between agg and source into the jit
    # step when every expression traces (the CachedExprsEvaluator work
    # moves INSIDE the XLA program: one dispatch per batch, ref rt.rs:156
    # whole-chain-in-one-task)
    # An Expand is absorbed too, but only where the stage loop will run
    # the stage: the loop alone folds a batch once a projection list
    # (placement is decided before plans build, so this is stable for the
    # task); everywhere else the Expand stays the child it was
    from blaze_tpu.plan import stage_compiler
    source, chain = _absorbable_chain(
        child, expand=mode == AggMode.PARTIAL
        and stage_compiler.stage_loop_active())
    node = FusedPartialAggExec(child, groups, aggs, specs, ranges,
                               complete, grow, source=source, chain=chain)
    if ranges is not None:
        node._mxu_meta = _plan_mxu_meta(child, specs, ranges, in_schema)
    return node


def _decimal_lane(t) -> bool:
    """A decimal(p <= 18) rides the aggregation lanes as its unscaled
    integer (int32 on the way in for p <= 9, int64 in the table): sums
    and counts are exact there, and min/max order as the values do."""
    return t.id == TypeId.DECIMAL and t.is_fixed_width


def _host_vectorized_eligible(group_exprs, specs, in_schema) -> bool:
    """Restrict the Arrow group_by path to where its semantics are
    bit-identical to the device kernels: integer-family (or utf8) keys
    (float keys need NaN/-0.0 normalization, decimals the unscaled-int
    representation) and sum/count on non-decimal args; min/max only on
    non-float args (Spark orders NaN largest; Arrow min_max skips
    NaN)."""
    for e, _n in group_exprs:
        t = e.data_type(in_schema)
        if t.is_floating or t.id == TypeId.DECIMAL:
            return False
    for rk, _ok, arg in specs:
        if arg is None:
            continue
        t = arg.data_type(in_schema)
        if t.id == TypeId.DECIMAL:
            return False
        if rk in ("min", "max") and t.is_floating:
            return False
    return True


def _absorbable_chain(child: ExecutionPlan, expand: bool = False):
    """Peel Filter/Project/FilterProject off the agg's child.  Returns
    (source_plan, chain_steps) where chain_steps apply source->agg order;
    (child, []) when nothing absorbs.  With `expand`, ONE ExpandExec is
    peeled as well: the step carries its K projection lists, and the
    program that runs the chain says which of them a call evaluates
    (`prepare`'s third argument), so the expanded rows never exist as
    batches."""
    steps = []
    node = child
    while True:
        if expand and isinstance(node, ExpandExec) \
                and _expand_traceable(node):
            steps.append(("expand", None, node._projections, node.schema))
            expand = False
        elif isinstance(node, FilterExec):
            steps.append(("filter", node._predicates, None, None))
        elif isinstance(node, ProjectExec):
            steps.append(("project", None, node._exprs, node.schema))
        elif isinstance(node, FilterProjectExec):
            # appended top-down; the final reverse() restores filter-then-
            # project execution order
            steps.append(("project", None, node._exprs, node.schema))
            steps.append(("filter", node._predicates, None, None))
        else:
            break
        node = node.children[0]
    steps.reverse()
    return node, steps


def _expand_traceable(node: ExpandExec) -> bool:
    """Every projection list gives the same types a column, and a utf8
    column is a bare reference or a NULL literal in each (a code lane
    under its sibling's dictionary, or no valid bit)."""
    in_schema = node.children[0].schema
    for j, f in enumerate(node.schema):
        for p in node._projections:
            e = p[j]
            if e.data_type(in_schema).id != f.data_type.id \
                    and not _null_literal(e):
                return False
            if f.data_type.id == TypeId.UTF8 and not (
                    isinstance(e, BoundReference) or _null_literal(e)):
                return False
            if not (f.data_type.is_fixed_width
                    or f.data_type.id == TypeId.UTF8):
                return False
    return True


def _null_literal(e) -> bool:
    from blaze_tpu.exprs.base import Literal
    return isinstance(e, Literal) and e.value is None


def chain_expand(chain) -> int:
    """The projection lists of the chain's Expand step, 0 without one."""
    return next((len(exprs) for kind, _p, exprs, _s in chain
                 if kind == "expand"), 0)


def _chain_cache_key(source_schema: Schema, chain, group_exprs, specs):
    chain_k = []
    for kind, preds, exprs, _schema in chain:
        if kind == "filter":
            chain_k.append(("f", tuple(p.cache_key() for p in preds)))
        elif kind == "expand":
            chain_k.append(("x", tuple(tuple(e.cache_key() for e in p)
                                       for p in exprs)))
        else:
            chain_k.append(("p", tuple(e.cache_key() for e in exprs)))
    return (tuple((f.name, f.data_type.id.value)
                  # (a decimal's scale decides what the chain computes)
                  + ((f.data_type.precision, f.data_type.scale)
                     if f.data_type.id == TypeId.DECIMAL else ())
                  for f in source_schema),
            tuple(chain_k),
            tuple(e.cache_key() for e, _ in group_exprs),
            tuple((rk, ok, a.cache_key() if a is not None else None)
                  for rk, ok, a in specs),
            # encoding knobs change what the chain traces (int32 code
            # slots for utf8; limb compares for unequal-scale decimals):
            # key them so toggling never reuses a stale prepare
            bool(config.ENCODING_DICT_ENABLE.get()),
            bool(config.ENCODING_DECIMAL_ENABLE.get()))


def _source_row_count(child: ExecutionPlan):
    """Total input rows from scan metadata (parquet footers / in-memory
    partitions); None when the source is opaque."""
    node = child
    while isinstance(node, _FUSABLE_CHAIN):
        node = node.children[0]
    if isinstance(node, ParquetScanExec):
        from blaze_tpu.ops.scan import parquet_metadata
        total = 0
        for group in node._file_groups:
            for path in group:
                try:
                    total += parquet_metadata(path).num_rows
                except Exception:
                    return None
        return total
    if isinstance(node, MemoryScanExec):
        return sum(cb.num_rows for part in node._partitions
                   for cb in part)
    return None


def _discover_ranges(child: ExecutionPlan,
                     groups) -> Optional[List[Tuple[int, int]]]:
    ranges = []
    for e, _name in groups:
        b = _column_bounds(child, e)
        if b is None:
            return None
        ranges.append(b)
    return ranges


def _column_bounds(node: ExecutionPlan, expr: PhysicalExpr,
                   float_ok: bool = False) -> Optional[Tuple]:
    """Trace a grouping expression down a schema-transparent chain to its
    source scan column and read global [min, max] from parquet row-group
    statistics (the stats the scan's own pruning uses) or an in-memory
    table pass.  `float_ok` additionally admits float statistics (the MXU
    strategy's fixed-point planning needs value bounds, not just keys)."""
    while True:
        if not isinstance(expr, BoundReference):
            return None
        if isinstance(node, (FilterExec, DebugExec)):
            node = node.children[0]
            continue
        if isinstance(node, (ProjectExec, FilterProjectExec)):
            exprs = node._exprs
            if expr.index >= len(exprs):
                return None
            expr = exprs[expr.index]
            node = node.children[0]
            continue
        break
    if isinstance(node, ParquetScanExec):
        return _parquet_bounds(node, expr.index, float_ok)
    if isinstance(node, MemoryScanExec):
        return _memory_bounds(node, expr.index, float_ok)
    return None


def _parquet_bounds(scan: ParquetScanExec, col_index: int,
                    float_ok: bool = False) -> Optional[Tuple]:
    from blaze_tpu.ops.scan import parquet_metadata
    name = scan.schema[col_index].name
    lo = hi = None
    is_float = False
    for group in scan._file_groups:
        for path in group:
            try:
                md = parquet_metadata(path)
            except Exception:
                return None
            fidx = md.schema.names.index(name) \
                if name in md.schema.names else -1
            if fidx < 0:
                return None
            for rg in range(md.num_row_groups):
                st = md.row_group(rg).column(fidx).statistics
                if st is None or not st.has_min_max:
                    return None
                mn, mx = st.min, st.max
                if isinstance(mn, float) and not isinstance(
                        mn, (int, np.integer)):
                    if not float_ok:
                        return None
                    is_float = True
                elif not isinstance(mn, (int, np.integer)):
                    return None
                lo = mn if lo is None else min(lo, mn)
                hi = mx if hi is None else max(hi, mx)
    if lo is None:
        return None
    if is_float:
        return float(lo), float(hi)
    return int(lo), int(hi)


def _memory_bounds(scan: MemoryScanExec, col_index: int,
                   float_ok: bool = False) -> Optional[Tuple]:
    lo = hi = None
    for part in scan._partitions:
        for cb in part:
            col = cb.columns[col_index]
            data = asnp(col.data)[:cb.num_rows]
            valid = asnp(col.validity)[:cb.num_rows]
            if cb.selection is not None:
                valid = valid & asnp(cb.selection)[:cb.num_rows]
            if not valid.any():
                continue
            if np.issubdtype(data.dtype, np.floating) and not float_ok:
                return None
            mn, mx = data[valid].min(), data[valid].max()
            lo = mn if lo is None else min(lo, mn)
            hi = mx if hi is None else max(hi, mx)
    if lo is None:
        return None
    if np.issubdtype(type(lo), np.floating) or isinstance(lo, float):
        return float(lo), float(hi)
    return int(lo), int(hi)


# ---------------------------------------------------------------------------
# MXU strategy planning (kernels/mxu_agg.py): compact dense tables
# aggregate as one-hot matmuls in an exact 8-bit-limb integer tier —
# the TPU fast path (no scatters, no 64-bit emulation in the hot loop)
# ---------------------------------------------------------------------------

from typing import NamedTuple


class _MxuVerifyFailed(Exception):
    """A float sum column failed the fixed-point exactness verify on
    device; the partition re-runs through the scatter strategy."""


class _MxuSpec(NamedTuple):
    kind: str          # count_star | count | sum | min | max
    arr_valid: int     # value-array index of the validity block (-1)
    arr_cents: int     # value-array index of the cents blocks (-1)
    scatter_idx: int   # min/max scatter accumulator index (-1)
    off: int           # integer offset subtracted into the limb domain
    scale: int         # 1 for ints; fixed-point scale for floats
    is_float: bool


class _MxuMeta(NamedTuple):
    layout: tuple      # MxuAggLayout
    specs: Tuple[_MxuSpec, ...]
    arrays: Tuple[Tuple[str, int], ...]   # ("valid"|"cents", spec_index)
    scatter: Tuple[Tuple[bool, int], ...]  # (is_min, spec_index)


def _plan_mxu_meta(child, specs, ranges, in_schema) -> Optional[_MxuMeta]:
    """Static eligibility + layout for the MXU dense strategy.  Every
    aggregated value must map to a non-negative integer domain that
    8-bit limbs cover: ints shift by their stats minimum; floats scale
    to fixed-point cents (verified exactly on device at runtime).  Any
    miss keeps the spec — and therefore the stage — on the scatter
    path."""
    import math

    from blaze_tpu.kernels import mxu_agg

    if not config.AGG_MXU_ENABLE.get():
        return None
    total = 1
    for lo, hi in ranges:
        total *= (hi - lo + 2)
    if total > config.AGG_MXU_MAX_SLOTS.get():
        return None
    scale_conf = config.AGG_MXU_DECIMAL_SCALE.get()
    arrays: List[Tuple[str, int]] = []
    bits: List[int] = []
    mspecs: List[_MxuSpec] = []
    scatter: List[Tuple[bool, int]] = []
    valid_by_arg: Dict = {}  # arg cache_key -> shared validity array idx

    def valid_block(si, arg) -> int:
        """Validity blocks dedup across specs over the same argument
        (sum+count+min over one column is the common rollup shape; each
        block is a full matmul column group, so sharing is real money)."""
        try:
            k = arg.cache_key()
        except Exception:
            k = ("id", id(arg))
        if k in valid_by_arg:
            return valid_by_arg[k]
        arrays.append(("valid", si))
        bits.append(1)
        valid_by_arg[k] = len(arrays) - 1
        return valid_by_arg[k]

    for si, (rk, _ok, arg) in enumerate(specs):
        if rk == "count":
            if arg is None:
                mspecs.append(_MxuSpec("count_star", -1, -1, -1, 0, 1,
                                       False))
            else:
                mspecs.append(_MxuSpec("count", valid_block(si, arg), -1,
                                       -1, 0, 1, False))
            continue
        if rk not in ("sum", "min", "max") or arg is None:
            return None
        t = arg.data_type(in_schema)
        is_float = t.is_floating
        if not (is_float or t.is_integer):
            return None
        if is_float and t.id != TypeId.FLOAT64:
            # float32 carries ~6e-8 relative rounding: the fixed-point
            # verify could never pass and every partition would fold
            # then fall back — strictly worse than going scatter direct
            return None
        b = _column_bounds(child, arg, float_ok=is_float)
        if b is None:
            return None
        lo, hi = b
        if is_float:
            if not (math.isfinite(float(lo)) and math.isfinite(float(hi))):
                return None
            clo = int(math.floor(float(lo) * scale_conf)) - 1
            chi = int(math.ceil(float(hi) * scale_conf)) + 1
            scale = scale_conf
        else:
            clo, chi, scale = int(lo), int(hi), 1
        span_bits = mxu_agg.limb_bits_for(clo, chi)
        if span_bits > 31:
            return None
        vi = valid_block(si, arg)
        if rk == "sum":
            arrays.append(("cents", si))
            bits.append(span_bits)
            mspecs.append(_MxuSpec("sum", vi, len(arrays) - 1, -1, clo,
                                   scale, is_float))
        else:
            scatter.append((rk == "min", si))
            mspecs.append(_MxuSpec(rk, vi, -1, len(scatter) - 1, clo,
                                   scale, is_float))
    layout = mxu_agg.plan_layout(total, bits)
    if layout is None:
        return None
    return _MxuMeta(layout, tuple(mspecs), tuple(arrays), tuple(scatter))


# ---------------------------------------------------------------------------
# the fused operator
# ---------------------------------------------------------------------------

class FusedPartialAggExec(ExecutionPlan):
    """Drop-in replacement for a partial/complete AggExec over fixed-width
    keys: same output schema, single-XLA-program loop body."""

    def __init__(self, child: ExecutionPlan, group_exprs, aggs,
                 specs: Sequence[Tuple[str, str, Optional[PhysicalExpr]]],
                 ranges: Optional[List[Tuple[int, int]]],
                 complete: bool, grow: bool = False,
                 source: Optional[ExecutionPlan] = None, chain=None):
        super().__init__([child])
        self._group_exprs = list(group_exprs)
        self._aggs = list(aggs)
        self._specs = list(specs)  # (reduce_kind, out_kind, arg)
        self._ranges = ranges
        self._complete = complete
        self._grow = grow  # exact modes grow the table instead of skipping
        self._in_schema = child.schema
        self._out_schema = self._build_schema()
        # chain absorption: iterate the SOURCE and run filter/project
        # inside the jit step.  Falls back to the eager child when the
        # chain doesn't trace (strings, host-only exprs).
        self._source = source if source is not None else child
        self._chain = list(chain or [])
        # projection lists of an absorbed Expand (0: none): the stage loop
        # alone runs such a chain, every other lane takes the child as it
        # was planned, ExpandExec and all
        self._expand = chain_expand(self._chain)
        self._prepare = None
        self._prepare_key = None
        self._mxu_meta = None  # set by _try_fuse_agg when stats qualify
        # per spec: the value lane is a decimal's unscaled integer
        self._decimal_specs = tuple(
            i for i, (_rk, _ok, arg) in enumerate(self._specs)
            if arg is not None
            and arg.data_type(self._in_schema).id == TypeId.DECIMAL)
        if self._chain or source is not None:
            self._prepare_key = _chain_cache_key(
                self._source.schema, self._chain, self._group_exprs,
                self._specs)
            self._prepare = _prepare_factory(
                self._prepare_key, self._source.schema, self._chain,
                self._group_exprs, self._specs)

    def _build_schema(self) -> Schema:
        fields: List[Field] = []
        for e, name in self._group_exprs:
            fields.append(Field(name, e.data_type(self._in_schema)))
        for fn, mode, name in self._aggs:
            if mode in (AggMode.FINAL, AggMode.COMPLETE):
                fields.append(Field(name, fn.output_type(self._in_schema)))
            else:
                for f in fn.acc_fields(self._in_schema):
                    fields.append(Field(f"{name}.{f.name}", f.data_type,
                                        f.nullable))
        return Schema(fields)

    @property
    def schema(self) -> Schema:
        return self._out_schema

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions

    @property
    def fused_mode(self) -> str:
        return "dense" if self._ranges is not None else "sorted"

    def _use_host_vectorized(self) -> bool:
        from blaze_tpu.bridge.placement import host_resident
        return (not self._expand and
                config.FUSED_HOST_VECTORIZED_ENABLE.get() and
                host_resident() and self._host_vectorized_eligible())

    @property
    def _has_var_keys(self) -> bool:
        return any(not e.data_type(self._in_schema).is_fixed_width
                   for e, _n in self._group_exprs)

    def _stage_loop_program(self):
        """StageProgram for the device-resident loop, or None when the
        knob or placement declines it / the stage doesn't compile.
        Under 'auto' the loop and the host Arrow lane are mutually
        exclusive (stage_loop_active requires device placement), so
        there is no priority question between them."""
        from blaze_tpu.plan import stage_compiler
        if not stage_compiler.stage_loop_active():
            return None
        return stage_compiler.try_compile(self)

    def execute(self, partition: int) -> BatchIterator:
        prog = self._stage_loop_program()
        if prog is not None:
            # device-resident stage loop (runtime/loop.py): ONE jit'd
            # program folds a chunk of batches, amortizing dispatch per
            # chunk instead of per batch.  The loop raises
            # StageLoopFallback only while it has emitted nothing (it
            # emits at its final drain, or from the point at which a
            # partial aggregation switches to pass-through, after which
            # an error fails the task instead), so the fallback is
            # lossless and the partition re-runs through the staged
            # lanes below.
            from blaze_tpu.runtime.loop import (StageLoopFallback,
                                                execute_loop)
            try:
                yield from execute_loop(prog, partition)
                return
            except StageLoopFallback as e:
                xla_stats.note_stage_loop_fallback(str(e))
                self.metrics.add("stage_loop_fallback", 1)
        if self._expand:
            # the chain holds an Expand, which only the loop folds in
            # place: the unfused aggregation over `ExpandExec.execute`
            yield from AggExec(self.children[0], self._group_exprs,
                               self._aggs).execute(partition)
            return
        if self._decimal_specs:
            # a decimal value lane is guarded against 64-bit wrap by the
            # stage loop alone (runtime/loop.py); every other lane of
            # this node sums int64 unguarded, so outside the loop a
            # decimal aggregation runs where it ran before it could fuse
            yield from AggExec(self.children[0], self._group_exprs,
                               self._aggs).execute(partition)
            return
        if self._has_var_keys and not self._use_host_vectorized():
            # re-check the ADMISSION-time exclusion (dict_ok in
            # _try_fuse_agg): a plan fused for the host path whose
            # placement/config drifted must fail LOUDLY, not run the
            # NaN-propagating fold on float min/max args
            dict_safe = not any(
                rk in ("min", "max") and arg is not None
                and arg.data_type(self._in_schema).is_floating
                for rk, _ok, arg in self._specs)
            if config.FUSED_DICT_DEVICE_ENABLE.get() and dict_safe:
                try:
                    yield from self._execute_dict_device(partition)
                    return
                except _DictCapExceeded:
                    # nothing emitted yet (dict path emits only at the
                    # final drain).  Arrow's host agg is only a valid
                    # stand-in where it is both ENABLED and eligible;
                    # otherwise the generic AggExec engine (exact Spark
                    # semantics incl. float-key normalization)
                    self.metrics.add("dict_device_fallback", 1)
                    if (config.FUSED_HOST_VECTORIZED_ENABLE.get()
                            and self._host_vectorized_eligible()):
                        for rb in self._execute_host_vectorized(
                                partition):
                            yield ColumnBatch.from_arrow(rb)
                    else:
                        agg = AggExec(self.children[0],
                                      self._group_exprs, self._aggs)
                        yield from agg.execute(partition)
                    return
            raise RuntimeError(
                "fused utf8-key aggregation requires host placement "
                "(placement changed after plan fusion?)")
        if self._use_host_vectorized():
            # host placement: Arrow's multithreaded C++ hash aggregation
            # (GIL-releasing) is the host-engine analog of the reference's
            # native vectorized agg — faster than driving XLA-CPU programs
            # batch-by-batch from Python (ref agg_table.rs InMemTable)
            for rb in self._execute_host_vectorized(partition):
                yield ColumnBatch.from_arrow(rb)
        elif self._ranges is not None:
            if self._mxu_meta is not None and self._mxu_active():
                try:
                    yield from self._execute_mxu(partition)
                    return
                except _MxuVerifyFailed:
                    # float column wasn't fixed-point-exact after all:
                    # nothing has been emitted yet (the MXU path only
                    # emits after its final drain), so the partition
                    # re-runs losslessly through the scatter strategy
                    self.metrics.add("mxu_verify_fallback", 1)
            yield from self._execute_dense(partition)
        else:
            yield from self._execute_sorted(partition)

    def _note_lane(self, batches: int, host: bool = False) -> None:
        """Observed-lane evidence, the same counters AggExec keeps:
        input batches this operator aggregated as device-resident
        columns vs on the host (numpy / Arrow) — what says where a
        stage really ran, whatever the session-level placement is."""
        from blaze_tpu.bridge.placement import host_resident
        self.metrics.add("host_lane_batches" if host or host_resident()
                         else "device_lane_batches", batches)

    def _mxu_active(self) -> bool:
        if self._prepare is None:
            return False
        if config.AGG_MXU_FORCE.get():
            return True
        from blaze_tpu.bridge.placement import host_resident
        return not host_resident() and jax.default_backend() == "tpu"

    def arrow_batches(self, partition: int):
        """Arrow-resident output: the host-vectorized path produces Arrow
        record batches natively; handing them to Arrow-resident consumers
        (runtime root, shuffle writer, Acero joins) skips the
        ColumnBatch round trip in both directions."""
        if self._use_host_vectorized():
            yield from self._execute_host_vectorized(partition)
        else:
            yield from super().arrow_batches(partition)

    # -- host placement: Arrow C++ hash aggregation ------------------------
    def _host_vectorized_eligible(self) -> bool:
        return _host_vectorized_eligible(self._group_exprs, self._specs,
                                         self._in_schema)

    def _execute_host_vectorized(self, partition: int) -> BatchIterator:
        import pyarrow as pa

        from blaze_tpu.memory import MemConsumer, MemManager

        key_names = [n for _e, n in self._group_exprs]

        state = {"chunks": [], "rows": 0, "bytes": 0, "merged": None}

        class _Consumer(MemConsumer):
            """Budget discipline for the buffered raw chunks: memory
            pressure forces the acc-table re-merge early (the InMemTable
            mem_used -> spill trigger analog, ref agg_table.rs:323)."""

            def __init__(c):
                super().__init__("host_vectorized_agg")
                c.metrics = self.metrics

            def spill(c) -> int:
                if not state["chunks"]:
                    return 0
                released = state["bytes"]
                state["merged"] = self._host_group_by(
                    state["chunks"], state["merged"], key_names)
                state["chunks"] = []
                state["rows"] = 0
                state["bytes"] = 0
                c.update_mem_used(
                    state["merged"].nbytes if state["merged"] is not None
                    else 0)
                return released

        consumer = _Consumer()
        consumer.set_spillable(MemManager.get())
        # re-merge threshold bounds memory by distinct groups instead of
        # input rows
        limit = config.FUSED_HOST_COLLECT_ROWS.get()
        # partial-agg skipping (the AGG_TRIGGER_PARTIAL_SKIPPING analog,
        # ref agg_table.rs:108-122): a PARTIAL aggregation whose observed
        # cardinality ratio is too high stops aggregating and passes raw
        # rows through in acc form — the final stage re-merges
        can_skip = (not self._complete and not self._grow and
                    config.PARTIAL_AGG_SKIPPING_ENABLE.get())
        skip_ratio = config.PARTIAL_AGG_SKIPPING_RATIO.get()
        skip_min = config.PARTIAL_AGG_SKIPPING_MIN_ROWS.get()
        next_check = skip_min  # re-probe every minRows stride: clustered
        # inputs whose tail turns high-cardinality must still trip the
        # protection (matches the non-fused path's per-flush check,
        # ops/agg/exec.py _should_skip_partials)
        rows_seen = 0
        skipping = False
        merged_bytes = 0
        try:
            for tbl in self._host_input_tables(partition, key_names):
                self._note_lane(1, host=True)
                if tbl is None or tbl.num_rows == 0:
                    continue
                if skipping:
                    xla_stats.note_partial_agg_rows(tbl.num_rows)
                    yield from self._host_passthrough(tbl, key_names)
                    continue
                rows_seen += tbl.num_rows
                state["chunks"].append(tbl)
                state["rows"] += tbl.num_rows
                state["bytes"] += tbl.nbytes  # running total: O(1)/batch
                if state["merged"] is not None:
                    merged_bytes = state["merged"].nbytes
                consumer.update_mem_used(state["bytes"] + merged_bytes)
                # the skip decision checkpoints at minRows-sized strides
                # (not only the much larger collect limit) on a BOUNDED
                # probe — a distinct-count over a UNIFORM row sample of
                # everything buffered, NOT a full merge (the reference
                # measures the ratio on the minRows-row prefix its hash
                # table absorbed, agg_table.rs:108-122; a uniform sample
                # across the whole buffer additionally catches cyclic
                # keys whose repeats a prefix/tail window would miss).
                # Skipping then releases the raw buffer straight through
                # without ever aggregating it.
                # NOTE: update_mem_used above may have spilled THIS
                # consumer synchronously, emptying the chunk buffer —
                # nothing left to probe until more rows arrive
                if can_skip and rows_seen >= next_check \
                        and state["chunks"]:
                    probe = self._sample_rows(
                        state["chunks"], state["rows"],
                        min(skip_min,
                            config.PARTIAL_AGG_SKIPPING_PROBE_ROWS.get()))
                    n_distinct = self._probe_distinct(probe, key_names)
                    xla_stats.note_partial_agg_probe(probe.num_rows,
                                                     n_distinct)
                    if (n_distinct / max(1, probe.num_rows)
                            > skip_ratio):
                        skipping = True
                        self.metrics.add("partial_skipped", 1)
                        xla_stats.note_partial_agg_skip(rows_seen)
                        if state["merged"] is not None:
                            yield from self._emit_host(state["merged"],
                                                       key_names)
                            state["merged"] = None
                        for c in state["chunks"]:
                            # buffered raw chunks leave UNAGGREGATED —
                            # they are pass-through rows too
                            xla_stats.note_partial_agg_rows(c.num_rows)
                            yield from self._host_passthrough(c, key_names)
                        state["chunks"] = []
                        state["rows"] = 0
                        state["bytes"] = 0
                        consumer.update_mem_used(0)
                        continue
                    next_check = rows_seen + skip_min
                if state["rows"] >= limit:
                    consumer.spill()
                    self.metrics.add("host_vectorized_merges", 1)
            if state["chunks"] or state["merged"] is not None:
                state["merged"] = self._host_group_by(
                    state["chunks"], state["merged"], key_names)
        finally:
            consumer.unregister()
        merged = state["merged"]
        if merged is None:
            return
        self.metrics.add("host_vectorized_batches", 1)
        yield from self._emit_host(merged, key_names)

    def _emit_host(self, merged, key_names) -> BatchIterator:
        yield from self._emit_batches(self._host_finalize(merged,
                                                          key_names))

    def _emit_batches(self, rb):
        """Arrow record-batch chunks (the host-vectorized generators stay
        Arrow-resident; execute() wraps into ColumnBatch at the edge)."""
        bs = config.BATCH_SIZE.get()
        for off in range(0, rb.num_rows, bs):
            yield rb.slice(off, min(bs, rb.num_rows - off))

    def _host_passthrough(self, tbl, key_names) -> BatchIterator:
        """One raw keys/args table emitted in PARTIAL-output (acc) form
        without grouping: sum acc = the value, count acc = 1 per valid
        row (1 per row for count(*)), min/max acc = the value.  The
        downstream FINAL aggregation re-merges (partial skipping)."""
        import pyarrow as pa
        import pyarrow.compute as pc
        out_arrow = self._out_schema.to_arrow()
        arrays = []
        for i, f in enumerate(out_arrow):
            if i < len(key_names):
                col = tbl.column(i)
            else:
                spec_i = i - len(key_names)
                rk, _ok, arg = self._specs[spec_i]
                src = tbl.column(len(key_names) + spec_i)
                if rk == "count":
                    col = (pa.array(np.ones(tbl.num_rows,
                                            dtype=np.int64))
                           if arg is None else
                           pc.if_else(pc.is_valid(src), 1, 0))
                else:
                    col = src
            if isinstance(col, pa.ChunkedArray):
                col = col.combine_chunks()
            if not col.type.equals(f.type):
                col = col.cast(f.type, safe=False)
            arrays.append(col)
        rb = pa.RecordBatch.from_arrays(arrays, schema=out_arrow)
        yield from self._emit_batches(rb)

    @staticmethod
    def _mask_filter(tbl, preds, schema, filt):
        """Conjunction of direct-kernel masks (cheaper than Acero's
        Table.filter(Expression) plan construction); Expression fallback
        when any predicate declines."""
        from blaze_tpu.exprs.arrow_compat import eval_filter_mask
        import pyarrow.compute as pc
        mask = None
        for p in preds:
            m = eval_filter_mask(p, schema, tbl)
            if m is None:
                return tbl.filter(filt)
            mask = m if mask is None else pc.and_kleene(mask, m)
        return tbl.filter(mask)

    @staticmethod
    def _probe_distinct(probe, key_names) -> int:
        """Distinct-group count of the probe sample.  Integer keys
        combine into one mixed hash and count via np.unique — ~3x
        cheaper than a group_by on the sample.  A hash collision merges
        two real groups, UNDER-counting distincts and biasing the ratio
        toward KEEPING the aggregation — mildly against the protection
        this probe provides — but at probe sizes (<=50K keys in a
        64-bit space) the expected collision count is ~1e-7, far below
        the ratio's decision margin.  Non-integer keys fall back to the
        exact group_by."""
        import numpy as np
        import pyarrow as pa
        mixed = None
        for name in key_names:
            col = probe.column(name)
            if isinstance(col, pa.ChunkedArray):
                col = col.combine_chunks()
            if not pa.types.is_integer(col.type):
                mixed = None
                break
            v = col.cast(pa.int64(), safe=False).fill_null(
                -0x6A09E667F3BCC909).to_numpy(zero_copy_only=False)
            h = (v.view(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) \
                if mixed is None else \
                ((mixed ^ v.view(np.uint64)) *
                 np.uint64(0x9E3779B97F4A7C15))
            mixed = h ^ (h >> np.uint64(29))
        if mixed is None:
            return probe.group_by(key_names,
                                  use_threads=True).aggregate([]).num_rows
        return int(len(np.unique(mixed)))

    @staticmethod
    def _sample_rows(chunks, total_rows: int, max_rows: int):
        """Uniform strided row sample (≤ max_rows) across all buffered
        chunks.  A sample that spans the whole buffer sees key REPEATS
        that any contiguous window would miss (e.g. keys cycling with a
        period longer than the window), so the cardinality ratio it
        yields under-estimates on repetitive data — the conservative
        direction for the skip decision."""
        tbl = (chunks[0] if len(chunks) == 1
               else pa.concat_tables(chunks))
        if total_rows <= max_rows:
            return tbl
        stride = total_rows / max_rows
        idx = np.minimum((np.arange(max_rows) * stride).astype(np.int64),
                         total_rows - 1)
        return tbl.take(idx)

    def _host_input_tables(self, partition: int, key_names):
        """Iterator of keys+args Arrow tables for the host-vectorized agg.

        Three paths, fastest first:
          1. pushdown scan -> Arrow-resident column selection (every
             grouping/arg expression is a bare column): record batches go
             from the parquet reader into the agg with ZERO numpy round
             trips;
          2. pushdown scan -> ColumnBatch expression evaluation;
          3. engine-side child stream (partition constants, non-arrow
             predicates, non-parquet sources).
        """
        scan = self._host_scan_arrow(partition)
        if scan is None and not self._chain:
            # sources that natively hold Arrow data (IpcReader: the
            # reduce-side merge input) stream it in without a ColumnBatch
            # round trip, same as the pushdown-scan path
            from blaze_tpu.ops.base import ExecutionPlan as _EP
            src = self._source
            if type(src).arrow_batches is not _EP.arrow_batches:
                scan = src.arrow_batches(partition)
        if scan is None:
            for batch in self.children[0].execute(partition):
                yield self._host_keys_args_table(batch, key_names)
            return
        idxs = self._bare_column_indices()
        for rb in scan:
            if rb.num_rows == 0:
                continue
            self.metrics.add("pushdown_rows", rb.num_rows)
            if idxs is not None:
                cols = [rb.column(i) for i in idxs]
                names = list(key_names) + [
                    f"__arg{i}" for i in range(len(self._specs))]
                yield pa.table(cols, names=names)
            elif isinstance(rb, pa.RecordBatch):
                yield self._host_keys_args_table(
                    ColumnBatch.from_arrow(rb), key_names)
            else:
                # eager reads hand back a Table: convert chunk-wise (a
                # combine_chunks of >2 GiB string data would overflow
                # 32-bit offsets)
                for piece in rb.to_batches():
                    if piece.num_rows:
                        yield self._host_keys_args_table(
                            ColumnBatch.from_arrow(piece), key_names)

    def _bare_column_indices(self):
        """Source-schema column index per key+arg when every expression is
        a BoundReference (valid only for an all-filter chain, where the
        agg input schema IS the source schema); None otherwise."""
        if any(kind != "filter" for kind, *_rest in self._chain):
            return None
        idxs = []
        for e, _n in self._group_exprs:
            if not isinstance(e, BoundReference):
                return None
            idxs.append(e.index)
        for _rk, _ok, arg in self._specs:
            if arg is None:  # count(*): any column carries the row count
                idxs.append(idxs[0])
            elif isinstance(arg, BoundReference):
                idxs.append(arg.index)
            else:
                return None
        return idxs

    def _leading_filters(self):
        """The conjuncts of the chain's filter steps that come before any
        step that re-numbers columns: conditions over the SOURCE's schema
        that every row the aggregation folds meets."""
        conjuncts = []
        for kind, preds, _exprs, _schema in self._chain:
            if kind != "filter":
                break
            conjuncts.extend(preds or ())
        return conjuncts

    def source_stream(self, partition: int) -> BatchIterator:
        """The source's stream for the lanes that run the chain inside
        their program (the stage loop opens it here too).  A parquet scan
        reads only the row groups whose statistics let a row pass the
        chain's leading filters, as the host lane's eager read does; the
        chain still filters row by row."""
        return self._source.execute_pruned(partition,
                                           self._leading_filters())

    def _host_scan_arrow(self, partition: int):
        """Push the absorbed filter chain into Arrow's C++ parquet reader
        (predicate + projection pushdown, the parquet_exec.rs analog) when
        the source is a plain parquet scan and every predicate translates
        exactly; None -> engine-side path.  Yields Arrow record batches
        (or tables).

        Small inputs take an EAGER read (pq.read_table + vectorized
        mask): measurably faster than the dataset scanner, which pays
        per-fragment scheduling overhead.  Inputs above the eager
        threshold stream through the scanner for bounded memory."""
        from blaze_tpu.exprs.arrow_compat import to_arrow_filter
        from blaze_tpu.ops.scan import ParquetScanExec, open_source
        src = self._source
        if not isinstance(src, ParquetScanExec):
            return None
        if src._partition_schema is not None:
            return None  # partition constants need engine-side assembly
        if any(kind != "filter" for kind, *_rest in self._chain):
            return None
        filt = None
        plain_preds = self._leading_filters()
        for p in plain_preds:
            e = to_arrow_filter(p, src.schema)
            if e is None:
                return None
            filt = e if filt is None else (filt & e)
        paths = src._file_groups[partition]
        if not paths:
            return iter(())
        import pyarrow.parquet as pq
        eager_limit = config.FUSED_HOST_EAGER_SCAN_BYTES.get()
        try:
            local = all(isinstance(p, str) and os.path.exists(p)
                        for p in paths)
            if (local and sum(os.path.getsize(p) for p in paths)
                    <= eager_limit):
                columns = [f.name for f in src._file_part]
                if plain_preds:
                    return self._eager_pruned_read(
                        paths, columns, plain_preds, src, filt)
                return iter((pq.read_table(paths, columns=columns,
                                           use_threads=True),))
            import pyarrow.dataset as ds
            dataset = ds.dataset([open_source(p) for p in paths],
                                 format="parquet",
                                 schema=src._file_part.to_arrow())
            scanner = dataset.scanner(filter=filt, batch_size=1 << 20,
                                      use_threads=True)
            return scanner.to_batches()
        except Exception:
            return None  # schema evolution etc.: engine-side scan

    def _eager_pruned_read(self, paths, columns, plain_preds, src, filt):
        """Eager read with row-group statistics pruning + mask elision.

        Parity: the reference's parquet row-group/page filtering (ref
        conf.rs:43 `enable.pageFiltering`, parquet_exec.rs page_filtering)
        applied to the eager host path.  A metadata-only pass drops row
        groups the predicate provably never matches; groups the stats
        prove FULLY matching skip the vectorized mask entirely (range
        predicates over date-clustered fact tables make both the common
        case).  Falls back to one whole read_table when nothing prunes —
        identical cost to the pre-pruning path."""
        import pyarrow as pa
        import pyarrow.parquet as pq
        from blaze_tpu.ops.pruning import (conjunction, prune_with_stats,
                                           split_covered)
        from blaze_tpu.ops.scan import open_source

        pred = conjunction(plain_preds)
        files = []          # (ParquetFile, covered_groups, boundary_groups)
        kept_total = 0
        groups_total = 0
        for p in paths:
            f = pq.ParquetFile(open_source(p))
            # deterministic schema-evolution guard: the lazy per-file
            # reads below run OUTSIDE the caller's try/fallback, so a
            # file missing a projected column must be detected HERE
            # (falling back to the engine-side scan, which aligns
            # schemas per batch)
            names = set(f.schema_arrow.names)
            if any(c not in names for c in columns):
                raise LookupError("schema evolution: engine-side scan")
            md = f.metadata
            kept = prune_with_stats(md, src.schema, pred,
                                    list(range(md.num_row_groups)))
            groups_total += md.num_row_groups
            kept_total += len(kept)
            if kept:
                # split kept groups into provably-fully-covered (mask
                # elided) vs boundary (masked) — only boundary rows pay
                # the vectorized filter; one metadata pass per file
                covered, boundary = split_covered(md, src.schema, pred,
                                                  kept)
                files.append((f, covered, boundary))
        self.metrics.add("pruned_row_groups", groups_total - kept_total)
        if kept_total == groups_total and all(
                not c for _f, c, _b in files):
            # nothing pruned, nothing elided: single multithreaded read
            # across files — identical cost to the pre-pruning path
            tbl = pq.read_table(paths, columns=columns, use_threads=True)
            return iter((self._mask_filter(tbl, plain_preds, src.schema,
                                           filt),))
        if not files:
            return iter(())

        def read_one(f, covered, boundary):
            """One file's kept rows: covered groups pass unmasked,
            boundary groups get the vectorized filter.  All kept groups
            decode in ONE read_row_groups call (one reader setup, one
            thread fan-out) — covered groups come first, so the
            unmasked region is a head slice and only the boundary tail
            pays the filter.  Decode errors past the (already-validated)
            metadata follow the scan operator's corrupted-file policy —
            these reads run lazily, outside the caller's fallback
            window."""
            try:
                kept_groups = list(covered) + list(boundary)
                if not kept_groups:
                    return None
                tbl = f.read_row_groups(kept_groups, columns=columns,
                                        use_threads=True)
                if not boundary:
                    return tbl
                md = f.metadata
                head_rows = sum(md.row_group(g).num_rows
                                for g in covered)
                btbl = self._mask_filter(tbl.slice(head_rows),
                                         plain_preds, src.schema, filt)
                if not covered:
                    return btbl
                return pa.concat_tables([tbl.slice(0, head_rows), btbl])
            except Exception:
                if config.IGNORE_CORRUPTED_FILES.get():
                    return None
                raise

        def gen():
            # double-buffer: file i+1 decodes on a worker thread (Arrow
            # releases the GIL) while file i flows through mask/agg/IPC
            # downstream — scan and compute overlap inside one task (the
            # tokio-pipelining analog of rt.rs:156)
            import concurrent.futures as cf
            with cf.ThreadPoolExecutor(max_workers=1) as pool:
                nxt = pool.submit(read_one, *files[0])
                for i in range(len(files)):
                    tbl = nxt.result()
                    if i + 1 < len(files):
                        nxt = pool.submit(read_one, *files[i + 1])
                    if tbl is not None and tbl.num_rows:
                        yield tbl
        return gen()

    def _host_keys_args_table(self, batch: ColumnBatch, key_names):
        """Evaluate keys + agg args on the (numpy-resident) batch and pack
        them into an Arrow table [k0..kn, a0..am]."""
        import pyarrow as pa
        batch = batch.compact()
        n = batch.num_rows
        if n == 0:
            return None
        arrays = []
        names = []
        for (e, name) in self._group_exprs:
            arrays.append(e.evaluate(batch).to_host(n))
            names.append(name)
        for i, (_rk, _ok, arg) in enumerate(self._specs):
            if arg is None:  # count(*): count rows via a key column
                arrays.append(arrays[0])
            else:
                arrays.append(arg.evaluate(batch).to_host(n))
            names.append(f"__arg{i}")
        return pa.table(arrays, names=names)

    @staticmethod
    def _pack_keys_info(tbl, key_names):
        """Integer group keys pack losslessly into ONE non-negative
        int64: per key k -> k - min + 1 (null -> 0, its own Spark group),
        mixed-radix combined across keys.  Returns (packed int64 column
        with no nulls, spans, mins), or None when any key is non-integer
        or the radix product would overflow int64."""
        import pyarrow as pa
        import pyarrow.compute as pc
        cols = []
        spans = []
        mins = []
        total = 1
        for n in key_names:
            col = tbl.column(n)
            if not pa.types.is_integer(col.type):
                return None
            mm = pc.min_max(col)
            if not mm["min"].is_valid:  # all-null key: span = {null}
                lo, span = 0, 1
            else:
                lo = mm["min"].as_py()
                span = mm["max"].as_py() - lo + 2  # +1 for the null slot
            total *= span
            if total > (1 << 62):
                return None
            cols.append(col)
            spans.append(span)
            mins.append(lo)
        # null-free keys pack in ONE fused numpy expression (zero-copy
        # views in, one output buffer) instead of a chain of pa.compute
        # dispatches; any null key falls back to the Arrow kernels,
        # whose fill_null provides the null->slot-0 encoding
        if all(c.null_count == 0 for c in cols):
            import numpy as np
            packed_np = None
            for col, span, lo in zip(cols, spans, mins):
                cc = (col.combine_chunks()
                      if isinstance(col, pa.ChunkedArray) else col)
                enc = cc.to_numpy(zero_copy_only=False).astype(
                    np.int64, copy=False) + (1 - lo)
                packed_np = enc if packed_np is None else \
                    packed_np * span + enc
            return pa.array(packed_np), spans, mins
        packed = None
        for col, span, lo in zip(cols, spans, mins):
            enc = pc.fill_null(
                pc.add(pc.cast(col, pa.int64(), safe=False), 1 - lo), 0)
            packed = enc if packed is None else \
                pc.add(pc.multiply(packed, span), enc)
        return packed, spans, mins

    @staticmethod
    def _unpack_np_keys(out_k, key_types, spans, mins):
        """Decode packed keys (numpy int64) back to per-key pa arrays,
        restoring nulls.  Null-free keys (the overwhelmingly common
        case: fact-table join/group keys) skip the mask pass entirely,
        letting pa.array zero-copy the decoded buffer instead of
        re-copying it next to a validity bitmap."""
        import numpy as np
        import pyarrow as pa
        parts = []
        k = out_k
        for span in reversed(spans):
            parts.append(k % span)
            k = k // span
        parts.reverse()
        out = []
        for enc, lo, t in zip(parts, mins, key_types):
            nulls = enc == 0
            mask = nulls if nulls.any() else None
            arr = pa.array(enc + (lo - 1), mask=mask)
            if not arr.type.equals(t):
                arr = arr.cast(t, safe=False)
            out.append(arr)
        return out

    _KERNEL_MIN_ROWS = 4096

    def _native_group_by(self, tbl, key_names, kinds):
        """Hash group-aggregation through the native agg kernel
        (agg_kernel.cpp blaze_group_agg_i64): packed int64 key + flat
        accumulator arrays, ~4x Arrow's group_by on high-cardinality
        integer keys.  `kinds` = [(op, col_name_or_None)] in __acc
        output order; op in sum/count/min/max.  Returns the full output
        column list [keys..., accs...] or None -> Arrow fallback."""
        import ctypes

        import numpy as np
        import pyarrow as pa
        import pyarrow.compute as pc

        from blaze_tpu.bridge.native import get_agg_kernel
        lib = get_agg_kernel()
        n = tbl.num_rows
        if (lib is None or not key_names or n < self._KERNEL_MIN_ROWS
                or n >= (1 << 31)):
            return None
        # op eligibility first — packing is two full passes over the
        # table, pointless if any agg can't ride the kernel anyway
        for op_name, colname in kinds:
            if colname is None or op_name == "count":
                continue
            t = tbl.column(colname).type
            if op_name == "sum":
                if not (pa.types.is_floating(t) or pa.types.is_integer(t)):
                    return None
            elif op_name in ("min", "max"):
                if not pa.types.is_integer(t):
                    return None
            else:
                return None
        info = self._pack_keys_info(tbl, key_names)
        if info is None:
            return None
        packed, spans, mins = info
        ops = []
        val_nps = []       # keeps numpy operands alive across the call
        valid_nps = []
        out_nps = []
        out_valid_nps = []
        post = []          # (arrow_type_or_None, is_count)
        for op_name, colname in kinds:
            if op_name == "count" and colname is None:
                ops.append(2)
                val_nps.append(None)
                valid_nps.append(None)
                out_nps.append(np.empty(n, np.int64))
                out_valid_nps.append(np.empty(n, np.uint8))
                post.append((None, True))
                continue
            col = tbl.column(colname)
            t = col.type
            if op_name == "count":
                # only the operand's validity matters; never cast values
                ops.append(2)
                val_nps.append(None)
                valid_nps.append(np.ascontiguousarray(
                    col.combine_chunks().is_valid().to_numpy(
                        zero_copy_only=False), dtype=np.uint8)
                    if col.null_count else None)
                out_nps.append(np.empty(n, np.int64))
                out_valid_nps.append(np.empty(n, np.uint8))
                post.append((None, True))
                continue
            if op_name == "sum" and pa.types.is_floating(t):
                op, target, out_t = 0, pa.float64(), None
            elif op_name == "sum" and pa.types.is_integer(t):
                op, target, out_t = 1, pa.int64(), None
            elif op_name in ("min", "max") and pa.types.is_integer(t):
                op = 3 if op_name == "min" else 4
                target, out_t = pa.int64(), t
            else:
                return None
            ops.append(op)
            cc = col.combine_chunks()
            if col.null_count:
                vals = pc.fill_null(pc.cast(cc, target, safe=False), 0)
                valid_nps.append(np.ascontiguousarray(
                    cc.is_valid().to_numpy(zero_copy_only=False),
                    dtype=np.uint8))
            else:
                # identity casts still copy; hand the buffer over as-is
                vals = cc if cc.type.equals(target) else \
                    pc.cast(cc, target, safe=False)
                valid_nps.append(None)
            val_nps.append(np.ascontiguousarray(
                vals.to_numpy(zero_copy_only=False)))
            out_nps.append(np.empty(
                n, np.float64 if op == 0 else np.int64))
            out_valid_nps.append(np.empty(n, np.uint8))
            post.append((out_t, op == 2))
        key_np = np.ascontiguousarray(
            packed.combine_chunks().to_numpy(zero_copy_only=False)
            if isinstance(packed, pa.ChunkedArray)
            else packed.to_numpy(zero_copy_only=False), dtype=np.int64)
        out_keys = np.empty(n, np.int64)

        def ptr(a):
            return ctypes.c_void_p(a.ctypes.data) if a is not None else None

        n_aggs = len(ops)
        has_rows = hasattr(lib, "blaze_group_agg_i64_rows")
        first_rows = np.empty(n, np.int32) if has_rows else None
        call_args = [
            ptr(key_np), n, n_aggs,
            (ctypes.c_int32 * n_aggs)(*ops),
            (ctypes.c_void_p * n_aggs)(*[ptr(a) for a in val_nps]),
            (ctypes.c_void_p * n_aggs)(*[ptr(a) for a in valid_nps]),
            ptr(out_keys),
            (ctypes.c_void_p * n_aggs)(*[ptr(a) for a in out_nps]),
            (ctypes.c_void_p * n_aggs)(*[ptr(a) for a in out_valid_nps])]
        if has_rows:
            ng = lib.blaze_group_agg_i64_rows(*call_args,
                                              ptr(first_rows))
        else:
            ng = lib.blaze_group_agg_i64(*call_args)
        if ng < 0:
            return None
        if has_rows:
            # materialize keys with one gather per original column —
            # nulls ride along for free; the mixed-radix int64 division
            # decode is the slowest scalar path numpy has
            idx = pa.array(first_rows[:ng])
            out = [pc.take(tbl.column(kn), idx) for kn in key_names]
        else:
            key_types = [tbl.column(kn).type for kn in key_names]
            out = self._unpack_np_keys(out_keys[:ng], key_types, spans,
                                       mins)
        for (out_t, is_count), vals, valid in zip(post, out_nps,
                                                  out_valid_nps):
            mask = None if is_count else (valid[:ng] == 0)
            arr = pa.array(vals[:ng], mask=mask)
            if out_t is not None and not arr.type.equals(out_t):
                arr = arr.cast(out_t, safe=False)
            out.append(arr)
        self.metrics.add("native_agg_rows", n)
        return out

    def _grouped(self, tbl, key_names, aggspec):
        """tbl.group_by with multi-integer-key PACKING: Arrow's hash
        aggregation hashes/compares every key column per row, so N
        integer keys pack into ONE computed int64 key (_pack_keys_info),
        cutting per-row hash work on multi-key aggregations.  The
        packed column is decoded back to the original key columns —
        including nulls, which Spark groups as their own key — after
        aggregation.  Falls back to the plain multi-column group_by
        whenever packing is inapplicable."""
        import pyarrow as pa
        if len(key_names) < 2 or tbl.num_rows < self._KERNEL_MIN_ROWS:
            return tbl.group_by(key_names, use_threads=True) \
                      .aggregate(aggspec), tbl, None
        info = self._pack_keys_info(tbl, key_names)
        if info is None:
            return tbl.group_by(key_names, use_threads=True) \
                      .aggregate(aggspec), tbl, None
        packed, spans, mins = info
        ptbl = tbl.drop_columns(key_names).append_column("__gk", packed)
        g = ptbl.group_by(["__gk"], use_threads=True).aggregate(aggspec)
        return g, tbl, (spans, mins)

    @classmethod
    def _unpack_keys(cls, g, tbl, key_names, packing):
        """Decode the packed __gk column of an aggregate result back to
        the original key columns (None packing: keys are already
        present).  Delegates to the single mixed-radix decoder."""
        import numpy as np
        import pyarrow as pa
        if packing is None:
            return [g.column(n) for n in key_names]
        spans, mins = packing
        k = g.column("__gk")
        if isinstance(k, pa.ChunkedArray):
            k = k.combine_chunks()
        key_types = [tbl.column(n).type for n in key_names]
        return cls._unpack_np_keys(
            np.ascontiguousarray(k.to_numpy(zero_copy_only=False),
                                 dtype=np.int64),
            key_types, spans, mins)

    def _host_group_by(self, chunks, merged, key_names):
        """group_by over buffered raw chunks, then merge with the running
        acc table (merge fns: sum->sum, count->sum, min/max idempotent).

        Output columns are selected BY NAME (`"{col}_{fn}"`), never by
        position — Arrow versions have differed on whether keys come
        first or last in aggregate output."""
        import pyarrow as pa
        import pyarrow.compute as pc
        acc_names = [f"__acc{i}" for i in range(len(self._specs))]
        out = None
        if chunks:
            tbl = pa.concat_tables(chunks)
            kinds = [(rk, None if (rk == "count" and arg is None)
                      else f"__arg{i}")
                     for i, (rk, _ok, arg) in enumerate(self._specs)]
            cols = self._native_group_by(tbl, key_names, kinds)
            if cols is not None:
                out = pa.table(cols, names=key_names + acc_names)
            else:
                aggspec = []
                out_names = []
                for i, (rk, _ok, arg) in enumerate(self._specs):
                    if rk == "count":
                        mode = "all" if arg is None else "only_valid"
                        aggspec.append((f"__arg{i}", "count",
                                        pc.CountOptions(mode=mode)))
                    else:
                        aggspec.append((f"__arg{i}", rk))
                    out_names.append(f"__arg{i}_{rk}")
                g, tbl, packing = self._grouped(tbl, key_names, aggspec)
                out = pa.table(
                    self._unpack_keys(g, tbl, key_names, packing) +
                    [g.column(n) for n in out_names],
                    names=key_names + acc_names)
        if merged is None:
            return out
        if out is None:
            return merged
        # merge two acc tables: counts sum, sums sum, min/max re-reduce
        both = pa.concat_tables([merged, out])
        merge_fns = [("sum" if rk in ("sum", "count") else rk,
                      f"__acc{i}")
                     for i, (rk, _ok, _a) in enumerate(self._specs)]
        cols = self._native_group_by(both, key_names, merge_fns)
        if cols is not None:
            return pa.table(cols, names=key_names + acc_names)
        merge_spec = []
        merge_names = []
        for f, cn in merge_fns:
            merge_spec.append((cn, f))
            merge_names.append(f"{cn}_{f}")
        m, both, packing = self._grouped(both, key_names, merge_spec)
        return pa.table(
            self._unpack_keys(m, both, key_names, packing) +
            [m.column(n) for n in merge_names],
            names=key_names + acc_names)

    def _host_finalize(self, merged, key_names):
        """Acc table -> output RecordBatch in self._out_schema order/types.
        `merged` columns are key_names + __acc{i} by construction."""
        import pyarrow as pa
        out_arrow = self._out_schema.to_arrow()
        arrays = []
        for i, f in enumerate(out_arrow):
            if i < len(key_names):
                col = merged.column(key_names[i])
            else:
                col = merged.column(f"__acc{i - len(key_names)}")
            if isinstance(col, pa.ChunkedArray):
                col = col.combine_chunks()
            if i >= len(key_names):
                _rk, ok, _a = self._specs[i - len(key_names)]
                if ok == "count" and col.null_count:
                    col = col.fill_null(0)  # count never nulls
            if not col.type.equals(f.type):
                col = col.cast(f.type, safe=False)
            arrays.append(col)
        return pa.RecordBatch.from_arrays(arrays, schema=out_arrow)

    def _acc_dtypes(self) -> Tuple:
        """Carry accumulator dtype per spec (no evaluation needed)."""
        out = []
        for rk, _ok, arg in self._specs:
            if rk == "count" or arg is None:
                out.append(jnp.int64)
                continue
            dt = arg.data_type(self._in_schema).jnp_dtype()
            if rk == "sum":
                dt = (jnp.float64 if jnp.issubdtype(dt, jnp.floating)
                      else jnp.int64)
            out.append(dt)
        return tuple(out)

    # -- MXU strategy: matmul aggregation in the i32 limb tier -------------
    def _execute_mxu(self, partition: int) -> BatchIterator:
        """Fold windows through the MXU histogram kernel; drain the i32
        limb table into host int64 accumulators within its exactness
        bound; emit once at partition end.  Raises _MxuVerifyFailed
        before any emission when a float column breaks the fixed-point
        contract."""
        from blaze_tpu.kernels import mxu_agg
        meta = self._mxu_meta
        layout = meta.layout
        S = layout.num_slots
        nb = layout.n_blocks
        use_pallas = jax.default_backend() == "tpu"
        fold = _mxu_fold_factory(self._prepare_key, self._prepare,
                                 tuple(self._ranges), meta, use_pallas)
        wide_presence = np.zeros(S, np.int64)
        wide_vals = [np.zeros(S, np.int64) for _ in meta.arrays]
        wide_mm = [np.full(S, (2**31 - 1) if is_min else -(2**31), np.int64)
                   for is_min, _si in meta.scatter]
        carry = None
        bound = 0
        n_batches = 0

        def fresh_carry():
            mm = tuple(jnp.full(S, (2**31 - 1) if is_min else -(2**31),
                                dtype=jnp.int32)
                       for is_min, _si in meta.scatter)
            return (jnp.zeros((layout.sh, layout.sl * nb), jnp.int32),
                    mm, jnp.asarray(True))

        def drain():
            nonlocal carry, bound
            if carry is None:
                return
            table, mm, ok = to_host(carry)
            carry = None
            bound = 0
            if not bool(ok):
                raise _MxuVerifyFailed()
            presence, vals = mxu_agg.split_blocks(np.asarray(table), layout)
            wide_presence[:] += presence
            for i in range(len(wide_vals)):
                wide_vals[i][:] += vals[i]
            for i, (is_min, _si) in enumerate(meta.scatter):
                op = np.minimum if is_min else np.maximum
                wide_mm[i][:] = op(wide_mm[i], np.asarray(mm[i], np.int64))

        for cols_stacked, masks, _rows, count in _batch_windows(
                self.source_stream(partition),
                config.FUSED_FOLD_WINDOW.get()):
            wrows = int(masks.shape[0]) * int(masks.shape[1])
            if wrows > mxu_agg.MAX_ROWS_PER_TABLE:
                # a single window breaching the int32 exactness bound
                # cannot drain mid-fold; nothing has been emitted, so
                # the scatter strategy re-runs the partition losslessly
                raise _MxuVerifyFailed()
            if bound + wrows > mxu_agg.MAX_ROWS_PER_TABLE:
                drain()
            if carry is None:
                carry = fresh_carry()
            carry = fold(carry, cols_stacked, masks)
            bound += wrows
            n_batches += count
        drain()
        self.metrics.add("fused_batches", n_batches)
        self._note_lane(n_batches)
        self.metrics.add("mxu_rows", int(wide_presence.sum()))

        slots = np.nonzero(wide_presence)[0]
        if len(slots) == 0:
            return
        keys = unpack_dense_keys(slots, self._ranges, xp=np)
        accs: List[np.ndarray] = []
        avalid: List[np.ndarray] = []
        ones = np.ones(len(slots), dtype=bool)
        for sp in meta.specs:
            if sp.kind == "count_star":
                accs.append(wide_presence[slots])
                avalid.append(ones)
            elif sp.kind == "count":
                accs.append(wide_vals[sp.arr_valid][slots])
                avalid.append(ones)
            elif sp.kind == "sum":
                vc = wide_vals[sp.arr_valid][slots]
                tot = wide_vals[sp.arr_cents][slots] + vc * sp.off
                accs.append(tot / sp.scale if sp.is_float else tot)
                avalid.append(vc > 0)
            else:  # min / max
                vc = wide_vals[sp.arr_valid][slots]
                raw = wide_mm[sp.scatter_idx][slots] + sp.off
                accs.append(raw / sp.scale if sp.is_float else raw)
                avalid.append(vc > 0)
        yield from self._emit_rows(keys, accs, avalid)

    # -- dense: no host syncs in the loop ----------------------------------
    def _execute_dense(self, partition: int) -> BatchIterator:
        num_slots = 1
        for lo, hi in self._ranges:
            num_slots *= (hi - lo + 2)
        kinds = [rk for rk, _ok, _a in self._specs]
        carry = None
        n_batches = 0
        if self._prepare is not None:
            # fold a WINDOW of batches through one XLA program: the
            # dispatch count drops by the window size and the carry is
            # updated in place inside the program (no per-batch
            # full-table copies — they dominate on backends without
            # donation)
            fold = _dense_fold_factory(self._prepare_key, self._prepare,
                                       tuple(self._ranges), tuple(kinds),
                                       num_slots)
            for cols_stacked, masks, _rows, count in _batch_windows(
                    self.source_stream(partition),
                    config.FUSED_FOLD_WINDOW.get()):
                if carry is None:
                    carry = _init_carry(kinds, self._acc_dtypes(),
                                        num_slots)
                carry = fold(carry, cols_stacked, masks)
                n_batches += count
        else:
            for batch in self.children[0].execute(partition):
                kd, kv, ad, av, mask = self._device_inputs(batch)
                step = self._dense_step(batch.capacity, num_slots,
                                        tuple(kinds))
                if carry is None:
                    carry = _init_carry(kinds, self._acc_dtypes(),
                                        num_slots)
                carry = step(carry, kd, kv, ad, av, mask)
                n_batches += 1
        self.metrics.add("fused_batches", n_batches)
        self._note_lane(n_batches)
        if carry is None:
            return
        yield from self._emit_dense(carry, num_slots)

    def _dense_step(self, capacity: int, num_slots: int, kinds):
        # the factory is memoized at module level so every task/plan
        # instance with the same (ranges, kinds, slots) shares one jit
        # cache — a fresh runtime per task must NOT recompile
        return _dense_step_factory(tuple(self._ranges), kinds, num_slots)

    @staticmethod
    def _drain_table(carry, num_slots: int):
        """Compact ON DEVICE before reading back: the table has
        num_slots entries (possibly millions) but only `count` occupied.
        Ship the occupied prefix, padded to a power-of-two bucket so XLA
        sees a handful of shapes instead of one per distinct count.
        Returns (host_accs, host_avalid, slots) trimmed to count, or
        None when the table is empty.  Shared by the dense and
        dict-device emit paths."""
        accs, avalid, occupied = carry
        count = int(to_host(jnp.sum(occupied)))
        if count == 0:
            return None
        padded = _bucket(count, num_slots)
        # nonzero with a static size is an O(slots) scan (vs argsort's
        # full sort) and keeps slot order; entries past `count` are fill
        slots_dev = jnp.nonzero(occupied, size=padded, fill_value=0)[0]
        fetch = ([jnp.take(a, slots_dev) for a in accs],
                 [jnp.take(v, slots_dev) for v in avalid],
                 slots_dev)
        host_accs, host_avalid, slots = to_host(fetch)
        return ([a[:count] for a in host_accs],
                [v[:count] for v in host_avalid], slots[:count])

    def _emit_dense(self, carry, num_slots: int) -> BatchIterator:
        with tracing.span("agg_drain", table="dense"):
            drained = self._drain_table(carry, num_slots)
            if drained is None:
                return
            host_accs, host_avalid, slots = drained
            # slot -> key decode host-side (shared stride logic, no round
            # trip)
            host_keys = unpack_dense_keys(slots, self._ranges, xp=np)
            rb = self._rows_to_arrow(host_keys, host_accs, host_avalid)
        yield from self._emit_chunks(rb)

    # -- var-width keys on device: dictionary-code dense strategy ----------
    # (VERDICT r4 #8 / SURVEY §7 hard-part #1: keep string group keys as
    # dense integer codes so the device never touches bytes — the
    # parquet-dictionary-code idea applied at the stage boundary)
    def _execute_dict_device(self, partition: int) -> BatchIterator:
        """Group by var-width keys ON DEVICE: every key column
        dictionary-encodes (host, vectorized pyarrow) against an
        accumulated per-key dictionary; the dense i32 codes pack into
        one group id and aggregate through the same sort-free
        scatter-reduce kernel as bounded int keys.  Dictionary growth
        past a key's power-of-two capacity re-lays the table out host-
        side (pure stride arithmetic) and recompiles once per doubling.
        Keys decode back through the dictionaries only at emit."""
        nkeys = len(self._group_exprs)
        kinds = tuple(rk for rk, _ok, _a in self._specs)
        dicts: List[Optional[pa.Array]] = [None] * nkeys
        caps = [16] * nkeys
        limit = config.FUSED_DICT_DEVICE_MAX_SLOTS.get()
        carry = None  # (accs, avalid, occupied) device arrays
        n_batches = 0

        def total_slots(cs):
            t = 1
            for c in cs:
                t *= (c + 1)  # +1: null slot per key (range 0..c-1)
            return t

        for batch in self.children[0].execute(partition):
            cap = batch.capacity
            sel = (batch.selected_mask() if batch.selection is not None
                   else None)
            code_cols = []
            grew = False
            for i, (e, _n) in enumerate(self._group_exprs):
                arr = e.evaluate(batch).to_host(batch.num_rows)
                if isinstance(arr, pa.ChunkedArray):
                    arr = arr.combine_chunks()
                codes, valid, dicts[i] = _global_dict_codes(
                    arr, dicts[i], cap, sel)
                while len(dicts[i]) > caps[i]:
                    caps[i] *= 2
                    grew = True
                code_cols.append((codes, valid))
            if total_slots(caps) > limit:
                raise _DictCapExceeded
            if grew and carry is not None:
                carry = _relayout_dict_table(carry, kinds,
                                             self._acc_dtypes(),
                                             old_caps, caps)
            old_caps = list(caps)
            ad, av = [], []
            for _rk, _ok, arg in self._specs:
                if arg is None:
                    ad.append(None)
                    av.append(None)
                else:
                    dv = arg.evaluate(batch).to_device(cap)
                    ad.append(_pad_lane(dv.data))
                    av.append(_pad_lane(dv.validity))
            mask = _pad_lane(batch.row_mask())
            pcap = mask.shape[0]
            if carry is None:
                carry = _init_carry(kinds, self._acc_dtypes(),
                                    total_slots(caps))
            step = _dict_dense_step(tuple(caps), kinds, pcap)
            kd = tuple(_pad_lane(c) for c, _v in code_cols)
            kv = tuple(_pad_lane(v) for _c, v in code_cols)
            carry = step(carry, kd, kv, tuple(ad), tuple(av), mask)
            n_batches += 1
        self.metrics.add("fused_batches", n_batches)
        self._note_lane(n_batches)
        self.metrics.add("dict_device_batches", n_batches)
        if carry is None:
            return
        yield from self._emit_dict(carry, caps, dicts)

    def _emit_dict(self, carry, caps, dicts) -> BatchIterator:
        with tracing.span("agg_drain", table="dict"):
            rb = self._dict_table_to_arrow(carry, caps, dicts)
        if rb is not None:
            yield from self._emit_chunks(rb)

    def _dict_table_to_arrow(self, carry, caps, dicts):
        num_slots = 1
        for c in caps:
            num_slots *= (c + 1)
        drained = self._drain_table(carry, num_slots)
        if drained is None:
            return None
        host_accs, host_avalid, slots = drained
        count = len(slots)
        ranges = [(0, c - 1) for c in caps]
        decoded = unpack_dense_keys(slots, ranges, xp=np)
        out_arrow = self._out_schema.to_arrow()
        key_fields = [out_arrow.field(i) for i in range(len(dicts))]
        arrays: List[pa.Array] = []
        for (code, kvalid), d, f in zip(decoded, dicts, key_fields):
            idx = pa.array(np.where(kvalid, code, 0), pa.int64(),
                           mask=~kvalid)  # null code -> null key
            arrays.append(d.take(idx).cast(f.type))
        i = len(dicts)
        for (_rk, out_kind, _arg), a, v in zip(self._specs, host_accs,
                                               host_avalid):
            f = out_arrow.field(i)
            if out_kind == "count":
                arrays.append(_to_arrow(a[:count],
                                        np.ones(count, bool), f.type))
            else:
                arrays.append(_to_arrow(a[:count], v[:count], f.type))
            i += 1
        return pa.RecordBatch.from_arrays(arrays, schema=out_arrow)

    # -- unbounded keys: device open-addressing hash table -----------------
    # (ref agg_hash_map.rs; replaces the earlier sort-based table — a
    # multi-operand lax.sort program takes minutes to COMPILE on TPU and
    # the eager form blew the SF10 reduce-stage timeout outright)
    def _execute_sorted(self, partition: int) -> BatchIterator:
        slots = _pow2(config.ON_DEVICE_AGG_CAPACITY.get())
        kinds = tuple(rk for rk, _ok, _a in self._specs)
        carry = None
        skipping = False
        if self._prepare is not None:
            # prepare is INLINED into the step jit: one dispatch per batch
            # (a second program would pay another dispatch and
            # materialize kd/kv/ad/av between programs)
            stream = self.source_stream(partition)
            raw_step = _hash_chain_step_factory(self._prepare_key,
                                                self._prepare, kinds)
            step = lambda c, b: raw_step(c, *_source_inputs(b))  # noqa: E731
        else:
            stream = self.children[0].execute(partition)
            raw_step = _hash_step_jit(kinds)
            step = lambda c, b: raw_step(  # noqa: E731
                c, *self._device_inputs(b))
        key_dtypes = [e.data_type(self._in_schema).jnp_dtype()
                      for e, _n in self._group_exprs]
        rows_seen = 0
        for batch in stream:
            self._note_lane(1)
            if skipping:
                # batch-local dedup then pass through (downstream
                # re-merges) — ref AGG_TRIGGER_PARTIAL_SKIPPING,
                # agg_table.rs:108-122
                xla_stats.note_partial_agg_rows(batch.selected_count())
                yield from self._emit_hash(
                    self._insert_batch_local(step, key_dtypes, kinds,
                                             batch))
                continue
            rows_seen += batch.selected_count()
            if carry is None:
                carry = init_hash_carry(key_dtypes, kinds,
                                        self._acc_dtypes(), slots)
            new_carry, overflow, _ng, _rounds = step(carry, batch)
            while int(to_host(overflow)) > 0:
                if not self._grow:
                    new_carry = None
                    break
                # exact modes (final/merge/complete) DOUBLE and rehash —
                # the step is atomic, so carry is intact and lossless
                slots *= 2
                self.metrics.add("table_grown", 1)
                # ... and hands back the carry's own group count, which
                # is ready with the overflow just read
                lanes = rehash_width(int(to_host(_ng)),
                                     carry.owner.shape[0])
                bigger, re_ovf, _, _ = _rehash_jit(kinds, slots,
                                                   lanes)(carry)
                if int(to_host(re_ovf)) > 0:
                    continue  # rare probe clustering: double again
                carry = bigger
                new_carry, overflow, _ng, _rounds = step(carry, batch)
            if new_carry is None:
                skipping = True
                self.metrics.add("partial_skipped", 1)
                xla_stats.note_partial_agg_skip(rows_seen)
                if carry is not None:
                    yield from self._emit_hash(carry)
                    carry = None
                yield from self._emit_hash(
                    self._insert_batch_local(step, key_dtypes, kinds,
                                             batch))
                continue
            carry = new_carry
        if carry is not None:
            yield from self._emit_hash(carry)

    def _insert_batch_local(self, step, key_dtypes, kinds, batch):
        """One batch into a fresh table (grow-on-overflow; a batch has at
        most capacity distinct groups, so this terminates)."""
        slots = _pow2(2 * batch.capacity)
        while True:
            local = init_hash_carry(key_dtypes, kinds,
                                    self._acc_dtypes(), slots)
            out, overflow, _ng, _rounds = step(local, batch)
            if int(to_host(overflow)) == 0:
                return out
            slots *= 2

    def _emit_hash(self, carry, key_dicts=None) -> BatchIterator:
        with tracing.span("agg_drain", table="hash"):
            sel, count = _used_slots(carry.used)
            if count == 0:
                return
            rb = self._take_to_arrow(sel, count, carry.keys, carry.accs,
                                     carry.acc_valid, owner=carry.owner,
                                     key_dicts=key_dicts)
        yield from self._emit_chunks(rb)

    def _take_to_arrow(self, sel, count: int, keys, accs, acc_valid,
                       key_valid=None, owner=None,
                       key_dicts=None) -> pa.RecordBatch:
        """Rows `sel[:count]` of device columns in accumulator form (a
        hash table's used slots, a pass-through window's live rows),
        read back and laid out in the out-schema.  `keys` holds per key
        its lanes (`split_key`); the keys' validity comes as a column a
        key (`key_valid`) or as a table's `owner` lane.  Lanes are
        joined and the owner's null bits read on the host, over the rows
        read back."""
        keys_h, kvalid_h, accs_h, avalid_h = to_host(
            jax.tree_util.tree_map(
                lambda a: jnp.take(a, sel),
                (keys, owner if key_valid is None else list(key_valid),
                 accs, acc_valid)))
        if key_valid is None:
            kvalid_h = key_valid_lanes(kvalid_h, len(keys_h))
        return self._rows_to_arrow(
            [(join_key(lanes)[:count], kv[:count])
             for lanes, kv in zip(keys_h, kvalid_h)],
            [a[:count] for a in accs_h], [v[:count] for v in avalid_h],
            key_dicts=key_dicts)

    # -- shared emission ----------------------------------------------------
    def _device_inputs(self, batch: ColumnBatch):
        cap = batch.capacity
        kd, kv = [], []
        for e, _name in self._group_exprs:
            dv = e.evaluate(batch).to_device(cap)
            kd.append(_pad_lane(dv.data))
            kv.append(_pad_lane(dv.validity))
        ad, av = [], []
        for _rk, _ok, arg in self._specs:
            if arg is None:
                ad.append(None)
                av.append(None)
            else:
                dv = arg.evaluate(batch).to_device(cap)
                ad.append(_pad_lane(dv.data))
                av.append(_pad_lane(dv.validity))
        return (tuple(kd), tuple(kv), tuple(ad), tuple(av),
                _pad_lane(batch.row_mask()))

    def _emit_rows(self, keys, accs, avalid) -> BatchIterator:
        with tracing.span("agg_drain", table="host"):
            rb = self._rows_to_arrow(keys, accs, avalid)
        yield from self._emit_chunks(rb)

    def _emit_chunks(self, rb) -> BatchIterator:
        for chunk in self._emit_batches(rb):
            yield ColumnBatch.from_arrow(chunk)

    def _rows_to_arrow(self, keys, accs, avalid,
                       key_dicts=None) -> pa.RecordBatch:
        n = len(accs[0]) if accs else len(keys[0][0])
        arrays: List[pa.Array] = []
        out_arrow = self._out_schema.to_arrow()
        i = coded = 0
        for j, ((kd, kv), f) in enumerate(zip(keys, out_arrow)):
            d = key_dicts[j] if key_dicts is not None else None
            if d is not None:
                # dict-encoded key: the table folded int32 codes, and they
                # leave as codes, under the stream's final dictionary
                # snapshot (its prefix covers every code of every earlier
                # batch): the next operator takes a `DictColumn`
                idx = pa.array(np.where(kv, kd, 0).astype(np.int32),
                               pa.int32(), mask=~kv)
                arrays.append(pa.DictionaryArray.from_arrays(idx, d))
                coded += 1
            else:
                arrays.append(_to_arrow(kd, kv, f.type))
            i += 1
        for (_rk, out_kind, _arg), a, v in zip(self._specs, accs, avalid):
            f = out_arrow.field(i)
            if out_kind == "count":
                # count never nulls, whether counted or summed from accs
                arrays.append(_to_arrow(a, np.ones(n, dtype=bool), f.type))
            else:
                arrays.append(_to_arrow(a, v, f.type))
            i += 1
        if coded:
            xla_stats.note_dict(dict_rows_coded=n * coded)
            return pa.RecordBatch.from_arrays(arrays, names=out_arrow.names)
        return pa.RecordBatch.from_arrays(arrays, schema=out_arrow)


import functools

from blaze_tpu.batch import DeviceColumn


def _pad_lane(a):
    """Pad a host-resident (numpy) array up to its capacity bucket before
    it enters a jit program — unpadded lengths would compile one program
    per distinct tail-batch size; the geometric ladder bounds the set of
    static shapes every stage kernel ever sees (batch.bucket_capacity)."""
    if not isinstance(a, np.ndarray):
        return a
    from blaze_tpu.batch import bucket_capacity
    cap = bucket_capacity(a.shape[0])
    if cap == a.shape[0]:
        return a
    return np.pad(a, (0, cap - a.shape[0]))


def _source_inputs(batch: ColumnBatch):
    """Flatten a source batch for the jit step: device columns become
    (data, validity) pairs; host (string) columns pass as None — any
    expression touching one failed the pre-trace and never reaches here."""
    cols_flat = tuple((_pad_lane(c.data), _pad_lane(c.validity))
                      if isinstance(c, DeviceColumn) else None
                      for c in batch.columns)
    return cols_flat, _pad_lane(batch.row_mask())


def _make_prepare(source_schema: Schema, chain, group_exprs, specs):
    """The in-graph chain evaluator: rebuild the batch from traced arrays,
    run filter/project expression trees, emit key/agg device columns.
    Where the chain holds an Expand, `prepare` takes the projection
    list's index as a third (traced) argument: every list is evaluated
    (bare references, literals) and the one asked for is selected lane
    by lane, so one program serves all K."""
    def prepare(cols_flat, mask, which=None):
        cap = mask.shape[0]
        cols = [DeviceColumn(f.data_type, cf[0], cf[1])
                if cf is not None else None
                for f, cf in zip(source_schema, cols_flat)]
        batch = ColumnBatch(source_schema, cols, cap, selection=mask)
        for kind, preds, exprs, out_schema in chain:
            if kind == "filter":
                m = None
                for p in preds:
                    pm = p.evaluate(batch).as_mask(batch)
                    m = pm if m is None else (m & pm)
                if m is not None:
                    batch = batch.with_selection(m)
            elif kind == "expand":
                batch = ColumnBatch(
                    out_schema, _expanded_columns(batch, exprs, out_schema,
                                                  which), cap,
                    batch.selection)
            else:
                new_cols = [e.evaluate(batch).to_column(cap)
                            for e in exprs]
                batch = ColumnBatch(out_schema, new_cols, cap,
                                    batch.selection)
        kd, kv, ad, av = [], [], [], []
        for e, _name in group_exprs:
            v = e.evaluate(batch).to_device(cap)
            kd.append(v.data)
            kv.append(v.validity)
        for _rk, _ok, arg in specs:
            if arg is None:
                ad.append(None)
                av.append(None)
            else:
                v = arg.evaluate(batch).to_device(cap)
                ad.append(v.data)
                av.append(v.validity)
        return tuple(kd), tuple(kv), tuple(ad), tuple(av), batch.row_mask()
    return prepare


def _expanded_columns(batch: ColumnBatch, projections, out_schema: Schema,
                      which):
    """An Expand's output columns for projection list `which` (a traced
    int32): each list's value a column, the asked one selected.  A NULL
    literal of type utf8 is a code lane with no valid bit."""
    cap = batch.capacity
    out = []
    for j, f in enumerate(out_schema):
        store = jnp.int32 if f.data_type.id == TypeId.UTF8 \
            else f.data_type.jnp_dtype()
        datas, valids = [], []
        for p in projections:
            if _null_literal(p[j]):
                datas.append(jnp.zeros(cap, store))
                valids.append(jnp.zeros(cap, bool))
                continue
            v = p[j].evaluate(batch).to_device(cap)
            datas.append(jnp.asarray(v.data).astype(store))
            valids.append(jnp.asarray(v.validity))
        k = jnp.clip(which, 0, len(projections) - 1)
        out.append(DeviceColumn(f.data_type, jax.lax.select_n(k, *datas),
                                jax.lax.select_n(k, *valids)))
    return out


# key -> raw prepare fn | None when the chain doesn't trace
_PREPARE_CACHE: Dict = {}
_DENSE_STEP_CACHE: Dict = {}
_CACHE_LIMIT = 128  # bounded like _dense_step_factory's lru_cache


def _evict_if_full(cache: Dict) -> None:
    if len(cache) >= _CACHE_LIMIT:
        cache.pop(next(iter(cache)))  # FIFO: oldest compiled entry


def _utf8_ref_free(expr, schema: Schema) -> bool:
    """True when no BoundReference in the tree resolves to utf8 — inside
    a traced chain such a reference would see raw dictionary codes,
    whose comparison/order semantics are NOT string semantics."""
    if isinstance(expr, BoundReference):
        return schema[expr.index].data_type.id != TypeId.UTF8
    return all(_utf8_ref_free(c, schema) for c in expr.children())


def _dict_chain_safe(source_schema: Schema, chain, group_exprs,
                     specs) -> bool:
    """Static admission for tracing utf8 columns as int32 dictionary
    codes: codes may only PASS THROUGH (identity projections, bare group
    references) — never be computed on.  A filter, computed projection,
    or agg argument touching utf8 would trace successfully on codes but
    compute code-order semantics, so any such use rejects the chain and
    it keeps the eager/staged path."""
    sch = source_schema
    for kind, preds, exprs, out_schema in chain:
        if kind == "filter":
            if not all(_utf8_ref_free(p, sch) for p in preds):
                return False
        elif kind == "expand":
            # (`_expand_traceable` holds a utf8 column to bare references
            # and NULL literals)
            if not all(isinstance(e, BoundReference) or _null_literal(e)
                       or _utf8_ref_free(e, sch)
                       for p in exprs for e in p):
                return False
            sch = out_schema
        else:
            for e in exprs:
                if isinstance(e, BoundReference):
                    continue  # identity: codes flow through unchanged
                if not _utf8_ref_free(e, sch):
                    return False
            sch = out_schema
    for e, _n in group_exprs:
        if (e.data_type(sch).id == TypeId.UTF8
                and not isinstance(e, BoundReference)):
            return False
    for _rk, _ok, arg in specs:
        if arg is not None and not _utf8_ref_free(arg, sch):
            return False
    return True


def _dict_key_sources(agg):
    """Per-group-key SOURCE column indices for dict-encoded utf8 keys
    (None entries = plain fixed-width key), or None when the stage's
    var-width keys are not admissible as dictionary codes.  Each utf8
    key must be a bare reference whose chain lineage is identity
    projections all the way down — the source index is what the runtime
    loop watches for dictionaries."""
    if not config.ENCODING_DICT_ENABLE.get():
        return None
    out = []
    for e, _n in agg._group_exprs:
        dt = e.data_type(agg._in_schema)
        if dt.is_fixed_width:
            out.append(None)
            continue
        if dt.id != TypeId.UTF8 or not isinstance(e, BoundReference):
            return None
        idx = e.index
        for kind, _preds, exprs, _schema in reversed(agg._chain):
            if kind == "expand":
                # every list that has the column at all has to take it
                # from ONE input column: its dictionary is the key's
                refs = {p[idx].index for p in exprs
                        if isinstance(p[idx], BoundReference)}
                if len(refs) != 1 or not all(
                        isinstance(p[idx], BoundReference)
                        or _null_literal(p[idx]) for p in exprs):
                    return None
                idx = refs.pop()
                continue
            if kind != "project":
                continue
            pe = exprs[idx]
            if not isinstance(pe, BoundReference):
                return None
            idx = pe.index
        out.append(idx)
    return tuple(out)


def _prepare_factory(key, source_schema: Schema, chain, group_exprs,
                     specs):
    if key in _PREPARE_CACHE:
        return _PREPARE_CACHE[key]
    _evict_if_full(_PREPARE_CACHE)
    prepare = _make_prepare(source_schema, chain, group_exprs, specs)
    dict_ok = (config.ENCODING_DICT_ENABLE.get()
               and _dict_chain_safe(source_schema, chain, group_exprs,
                                    specs))

    def _slot(f):
        if f.data_type.is_fixed_width:
            return (jax.ShapeDtypeStruct((128,), f.data_type.jnp_dtype()),
                    jax.ShapeDtypeStruct((128,), jnp.bool_))
        if dict_ok and f.data_type.id == TypeId.UTF8:
            # dict-encoded utf8: the program only ever sees int32 codes
            # (the runtime loop guards that every utf8 source column
            # actually arrives as a DictColumn, falling back otherwise)
            return (jax.ShapeDtypeStruct((128,), jnp.int32),
                    jax.ShapeDtypeStruct((128,), jnp.bool_))
        return None

    try:
        fake_cols = tuple(_slot(f) for f in source_schema)
        which = ((jax.ShapeDtypeStruct((), jnp.int32),)
                 if chain_expand(chain) else ())
        jax.eval_shape(prepare, fake_cols,
                       jax.ShapeDtypeStruct((128,), jnp.bool_), *which)
        result = prepare  # consumers inline it into their own jit step
    except Exception:
        result = None  # strings / host-only exprs: stay on the eager path
    _PREPARE_CACHE[key] = result
    return result


def _batch_windows(stream, window: int, pad_tail: bool = False):
    """Up to `window` source batches a time as (cols_stacked, masks,
    batch_rows, count): every column, validity and mask stacked on a new
    leading axis by ONE device program a window (`_window_jit`), with the
    masks' selected lanes a real batch beside them.  With `pad_tail` a
    window of fewer batches is widened to `window` with masked-out ones,
    so every window of a stream shares its consumer's one jit signature
    (the batch-axis analog of the row-axis bucket ladder); `count` is the
    number of real batches either way."""
    buf = []
    for batch in stream:
        buf.append(_source_inputs(batch))
        if len(buf) >= window:
            yield _assemble_window(buf, len(buf))
            buf = []
    if buf:
        yield _assemble_window(buf, window if pad_tail else len(buf))


def _assemble_window(items, width: int):
    """The window program enters all of a window's batches at ONE
    capacity (its signature is capacity, batch count, column dtypes): an
    odd batch, a stream's tail or a short IPC batch, is padded with
    masked lanes to the window's capacity first, array by array, and
    counted (`padded`).  The pulls of the source stay outside the span."""
    cap = max(m.shape[0] for _c, m in items)
    short = [i for i, (_c, m) in enumerate(items) if m.shape[0] != cap]
    with tracing.span("loop_window", batches=len(items), padded=len(short)):
        for i in short:
            items[i] = jax.tree_util.tree_map(
                lambda a: jnp.pad(a, (0, cap - a.shape[0])), items[i])
        cols_stacked, masks, batch_rows = _window_jit(width)(tuple(items))
    xla_stats.note_stage_loop_window(fused=not short,
                                     chip=current_task().device_id)
    return cols_stacked, masks, batch_rows, len(items)


@functools.lru_cache(maxsize=128)
def _window_jit(width: int):
    """ONE program a window: `items` are the window's (cols_flat, mask)
    as they leave `_source_inputs`, all at one capacity; host (string)
    columns are None and stay None."""
    def window_impl(items):
        cols, masks = jax.tree_util.tree_map(
            lambda *arrays: jnp.pad(jnp.stack(arrays),
                                    ((0, width - len(items)), (0, 0))),
            *items)
        return cols, masks, jnp.sum(masks[:len(items)], axis=1)

    return meter_jit(window_impl, name="runtime.stage_loop_window")


def _dense_fold_factory(key, prepare, ranges, kinds, num_slots: int):
    """ONE XLA program folding a whole window of batches into the carry
    (fori_loop keeps the carry in place inside the program)."""
    skey = ("fold", key, ranges, kinds, num_slots)
    fold = _DENSE_STEP_CACHE.get(skey)
    if fold is not None:
        return fold
    _evict_if_full(_DENSE_STEP_CACHE)

    def fold_impl(carry, cols_stacked, masks):
        def body(b, c):
            cols_b = tuple(
                None if col is None else (col[0][b], col[1][b])
                for col in cols_stacked)
            kd, kv, ad, av, m = prepare(cols_b, masks[b])
            gid, _total = pack_dense_keys(list(zip(kd, kv)), list(ranges))
            return _scatter_into_carry(c, gid, kinds, ad, av, m,
                                       num_slots)
        return jax.lax.fori_loop(0, masks.shape[0], body, carry)

    fold = meter_jit(fold_impl, name="fused.dense_fold", donate_argnums=0)
    _DENSE_STEP_CACHE[skey] = fold
    return fold


def _mxu_fold_factory(key, prepare, ranges, meta: _MxuMeta,
                      use_pallas: bool):
    """ONE XLA program folding a window of batches through the MXU
    histogram kernel (kernels/mxu_agg.py).  The whole chain — filter/
    project, i32 group-id packing, fixed-point limb extraction, the
    matmul table update and the min/max scatters — lowers into a single
    dispatch; no 64-bit op survives into the hot loop except the one
    `value - offset` shift per aggregated column."""
    from blaze_tpu.kernels import mxu_agg
    from blaze_tpu.parallel.stage import pack_dense_keys_i32

    skey = ("mxu", key, ranges, meta, use_pallas)
    fold = _DENSE_STEP_CACHE.get(skey)
    if fold is not None:
        return fold
    _evict_if_full(_DENSE_STEP_CACHE)
    layout = meta.layout
    sentinel = jnp.int32(layout.num_slots)

    def fold_impl(carry, cols_stacked, masks):
        def body(b, c):
            table, mm_accs, ok = c
            cols_b = tuple(
                None if col is None else (col[0][b], col[1][b])
                for col in cols_stacked)
            kd, kv, ad, av, m = prepare(cols_b, masks[b])
            gid, _total = pack_dense_keys_i32(list(zip(kd, kv)),
                                              list(ranges))
            gid = jnp.where(m, gid, sentinel)
            valids = {}
            cents = {}
            for si, sp in enumerate(meta.specs):
                if sp.kind == "count_star":
                    continue
                v = av[si]
                valids[si] = v if v is not None else jnp.ones_like(m)
                if sp.kind == "count":
                    continue
                data = ad[si]
                if sp.is_float:
                    scale = float(sp.scale)
                    c = jnp.rint(data * scale)
                    # fixed-point verify WITHOUT division: XLA may fold
                    # `c / scale == data` into a reciprocal multiply
                    # (excess precision), breaking FP equality.  A
                    # genuine scaled value satisfies |v*s - rint(v*s)|
                    # <= |c| * 4.5e-16 (two roundings); 1e-12 leaves a
                    # 2000x margin while any dirt it admits perturbs
                    # the sum below 1e-12 relative — under the 1e-9
                    # result comparator by three orders.
                    exact = (jnp.abs(data * scale - c)
                             <= (jnp.abs(c) + 1.0) * 1e-12)
                    exact = exact | ~valids[si] | ~m
                    ok = ok & exact.all()
                    cents[si] = (c - sp.off).astype(jnp.int32)
                else:
                    cents[si] = (data.astype(jnp.int64) - sp.off
                                 ).astype(jnp.int32)
            arrays = []
            for akind, si in meta.arrays:
                if akind == "valid":
                    arrays.append((valids[si] & m).astype(jnp.int32))
                else:
                    arrays.append(jnp.where(valids[si], cents[si], 0))
            table = table + mxu_agg.window_table(
                gid, arrays, layout, force_ref=not use_pallas)
            new_mm = []
            for (is_min, si), acc in zip(meta.scatter, mm_accs):
                ident = jnp.int32((2**31 - 1) if is_min else -(2**31))
                val = jnp.where(valids[si] & m, cents[si], ident)
                if is_min:
                    acc = acc.at[gid].min(val, mode="drop")
                else:
                    acc = acc.at[gid].max(val, mode="drop")
                new_mm.append(acc)
            return (table, tuple(new_mm), ok)
        return jax.lax.fori_loop(0, masks.shape[0], body, carry)

    fold = meter_jit(fold_impl, name="fused.mxu_fold", donate_argnums=0)
    _DENSE_STEP_CACHE[skey] = fold
    return fold


@functools.lru_cache(maxsize=128)
def _dense_step_factory(ranges, kinds, num_slots: int):
    ranges = list(ranges)

    @partial(meter_jit, name="fused.dense_step", donate_argnums=0)
    def step(carry, key_data, key_valid, agg_data, agg_valid, mask):
        gid, _total = pack_dense_keys(list(zip(key_data, key_valid)),
                                      ranges)
        return _scatter_into_carry(carry, gid, kinds, agg_data, agg_valid,
                                   mask, num_slots)

    return step


def _scatter_into_carry(carry, gid, kinds, agg_data, agg_valid, mask,
                        num_slots: int):
    """In-place (donated) scatter update: O(batch) work per step instead of
    materializing and merging a full O(num_slots) per-batch table.  The
    accumulate switch itself is shared with the hash table
    (stage.scatter_accumulate) so null/identity semantics stay in one
    place."""
    accs, avalid, occupied = carry
    g = jnp.where(mask, gid, num_slots)  # masked rows drop out of range
    occupied = occupied.at[g].max(mask, mode="drop")
    specs = [(k, d, v) for k, d, v in zip(kinds, agg_data, agg_valid)]
    new_a, new_v = scatter_accumulate(g, specs, mask, accs, avalid)
    return (tuple(new_a), tuple(new_v), occupied)


def _init_carry(kinds, acc_dtypes, num_slots: int):
    accs, avalid = init_accumulators(kinds, acc_dtypes, num_slots)
    occupied = jnp.zeros(num_slots, dtype=bool)
    return (accs, avalid, occupied)


class _DictCapExceeded(Exception):
    """Dict-device code table would exceed maxSlots; caller falls back."""


def _global_dict_codes(arr: pa.Array, global_arr: Optional[pa.Array],
                       cap: int, sel: Optional[np.ndarray] = None):
    """Fused-stage wrapper over the SHARED incremental encoder
    (ops/agg/exec.py incremental_dict_codes): i32 codes for the
    pack_dense_keys_i32 tier, and filter-DESELECTED rows nulled out
    BEFORE encoding so they can neither grow the dictionary (spurious
    _DictCapExceeded on selective filters) nor inflate the code table
    capacity — the agg mask drops them from the reduction anyway."""
    from blaze_tpu.ops.agg.exec import incremental_dict_codes
    if sel is not None and not sel.all():
        import pyarrow.compute as pc
        arr = pc.if_else(pa.array(sel[:len(arr)]), arr,
                         pa.nulls(len(arr), arr.type))
    codes, valid, global_arr, _grew = incremental_dict_codes(
        arr, global_arr, cap)
    return codes.astype(np.int32), valid, global_arr


def _relayout_dict_table(carry, kinds, acc_dtypes, old_caps, new_caps):
    """Move a dict-code dense table to a larger layout after dictionary
    growth: decode occupied slots to per-key codes (pure stride math,
    host-side), recompute slot ids under the new strides, scatter accs
    1:1 (codes are unique per slot, no merging)."""
    accs, avalid, occupied = to_host(carry)
    occ = np.nonzero(occupied)[0]
    old_ranges = [(0, c - 1) for c in old_caps]
    decoded = unpack_dense_keys(occ, old_ranges, xp=np)
    new_total = 1
    strides = []
    for c in new_caps:
        strides.append(new_total)
        new_total *= (c + 1)
    new_slot = np.zeros(len(occ), dtype=np.int64)
    for (code, kvalid), c, stride in zip(decoded, new_caps, strides):
        k = np.where(kvalid, code, c)  # null slot is code==cap
        new_slot += k * stride
    n_accs, n_avalid = [], []
    from blaze_tpu.parallel.stage import init_accumulators
    fresh_accs, fresh_avalid = init_accumulators(kinds, acc_dtypes,
                                                 new_total)
    for fa, a in zip(fresh_accs, accs):
        na = asnp(fa).copy()
        na[new_slot] = a[occ]
        n_accs.append(na)
    for fv, v in zip(fresh_avalid, avalid):
        nv = asnp(fv).copy()
        nv[new_slot] = v[occ]
        n_avalid.append(nv)
    n_occ = np.zeros(new_total, dtype=bool)
    n_occ[new_slot] = True
    return to_device((tuple(n_accs), tuple(n_avalid), n_occ))


@functools.lru_cache(maxsize=64)
def _dict_dense_step(caps: tuple, kinds: tuple, capacity: int):
    """One jit program per (caps, kinds, capacity): pack the per-key
    codes into a dense group id and fold the batch into the carry —
    combine is elementwise (slots are stable), so the carry never
    round-trips to host between batches."""
    from blaze_tpu.parallel.stage import (_identity, dense_partial_agg,
                                          pack_dense_keys_i32)
    ranges = tuple((0, c - 1) for c in caps)

    @partial(meter_jit, name="fused.dict_device_step")
    def step(carry, kd, kv, ad, av, mask):
        accs, avalid, occupied = carry
        gid, total = pack_dense_keys_i32(list(zip(kd, kv)), ranges)
        specs = [(k, a, v) for k, a, v in zip(kinds, ad, av)]
        b_accs, b_avalid, b_occ = dense_partial_agg(
            gid.astype(jnp.int64), total, specs, mask)
        out_accs, out_avalid = [], []
        for kind, ca, cv, ba, bv in zip(kinds, accs, avalid,
                                        b_accs, b_avalid):
            if kind in ("sum", "count"):
                out_accs.append(ca + ba)  # empty batch slots are 0
            elif kind == "min":
                # dense_partial_agg ZEROES empty slots — re-identity
                # them or a later batch drags every min toward 0
                ba = jnp.where(bv, ba, _identity(ba.dtype, False))
                out_accs.append(jnp.minimum(ca, ba))
            else:  # max
                ba = jnp.where(bv, ba, _identity(ba.dtype, True))
                out_accs.append(jnp.maximum(ca, ba))
            out_avalid.append(cv | bv)
        return (tuple(out_accs), tuple(out_avalid), occupied | b_occ)

    return step


def _bucket(count: int, cap: int) -> int:
    """Next power of two >= count (min 1024), clamped to cap — keeps the
    device slice shapes to a handful of variants."""
    b = 1024
    while b < count:
        b <<= 1
    return min(b, cap)


def _used_slots(used):
    """(sel, count): the indices of a hash table's used slots, as a
    device array padded to the bucket of their count (padding points at
    slot 0), and their count.  The mask goes to the host bit-packed and
    the indices come back, because `jnp.nonzero(size=...)` is a
    scatter-add over EVERY slot: 65 ns a slot on a TPU v5e, 0.27 s for a
    2^22-slot table however few groups it holds (PERF.md section 6,
    PR 25)."""
    slots = used.shape[0]
    idx = np.flatnonzero(np.unpackbits(to_host(jnp.packbits(used)),
                                       count=slots))
    count = len(idx)
    if count == 0:
        return None, 0
    sel = np.zeros(_bucket(count, slots), dtype=np.int32)
    sel[:count] = idx
    return to_device(sel), count


def _pow2(n: int) -> int:
    return max(16, 1 << (int(n) - 1).bit_length())


@functools.lru_cache(maxsize=128)
def _hash_step_jit(kinds):
    """One compiled program per batch: probe-insert + scatter-accumulate
    into the device hash table (kernels in parallel/stage.py)."""
    def f(carry, kd, kv, ad, av, mask):
        specs = [(k, d, v) for k, d, v in zip(kinds, ad, av)]
        return hash_agg_step(carry, list(zip(kd, kv)), specs, mask)
    return meter_jit(f, name="fused.hash_step")


@functools.lru_cache(maxsize=128)
def _rehash_jit(kinds, new_slots: int, lanes: Optional[int] = None):
    """The table given re-inserted into `new_slots` slots, over `lanes`
    lanes where the caller holds its group count (rehash_width)."""
    return meter_jit(
        lambda c: rehash_carry(c, list(kinds), new_slots, lanes),
        name="fused.rehash")


def _hash_chain_step_factory(key, prepare, kinds):
    """Chain + probe-insert + accumulate as ONE compiled program."""
    skey = ("hash", key, kinds)
    step = _DENSE_STEP_CACHE.get(skey)
    if step is not None:
        return step
    _evict_if_full(_DENSE_STEP_CACHE)

    @partial(meter_jit, name="fused.hash_chain_step")
    def step(carry, cols_flat, mask):
        kd, kv, ad, av, m = prepare(cols_flat, mask)
        specs = [(k, d, v) for k, d, v in zip(kinds, ad, av)]
        return hash_agg_step(carry, list(zip(kd, kv)), specs, m)

    _DENSE_STEP_CACHE[skey] = step
    return step


def _to_arrow(data: np.ndarray, valid: np.ndarray,
              t: pa.DataType) -> pa.Array:
    if pa.types.is_decimal(t):
        # the lane holds the unscaled value: no cast (it would rescale),
        # and NULL past the type's bound (a sum's CheckOverflow)
        from blaze_tpu.batch import bounded_decimal
        return bounded_decimal(data, valid, t)
    arr = pa.array(data, mask=~np.asarray(valid, dtype=bool))
    if not arr.type.equals(t):
        arr = arr.cast(t, safe=False)
    return arr

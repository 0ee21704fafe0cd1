"""Whole-stage expression compilation: one XLA program per filter->project
chain, cached across batches, partitions and queries.

The eager evaluator (evaluator.py) dispatches one kernel launch per jnp op
— fine on numpy, dominated by per-dispatch overhead on a real device and
never fused by XLA.  Here an eligible expression chain is lowered into ONE
traced function: the referenced input columns enter as (data, validity)
tracer pairs, `PhysicalExpr.evaluate` runs unchanged inside the trace
(`xputil.xp_of` routes tracers to jnp), and XLA fuses + CSEs the whole
DAG.  Three program shapes cover the stage operators:

  filter          -> combined conjunct mask over capacity
  project         -> ((data, validity), ...) per output column
  filter_project  -> (mask, ((data, validity), ...))

The mask never compacts — callers AND it into `batch.selection` exactly
like the eager path (CoalesceStream compacts later), so fused and eager
outputs are bit-identical.

Programs live in a process-wide bounded LRU keyed by FINGERPRINT
(expression cache_keys + input dtype signature + semantics-relevant
config), so every partition-local evaluator instance resolves to the one
metered jit callable per fingerprint: jax's own signature cache handles
the per-bucket-capacity variants, and `bridge/xla_stats` sees a single
kernel name per program — per-partition instances cannot report false
recompiles.

Eligibility is a strict whitelist: fixed-width non-decimal dtypes through
BinaryExpr/Not/IsNull/IsNotNull/If/CaseWhen/Coalesce/InList/Cast only.
Host-only exprs (strings, UDFs, decimals), ANSI mode (its checks sync
`bool(any(...))`, which cannot trace) and batches without device columns
fall back to the eager evaluator per batch, counted via
`xla_stats.note_expr_dispatch`.
"""

from __future__ import annotations

import collections
import hashlib
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from blaze_tpu import config
from blaze_tpu.batch import (ColumnBatch, DeviceColumn, HostColumn,
                             bucket_capacity)
from blaze_tpu.exprs.base import BoundReference, Literal, PhysicalExpr
from blaze_tpu.exprs.binary import _ARITH, _BOOLEAN, _CMP, BinaryExpr
from blaze_tpu.exprs.cast import Cast, _device_supported
from blaze_tpu.exprs.conditional import (CaseWhen, Coalesce, If, InList,
                                         IsNotNull, IsNull, Not)
from blaze_tpu.exprs.evaluator import CachedExprsEvaluator, split_conjuncts
from blaze_tpu.kernels import decimal128 as d128
from blaze_tpu.schema import DataType, Schema, TypeId


# ---------------------------------------------------------------------------
# traceability
# ---------------------------------------------------------------------------

def _dtype_ok(dt: DataType) -> bool:
    # decimals route through host decimal_arith for exact Spark scale
    # semantics; var-width/nested/null are host-resident by construction
    return dt.is_fixed_width and dt.id != TypeId.DECIMAL


def _ref_dtype_ok(dt: DataType) -> bool:
    """Operand gate for BoundReference/BinaryExpr positions: with the
    decimal-encoding knob on, narrow decimals ride the int lanes as
    scaled integers (the op-level checks below still require exactness
    — equal-scale device math or the limb rescale for compares)."""
    if dt.id == TypeId.DECIMAL:
        # (one wider than 18 digits enters as its two limbs)
        return bool(config.ENCODING_DECIMAL_ENABLE.get())
    return _dtype_ok(dt)


def _limb_column(col) -> bool:
    return isinstance(col, HostColumn) \
        and col.dtype.id == TypeId.DECIMAL


def is_traceable(expr: PhysicalExpr, schema: Schema) -> bool:
    """True when `expr` evaluates as pure device array math over
    fixed-width columns — i.e. `evaluate` can run under a jit trace."""
    try:
        return _traceable(expr, schema)
    except Exception:
        return False


def _traceable(e: PhysicalExpr, schema: Schema) -> bool:
    if isinstance(e, BoundReference):
        return _ref_dtype_ok(schema[e.index].data_type)
    if isinstance(e, Literal):
        if e.dtype.id == TypeId.DECIMAL:
            return _ref_dtype_ok(e.dtype) and e.dtype.is_fixed_width
        return _dtype_ok(e.dtype)
    if isinstance(e, BinaryExpr):
        if e.op not in _ARITH and e.op not in _CMP and e.op not in _BOOLEAN:
            return False
        lt, rt = e._child_types(schema)
        if not (_ref_dtype_ok(lt) and _ref_dtype_ok(rt)):
            return False
        if TypeId.DECIMAL in (lt.id, rt.id):
            # only the ops whose device math is exact may trace: equal-
            # scale compares/+- and narrow products on the unscaled ints,
            # compares and a multiply by a constant through the limbs, a
            # narrow decimal beside a double as a double.  Everything
            # else routes decimal_arith's host path, which cannot trace.
            dec = e._decimal_types(lt, rt)
            if dec is None:
                if not (e._decimal_beside_float(lt, rt)
                        and lt.is_fixed_width and rt.is_fixed_width):
                    return False
            elif not (e._decimal_device_ok(*dec)
                      and lt.is_fixed_width and rt.is_fixed_width) \
                    and e._decimal_limb_plan(*dec) is None:
                return False
        return _traceable(e.left, schema) and _traceable(e.right, schema)
    if isinstance(e, (Not, IsNull, IsNotNull)):
        return _traceable(e.child, schema)
    if isinstance(e, (If, CaseWhen, Coalesce)):
        if not _dtype_ok(e.data_type(schema)):
            return False
        return all(_traceable(c, schema) for c in e.children())
    if isinstance(e, InList):
        return _dtype_ok(e.child.data_type(schema)) and \
            _traceable(e.child, schema)
    if isinstance(e, Cast):  # covers TryCast
        src = e.child.data_type(schema)
        return _dtype_ok(src) and _dtype_ok(e.to) and \
            _device_supported(src, e.to) and _traceable(e.child, schema)
    return False


def eviction_reason(exprs: Sequence[PhysicalExpr],
                    schema: Schema) -> str:
    """Classify WHY a chain left the device lanes, by the first
    referenced column dtype the gates reject: 'string' / 'decimal' /
    'other'.  The per-column accounting behind host_evictions_* — a
    string column merely present in the schema no longer brands the
    whole stage, only chains that actually reference one."""
    for i in _collect_refs(list(exprs)):
        dt = schema[i].data_type
        if dt.id in (TypeId.UTF8, TypeId.BINARY):
            return "string"
        if dt.id == TypeId.DECIMAL:
            return "decimal"
    return "other"


def _note_host_eviction(exprs: Sequence[PhysicalExpr],
                        schema: Schema) -> None:
    from blaze_tpu.bridge import xla_stats
    reason = eviction_reason(exprs, schema)
    xla_stats.note_encoding(**{f"host_evictions_{reason}": 1})


def _decimal_operand(exprs: Sequence[PhysicalExpr],
                     schema: Schema) -> Optional[DataType]:
    """The type of the first decimal a chain reads or computes."""
    def walk(e: PhysicalExpr):
        try:
            t = e.data_type(schema)
        except Exception:
            t = None
        if t is not None and t.id == TypeId.DECIMAL:
            return t
        for c in e.children():
            found = walk(c)
            if found is not None:
                return found
        return None

    for e in exprs:
        found = walk(e)
        if found is not None:
            return found
    return None


def _collect_refs(exprs: Sequence[PhysicalExpr]) -> List[int]:
    refs: set = set()

    def walk(e: PhysicalExpr):
        if isinstance(e, BoundReference):
            refs.add(e.index)
        for c in e.children():
            walk(c)

    for e in exprs:
        walk(e)
    return sorted(refs)


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------

def _schema_sig(schema: Schema) -> tuple:
    return tuple((f.data_type.id.value, f.data_type.precision,
                  f.data_type.scale) for f in schema)


def program_fingerprint(mode: str, filters: Sequence[PhysicalExpr],
                        projections: Sequence[PhysicalExpr],
                        in_schema: Schema) -> tuple:
    """Hashable identity of a compiled program: what it computes (the
    expression cache_keys), over what (input dtype signature), and under
    which semantics-relevant config (donation changes jit buffers)."""
    return (mode,
            tuple(f.cache_key() for f in filters),
            tuple(p.cache_key() for p in projections),
            _schema_sig(in_schema),
            bool(config.EXPR_DONATE.get()),
            # encoding knobs change what the trace computes (limb
            # compares, scaled-int decimal operands): new setting ->
            # new program, zero steady-state recompiles within one
            # (where the input has a decimal column: a chain over none
            # computes the same either way, and keeps its name)
            bool(config.ENCODING_DECIMAL_ENABLE.get())
            and any(f.data_type.id == TypeId.DECIMAL for f in in_schema),
            bool(config.ENCODING_DICT_ENABLE.get()))


# ---------------------------------------------------------------------------
# the compiled program
# ---------------------------------------------------------------------------

class ExprProgram:
    """One metered jit callable for a (filters, projections) chain over a
    fixed input schema.  Shared process-wide via `get_program`; jax's
    signature cache holds the per-bucket-capacity executables."""

    def __init__(self, mode: str, filters: Sequence[PhysicalExpr],
                 projections: Sequence[PhysicalExpr], in_schema: Schema,
                 fingerprint: tuple):
        from blaze_tpu.bridge import xla_stats
        self.mode = mode
        self.filters = list(filters)
        self.projections = list(projections)
        self.in_schema = in_schema
        self.fingerprint = fingerprint
        self.ref_idx = _collect_refs(self.filters + self.projections)
        digest = hashlib.blake2s(repr(fingerprint).encode()).hexdigest()[:12]
        self.name = f"expr_program_{digest}"
        jit_kwargs = {}
        if config.EXPR_DONATE.get():
            jit_kwargs["donate_argnums"] = tuple(
                range(2 * len(self.ref_idx)))
        self._fn = xla_stats.meter_jit(self._traced, name=self.name,
                                       **jit_kwargs)

    # -- traced body --------------------------------------------------------
    def _traced(self, *flat):
        """flat = (data, validity) per referenced column, in ref_idx
        order.  Runs only while XLA traces; rebuilds a ColumnBatch view
        over the tracers so `PhysicalExpr.evaluate` runs unchanged."""
        cap = flat[0].shape[0]
        ref_pos = {idx: 2 * k for k, idx in enumerate(self.ref_idx)}
        cols: List[Optional[DeviceColumn]] = []
        for i, f in enumerate(self.in_schema):
            p = ref_pos.get(i)
            if p is None:
                cols.append(None)  # never read: ref_idx covers all exprs
            else:
                cols.append(DeviceColumn(f.data_type, flat[p], flat[p + 1]))
        batch = ColumnBatch(self.in_schema, cols, cap)
        mask = None
        for f in self.filters:
            m = f.evaluate(batch).as_mask(batch)
            mask = m if mask is None else (mask & m)
        pairs = tuple((v.data, v.validity) for v in
                      (p.evaluate(batch) for p in self.projections))
        if self.mode == "filter":
            return mask
        if self.mode == "project":
            return pairs
        return mask, pairs

    # -- dispatch -----------------------------------------------------------
    def _gather(self, batch: ColumnBatch):
        """Flatten + bucket-pad the referenced columns.  Host-resident
        batches carry unpadded numpy buffers (capacity == num_rows); the
        pad keeps the program's static-shape universe on the ladder —
        one compile per (program, rung), same policy as the fused-stage
        jit entry (plan/fused.py _pad_lane)."""
        cap = batch.capacity
        pcap = bucket_capacity(cap)
        flat = []
        for i in self.ref_idx:
            col = batch.columns[i]
            if not isinstance(col, DeviceColumn):
                # a decimal wider than 18 digits: its two limbs
                flat.extend(d128.host_limbs(col.array, pcap))
                continue
            for a in (col.data, col.validity):
                if pcap != cap and isinstance(a, np.ndarray):
                    a = np.pad(a, (0, pcap - a.shape[0]))
                flat.append(a)
        return flat, cap

    def batch_ok(self, batch: ColumnBatch) -> bool:
        return all(isinstance(batch.columns[i], DeviceColumn)
                   or _limb_column(batch.columns[i])
                   for i in self.ref_idx)

    def run_filter(self, batch: ColumnBatch) -> ColumnBatch:
        from blaze_tpu.bridge import xla_stats
        flat, cap = self._gather(batch)
        mask = self._fn(*flat)[:cap]
        if batch._xp() is np:
            mask = np.asarray(mask)
        xla_stats.note_expr_dispatch(fused=1)
        return batch.with_selection(mask)

    def run_project(self, batch: ColumnBatch, out_schema: Schema
                    ) -> ColumnBatch:
        from blaze_tpu.bridge import xla_stats
        flat, cap = self._gather(batch)
        pairs = self._fn(*flat)
        xla_stats.note_expr_dispatch(fused=1)
        return self._assemble(batch, out_schema, pairs, batch.selection)

    def run_filter_project(self, batch: ColumnBatch, out_schema: Schema
                           ) -> ColumnBatch:
        from blaze_tpu.bridge import xla_stats
        flat, cap = self._gather(batch)
        mask, pairs = self._fn(*flat)
        xla_stats.note_expr_dispatch(fused=1)
        sel = batch.selection
        if sel is not None and sel.shape[0] < mask.shape[0]:
            sel = np.pad(np.asarray(sel), (0, mask.shape[0] - sel.shape[0]))
        sel = mask if sel is None else (sel & mask)
        return self._assemble(batch, out_schema, pairs, sel)

    def _assemble(self, batch: ColumnBatch, out_schema: Schema, pairs,
                  selection) -> ColumnBatch:
        # outputs are padded to the bucket; the result batch adopts that
        # capacity uniformly (selection re-pads with False = deselected)
        to_np = batch._xp() is np
        cols = []
        pcap = pairs[0][0].shape[0] if pairs else batch.capacity
        for f, (data, valid) in zip(out_schema, pairs):
            if d128.is_limbs(data):
                # a limb lane: a decimal wider than 18 digits leaves the
                # program as the host column its type is
                from blaze_tpu.xputil import to_host
                data, valid = to_host((data, valid))
                cols.append(HostColumn(f.data_type, d128.limbs_to_arrow(
                    data, valid, batch.num_rows, f.data_type.to_arrow())))
                continue
            if to_np:
                data, valid = np.asarray(data), np.asarray(valid)
            cols.append(DeviceColumn(f.data_type, data, valid))
        if selection is not None and selection.shape[0] < pcap:
            selection = np.pad(np.asarray(selection),
                               (0, pcap - selection.shape[0]))
        if to_np and selection is not None:
            selection = np.asarray(selection)
        return ColumnBatch(out_schema, cols, batch.num_rows, selection)


# ---------------------------------------------------------------------------
# the process-wide program cache
# ---------------------------------------------------------------------------

_cache_lock = threading.Lock()
_programs: "collections.OrderedDict[tuple, ExprProgram]" = \
    collections.OrderedDict()


def get_program(mode: str, filters: Sequence[PhysicalExpr],
                projections: Sequence[PhysicalExpr],
                in_schema: Schema) -> ExprProgram:
    """Resolve (or build) the shared program for this chain.  Bounded
    LRU: evicting a program drops its jit executables with it."""
    from blaze_tpu.bridge import xla_stats
    fp = program_fingerprint(mode, filters, projections, in_schema)
    with _cache_lock:
        prog = _programs.get(fp)
        if prog is not None:
            _programs.move_to_end(fp)
            xla_stats.note_expr_program(cache_hit=True)
            return prog
        prog = ExprProgram(mode, filters, projections, in_schema, fp)
        _programs[fp] = prog
        xla_stats.note_expr_program(built=True)
        limit = max(1, config.EXPR_CACHE_SIZE.get())
        while len(_programs) > limit:
            _programs.popitem(last=False)
            xla_stats.note_expr_program(evicted=True)
        return prog


def program_cache_info() -> dict:
    with _cache_lock:
        return {"size": len(_programs),
                "names": [p.name for p in _programs.values()]}


def clear_program_cache() -> None:
    with _cache_lock:
        _programs.clear()


# ---------------------------------------------------------------------------
# the evaluator ops/basic.py uses
# ---------------------------------------------------------------------------

class FusedExprsEvaluator:
    """Drop-in for CachedExprsEvaluator that routes eligible batches
    through the shared compiled program and everything else through the
    eager evaluator.  Eligibility and the program resolve once per
    operator partition (construction); per-batch checks are cheap."""

    def __init__(self, filters: Sequence[PhysicalExpr] = (),
                 projections: Sequence[PhysicalExpr] = (),
                 in_schema: Optional[Schema] = None):
        # conjuncts split unconditionally here: AND of all masks equals
        # sequential narrowing (device exprs compute over all rows), and
        # the canonical split keeps fingerprints stable across
        # FORCE_SHORT_CIRCUIT_AND_OR settings
        self.filters: List[PhysicalExpr] = []
        for f in filters:
            self.filters.extend(split_conjuncts(f))
        self.projections = list(projections)
        self._eager = CachedExprsEvaluator(filters=filters,
                                           projections=projections)
        self._filter_prog: Optional[ExprProgram] = None
        self._project_prog: Optional[ExprProgram] = None
        self._fp_prog: Optional[ExprProgram] = None
        # the type of the first decimal operand in the chain, if any
        self._decimal: Optional[DataType] = None if in_schema is None \
            else _decimal_operand(self.filters + self.projections, in_schema)
        if in_schema is None or not config.EXPR_FUSE.get() or \
                config.ANSI_ENABLED.get():
            return
        # literal-only chains reference no columns: the jit would have no
        # array argument to carry the batch shape — leave those eager
        filters_ok = bool(self.filters) and all(
            is_traceable(f, in_schema) for f in self.filters) and \
            bool(_collect_refs(self.filters))
        projections_ok = bool(self.projections) and all(
            is_traceable(p, in_schema) for p in self.projections) and \
            bool(_collect_refs(self.projections))
        if (self.filters and not filters_ok) or \
                (self.projections and not projections_ok):
            _note_host_eviction(self.filters + self.projections, in_schema)
        # resolve only the program the operator shape will dispatch:
        # Filter -> filter, Project -> project, FilterProject -> the
        # combined program (or the filter half when projections are
        # host-only, fused mask + eager project)
        if filters_ok and projections_ok:
            self._fp_prog = get_program(
                "filter_project", self.filters, self.projections, in_schema)
        elif filters_ok:
            self._filter_prog = get_program(
                "filter", self.filters, (), in_schema)
        elif projections_ok and not self.filters:
            self._project_prog = get_program(
                "project", (), self.projections, in_schema)

    @staticmethod
    def _fusion_on() -> bool:
        return config.EXPR_FUSE.get() and not config.ANSI_ENABLED.get()

    def _usable(self, prog: Optional[ExprProgram], batch: ColumnBatch
                ) -> bool:
        return prog is not None and self._fusion_on() and \
            prog.batch_ok(batch)

    def _in_program(self) -> None:
        if self._decimal is not None:
            from blaze_tpu.bridge import xla_stats
            xla_stats.note_decimal(expr_device_batches=1)

    def _eagerly(self, batch: ColumnBatch, op: str):
        """Accounts one batch of the eager evaluator; where the chain has
        a decimal operand, the real interval of its work on the host."""
        from contextlib import nullcontext
        from blaze_tpu.bridge import xla_stats
        from blaze_tpu.exprs.decimal_arith import host_interval
        xla_stats.note_expr_dispatch(eager=1)
        if self._decimal is None:
            return nullcontext()
        xla_stats.note_decimal(expr_host_batches=1)
        return host_interval(op, batch.num_rows, self._decimal)

    def filter(self, batch: ColumnBatch) -> ColumnBatch:
        if self._usable(self._filter_prog, batch):
            self._in_program()
            return self._filter_prog.run_filter(batch)
        with self._eagerly(batch, "filter"):
            return self._eager.filter(batch)

    def project(self, batch: ColumnBatch, out_schema: Schema) -> ColumnBatch:
        if self._usable(self._project_prog, batch):
            self._in_program()
            return self._project_prog.run_project(batch, out_schema)
        with self._eagerly(batch, "project"):
            return self._eager.project(batch, out_schema)

    def filter_project(self, batch: ColumnBatch, out_schema: Schema
                       ) -> ColumnBatch:
        if self._usable(self._fp_prog, batch):
            self._in_program()
            return self._fp_prog.run_filter_project(batch, out_schema)
        if self._usable(self._filter_prog, batch):
            # traceable filter + host-only projection: fuse the mask,
            # project eagerly on the narrowed batch
            filtered = self._filter_prog.run_filter(batch)
            return self._eager.project(filtered, out_schema)
        with self._eagerly(batch, "filter_project"):
            return self._eager.filter_project(batch, out_schema)


def fused_filter(predicates: Sequence[PhysicalExpr], schema: Schema
                 ) -> Optional[Callable[[ColumnBatch], ColumnBatch]]:
    """Scan-embedded filtering: a callable applying the fused predicate
    mask to a decoded batch, or None when the chain is not fully
    traceable (the scan then leaves filtering to the operator above).
    Runs inside the scan's prefetch transform, i.e. on the IO worker
    thread — the mask computation overlaps downstream compute."""
    from blaze_tpu.bridge import xla_stats
    if not predicates or not FusedExprsEvaluator._fusion_on():
        return None
    conjuncts: List[PhysicalExpr] = []
    for p in predicates:
        conjuncts.extend(split_conjuncts(p))
    if not all(is_traceable(c, schema) for c in conjuncts) or \
            not _collect_refs(conjuncts):
        return None
    prog = get_program("filter", conjuncts, (), schema)

    def apply(batch: ColumnBatch) -> ColumnBatch:
        if FusedExprsEvaluator._fusion_on() and prog.batch_ok(batch):
            return prog.run_filter(batch)
        xla_stats.note_expr_dispatch(eager=1)
        return batch

    return apply

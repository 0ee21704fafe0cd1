"""Column-pruning optimizer pass (Catalyst ColumnPruning analog).

The reference receives plans already pruned by Catalyst — every
FileSourceScanExec carries a projection of exactly the referenced columns
(ref NativeParquetScanBase.scala:55).  Plans authored directly against
the engine IR (tests, itest queries, embedded users) scan full schemas,
which on wide TPC-DS facts wastes most of the parquet decode + host
conversion.  This pass recovers Catalyst's behavior engine-side:

  * REQUIRED column indices flow DOWN the decoded ExecutionPlan tree
    (each operator contributes the columns its own expressions touch);
  * at an unpartitioned ParquetScanExec the projection narrows to the
    required columns (schema order);
  * an old->new index MAPPING flows back UP through schema-preserving
    operators (filter/sort/limit/exchange), and every affected
    expression rewrites its BoundReferences; joins merge the two child
    mappings with the right-side offset shift.

Operators not modeled here act as barriers: their subtree is revisited
with required=None, so pruning still happens beneath nested
projections/aggregations deeper down.  Gated by `auron.tpu.columnPruning`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

from blaze_tpu.exprs.base import BoundReference, PhysicalExpr

Mapping = Optional[Dict[int, int]]


# ---------------------------------------------------------------------------
# expression helpers
# ---------------------------------------------------------------------------

def expr_columns(e: PhysicalExpr, out: Set[int]) -> None:
    if isinstance(e, BoundReference):
        out.add(e.index)
    for c in e.children():
        expr_columns(c, out)


def _rewrite_value(v, mapping: Dict[int, int]):
    if isinstance(v, BoundReference):
        return BoundReference(mapping[v.index], v.name)
    if isinstance(v, PhysicalExpr):
        return rewrite_expr(v, mapping)
    if isinstance(v, tuple):
        return tuple(_rewrite_value(x, mapping) for x in v)
    if isinstance(v, list):
        return [_rewrite_value(x, mapping) for x in v]
    return v


def rewrite_expr(e: PhysicalExpr, mapping: Dict[int, int]) -> PhysicalExpr:
    """Rebuild an expression tree with BoundReference indices remapped.
    Expressions are frozen dataclasses whose PhysicalExpr-valued fields
    (possibly inside tuples/lists) are rewritten recursively."""
    if isinstance(e, BoundReference):
        return BoundReference(mapping[e.index], e.name)
    if not dataclasses.is_dataclass(e):
        # non-dataclass expression: bail out conservatively by signaling
        # the caller (treated as a barrier upstream)
        raise _Unprunable()
    changes = {}
    for f in dataclasses.fields(e):
        old = getattr(e, f.name)
        new = _rewrite_value(old, mapping)
        if new is not old:
            changes[f.name] = new
    return dataclasses.replace(e, **changes) if changes else e


class _Unprunable(Exception):
    pass


def _cols_of(exprs: Sequence[PhysicalExpr]) -> Set[int]:
    out: Set[int] = set()
    for e in exprs:
        expr_columns(e, out)
    return out


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def prune_columns(plan):
    """Entry point: returns the (possibly rebuilt) plan."""
    from blaze_tpu import config
    if not config.COLUMN_PRUNING_ENABLE.get():
        return plan
    try:
        new, _mapping = _prune(plan, None)
        return new
    except _Unprunable:
        return plan


def _identity(n: int) -> Dict[int, int]:
    return {i: i for i in range(n)}


def _prune(plan, required: Optional[Set[int]]):
    """Returns (new_plan, mapping).  `mapping` is None when the node's
    output columns are unchanged; otherwise old->new indices (parents
    MUST rewrite their expressions through it)."""
    from blaze_tpu.ops.agg.exec import AggExec
    from blaze_tpu.ops.basic import (DebugExec, FilterExec,
                                     FilterProjectExec, LimitExec,
                                     ProjectExec)
    from blaze_tpu.ops.joins.exec import BaseJoinExec
    from blaze_tpu.ops.scan import ParquetScanExec
    from blaze_tpu.ops.sort import SortExec

    if isinstance(plan, ParquetScanExec):
        return _prune_scan(plan, required)

    if isinstance(plan, FilterExec):
        child_req = (None if required is None else
                     required | _cols_of(plan._predicates))
        child, m = _prune(plan.children[0], child_req)
        if m is None:
            plan.children[0] = child
            return plan, None
        preds = [rewrite_expr(p, m) for p in plan._predicates]
        return FilterExec(child, preds), m

    if isinstance(plan, (DebugExec, LimitExec)):
        child, m = _prune(plan.children[0], required)
        plan.children[0] = child
        if m is None:
            return plan, None
        return plan, m  # schema passthrough; parent rewrites

    if isinstance(plan, SortExec):
        child_req = (None if required is None else
                     required | _cols_of([s[0] for s in plan._specs]))
        child, m = _prune(plan.children[0], child_req)
        if m is None:
            plan.children[0] = child
            return plan, None
        specs = [(rewrite_expr(e, m), d, nf) for e, d, nf in plan._specs]
        return SortExec(child, specs, fetch=getattr(plan, "_fetch",
                                                    None)), m

    if isinstance(plan, (ProjectExec, FilterProjectExec)):
        exprs = list(plan._exprs)
        preds = list(getattr(plan, "_predicates", []) or [])
        child_req = _cols_of(exprs + preds)
        child, m = _prune(plan.children[0], child_req)
        if m is None:
            plan.children[0] = child
            return plan, None
        new_exprs = [rewrite_expr(e, m) for e in exprs]
        names = [f.name for f in plan.schema]
        if isinstance(plan, FilterProjectExec):
            new_preds = [rewrite_expr(p, m) for p in preds]
            return (FilterProjectExec(child, new_preds, new_exprs,
                                      names), None)
        return ProjectExec(child, new_exprs, names), None

    if isinstance(plan, AggExec):
        group_exprs = [e for e, _n in plan._group_exprs]
        arg_exprs: List[PhysicalExpr] = []
        for fn, _mode, _name in plan._aggs:
            arg_exprs.extend(fn.children)
        child_req = _cols_of(group_exprs + arg_exprs)
        child, m = _prune(plan.children[0], child_req)
        if m is None:
            plan.children[0] = child
            return plan, None
        groups = [(rewrite_expr(e, m), n) for e, n in plan._group_exprs]
        aggs = []
        for fn, mode, name in plan._aggs:
            new_fn = type(fn).__new__(type(fn))
            new_fn.__dict__.update(fn.__dict__)
            new_fn.children = [rewrite_expr(c, m) for c in fn.children]
            aggs.append((new_fn, mode, name))
        return (type(plan)(child, groups, aggs,
                           exec_mode=plan._exec_mode), None)

    if isinstance(plan, BaseJoinExec):
        n_left = len(plan.children[0].schema)
        n_right = len(plan.children[1].schema)
        jt = plan.join_type.value
        if required is None or jt not in ("inner", "left", "right",
                                          "full"):
            # semi/anti/existence output shapes differ; recurse with
            # key+filter requirements only when output is one side —
            # keep it simple: no pruning through those joins, but still
            # descend for nested opportunities
            plan.children[0] = _prune(plan.children[0], None)[0]
            plan.children[1] = _prune(plan.children[1], None)[0]
            return plan, None
        filt_cols: Set[int] = set()
        if plan.join_filter is not None:
            expr_columns(plan.join_filter, filt_cols)
        left_req = ({i for i in required if i < n_left} |
                    _cols_of(plan.left_keys) |
                    {i for i in filt_cols if i < n_left})
        right_req = ({i - n_left for i in required if i >= n_left} |
                     _cols_of(plan.right_keys) |
                     {i - n_left for i in filt_cols if i >= n_left})
        lchild, lm = _prune(plan.children[0], left_req)
        rchild, rm = _prune(plan.children[1], right_req)
        from blaze_tpu.ops.joins.exec import BroadcastJoinExec
        if isinstance(plan, BroadcastJoinExec):
            if plan.build_side == "right":
                rchild, rm = _narrow_build(rchild, rm, right_req, n_right)
            else:
                lchild, lm = _narrow_build(lchild, lm, left_req, n_left)
        if lm is None and rm is None:
            plan.children[0] = lchild
            plan.children[1] = rchild
            return plan, None
        lm = lm or _identity(n_left)
        rm = rm or _identity(n_right)
        new_n_left = len(lchild.schema)
        joined = dict(lm)
        joined.update({n_left + o: new_n_left + n
                       for o, n in rm.items()})
        kwargs = dict(join_type=plan.join_type,
                      build_side=plan.build_side,
                      join_filter=(rewrite_expr(plan.join_filter, joined)
                                   if plan.join_filter is not None
                                   else None),
                      existence_col=plan._existence_col,
                      null_aware_anti=plan.null_aware_anti)
        if isinstance(plan, BroadcastJoinExec):
            kwargs["broadcast_id"] = plan._broadcast_id
        new = type(plan)(lchild, rchild,
                         [rewrite_expr(k, lm) for k in plan.left_keys],
                         [rewrite_expr(k, rm) for k in plan.right_keys],
                         **kwargs)
        return new, joined

    # unknown operator: barrier — no requirements cross it, but nested
    # subtrees still get their own chances
    for i, child in enumerate(plan.children):
        plan.children[i] = _prune(child, None)[0]
    return plan, None


def _narrow_build(child, mapping: Mapping, required: Set[int], n: int):
    """A broadcast join's build side under a projection of the columns
    the join and its parents read, where the pass could not narrow it to
    them beneath (its scan lies under an aggregation or another join):
    the build side is collected whole and kept, by every task of the
    stage, and a build side of fixed-width columns alone stays on the
    device (ops/joins/exec.py `_probes_on_device`).  (child, mapping) as
    `_prune` returns them.  A build-map stage keeps its own schema: its
    map is made from it."""
    from blaze_tpu.ops.basic import ProjectExec
    from blaze_tpu.ops.joins.exec import BuildHashMapExec
    if len(required) >= len(child.schema) \
            or isinstance(child, BuildHashMapExec):
        return child, mapping
    at = mapping or _identity(n)
    kept = sorted(required)
    exprs = [BoundReference(at[i], child.schema[at[i]].name) for i in kept]
    return (ProjectExec(child, exprs, [e.name for e in exprs]),
            {old: new for new, old in enumerate(kept)})


def _prune_scan(scan, required: Optional[Set[int]]):
    from blaze_tpu.ops.scan import ParquetScanExec
    if required is None or scan._partition_schema is not None:
        return scan, None
    n = len(scan.schema)
    req = sorted(i for i in required if i < n)
    if len(req) == n:
        return scan, None
    names = [scan.schema[i].name for i in req]
    new = ParquetScanExec(scan._file_schema, scan._file_groups,
                          projection=names,
                          predicate=scan._predicate,
                          batch_rows=scan._batch_rows)
    mapping = {old: new_i for new_i, old in enumerate(req)}
    return new, mapping


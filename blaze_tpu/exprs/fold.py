"""Bind-time constant folding: literal-only subexpressions -> Literal.

The reference folds constants on the Spark side before the plan crosses
the wire (Catalyst ConstantFolding), so its native planner rarely sees
`lit(2) * lit(3)`.  Directly-authored IR (tests, the itest
builders) has no such pass — and every unfolded constant subtree widens
the expression fingerprint of the whole-stage program cache
(exprs/program.py), so identical queries written with equivalent
constants would compile distinct XLA programs.

Folding EVALUATES the literal-only node over a 1-row empty-schema batch
(the numpy path — no device work, no jit) and replaces it with a
`Literal` of the computed value.  Anything that raises during the probe
(ANSI cast errors, unsupported host ops, decimal edge cases) is left
unfolded so the error surfaces at run time exactly as before.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np

from blaze_tpu.batch import ColumnBatch
from blaze_tpu.exprs.base import ColVal, Literal, PhysicalExpr
from blaze_tpu.exprs.binary import BinaryExpr
from blaze_tpu.exprs.cast import Cast
from blaze_tpu.exprs.conditional import (CaseWhen, Coalesce, If, InList,
                                         IsNotNull, IsNull, Not)
from blaze_tpu.exprs.strings import Like, RLike, StringPredicate
from blaze_tpu.schema import Schema, TypeId

#: Pure value-level expression classes: output depends only on child
#: values, so evaluating them over literal children at bind time is
#: exactly the run-time result.  Stateful/contextual exprs (Rand,
#: RowNum, subqueries, UDFs...) and anything not listed stay unfolded.
_FOLDABLE = (BinaryExpr, Not, IsNull, IsNotNull, If, CaseWhen, Coalesce,
             InList, Cast, Like, RLike, StringPredicate)

_EMPTY_SCHEMA = Schema([])


def map_exprs(e: PhysicalExpr, fn: Callable[[PhysicalExpr], PhysicalExpr]
              ) -> PhysicalExpr:
    """Rebuild `e` with `fn` applied to each direct PhysicalExpr child
    (covers plain fields, tuples and lists of exprs, and CaseWhen's
    tuple-of-pairs).  Raises TypeError for non-dataclass exprs."""
    if not dataclasses.is_dataclass(e):
        raise TypeError(f"cannot rebuild non-dataclass expr {type(e).__name__}")

    def one(v):
        if isinstance(v, PhysicalExpr):
            return fn(v)
        if isinstance(v, tuple):
            return tuple(one(x) for x in v)
        if isinstance(v, list):
            return [one(x) for x in v]
        return v

    changes = {}
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        nv = one(v)
        if nv is not v:
            changes[f.name] = nv
    return dataclasses.replace(e, **changes) if changes else e


def _scalar_of(v: ColVal):
    """Row 0 of an evaluated literal-only expression as a Python value."""
    if v.is_device:
        if not bool(np.asarray(v.validity)[0]):
            return None
        return np.asarray(v.data)[0].item()
    if len(v.array) == 0:
        return None
    return v.array[0].as_py()


def fold_node(e: PhysicalExpr, schema: Optional[Schema] = None
              ) -> PhysicalExpr:
    """Fold THIS node if it is a pure expr over all-Literal children.
    Applied at each level of the plan decoder (children fold first by
    recursion), one bottom-up pass falls out for free."""
    from blaze_tpu import config
    if not isinstance(e, _FOLDABLE):
        return e
    if not config.EXPR_CONST_FOLD.get():
        return e
    children = e.children()
    if not children or not all(isinstance(c, Literal) for c in children):
        return e
    try:
        dtype = e.data_type(schema if schema is not None else _EMPTY_SCHEMA)
        if dtype.id == TypeId.DECIMAL or \
                any(c.dtype.id == TypeId.DECIMAL for c in children):
            # decimal literal values round-trip through scale-sensitive
            # representations; not worth folding
            return e
        probe = ColumnBatch(_EMPTY_SCHEMA, [], 1)
        return Literal(_scalar_of(e.evaluate(probe)), dtype)
    except Exception:
        return e


def fold_constants(e: PhysicalExpr, schema: Optional[Schema] = None
                   ) -> PhysicalExpr:
    """Recursive bottom-up fold (direct-API entry; the plan decoder gets
    the same effect by calling fold_node per decoded level)."""
    if e.children():
        try:
            e = map_exprs(e, lambda c: fold_constants(c, schema))
        except TypeError:
            return e
    return fold_node(e, schema)

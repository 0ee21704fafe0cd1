"""Spark decimal binary arithmetic.

Parity: the reference's native decimal kernels + Catalyst's
DecimalPrecision result-type rules (ref datafusion-ext-exprs decimal
paths; Spark `DecimalPrecision.adjustPrecisionScale`,
`CheckOverflow` non-ANSI overflow -> NULL):

  add/sub : s = max(s1,s2);           p = max(p1-s1, p2-s2) + s + 1
  mul     : s = s1+s2;                p = p1+p2+1
  div     : s = max(6, s1+p2+1);      p = p1-s1+s2+s
  mod     : s = max(s1,s2);           p = min(p1-s1, p2-s2) + s
  cap at 38 with allowPrecisionLoss scale reduction (minScale 6).

Values are exact `decimal.Decimal` host-side (the same representation
the cast path uses); a mis-scaled unscaled-int64 add on device was the
failure mode this replaces.  Division/modulo by zero -> NULL (non-ANSI);
results beyond the capped precision -> NULL (CheckOverflow).
"""

from __future__ import annotations

import decimal as pydec
from typing import Optional

import pyarrow as pa

from blaze_tpu.schema import BOOL, DataType, TypeId

_MAX_PRECISION = 38
_MIN_DIVISION_SCALE = 6

#: integral operand widths as decimal (Spark DecimalType.forType —
#: which has NO DateType entry; date comparisons stay on device)
_INT_AS_DECIMAL = {"int8": (3, 0), "int16": (5, 0), "int32": (10, 0),
                   "int64": (20, 0), "bool": (1, 0)}


def host_interval(op: str, rows: int, t: DataType):
    """The real interval of decimal work outside a device program: an
    eager expression batch, an eager aggregation's batch, an average's
    final quotient (`t`: the first decimal operand's type)."""
    from blaze_tpu.bridge import tracing
    return tracing.span("decimal_host_eval", op=op, rows=int(rows),
                        precision=t.precision, scale=t.scale)


def as_decimal_type(t: DataType) -> Optional[DataType]:
    if t.id == TypeId.DECIMAL:
        return t
    ps = _INT_AS_DECIMAL.get(t.id.value)
    if ps is None:
        return None
    return DataType(TypeId.DECIMAL, ps[0], ps[1])


def _adjust(p: int, s: int) -> DataType:
    """DecimalPrecision.adjustPrecisionScale (allowPrecisionLoss=true,
    the Spark default): cap precision at 38, sacrificing scale down to
    min(s, 6) before overflowing."""
    if p <= _MAX_PRECISION:
        return DataType(TypeId.DECIMAL, max(p, 1), s)
    int_digits = p - s
    min_scale = min(s, _MIN_DIVISION_SCALE)
    adj_scale = max(_MAX_PRECISION - int_digits, min_scale)
    return DataType(TypeId.DECIMAL, _MAX_PRECISION, adj_scale)


def result_type(op: str, lt: DataType, rt: DataType) -> DataType:
    p1, s1 = lt.precision, lt.scale
    p2, s2 = rt.precision, rt.scale
    if op in ("+", "-"):
        s = max(s1, s2)
        p = max(p1 - s1, p2 - s2) + s + 1
    elif op == "*":
        s = s1 + s2
        p = p1 + p2 + 1
    elif op == "/":
        s = max(_MIN_DIVISION_SCALE, s1 + p2 + 1)
        p = p1 - s1 + s2 + s
    elif op in ("%", "pmod"):
        s = max(s1, s2)
        p = min(p1 - s1, p2 - s2) + s
    else:
        raise TypeError(f"unsupported decimal op {op!r}")
    return _adjust(p, s)


def _to_pylist(cv, n: int, t: DataType):
    arr = cv.to_host(n)
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    out = []
    for x in arr:
        if not x.is_valid:
            out.append(None)
            continue
        v = x.as_py()
        out.append(v if isinstance(v, pydec.Decimal)
                   else pydec.Decimal(int(v)))
    return out


def evaluate(op: str, a_cv, b_cv, lt: DataType, rt: DataType, batch,
             ansi: Optional[bool] = None):
    """Exact decimal arithmetic / comparison over host values.
    Returns a host ColVal of the Spark result type (arith) or BOOL.
    ANSI mode raises DIVIDE_BY_ZERO / NUMERIC_VALUE_OUT_OF_RANGE for
    SELECTED rows instead of yielding null.  `ansi` overrides the
    session conf — try_* callers pass False EXPLICITLY rather than
    scoping the process-global config (worker threads share it)."""
    from blaze_tpu import config
    from blaze_tpu.exprs.base import ColVal
    n = batch.num_rows
    if ansi is None:
        ansi = config.ANSI_ENABLED.get()
    _selected = batch.is_selected
    av = _to_pylist(a_cv, n, lt)
    bv = _to_pylist(b_cv, n, rt)
    if op in ("==", "!=", "<", "<=", ">", ">=", "<=>"):
        out = []
        for x, y in zip(av, bv):
            if x is None or y is None:
                out.append((x is None and y is None) if op == "<=>"
                           else None)
                continue
            out.append({"==": x == y, "!=": x != y, "<": x < y,
                        "<=": x <= y, ">": x > y, ">=": x >= y,
                        "<=>": x == y}[op])
        return ColVal.host(BOOL, pa.array(out, type=pa.bool_()))
    rt_out = result_type(op, lt, rt)
    quant = pydec.Decimal(1).scaleb(-rt_out.scale)
    limit = 10 ** rt_out.precision
    out = []
    with pydec.localcontext() as ctx:
        ctx.prec = 76  # two full decimal128 operands
        for row, (x, y) in enumerate(zip(av, bv)):
            if x is None or y is None:
                out.append(None)
                continue
            if op in ("/", "%", "pmod") and y == 0:
                if ansi and _selected(row):
                    raise ValueError(
                        "[DIVIDE_BY_ZERO] decimal division by zero "
                        "(ANSI mode)")
                out.append(None)  # non-ANSI
                continue
            try:
                if op == "+":
                    r = x + y
                elif op == "-":
                    r = x - y
                elif op == "*":
                    r = x * y
                elif op == "/":
                    r = x / y
                elif op == "%":
                    r = x % y  # sign follows dividend (Java remainder)
                else:  # pmod
                    r = x % y
                    if r != 0 and (r < 0) != (y < 0):
                        r += y
                r = r.quantize(quant, rounding=pydec.ROUND_HALF_UP)
            except pydec.InvalidOperation:
                out.append(None)
                continue
            unscaled = int(r.scaleb(rt_out.scale))
            if abs(unscaled) >= limit:
                # CheckOverflow: beyond the capped precision
                if ansi and _selected(row):
                    raise ValueError(
                        "[NUMERIC_VALUE_OUT_OF_RANGE] decimal overflow "
                        f"at {rt_out.precision},{rt_out.scale} "
                        "(ANSI mode)")
                out.append(None)
            else:
                out.append(r)
    return ColVal.host(rt_out, pa.array(out, type=rt_out.to_arrow()))

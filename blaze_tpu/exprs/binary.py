"""Binary operators with Spark null semantics.

Parity: the proto binary-op surface (ref auron-planner/src/lib.rs:73
`from_proto_binary_op`: And/Or/Eq/NotEq/Lt/LtEq/Gt/GtEq/Plus/Minus/Multiply/
Divide/Modulo/BitwiseAnd/BitwiseOr/BitwiseXor/BitwiseShl/BitwiseShr) plus
Spark specifics the reference implements in datafusion-ext-*:

  * arithmetic on mismatched widths promotes like Spark (widest int wins,
    any float -> double math for int/float mixes follows jnp promotion);
  * `/ 0`, `% 0` -> NULL (non-ANSI Spark), including decimal;
  * AND/OR use Kleene three-valued logic;
  * comparisons on floats: NaN == NaN is FALSE under `=`, but `<=>`
    (null-safe eq, EqNullSafe) treats null==null as TRUE.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow.compute as pc

from blaze_tpu.batch import ColumnBatch
from blaze_tpu.exprs.base import ColVal, PhysicalExpr
from blaze_tpu.schema import BOOL, DataType, Schema, TypeId
from blaze_tpu.xputil import xp_of


def _both_valid(a: ColVal, b: ColVal) -> jax.Array:
    return a.validity & b.validity


def _promote(a: ColVal, b: ColVal):
    dt = jnp.promote_types(a.data.dtype, b.data.dtype)
    return a.data.astype(dt), b.data.astype(dt)


_ARITH = {"+", "-", "*", "/", "%", "pmod",
          "&", "|", "^", "<<", ">>"}
_CMP = {"==", "!=", "<", "<=", ">", ">=", "<=>"}
_BOOLEAN = {"and", "or"}


@dataclass(frozen=True, repr=False)
class BinaryExpr(PhysicalExpr):
    op: str
    left: PhysicalExpr
    right: PhysicalExpr

    def children(self):
        return (self.left, self.right)

    def _decimal_types(self, lt: DataType, rt: DataType):
        """(lt, rt) as decimal types when this op is decimal-valued:
        either side DECIMAL, the other decimal-coercible (ints), and an
        arithmetic/compare op.  Float operands promote the whole op to
        f64 like Spark, so they never reach here.  Takes the child
        types ALREADY computed — recomputing them here made type
        derivation exponential in arithmetic-chain depth."""
        if self.op in _BOOLEAN or self.op in ("&", "|", "^", "<<", ">>"):
            return None
        if TypeId.DECIMAL not in (lt.id, rt.id):
            return None
        if lt.is_floating or rt.is_floating:
            return None
        from blaze_tpu.exprs import decimal_arith as D
        ldt, rdt = D.as_decimal_type(lt), D.as_decimal_type(rt)
        if ldt is None or rdt is None:
            return None
        return ldt, rdt

    def data_type(self, schema: Schema) -> DataType:
        lt, rt = self._child_types(schema)
        if self.op in _CMP or self.op in _BOOLEAN:
            return BOOL
        dec = self._decimal_types(lt, rt)
        if dec is not None:
            from blaze_tpu.exprs import decimal_arith as D
            return D.result_type(self.op, *dec)
        if self._decimal_beside_float(lt, rt):
            from blaze_tpu.schema import FLOAT64
            return FLOAT64
        if not lt.is_fixed_width:
            return lt
        if not rt.is_fixed_width:
            return rt
        dt = jnp.promote_types(lt.jnp_dtype(), rt.jnp_dtype())
        from blaze_tpu import schema as S
        m = {"bool": S.BOOL, "int8": S.INT8, "int16": S.INT16, "int32": S.INT32,
             "int64": S.INT64, "float32": S.FLOAT32, "float64": S.FLOAT64}
        return m[jnp.dtype(dt).name]

    def _child_types(self, schema: Schema):
        """(lt, rt) memoized per schema identity: evaluate() runs per
        BATCH, and re-deriving child types walks the whole subtree —
        quadratic in expression depth without the cache."""
        cached = getattr(self, "_ct_cache", None)
        if cached is not None and cached[0] is schema:
            return cached[1], cached[2]
        lt = self.left.data_type(schema)
        rt = self.right.data_type(schema)
        # hold the schema itself, not id(schema): a freed schema's id can
        # be reused by a NEW schema at the same address, silently serving
        # stale types (keeping the reference alive also pins the id)
        object.__setattr__(self, "_ct_cache", (schema, lt, rt))
        return lt, rt

    def _decimal_beside_float(self, lt: DataType, rt: DataType) -> bool:
        """A decimal beside a float or a double: Spark casts the decimal
        to double and the operation is a double's."""
        return (self.op in _ARITH or self.op in _CMP) and (
            (lt.id == TypeId.DECIMAL and rt.is_floating)
            or (rt.id == TypeId.DECIMAL and lt.is_floating))

    @staticmethod
    def _decimal_as_double(cv: ColVal, batch: ColumnBatch) -> ColVal:
        """The value a decimal has as a double: its unscaled integer
        over 10^scale (both exact in a double up to 2^53 and 10^22, so
        the quotient is the correctly rounded one, as Spark's
        Decimal.toDouble gives)."""
        from blaze_tpu.schema import FLOAT64
        if cv.dtype.id != TypeId.DECIMAL:
            return cv
        if not cv.is_device:
            import pyarrow as pa
            return ColVal.host(FLOAT64, cv.array.cast(pa.float64())) \
                .to_device(batch.capacity)
        xp = xp_of(cv.data)
        data = cv.data.astype(xp.float64) / np.float64(10 ** cv.dtype.scale)
        return ColVal(FLOAT64, data=data, validity=cv.validity,
                      literal=cv.literal)

    def _decimal_limbs(self, a: ColVal, b: ColVal, ldt: DataType,
                       rdt: DataType, batch: ColumnBatch
                       ) -> Optional[ColVal]:
        """The decimal operations that are exact in two int64 limbs
        (kernels/decimal128.py) and so can run inside a device program:
        a comparison at the larger scale, and a multiply by a constant.
        None where this is neither, or an operand has no lane."""
        plan = self._decimal_limb_plan(ldt, rdt)
        if plan is None:
            return None
        from blaze_tpu.kernels import decimal128 as d128
        cap = batch.capacity
        forms = []
        for cv in (a, b):
            if cv.is_device:
                forms.append(cv)
            elif cv.dtype.id == TypeId.DECIMAL \
                    and not cv.dtype.is_fixed_width:
                data, valid = d128.host_limbs(cv.array, cap)
                forms.append(ColVal(cv.dtype, data=data, validity=valid))
            elif cv.dtype.is_fixed_width:
                forms.append(cv.to_device(cap))
            else:
                return None
        fa, fb = forms
        if plan == "cmp":
            return d128.compare_colvals(self.op, fa, fb, ldt, rdt)
        from blaze_tpu.exprs import decimal_arith as D
        out_t = D.result_type("*", ldt, rdt)
        m = self._constant_factor()
        lit_is_right = m[0] == "r"
        out = d128.multiply_colvals(fa if lit_is_right else fb,
                                    fb if lit_is_right else fa, m[1], out_t)
        if d128.is_limbs(out.data) \
                and not isinstance(out.data, jax.core.Tracer):
            # outside a trace a wider decimal is a host column
            return ColVal.host(out_t, d128.limbs_to_arrow(
                out.data, out.validity, batch.num_rows, out_t.to_arrow()))
        return out

    def _constant_factor(self):
        """("l" | "r", unscaled value) where one side of a multiply is
        a non-NULL decimal or integer literal under 2^31, else None."""
        from blaze_tpu.exprs.base import Literal
        for side, e in (("r", self.right), ("l", self.left)):
            if not isinstance(e, Literal) or e.value is None:
                continue
            if e.dtype.id == TypeId.DECIMAL:
                v = e.unscaled()
            elif e.dtype.is_integer and not isinstance(e.value, bool):
                v = int(e.value)
            else:
                continue
            if abs(v) < (1 << 31):
                return side, v
        return None

    def _decimal_limb_plan(self, ldt: DataType, rdt: DataType
                           ) -> Optional[str]:
        """"cmp": both sides fit 38 digits at the larger scale; "mul":
        one side is a constant under 2^31 and the product's type keeps
        every digit (p1 + p2 + 1 <= 38)."""
        from blaze_tpu import config
        if not config.ENCODING_DECIMAL_ENABLE.get():
            return None
        if self.op in _CMP:
            s = max(ldt.scale, rdt.scale)
            if max(ldt.precision + s - ldt.scale,
                   rdt.precision + s - rdt.scale) <= 38:
                return "cmp"
            return None
        if self.op == "*" and ldt.precision + rdt.precision + 1 <= 38 \
                and self._constant_factor() is not None:
            return "mul"
        return None

    def evaluate(self, batch: ColumnBatch) -> ColVal:
        a = self.left.evaluate(batch)
        b = self.right.evaluate(batch)
        lt, rt = self._child_types(batch.schema)
        dec = self._decimal_types(lt, rt)
        if dec is None and self._decimal_beside_float(lt, rt):
            a = self._decimal_as_double(a, batch)
            b = self._decimal_as_double(b, batch)
        if dec is not None and not (self._decimal_device_ok(*dec)
                                    and a.is_device and b.is_device):
            # a comparison of unequal scales, a wider operand, a multiply
            # by a constant: through the two-limb int128 kernels, exact
            # (no rounding; a product of in-bound operands cannot pass its
            # type) and traceable, so these keep their stage
            # device-resident
            out = self._decimal_limbs(a, b, dec[0], dec[1], batch)
            if out is not None:
                return out
            # exact Spark decimal semantics (scale alignment, result
            # widening, overflow -> null) — the unscaled-int64 device
            # math below is only correct for EQUAL scales within p<=18,
            # and HOST-form operands (wide intermediates) must not fall
            # into _evaluate_host, which has no arithmetic
            from blaze_tpu.exprs import decimal_arith as D
            return D.evaluate(self.op, a, b, dec[0], dec[1], batch)
        if a.dictionary is not None or b.dictionary is not None:
            # dict-encoded utf8 operands: the generic device paths below
            # would compare raw CODES (meaningless across dictionaries) —
            # equality answers on codes when dictionaries line up,
            # everything else decodes per-expression
            dv = self._evaluate_dict(batch, a, b)
            if dv is not None:
                return dv
            return self._evaluate_host(batch, a, b)
        if not a.is_device or not b.is_device:
            return self._evaluate_host(batch, a, b)
        if self.op in _BOOLEAN:
            return _kleene(self.op, a, b)
        if self.op in _CMP:
            return _compare(self.op, a, b)
        if dec is not None and self.op == "*":
            # two int32 lanes (p <= 9 each) would multiply in int32
            a = ColVal(a.dtype, data=a.data.astype(jnp.int64),
                       validity=a.validity)
        out = _arith(self.op, a, b, self.data_type(batch.schema))
        if self.op in ("+", "-", "*", "/", "%", "pmod"):
            from blaze_tpu import config
            if config.ANSI_ENABLED.get():
                self._ansi_arith_check(batch, a, b, out)
        return out

    def _ansi_arith_check(self, batch, a: ColVal, b: ColVal,
                          out: ColVal) -> None:
        """ANSI mode: integral division/modulo by zero raises
        DIVIDE_BY_ZERO and integer overflow raises ARITHMETIC_OVERFLOW
        instead of null/wrap.  Mirrors Cast._ansi_check_device: only
        SELECTED rows can raise (filters set the mask without
        compacting), one device sync per op, zero cost with ANSI off."""
        from blaze_tpu.xputil import xp_of
        mask = batch.row_mask()
        both = _both_valid(a, b) & mask
        xp = xp_of(a.data, b.data)
        if self.op in ("/", "%", "pmod"):
            # the non-ANSI kernel encodes /0 as result-null for every
            # numeric type (DivModLike); a row that was valid on both
            # inputs but null in the output divided by zero
            lost = both & ~out.validity
            if bool(xp_of(lost).any(lost)):
                raise ValueError(
                    "[DIVIDE_BY_ZERO] division by zero (ANSI mode; "
                    "use try_divide or nullif to tolerate)")
        if jnp.issubdtype(out.data.dtype, jnp.integer) and \
                self.op in ("+", "-", "*", "/"):
            x = a.data.astype(out.data.dtype)
            y = b.data.astype(out.data.dtype)
            r = out.data
            int_min = jnp.iinfo(out.data.dtype).min
            if self.op == "+":
                ovf = ((x > 0) & (y > 0) & (r < 0)) | \
                      ((x < 0) & (y < 0) & (r >= 0))
            elif self.op == "-":
                ovf = ((x >= 0) & (y < 0) & (r < 0)) | \
                      ((x < 0) & (y > 0) & (r >= 0))
            elif self.op == "*":
                # verify by division (exact where y != 0); the verify
                # division ITSELF wraps for INT_MIN // -1, so that pair
                # needs an explicit clause
                y_safe = xp.where(y == 0, xp.ones_like(y), y)
                with np.errstate(all="ignore"):  # wrap IS the signal
                    ovf = ((y != 0) & (r // y_safe != x)) | \
                          ((x == int_min) & (y == -1)) | \
                          ((y == int_min) & (x == -1))
            else:
                # integral division overflows ONLY at INT_MIN / -1
                # (wraps to a perfectly valid INT_MIN)
                ovf = (x == int_min) & (y == -1)
            ovf = ovf & both
            if bool(xp_of(ovf).any(ovf)):
                raise ValueError(
                    "[ARITHMETIC_OVERFLOW] integer overflow (ANSI "
                    "mode; use try_add/try_multiply to tolerate)")

    def _decimal_device_ok(self, ldt: DataType, rdt: DataType) -> bool:
        """Equal-scale narrow decimals keep the vectorized device path:
        comparisons and +/- on the unscaled int64s are exact there (the
        +/- result precision max(p1,p2)+1 <= 18 cannot overflow int64).
        Everything else (mixed scales, *, /, %, wide) needs the exact
        host path."""
        if self.op == "*":
            # |x * y| < 10^(p1 + p2): inside int64, and inside its type
            return ldt.precision + rdt.precision + 1 <= 18
        if ldt.scale != rdt.scale:
            return False
        if max(ldt.precision, rdt.precision) > 18:
            return False
        if self.op in _CMP:
            return True
        return self.op in ("+", "-") and \
            max(ldt.precision, rdt.precision) + 1 <= 18

    def _evaluate_dict(self, batch: ColumnBatch, a: ColVal,
                       b: ColVal) -> Optional[ColVal]:
        """Equality family over dict-encoded codes, or None to decode.
        Codes are first-seen order, so ONLY (in)equality is answerable
        on them; ordering comparisons decode."""
        if self.op not in ("==", "!=", "<=>"):
            return None
        import pyarrow as pa
        from blaze_tpu.xputil import asnp
        if a.dictionary is not None and b.dictionary is not None:
            xp = xp_of(a.data, b.data)
            if a.dictionary is b.dictionary or \
                    a.dictionary.equals(b.dictionary):
                bcodes = b.data
            else:
                pos = pc.index_in(b.dictionary, value_set=a.dictionary)
                remap = np.asarray(pos.fill_null(-1)).astype(np.int64)
                bcodes = remap[asnp(b.data)] if xp is np \
                    else jnp.asarray(remap)[b.data]
            return self._dict_eq(a.data, a.validity, bcodes, b.validity)
        # dict vs utf8 literal: look the literal up in the dictionary
        # once — absent literals compare against code -1 (never matches)
        d_side, o_side = (a, b) if a.dictionary is not None else (b, a)
        if not (o_side.literal and o_side.array is not None):
            return None
        val = o_side.array[0].as_py() if len(o_side.array) else None
        if val is None:
            return None  # null literal: host path has the semantics
        pos = pc.index_in(pa.array([val]), value_set=d_side.dictionary)[0]
        code = -1 if not pos.is_valid else pos.as_py()
        xp = xp_of(d_side.data)
        lit_codes = xp.full(d_side.data.shape[0], code,
                            dtype=d_side.data.dtype)
        lit_valid = xp.ones(d_side.data.shape[0], dtype=bool)
        if d_side is a:
            return self._dict_eq(a.data, a.validity, lit_codes, lit_valid)
        return self._dict_eq(lit_codes, lit_valid, b.data, b.validity)

    def _dict_eq(self, ac, av, bc, bv) -> ColVal:
        xp = xp_of(ac, bc)
        eq = ac.astype(xp.int64) == bc.astype(xp.int64)
        if self.op == "<=>":
            data = (eq & av & bv) | (~av & ~bv)
            return ColVal.device(BOOL, data)
        valid = av & bv
        data = (eq if self.op == "==" else ~eq) & valid
        return ColVal(BOOL, data=data, validity=valid)

    def _evaluate_host(self, batch: ColumnBatch, a: ColVal, b: ColVal) -> ColVal:
        """String/binary comparisons, Kleene and/or over mixed host/device
        operands, and concat run on host Arrow arrays."""
        n = batch.num_rows
        ha, hb = a.to_host(n), b.to_host(n)
        fns: dict[str, Callable] = {
            "==": pc.equal, "!=": pc.not_equal, "<": pc.less,
            "<=": pc.less_equal, ">": pc.greater, ">=": pc.greater_equal,
        }
        if self.op in fns:
            return ColVal.host(BOOL, fns[self.op](ha, hb))
        if self.op in ("and", "or"):
            # one side host (e.g. an in_list over strings), one device:
            # three-valued logic via Arrow's Kleene kernels
            f = pc.and_kleene if self.op == "and" else pc.or_kleene
            return ColVal.host(BOOL, f(ha.cast("bool"), hb.cast("bool")))
        if self.op == "<=>":
            eq = pc.equal(ha, hb)
            both_null = pc.and_(pc.is_null(ha), pc.is_null(hb))
            return ColVal.host(BOOL, pc.or_kleene(eq.fill_null(False),
                                                  both_null).fill_null(False))
        if self.op == "+":  # string concat via binary `+` is not Spark; but
            raise TypeError("use Concat for strings")
        raise TypeError(f"unsupported host binary op {self.op}")

    def cache_key(self):
        return ("bin", self.op, self.left.cache_key(), self.right.cache_key())

    def __repr__(self):
        return f"({self.left!r} {self.op} {self.right!r})"


def _kleene(op: str, a: ColVal, b: ColVal) -> ColVal:
    """Three-valued AND/OR (Spark/SQL semantics)."""
    av, bv = a.validity, b.validity
    ad = a.data.astype(bool)
    bd = b.data.astype(bool)
    if op == "and":
        data = ad & bd
        # known when: both valid, or either side is a known False
        valid = (av & bv) | (av & ~ad) | (bv & ~bd)
    else:
        data = ad | bd
        valid = (av & bv) | (av & ad) | (bv & bd)
    return ColVal(BOOL, data=data & valid, validity=valid)


def _compare(op: str, a: ColVal, b: ColVal) -> ColVal:
    x, y = _promote(a, b)
    if op == "<=>":
        from blaze_tpu.kernels.compare import null_aware_eq
        # Spark's EqNullSafe: null<=>null TRUE; NaN<=>NaN TRUE (same as
        # grouping equality, ref eq_comparator.rs)
        eq = null_aware_eq(x, a.validity, y, b.validity)
        return ColVal.device(BOOL, eq)
    import operator as _op
    fns = {"==": _op.eq, "!=": _op.ne, "<": _op.lt,
           "<=": _op.le, ">": _op.gt, ">=": _op.ge}
    data = fns[op](x, y)
    valid = _both_valid(a, b)
    return ColVal(BOOL, data=data & valid, validity=valid)


def _arith(op: str, a: ColVal, b: ColVal, out_dtype: DataType) -> ColVal:
    x, y = _promote(a, b)
    xp = xp_of(x, y)
    valid = _both_valid(a, b)
    is_float = jnp.issubdtype(x.dtype, jnp.floating)

    if op in ("/", "%", "pmod"):
        # Spark DivModLike: divisor == 0 -> NULL for ALL numeric types in
        # non-ANSI mode — double division by literal zero is NULL, not
        # ±Inf (Inf/NaN only arise from non-zero divisor math below)
        zero = y == 0
        valid = valid & ~zero
        y = xp.where(zero, xp.ones_like(y), y)  # avoid div-by-zero traps

    with np.errstate(all="ignore"):  # numpy path: inf/nan silently, like XLA
        if op == "+":
            data = x + y
        elif op == "-":
            data = x - y
        elif op == "*":
            data = x * y
        elif op == "/":
            if is_float:
                data = x / y      # zero divisors already nulled above
            elif a.dtype.id == TypeId.DECIMAL or b.dtype.id == TypeId.DECIMAL:
                data = x // y     # decimal div handled by planner rescale
            else:
                # Spark integral `/` yields double; `div` yields long.  The
                # planner emits Cast around this node; here: truncating int
                # div like Java (toward zero), not floor
                q = xp.abs(x) // xp.abs(y)
                data = xp.where((x < 0) ^ (y < 0), -q, q)
        elif op == "%":
            if is_float:
                data = xp.where(xp.isfinite(y) | xp.isnan(y),
                                x - xp.trunc(x / y) * y, x)
                data = xp.where(xp.isinf(y) & xp.isfinite(x), x, data)
            else:
                # Java %: sign follows dividend
                r = xp.abs(x) % xp.abs(y)
                data = xp.where(x < 0, -r, r)
        elif op == "pmod":
            # Spark Pmod: r = x % y (Java %: truncated, sign follows
            # dividend); if r < 0 then (r + y) % y else r — NOT
            # floor-mod: a non-negative remainder stays put even for a
            # negative divisor (pmod(7,-3)=1, pmod(-7,-3)=-1).
            # xp.fmod IS Java % for both ints and floats: it handles
            # inf divisors (fmod(5.0, inf)=5.0) and INT64_MIN (where an
            # abs()-based form overflows) — both corpus/review-verified.
            r = xp.fmod(x, y)
            data = xp.where(r < 0, xp.fmod(r + y, y), r)
        elif op == "&":
            data = x & y
        elif op == "|":
            data = x | y
        elif op == "^":
            data = x ^ y
        elif op == "<<":
            data = x << (y.astype(x.dtype) & (x.dtype.itemsize * 8 - 1))
        elif op == ">>":
            data = x >> (y.astype(x.dtype) & (x.dtype.itemsize * 8 - 1))
        else:
            raise TypeError(f"unknown arithmetic op {op}")

    if out_dtype.is_fixed_width and data.dtype != out_dtype.jnp_dtype():
        data = data.astype(out_dtype.jnp_dtype())
    data = xp.where(valid, data, xp.zeros_like(data))
    return ColVal(out_dtype, data=data, validity=valid)


# convenience builders --------------------------------------------------------

def and_(l: PhysicalExpr, r: PhysicalExpr) -> BinaryExpr:
    return BinaryExpr("and", l, r)


def or_(l: PhysicalExpr, r: PhysicalExpr) -> BinaryExpr:
    return BinaryExpr("or", l, r)


def eq(l: PhysicalExpr, r: PhysicalExpr) -> BinaryExpr:
    return BinaryExpr("==", l, r)

"""Physical expression base: evaluation over ColumnBatch.

The reference evaluates DataFusion `PhysicalExpr` trees decoded from proto
(ref: native-engine/auron-planner/src/planner.rs:924 try_parse_physical_expr;
Spark-specific exprs in datafusion-ext-exprs/).  Here an expression evaluates
a `ColumnBatch` to a `ColVal` — either a device (data, validity) pair over the
batch's static capacity, or a host Arrow array of exactly num_rows for
variable-width results.  Device results are what jit'd stage functions
compose; host results cross to device only through dedicated kernels.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import numpy as np
import pyarrow as pa

from blaze_tpu.batch import ColumnBatch, DeviceColumn, HostColumn
from blaze_tpu.schema import DataType, Schema, TypeId
from blaze_tpu import xputil
from blaze_tpu.xputil import xp_of


@dataclass
class ColVal:
    """Evaluated column value: device (padded) or host (exact-length) form."""

    dtype: DataType
    data: Optional[jax.Array] = None      # (capacity,) when device-form
    validity: Optional[jax.Array] = None  # (capacity,) bool when device-form
    array: Optional[pa.Array] = None      # num_rows-long when host-form
    literal: bool = False                 # evaluated from a Literal expr
    # dictionary-encoded utf8 (batch.DictColumn): `data` holds int32
    # codes into this host value array; to_host decodes, so eager host
    # expressions stay correct per-expression without knowing about it
    dictionary: Optional[pa.Array] = None

    @property
    def is_device(self) -> bool:
        return self.data is not None

    # -- constructors -------------------------------------------------------
    @staticmethod
    def device(dtype: DataType, data: jax.Array,
               validity: Optional[jax.Array] = None) -> "ColVal":
        if validity is None:
            validity = xp_of(data).ones(data.shape[0], dtype=bool)
        return ColVal(dtype, data=data, validity=validity)

    @staticmethod
    def host(dtype: DataType, array: pa.Array) -> "ColVal":
        return ColVal(dtype, array=array)

    @staticmethod
    def from_column(col, capacity: int) -> "ColVal":
        from blaze_tpu.batch import DictColumn
        if isinstance(col, DictColumn):
            return ColVal(col.dtype, data=col.data, validity=col.validity,
                          dictionary=col.dictionary)
        if isinstance(col, DeviceColumn):
            return ColVal(col.dtype, data=col.data, validity=col.validity)
        return ColVal(col.dtype, array=col.array)

    # -- conversions --------------------------------------------------------
    def to_host(self, num_rows: int) -> pa.Array:
        """Materialize as an Arrow array of num_rows (device sync)."""
        if self.array is not None:
            return self.array.slice(0, num_rows)
        if self.dictionary is not None:
            from blaze_tpu.batch import DictColumn
            return DictColumn(self.dtype, self.data, self.validity,
                              dictionary=self.dictionary).to_arrow(num_rows)
        return DeviceColumn(self.dtype, self.data, self.validity).to_arrow(num_rows)

    def to_device(self, capacity: int) -> "ColVal":
        """Materialize host-form as a padded device pair (fixed-width only)."""
        if self.is_device:
            return self
        dc = DeviceColumn.from_arrow(self.array, self.dtype, capacity)
        return ColVal(self.dtype, data=dc.data, validity=dc.validity)

    def to_column(self, capacity: int):
        if self.dictionary is not None and self.is_device:
            from blaze_tpu.batch import DictColumn
            return DictColumn(self.dtype, self.data, self.validity,
                              dictionary=self.dictionary)
        if self.is_device:
            return DeviceColumn(self.dtype, self.data, self.validity)
        if self.dtype.is_fixed_width:
            # keep the invariant: fixed-width columns live on device
            v = self.to_device(capacity)
            return DeviceColumn(v.dtype, v.data, v.validity)
        return HostColumn(self.dtype, self.array)

    def as_mask(self, batch: ColumnBatch) -> jax.Array:
        """SQL predicate -> device bool over capacity (null counts as False)."""
        if self.is_device:
            return self.data.astype(bool) & self.validity
        vals = self.array.slice(0, batch.num_rows)
        np_mask = np.asarray(vals.fill_null(False), dtype=bool)
        padded = np.zeros(batch.capacity, dtype=bool)
        padded[:len(np_mask)] = np_mask
        if batch._xp() is np:
            return padded
        return xputil.to_device(padded)


class PhysicalExpr:
    """Base physical expression (ref planner.rs:924 expr kinds)."""

    def data_type(self, schema: Schema) -> DataType:
        raise NotImplementedError

    def children(self) -> Sequence["PhysicalExpr"]:
        return ()

    def evaluate(self, batch: ColumnBatch) -> ColVal:
        raise NotImplementedError

    # cache key for the common-subexpression evaluator
    # (ref common/cached_exprs_evaluator.rs:522).  Derived from ALL
    # dataclass fields, not just children: two same-class exprs that
    # differ only in a scalar parameter (ordinal, pattern, function
    # name...) must never share a cache slot.
    def cache_key(self) -> Any:
        import dataclasses
        if dataclasses.is_dataclass(self):
            parts = []
            for f in dataclasses.fields(self):
                v = getattr(self, f.name)
                if isinstance(v, PhysicalExpr):
                    parts.append(v.cache_key())
                elif isinstance(v, (tuple, list)):
                    parts.append(tuple(
                        x.cache_key() if isinstance(x, PhysicalExpr)
                        else repr(x) for x in v))
                else:
                    parts.append(repr(v))
            return (type(self).__name__, *parts)
        # non-dataclass without an explicit override: disable sharing
        # rather than risk a collision
        return (type(self).__name__, id(self))

    def __repr__(self):
        cs = ", ".join(repr(c) for c in self.children())
        return f"{type(self).__name__}({cs})"


@dataclass(frozen=True, repr=False)
class BoundReference(PhysicalExpr):
    """Column by ordinal (proto PhysicalColumn, auron.proto expr `column`)."""

    index: int
    name: str = ""

    def data_type(self, schema: Schema) -> DataType:
        return schema[self.index].data_type

    def evaluate(self, batch: ColumnBatch) -> ColVal:
        return ColVal.from_column(batch.columns[self.index], batch.capacity)

    def cache_key(self):
        return ("col", self.index)

    def __repr__(self):
        return f"#{self.index}" + (f"({self.name})" if self.name else "")


def col(index: int, name: str = "") -> BoundReference:
    return BoundReference(index, name)


@dataclass(frozen=True, repr=False)
class Literal(PhysicalExpr):
    """Scalar literal (proto PhysicalScalarValue / ScalarValue serde,
    ref datafusion-ext-commons/src/scalar_value.rs)."""

    value: Any
    dtype: DataType

    def data_type(self, schema: Schema) -> DataType:
        return self.dtype

    def evaluate(self, batch: ColumnBatch) -> ColVal:
        cap = batch.capacity
        if self.dtype.is_fixed_width:
            # numpy constants are safe both eagerly (host residency) and
            # inside jit traces (embedded as XLA constants)
            xp = batch._xp()
            if self.value is None:
                data = xp.zeros(cap, dtype=self.dtype.jnp_dtype())
                return ColVal(self.dtype, data=data,
                              validity=xp.zeros(cap, dtype=bool),
                              literal=True)
            value = self.value
            if self.dtype.id == TypeId.DECIMAL:
                value = self.unscaled()  # the lane holds the unscaled int
            data = xp.full(cap, value, dtype=self.dtype.jnp_dtype())
            return ColVal(self.dtype, data=data,
                          validity=xp.ones(cap, dtype=bool), literal=True)
        arr = pa.array([self.value] * batch.num_rows, type=self.dtype.to_arrow())
        return ColVal(self.dtype, array=arr, literal=True)

    def unscaled(self) -> int:
        """A decimal literal's value at its type's scale, exactly."""
        import decimal as pydec
        v = pydec.Decimal(str(self.value)) if isinstance(self.value, float) \
            else pydec.Decimal(self.value)
        u = v.scaleb(self.dtype.scale)
        if u != u.to_integral_value() or \
                abs(int(u)) >= 10 ** self.dtype.precision:
            raise ValueError(f"literal {self.value!r} is not a "
                             f"decimal({self.dtype.precision},"
                             f"{self.dtype.scale})")
        return int(u)

    def cache_key(self):
        if self.dtype.id == TypeId.DECIMAL:
            return ("lit", self.dtype.id.value, self.dtype.precision,
                    self.dtype.scale, str(self.value))
        return ("lit", self.dtype.id.value, self.value)

    def __repr__(self):
        return f"lit({self.value!r})"


def lit(value: Any, dtype: Optional[DataType] = None) -> Literal:
    from blaze_tpu import schema as S
    if dtype is None:
        if isinstance(value, bool):
            dtype = S.BOOL
        elif isinstance(value, int):
            dtype = S.INT64
        elif isinstance(value, float):
            dtype = S.FLOAT64
        elif isinstance(value, str):
            dtype = S.UTF8
        elif isinstance(value, bytes):
            dtype = S.BINARY
        elif value is None:
            dtype = S.NULL
        else:
            raise TypeError(f"cannot infer literal type of {value!r}")
    return Literal(value, dtype)

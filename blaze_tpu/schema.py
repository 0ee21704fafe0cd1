"""Logical types and schemas.

Mirrors the Arrow-type serde section of the reference plan proto
(ref: native-engine/auron-planner/proto/auron.proto:825-988) — the engine is
columnar end-to-end, so the logical type system is Arrow's, restricted to what
Spark emits.  Device representation rules (TPU has no pointers):

  fixed-width (bool/int/float/date/ts/decimal) -> one jnp data array + bool
      validity array, padded to the static batch capacity.
  utf8/binary -> host-resident by default; materialized on device on demand as
      (offsets:int32[cap+1], bytes:uint8[byte_cap]) for hash/compare kernels.
  decimal(p<=18) -> int64 unscaled values (Spark's long-backed decimals).
  decimal(p>18)  -> a host Arrow column between operators; inside an
      expression program its two int64 limbs, (capacity, 2), low first
      (kernels/decimal128.py).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np
import pyarrow as pa


class TypeId(enum.Enum):
    BOOL = "bool"
    INT8 = "int8"
    INT16 = "int16"
    INT32 = "int32"
    INT64 = "int64"
    FLOAT32 = "float32"
    FLOAT64 = "float64"
    DATE32 = "date32"          # days since epoch, int32
    TIMESTAMP_MICROS = "timestamp_us"  # int64
    DECIMAL = "decimal"        # unscaled int64 (precision <= 18) for now
    UTF8 = "utf8"
    BINARY = "binary"
    NULL = "null"
    # nested types decode in the plan serde but execute host-side for now
    LIST = "list"
    STRUCT = "struct"
    MAP = "map"


@dataclass(frozen=True)
class DataType:
    id: TypeId
    precision: int = 0       # decimal only
    scale: int = 0           # decimal only
    children: Tuple["Field", ...] = ()  # nested only

    # -- classification ----------------------------------------------------
    @property
    def is_fixed_width(self) -> bool:
        if self.id == TypeId.DECIMAL:
            # p>18 exceeds int64 unscaled range -> host-resident column
            return self.precision <= 18
        return self.id not in (TypeId.UTF8, TypeId.BINARY, TypeId.LIST,
                               TypeId.STRUCT, TypeId.MAP, TypeId.NULL)

    @property
    def is_nested(self) -> bool:
        return self.id in (TypeId.LIST, TypeId.STRUCT, TypeId.MAP)

    @property
    def is_floating(self) -> bool:
        return self.id in (TypeId.FLOAT32, TypeId.FLOAT64)

    @property
    def is_integer(self) -> bool:
        return self.id in (TypeId.INT8, TypeId.INT16, TypeId.INT32, TypeId.INT64,
                           TypeId.DATE32, TypeId.TIMESTAMP_MICROS)

    # -- device representation --------------------------------------------
    def jnp_dtype(self):
        m = {
            TypeId.BOOL: jnp.bool_,
            TypeId.INT8: jnp.int8,
            TypeId.INT16: jnp.int16,
            TypeId.INT32: jnp.int32,
            TypeId.INT64: jnp.int64,
            TypeId.FLOAT32: jnp.float32,
            TypeId.FLOAT64: jnp.float64,
            TypeId.DATE32: jnp.int32,
            TypeId.TIMESTAMP_MICROS: jnp.int64,
            TypeId.DECIMAL: jnp.int64,
        }
        if self.id not in m:
            raise TypeError(f"{self} has no device dtype")
        return m[self.id]

    def np_dtype(self):
        return np.dtype(jnp.dtype(self.jnp_dtype()).name)

    # -- arrow mapping ------------------------------------------------------
    def to_arrow(self) -> pa.DataType:
        m = {
            TypeId.BOOL: pa.bool_(),
            TypeId.INT8: pa.int8(),
            TypeId.INT16: pa.int16(),
            TypeId.INT32: pa.int32(),
            TypeId.INT64: pa.int64(),
            TypeId.FLOAT32: pa.float32(),
            TypeId.FLOAT64: pa.float64(),
            TypeId.DATE32: pa.date32(),
            TypeId.TIMESTAMP_MICROS: pa.timestamp("us"),
            TypeId.UTF8: pa.utf8(),
            TypeId.BINARY: pa.binary(),
            TypeId.NULL: pa.null(),
        }
        if self.id == TypeId.DECIMAL:
            return pa.decimal128(self.precision, self.scale)
        if self.id == TypeId.LIST:
            return pa.list_(self.children[0].data_type.to_arrow())
        if self.id == TypeId.STRUCT:
            return pa.struct([(f.name, f.data_type.to_arrow()) for f in self.children])
        if self.id == TypeId.MAP:
            return pa.map_(self.children[0].data_type.to_arrow(),
                           self.children[1].data_type.to_arrow())
        return m[self.id]

    @staticmethod
    def from_arrow(t: pa.DataType) -> "DataType":
        if pa.types.is_boolean(t):
            return BOOL
        if pa.types.is_int8(t):
            return INT8
        if pa.types.is_int16(t):
            return INT16
        if pa.types.is_int32(t):
            return INT32
        if pa.types.is_int64(t):
            return INT64
        if pa.types.is_float32(t):
            return FLOAT32
        if pa.types.is_float64(t):
            return FLOAT64
        if pa.types.is_date32(t):
            return DATE32
        if pa.types.is_timestamp(t):
            return TIMESTAMP_MICROS
        if pa.types.is_decimal(t):
            if t.precision > 18:
                # decimal128 with p>18 falls back to host columns
                return DataType(TypeId.DECIMAL, t.precision, t.scale)
            return DataType(TypeId.DECIMAL, t.precision, t.scale)
        if pa.types.is_string(t) or pa.types.is_large_string(t):
            return UTF8
        if pa.types.is_binary(t) or pa.types.is_large_binary(t):
            return BINARY
        if pa.types.is_null(t):
            return NULL
        if pa.types.is_dictionary(t):
            # dictionary encoding is a physical layout, not a logical
            # type: the schema keeps the value type (batch.DictColumn
            # carries the codes)
            return DataType.from_arrow(t.value_type)
        if pa.types.is_list(t):
            return DataType(TypeId.LIST, children=(
                Field("item", DataType.from_arrow(t.value_type), True),))
        if pa.types.is_struct(t):
            return DataType(TypeId.STRUCT, children=tuple(
                Field(f.name, DataType.from_arrow(f.type), f.nullable) for f in t))
        if pa.types.is_map(t):
            return DataType(TypeId.MAP, children=(
                Field("key", DataType.from_arrow(t.key_type), False),
                Field("value", DataType.from_arrow(t.item_type), True)))
        raise TypeError(f"unsupported arrow type {t}")

    def __repr__(self):
        if self.id == TypeId.DECIMAL:
            return f"decimal({self.precision},{self.scale})"
        return self.id.value


BOOL = DataType(TypeId.BOOL)
INT8 = DataType(TypeId.INT8)
INT16 = DataType(TypeId.INT16)
INT32 = DataType(TypeId.INT32)
INT64 = DataType(TypeId.INT64)
FLOAT32 = DataType(TypeId.FLOAT32)
FLOAT64 = DataType(TypeId.FLOAT64)
DATE32 = DataType(TypeId.DATE32)
TIMESTAMP_MICROS = DataType(TypeId.TIMESTAMP_MICROS)
UTF8 = DataType(TypeId.UTF8)
BINARY = DataType(TypeId.BINARY)
NULL = DataType(TypeId.NULL)


def decimal(precision: int, scale: int) -> DataType:
    return DataType(TypeId.DECIMAL, precision, scale)


@dataclass(frozen=True)
class Field:
    name: str
    data_type: DataType
    nullable: bool = True

    def to_arrow(self) -> pa.Field:
        return pa.field(self.name, self.data_type.to_arrow(), self.nullable)

    @staticmethod
    def from_arrow(f: pa.Field) -> "Field":
        return Field(f.name, DataType.from_arrow(f.type), f.nullable)


@dataclass(frozen=True)
class Schema:
    fields: Tuple[Field, ...]

    def __init__(self, fields):
        object.__setattr__(self, "fields", tuple(fields))

    def __len__(self):
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)

    def __getitem__(self, i):
        return self.fields[i]

    def index_of(self, name: str, case_sensitive: bool = False) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name or (not case_sensitive and f.name.lower() == name.lower()):
                return i
        raise KeyError(name)

    def field(self, name: str) -> Field:
        return self.fields[self.index_of(name)]

    @property
    def names(self) -> List[str]:
        return [f.name for f in self.fields]

    def to_arrow(self) -> pa.Schema:
        return pa.schema([f.to_arrow() for f in self.fields])

    @staticmethod
    def from_arrow(s: pa.Schema) -> "Schema":
        return Schema([Field.from_arrow(f) for f in s])

    def select(self, indices) -> "Schema":
        return Schema([self.fields[i] for i in indices])

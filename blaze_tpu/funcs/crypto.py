"""Hash/crypto functions.

Parity: spark_crypto.rs (md5/sha1/sha2/crc32), spark_murmur3_hash.rs,
spark_xxhash64.rs — hash() and xxhash64() reuse the validated device
kernels so expression results match shuffle partition hashing bit-exactly.
"""

from __future__ import annotations

import hashlib
import zlib

import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu.exprs.base import ColVal
from blaze_tpu.funcs import register
from blaze_tpu.kernels import hashing as H
from blaze_tpu.schema import INT32, INT64, UTF8, TypeId


def _digest(fn_name: str):
    def impl(args, batch, out_type):
        (a,) = [x.to_host(batch.num_rows) for x in args[:1]]
        py = []
        for x in a:
            if not x.is_valid:
                py.append(None)
                continue
            v = x.as_py()
            data = v.encode() if isinstance(v, str) else bytes(v)
            py.append(hashlib.new(fn_name, data).hexdigest())
        return ColVal.host(UTF8, pa.array(py, type=pa.utf8()))
    return impl


register("md5", lambda ts: UTF8)(_digest("md5"))
register("sha1", lambda ts: UTF8)(_digest("sha1"))


@register("sha2", lambda ts: UTF8)
def _sha2(args, batch, out_type):
    a = args[0].to_host(batch.num_rows)
    bits = 256
    if len(args) > 1:
        b = args[1].to_host(batch.num_rows)
        if len(b) and b[0].is_valid:
            bits = int(b[0].as_py())
    if bits == 0:
        bits = 256
    name = {224: "sha224", 256: "sha256", 384: "sha384", 512: "sha512"}.get(bits)
    py = []
    for x in a:
        if not x.is_valid or name is None:
            py.append(None)
            continue
        v = x.as_py()
        data = v.encode() if isinstance(v, str) else bytes(v)
        py.append(hashlib.new(name, data).hexdigest())
    return ColVal.host(UTF8, pa.array(py, type=pa.utf8()))


@register("crc32", lambda ts: INT64)
def _crc32(args, batch, out_type):
    a = args[0].to_host(batch.num_rows)
    py = []
    for x in a:
        if not x.is_valid:
            py.append(None)
            continue
        v = x.as_py()
        data = v.encode() if isinstance(v, str) else bytes(v)
        py.append(zlib.crc32(data) & 0xFFFFFFFF)
    return ColVal.host(INT64, pa.array(py, type=pa.int64()))


def _hash_impl(algo: str, out_dtype):
    def impl(args, batch, out_type):
        # seed is the LAST argument when it is an int literal (Spark's
        # hash(..., seed)); default 42
        cols = []
        n = batch.num_rows
        for v in args:
            if v.is_device and v.dictionary is None:
                cols.append((v.data, v.validity, v.dtype.id.value))
            else:
                arr = v.to_host(n)
                (mat, lengths), valid = H.string_column_to_padded_bytes(arr)
                pad_valid = np.zeros(mat.shape[0], dtype=bool)
                pad_valid[:len(valid)] = valid
                cols.append(((jnp.asarray(mat), jnp.asarray(lengths)),
                             jnp.asarray(pad_valid), "utf8"))
        h = H.hash_columns(cols, seed=42, xp=jnp, algo=algo)
        cap = batch.capacity
        data = jnp.asarray(h)
        if data.shape[0] != cap:
            pad = jnp.zeros(cap - data.shape[0], dtype=data.dtype)
            data = jnp.concatenate([data, pad])
        return ColVal(out_dtype, data=data.astype(out_dtype.jnp_dtype()),
                      validity=jnp.ones(cap, dtype=bool))
    return impl


register("hash", lambda ts: INT32)(_hash_impl("murmur3", INT32))
register("murmur3_hash", lambda ts: INT32)(_hash_impl("murmur3", INT32))
register("xxhash64", lambda ts: INT64)(_hash_impl("xxhash64", INT64))

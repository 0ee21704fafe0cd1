"""ctypes loaders — and the build — for the native libraries.

The codec library accelerates the framed-IPC hot path (shuffle/spill
compression); the partition and agg kernels are host fast paths; the
host-bridge library is the embedding surface for non-Python host
engines.  A missing library selects a slower pure-Python / numpy /
pyarrow path with identical results; `loaded_libraries()` says which
were found, so a run can state what it used instead of degrading in
silence.

`build_native_libs()` builds them from native/src + native/CMakeLists.txt
into native/build (git-ignored): a checkout holds no .so, and a copied
tree resets file times, so freshness is decided by a CONTENT stamp of the
sources, never by mtimes.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
from typing import Dict, Optional

_HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE = os.path.join(_HERE, "native")
_BUILD = os.path.join(_NATIVE, "build")
_SEARCH = [
    _BUILD,
    os.path.join(_NATIVE, "lib"),
    os.environ.get("BLAZE_TPU_NATIVE_DIR", ""),
]
_LIBS = ("libblaze_ipc_codec.so", "libblaze_host_bridge.so",
         "libblaze_jni_bridge.so", "libblaze_agg_kernel.so",
         "libblaze_partition_kernel.so")
_STAMP = os.path.join(_BUILD, ".source_sha256")


def _find(name: str) -> Optional[str]:
    for d in _SEARCH:
        if not d:
            continue
        p = os.path.join(d, name)
        if os.path.exists(p):
            return p
    return None


class NativeBuildError(RuntimeError):
    """The native libraries could not be built (toolchain missing or a
    compile error); callers run on the pure-Python paths and say so."""


def _source_digest() -> str:
    h = hashlib.sha256()
    files = [os.path.join(_NATIVE, "CMakeLists.txt")]
    for sub in ("src", "include"):
        d = os.path.join(_NATIVE, sub)
        files += sorted(os.path.join(d, f) for f in os.listdir(d))
    for path in files:
        h.update(os.path.relpath(path, _NATIVE).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def build_native_libs() -> str:
    """Make native/build hold libraries built from the sources as they
    are now.  Returns "current" when the content stamp matches and every
    library is present, "built" after a from-scratch build; raises
    NativeBuildError when cmake/ninja/the compiler is missing or fails."""
    global _codec_checked
    digest = _source_digest()
    if all(os.path.exists(os.path.join(_BUILD, lib)) for lib in _LIBS):
        try:
            with open(_STAMP) as f:
                if f.read().strip() == digest:
                    return "current"
        except FileNotFoundError:
            pass
    # from scratch: a build directory configured at another path (a
    # copied tree) carries a CMakeCache that cmake refuses to reuse
    shutil.rmtree(_BUILD, ignore_errors=True)
    try:
        subprocess.run(["cmake", "-S", _NATIVE, "-B", _BUILD, "-G", "Ninja"],
                       check=True, capture_output=True, timeout=300)
        subprocess.run(["cmake", "--build", _BUILD], check=True,
                       capture_output=True, timeout=600)
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        raise NativeBuildError(
            f"native build failed: {e} "
            f"{detail.decode(errors='replace')[-500:]}") from e
    with open(_STAMP, "w") as f:
        f.write(digest)
    # forget earlier misses so this process loads what it just built
    _codec_checked = False
    _kernels.clear()
    return "built"


def loaded_libraries() -> Dict[str, bool]:
    """Which native libraries this process resolves; False names a
    pure-Python fallback in use (zstd codec in Python, numpy murmur3
    partition ids, pyarrow group-by in the host agg lane)."""
    return {"ipc_codec": get_codec() is not None,
            "partition_kernel": get_partition_kernel() is not None,
            "agg_kernel": get_agg_kernel() is not None,
            "host_bridge": _find("libblaze_host_bridge.so") is not None}


class _Codec:
    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        lib.blaze_ipc_compress_frame.restype = ctypes.c_int64
        lib.blaze_ipc_compress_frame.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))]
        lib.blaze_ipc_decompress.restype = ctypes.c_int64
        lib.blaze_ipc_decompress.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64]
        lib.blaze_ipc_decompressed_size.restype = ctypes.c_int64
        lib.blaze_ipc_decompressed_size.argtypes = [
            ctypes.c_char_p, ctypes.c_int64]
        lib.blaze_free.argtypes = [ctypes.c_void_p]

    def compress_frame(self, payload: bytes, level: int = 1) -> bytes:
        """Whole frame (header + compressed payload)."""
        out = ctypes.POINTER(ctypes.c_uint8)()
        n = self._lib.blaze_ipc_compress_frame(payload, len(payload), level,
                                               ctypes.byref(out))
        if n < 0:
            raise RuntimeError("native zstd compression failed")
        try:
            return ctypes.string_at(out, n)
        finally:
            self._lib.blaze_free(out)

    def decompress(self, payload: bytes) -> bytes:
        size = self._lib.blaze_ipc_decompressed_size(payload, len(payload))
        if size < 0:
            raise RuntimeError("unknown decompressed size")
        buf = ctypes.create_string_buffer(int(size))
        n = self._lib.blaze_ipc_decompress(payload, len(payload), buf, size)
        if n < 0:
            raise RuntimeError("native zstd decompression failed")
        return buf.raw[:n]


_codec: Optional[_Codec] = None
_codec_checked = False


def get_codec() -> Optional[_Codec]:
    global _codec, _codec_checked
    if not _codec_checked:
        _codec_checked = True
        path = _find("libblaze_ipc_codec.so")
        if path:
            try:
                _codec = _Codec(ctypes.CDLL(path))
            except OSError:
                _codec = None
    return _codec


_kernels: dict = {}  # so_name -> CDLL | None, cached incl. misses


def _load_kernel(so_name: str, configure) -> Optional[ctypes.CDLL]:
    """Shared cached loader: find the .so, CDLL it, apply `configure`
    (restype/argtypes setup); None — and remembered as None — when the
    library is absent or unloadable (the pure-Python fallback path)."""
    if so_name not in _kernels:
        lib = None
        path = _find(so_name)
        if path:
            try:
                lib = ctypes.CDLL(path)
                configure(lib)
            except (OSError, AttributeError):
                # AttributeError: stale .so missing a symbol configure
                # binds — a miss to cache, not an error to re-raise on
                # every hot-path call
                lib = None
        _kernels[so_name] = lib
    return _kernels[so_name]


def get_partition_kernel() -> Optional[ctypes.CDLL]:
    """Fused Spark-murmur3 + pmod partition-id kernel
    (partition_kernel.cpp); None (numpy fallback) when unbuilt."""
    def configure(lib):
        lib.blaze_murmur3_pmod.restype = ctypes.c_int64
        lib.blaze_murmur3_pmod.argtypes = [
            ctypes.c_int64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_int32, ctypes.c_void_p]
    return _load_kernel("libblaze_partition_kernel.so", configure)


def get_agg_kernel() -> Optional[ctypes.CDLL]:
    """Specialized i64-key hash group-aggregation (agg_kernel.cpp);
    None (pure-Arrow fallback) when unbuilt."""
    def configure(lib):
        lib.blaze_group_agg_i64.restype = ctypes.c_int64
        lib.blaze_group_agg_i64.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p)]
        # first-row-index variant (newer builds); callers probe with
        # hasattr
        if hasattr(lib, "blaze_group_agg_i64_rows"):
            lib.blaze_group_agg_i64_rows.restype = ctypes.c_int64
            lib.blaze_group_agg_i64_rows.argtypes = (
                lib.blaze_group_agg_i64.argtypes + [ctypes.c_void_p])
    return _load_kernel("libblaze_agg_kernel.so", configure)


def get_host_bridge() -> Optional[ctypes.CDLL]:
    """The C-ABI entry-point library (tests exercise it in-process)."""
    path = _find("libblaze_host_bridge.so")
    if not path:
        return None
    lib = ctypes.CDLL(path)
    lib.blaze_call_native.restype = ctypes.c_int64
    lib.blaze_call_native.argtypes = [ctypes.c_char_p,
                                      ctypes.POINTER(ctypes.c_char_p)]
    lib.blaze_next_batch.restype = ctypes.c_int64
    lib.blaze_next_batch.argtypes = [
        ctypes.c_int64, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_char_p)]
    lib.blaze_finalize_native.restype = ctypes.c_int64
    lib.blaze_finalize_native.argtypes = [
        ctypes.c_int64, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_char_p)]
    lib.blaze_free_buffer.argtypes = [ctypes.c_void_p]
    # Arrow C-Data zero-copy surface (include/arrow_abi.h); a stale .so
    # from before the FFI symbols must degrade to the IPC path, not
    # crash the loader (same policy as _load_kernel's AttributeError
    # handling)
    try:
        lib.blaze_next_batch_ffi.restype = ctypes.c_int64
        lib.blaze_next_batch_ffi.argtypes = [
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_char_p)]
        lib.blaze_ffi_import_batch.restype = ctypes.c_int64
        lib.blaze_ffi_import_batch.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_char_p)]
        lib.has_cdata_ffi = True
    except AttributeError:
        lib.has_cdata_ffi = False
    return lib


class ArrowArrayStruct(ctypes.Structure):
    """Arrow C-Data ArrowArray (arrow_abi.h), for in-process FFI pulls."""
    _fields_ = [("length", ctypes.c_int64), ("null_count", ctypes.c_int64),
                ("offset", ctypes.c_int64), ("n_buffers", ctypes.c_int64),
                ("n_children", ctypes.c_int64), ("buffers", ctypes.c_void_p),
                ("children", ctypes.c_void_p),
                ("dictionary", ctypes.c_void_p),
                ("release", ctypes.c_void_p),
                ("private_data", ctypes.c_void_p)]


class ArrowSchemaStruct(ctypes.Structure):
    _fields_ = [("format", ctypes.c_char_p), ("name", ctypes.c_char_p),
                ("metadata", ctypes.c_void_p), ("flags", ctypes.c_int64),
                ("n_children", ctypes.c_int64),
                ("children", ctypes.c_void_p),
                ("dictionary", ctypes.c_void_p),
                ("release", ctypes.c_void_p),
                ("private_data", ctypes.c_void_p)]


def bridge_pull_batch(lib: ctypes.CDLL, handle: int):
    """Pull one batch from a host-bridge task handle as a pyarrow
    RecordBatch (None = end of stream).

    Prefers the zero-copy Arrow C-Data path; a stale .so without the FFI
    symbols (has_cdata_ffi False) degrades to the IPC-bytes path — the
    documented fallback policy, enforced here rather than at every call
    site."""
    import pyarrow as pa
    err = ctypes.c_char_p()
    if getattr(lib, "has_cdata_ffi", False):
        arr = ArrowArrayStruct()
        schema = ArrowSchemaStruct()
        r = lib.blaze_next_batch_ffi(handle, ctypes.byref(arr),
                                     ctypes.byref(schema),
                                     ctypes.byref(err))
        if r < 0:
            raise RuntimeError((err.value or b"ffi pull failed").decode())
        if r == 0:
            return None
        return pa.RecordBatch._import_from_c(ctypes.addressof(arr),
                                             ctypes.addressof(schema))
    buf = ctypes.POINTER(ctypes.c_uint8)()
    n = lib.blaze_next_batch(handle, ctypes.byref(buf), ctypes.byref(err))
    if n < 0:
        raise RuntimeError((err.value or b"pull failed").decode())
    if n == 0:
        return None
    try:
        data = ctypes.string_at(buf, n)
    finally:
        lib.blaze_free_buffer(buf)
    with pa.ipc.open_stream(data) as rd:
        batches = list(rd)
    return batches[0] if batches else None

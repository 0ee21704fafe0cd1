"""One compilation a program, whichever chip asks for it.

A task runs on its own chip (bridge/context.TaskContext.device), and JAX
compiles a single-device program for the device it is to run on: its
persistent-cache key holds the device assignment, so on a four-chip host
every program of a map task would be compiled four times, by four task
threads at the same moment, and kept four times in the cache directory.
The first four-chip run of the benchmark's q06 cell (PERF.md, PR 30) had
not finished its first map stage after 1,200 s of that, where one chip
compiles the whole query in 213-383 s.

JAX can load an executable for another device than it was compiled for:
`deserialize_executable` is handed the devices and the compile options of
the asking side (it is how JAX shares binaries between the processes of a
GPU job, whose cache keys leave the device assignment out).  So, for a
program of ONE device, where the persistent cache is in use:

- `cache_key.get` is asked for the key the backend's first device would
  get, so the chips share one cache entry a program;
- `compiler.compile_or_get_cached` lets one thread at a time through for
  one cache key: the first compiles and writes the entry, the others
  find it and load it.

`parallel/mesh.task_device` installs both the first time a task is given
one chip of several; a process with one device never does, and JAX is
what it was.  Both reach into JAX's private modules: where a name has
moved, `install` raises, because what would follow (every chip compiling
for itself) is the state that did not finish in 1,800 s.
"""

from __future__ import annotations

import copy
import threading

_install_lock = threading.Lock()
_installed = False
_key_locks: dict = {}
_key_locks_guard = threading.Lock()


def _first_device_key(get):
    def key_of_first_device(module, devices, compile_options, backend,
                            *args, **kwargs):
        assignment = compile_options.device_assignment
        if devices.size == 1 and assignment is not None \
                and assignment.replica_count() == 1 \
                and assignment.computation_count() == 1:
            first = backend.local_devices()[0]
            if devices.flat[0] != first:
                import numpy as np
                from jax._src.lib import xla_client
                compile_options = copy.deepcopy(compile_options)
                compile_options.device_assignment = \
                    xla_client.DeviceAssignment.create(
                        np.array([[first.id]]))
                devices = np.array([first])
        return get(module, devices, compile_options, backend, *args,
                   **kwargs)
    return key_of_first_device


def _one_compile_a_key(compile_or_get_cached, cache_key, compilation_cache):
    from jax._src.lib import xla_client

    def one_at_a_time(backend, computation, devices, compile_options,
                      *args, **kwargs):
        if devices.size != 1 or len(backend.local_devices()) == 1 \
                or not compilation_cache.is_cache_used(backend):
            return compile_or_get_cached(backend, computation, devices,
                                         compile_options, *args, **kwargs)
        try:
            key = cache_key.get(computation, devices, compile_options,
                                backend)
        except xla_client.XlaRuntimeError:   # JAX skips the cache, and says so
            return compile_or_get_cached(backend, computation, devices,
                                         compile_options, *args, **kwargs)
        with _key_locks_guard:
            lock = _key_locks.setdefault(key, threading.Lock())
        with lock:
            return compile_or_get_cached(backend, computation, devices,
                                         compile_options, *args, **kwargs)
    return one_at_a_time


def install() -> None:
    """Idempotent.  Raises where JAX's compile cache is not where this
    module reaches for it."""
    global _installed
    with _install_lock:
        if _installed:
            return
        try:
            from jax._src import cache_key, compilation_cache, compiler
            key = _first_device_key(cache_key.get)
            compile_ = _one_compile_a_key(compiler.compile_or_get_cached,
                                          cache_key, compilation_cache)
            compilation_cache.is_cache_used  # noqa: B018 (must be there)
        except (ImportError, AttributeError) as e:
            raise RuntimeError(
                "tasks are placed on several chips, and JAX's compile "
                "cache is not where blaze_tpu/bridge/compile_share.py "
                "shares compilations between them (jax._src.cache_key."
                "get, compiler.compile_or_get_cached, compilation_cache."
                "is_cache_used): every chip would compile every program "
                "for itself") from e
        cache_key.get = key
        compiler.compile_or_get_cached = compile_
        _installed = True

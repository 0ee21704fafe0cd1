"""Array-namespace dispatch: numpy for host-resident batches, jnp on device.

The engine's per-batch glue (padding, masks, promotions, null plumbing)
historically ran as *eager* jax ops.  Each eager dispatch costs ~0.1-1 ms
of XLA program-launch overhead (a directly attached v5e measured 1.0 ms
per dispatch+readback, CHANGES.md PR 21); a SF1 query issues hundreds of
them, so fixed cost — not kernels — dominates the wall clock.  The
reference has no such boundary tax: its glue is plain Rust (ref
datafusion-ext-plans/src/common/cached_exprs_evaluator.rs).

The fix mirrors the reference's split between scalar glue and vectorized
kernels: when compute placement pins to host (placement.py), batch columns
stay numpy end-to-end and the glue runs as numpy (nanosecond dispatch,
zero-copy views); the fused hot loops remain jit'd XLA programs, which
accept numpy operands directly.  On a locally-attached accelerator the
columns are jax arrays and everything routes through jnp exactly as
before.  Inside a jit trace operands are tracers, which `xp_of` sends to
jnp — so the same expression code traces unchanged.
"""

from __future__ import annotations

import time

import numpy as np

_jnp = None


def _lazy_jnp():
    global _jnp
    if _jnp is None:
        import jax.numpy as jnp
        _jnp = jnp
    return _jnp


def is_np(a) -> bool:
    """True when `a` is host-resident data (numpy scalar/array, python
    scalar, or None) — anything a jax op is NOT required for."""
    return a is None or isinstance(a, (np.ndarray, np.generic, int, float,
                                       bool, complex))


def xp_of(*arrays):
    """numpy when every operand is host-resident; jnp when any operand is
    a jax array or tracer (including inside jit traces)."""
    for a in arrays:
        if not is_np(a):
            return _lazy_jnp()
    return np


def _task():
    from blaze_tpu.bridge.context import current_task
    return current_task()


def task_device():
    """The chip the current task runs on (`TaskContext.device`), or None
    where nothing is pinned: no task, one device, compute on the host."""
    return _task().device


def to_host(tree):
    """Device -> host: `jax.device_get` of an array or pytree, and the
    one place in `blaze_tpu/` where the host blocks on the device.
    Counts the bytes of the device leaves (`d2h_bytes`, one
    `d2h_transfers`, by the task's chip too) and the time the caller was
    blocked (`d2h_wait_ns`, which includes waiting for the programs that
    produce the value), under a `d2h` span.  A tree with no device leaf
    is returned as device_get returns it, uncounted."""
    if is_np(tree):
        return tree
    import jax
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)
                 if isinstance(x, jax.Array))
    if not nbytes:
        return jax.device_get(tree)
    from blaze_tpu.bridge import tracing, xla_stats
    chip = _task().device_id
    t0 = time.perf_counter_ns()
    with tracing.span("d2h", bytes=nbytes, device=chip):
        out = jax.device_get(tree)
    xla_stats.note_d2h(nbytes, time.perf_counter_ns() - t0, chip)
    return out


def to_device(tree, sharding=None):
    """Host -> device: one `jax.device_put` over an array or pytree of
    numpy buffers, committed to the current task's chip where it has one
    (`task_device`), else to JAX's default device as ever; or spread as
    `sharding` says (a staged exchange's columns, cut over the mesh:
    counted under the calling thread's chip all the same).  Counts their
    bytes (`h2d_bytes`, one `h2d_transfers`, by chip too) and the time
    spent here (`h2d_ns`), under an `h2d` span.  device_put returns
    before the copy lands, so the time is host staging and dispatch, not
    the transfer."""
    import jax
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)
                 if isinstance(x, np.ndarray))
    from blaze_tpu.bridge import tracing, xla_stats
    task = _task()
    t0 = time.perf_counter_ns()
    with tracing.span("h2d", bytes=nbytes, device=task.device_id):
        out = jax.device_put(
            tree, task.device if sharding is None else sharding)
    xla_stats.note_h2d(nbytes, time.perf_counter_ns() - t0, task.device_id)
    return out


def on_task_chip(tree, chip=None):
    """`tree` with every single-device array that lies on another chip
    than `chip` (the current task's, where none is given) moved there,
    explicitly, and its bytes counted (`cross_chip_bytes`): outside the
    exchange's collective no row should change chip, so the counter
    reads 0 where placement holds.  Arrays spread over a mesh, tracers
    and host values pass; so does everything where there is no chip."""
    dev = chip if chip is not None else task_device()
    if dev is None:
        return tree
    import jax

    def foreign(x):
        return (isinstance(x, jax.Array)
                and not isinstance(x, jax.core.Tracer)
                and len(x.sharding.device_set) == 1
                and dev not in x.sharding.device_set)

    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)
                 if foreign(x))
    if not nbytes:
        return tree
    from blaze_tpu.bridge import xla_stats
    xla_stats.note_cross_chip(nbytes)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, dev) if foreign(x) else x, tree)


def asnp(a) -> np.ndarray:
    """Pull an array to host numpy (zero-copy for numpy and for CPU-backend
    jax arrays).  Device pulls go through `to_host`."""
    if isinstance(a, np.ndarray):
        return a
    if is_np(a) or isinstance(a, (list, tuple)):
        return np.asarray(a)
    return np.asarray(to_host(a))

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


def asnp(a) -> np.ndarray:
    """Pull an array to host numpy (zero-copy for numpy and for CPU-backend
    jax arrays).  Device pulls are accounted as D2H transfer volume."""
    if isinstance(a, np.ndarray):
        return a
    out = np.asarray(a)
    from blaze_tpu.bridge import xla_stats
    xla_stats.note_d2h(out.nbytes)
    return out

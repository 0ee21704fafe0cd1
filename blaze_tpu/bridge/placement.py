"""Compute placement: accelerator vs host-XLA backend.

A batch SQL engine is data-movement bound; whether an accelerator wins
depends on what sits between it and the host.  The reference makes the
same class of decision per-operator (AuronConvertStrategy's
removeInefficientConverts un-converts plans whose native gain doesn't
pay for the row<->columnar boundary, AuronConvertStrategy.scala:205).
Here the boundary is host<->device.  The runtime measures the dispatch
round trip ONCE per process; `auto` keeps stage compute on the
accelerator unless that round trip exceeds
`auron.tpu.placement.rtt.threshold.ms`, in which case it pins
computation to the XLA CPU backend — same jitted kernels, same programs,
compiled for host — and says so at WARNING.  `auron.tpu.placement`
forces either side; forcing `device` where jax found no accelerator is
an error, never a silent host run.

The decision is exported (`placement_info()`) so every benchmark and
smoke run reports where compute actually ran.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import Optional

log = logging.getLogger("blaze_tpu.placement")

_lock = threading.Lock()
_info: Optional["PlacementInfo"] = None


@dataclass(frozen=True)
class PlacementInfo:
    device_kind: str          # "tpu" | "cpu"
    default_platform: str     # what jax would have used
    rtt_ms: float             # measured dispatch+readback round trip
    policy: str               # "auto" | forced value


def _measure_rtt_ms() -> float:
    import jax
    import jax.numpy as jnp
    def placement_rtt_probe(a):
        return (a + 1).sum()

    f = jax.jit(placement_rtt_probe)
    x = jnp.ones(8)
    float(f(x))  # compile + warm
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(f(x))  # dispatch + readback: what a blocking glue op pays
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[1] * 1000.0


def ensure_placement() -> PlacementInfo:
    """Idempotent; called at runtime startup (NativeExecutionRuntime /
    DagScheduler).  May switch jax's default device to the CPU backend."""
    global _info
    with _lock:
        if _info is not None:
            return _info
        import jax

        from blaze_tpu import config
        policy = config.PLACEMENT.get()
        if policy == "host":
            # forced host must NOT touch the accelerator at all — the
            # override exists precisely for a wedged backend, so decide
            # BEFORE any call that would initialize the default backend
            jax.config.update("jax_platforms", "cpu")
            cpu = jax.local_devices(backend="cpu")[0]
            jax.config.update("jax_default_device", cpu)
            log.warning("auron.tpu.placement=host: stage compute forced "
                        "onto the host XLA backend")
            _info = PlacementInfo("cpu", "unknown (not initialized)", -1.0,
                                  policy)
            return _info
        platform = jax.default_backend()
        if platform == "cpu":
            if policy == "device":
                raise RuntimeError(
                    "auron.tpu.placement=device but jax found no "
                    "accelerator (default backend is 'cpu'; "
                    f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
            _info = PlacementInfo("cpu", platform, 0.0, policy)
            return _info
        rtt = _measure_rtt_ms()
        threshold = config.PLACEMENT_RTT_THRESHOLD_MS.get()
        if policy == "auto" and rtt > threshold:
            cpu = jax.local_devices(backend="cpu")[0]
            jax.config.update("jax_default_device", cpu)
            log.warning(
                "placing stage compute on host XLA backend: measured "
                "accelerator dispatch RTT %.1f ms > %.1f ms threshold; "
                "force with auron.tpu.placement", rtt, threshold)
            _info = PlacementInfo("cpu", platform, rtt, policy)
        else:
            _info = PlacementInfo(platform, platform, rtt, policy)
        return _info


def placement_info() -> Optional[PlacementInfo]:
    return _info


def host_resident() -> bool:
    """True when per-batch columns should live as numpy arrays (compute
    pinned to host XLA): glue ops then run as numpy with nanosecond
    dispatch while the fused loops stay jit'd (see xputil.py).  Before
    placement is decided, fall back to the default backend — tests run
    with JAX_PLATFORMS=cpu and get the fast path; a live accelerator
    keeps device residency."""
    if _info is not None:
        return _info.device_kind == "cpu"
    import jax
    return jax.default_backend() == "cpu"


def refuse_chip_contention(env: dict, who: str) -> None:
    """One process per chip: a parent whose engine runs on the
    accelerator holds it, and a child that opens it too fails or hangs.
    So while this process has placed compute on an accelerator, only a
    child whose JAX_PLATFORMS (in the spawn `env`) says `cpu` may be
    spawned; anything else raises.  Chip-per-worker pinning is not
    implemented.  Reads the placement decision only — a parent that
    never started the engine is not made to open the chip by asking."""
    platforms = env.get("JAX_PLATFORMS") or ""
    holds = (_info is not None and _info.policy != "host"
             and _info.default_platform != "cpu")
    if holds and platforms != "cpu":
        raise RuntimeError(
            f"refusing to spawn {who}: this process holds the "
            f"{_info.default_platform} and the child's JAX_PLATFORMS="
            f"{platforms!r} would open it too; spawn it with "
            f"JAX_PLATFORMS=cpu")

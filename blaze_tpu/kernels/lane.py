"""Scatter/hash lane selection for the Pallas kernel layer (ISSUE 9).

The scatter-shaped hot paths — open-addressing hash-table update and
radix partitioning — run through one of three lanes:

  * ``scatter``   — the original whole-batch XLA scatter formulation
                    (parallel/stage.py, parallel/collective.py).  Always
                    available; the verified reference.
  * ``pallas``    — the Mosaic-compiled Pallas kernels (kernels/
                    hash_update.py, kernels/radix.py) with the table /
                    partition cursors resident in VMEM.  TPU backends.
  * ``interpret`` — the same Pallas kernels through the interpreter:
                    traceable on any backend, bit-identical to the
                    compiled kernel by construction.  CPU CI coverage
                    and the parity oracle for tests.

One knob drives the choice (`auron.tpu.kernels.pallas` = auto/on/off):
`auto` takes the Pallas lane only where Mosaic compiles it; `on` forces
the kernel layer (interpret off-TPU — tests, benches, parity sweeps —
and an ERROR on a TPU whose compiler refuses the kernel, never a silent
degrade); `off` pins the scatter formulation.

`MOSAIC_REFUSED` is the record of what the compiler said on the chip.
Both kernels walk rows serially with scalar loads/stores at
data-dependent lane indices of VMEM refs, which Mosaic does not lower;
moving the walk state to SMEM compiles but overflows the 1 MiB SMEM at
65,536-row batches and ran 3x slower than XLA's stable argsort at 32,768
(CHANGES.md PR 21).  Until the kernels are redesigned (ROADMAP S4) the
default path on TPU runs the scatter formulation BY DECISION.

Lane resolution happens HOST-SIDE (at program build / dispatch time,
never inside a traced computation) so the resolved lane can key every
jit/fold cache — flipping the knob retraces instead of serving a stale
program.  Each resolution is counted in xla_stats and surfaced in the
explain_analyze footer; the `pallas-kernel` fault site injects scripted
lane failures which degrade to the scatter formulation (lossless by the
bit-identity contract — the chaos suite proves it).
"""

from __future__ import annotations

_VALID = ("auto", "on", "off")

# kind -> the compiler's message, verbatim (jax 0.9.0 / libtpu 0.0.34,
# TPU v5e).  A kind listed here is out of the TPU lane set.  The tier-1
# test lowers both kernels for the TPU platform and fails when this
# record goes stale, so a redesigned kernel re-enters `auto` by deleting
# its entry.
MOSAIC_REFUSED = {
    "hash": "Cannot store scalars to VMEM",
    "partition": "Cannot store scalars to VMEM",
}


def knob() -> str:
    """The raw `auron.tpu.kernels.pallas` setting (auto/on/off)."""
    from blaze_tpu import config
    v = str(config.KERNELS_PALLAS.get()).strip().lower()
    return v if v in _VALID else "auto"


def resolve(kind: str) -> str:
    """Resolve the lane for one kernel dispatch: 'pallas' | 'interpret'
    | 'scatter'.  `kind` is 'hash' or 'partition' (the xla_stats
    bucket).  Host-side only — the result is a static trace-time choice
    and must be part of any cache key that closes over it."""
    from blaze_tpu import faults
    from blaze_tpu.bridge import xla_stats

    mode = knob()
    if mode == "off":
        lane = "scatter"
    else:
        import jax
        on_tpu = jax.default_backend() == "tpu"
        refused = MOSAIC_REFUSED.get(kind) if on_tpu else None
        if mode == "on":
            if refused:
                raise RuntimeError(
                    f"auron.tpu.kernels.pallas=on but Mosaic refuses the "
                    f"{kind!r} kernel on this TPU: {refused}")
            lane = "pallas" if on_tpu else "interpret"
        else:  # auto: Mosaic where it compiles, scatter elsewhere
            lane = "pallas" if on_tpu and not refused else "scatter"
    if lane != "scatter":
        try:
            faults.maybe_fail("pallas-kernel", kind=kind)
        except faults.InjectedFault:
            # scripted chaos: the kernel lane "fails" and the dispatch
            # degrades to the scatter formulation — identical results
            # by the bit-identity contract, never a new failure mode
            xla_stats.note_scatter_lane_fault()
            lane = "scatter"
    xla_stats.note_scatter_lane(kind, lane)
    return lane


def vmem_budget() -> int:
    from blaze_tpu import config
    return int(config.KERNELS_PALLAS_VMEM_BUDGET.get())


def decline(kind: str, reason: str) -> None:
    """A kernel-lane dispatch fell outside the kernel's envelope
    (VMEM footprint, shape) and degraded to the scatter formulation."""
    from blaze_tpu.bridge import xla_stats
    xla_stats.note_scatter_lane_decline()

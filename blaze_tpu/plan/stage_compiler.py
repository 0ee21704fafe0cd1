"""Stage compiler: eligible fused-agg pipelines -> StageProgram.

The whole-stage expression pass (exprs/program.py, PR 3) collapsed
filter/project chains into one program *per batch*; the fused-agg pass
(plan/fused.py) made the agg loop body one program *per batch*.  Both
still pay a Python dispatch per batch x operator.  This pass walks a map
stage's operator chain and, when the whole post-scan pipeline is
traceable, emits a `StageProgram` the device-resident loop
(runtime/loop.py) folds in chunks — ONE dispatch per chunk of batches,
the Flare whole-stage-compilation analog.

Eligibility (anything else stays on the staged per-batch executor):
  * the stage root (under CoalesceBatches re-batching) is a
    FusedPartialAggExec on the HASH lane (`_ranges is None`) — the dense
    lane already has its own windowed fold (fused.dense_fold);
  * the filter/project chain traced (`_prepare` survived the
    jax.eval_shape probe — no strings / host-only exprs in the chain);
  * every group key is fixed-width or a utf8 column that arrives
    dictionary-encoded (its int32 codes are a key lane; the chain may only
    pass codes through, `fused._dict_chain_safe`), and there is at least
    one group key; an Expand in the chain is folded a projection list a
    step (`StageProgram.expand`);
  * the source plan is re-executable, so a wholesale fallback can re-run
    the partition from scratch losslessly (the loop falls back only
    while it has emitted nothing: runtime/loop.py).

One `StageProgram` fingerprint = (chain cache key, reduce kinds, key
dtypes, acc dtypes).  The loop's fold program is cached per
fingerprint; capacity rungs and chunk widths become jit signatures
inside that one program, so steady state sees zero recompiles
(stage_loop_programs_built / stage_loop_program_cache_hits account the
fingerprint-level lookups).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Optional, Tuple

from blaze_tpu import config
from blaze_tpu.bridge import xla_stats

_retry_local = threading.local()


class decline_loop_scope:
    """`with decline_loop_scope():` — stage_loop_active() is False on
    this thread for the duration.  The task retry loop (bridge/tasks.py)
    wraps attempts 2..n in it: a retried task takes the most
    conservative path, since the loop is an optimization and was live
    during the attempt that just failed."""

    def __enter__(self):
        _retry_local.decline = getattr(_retry_local, "decline", 0) + 1

    def __exit__(self, *exc):
        _retry_local.decline -= 1
        return False


class StageLoopIneligible(RuntimeError):
    """The stage does not compile to a device-resident loop; the caller
    uses the staged per-batch executor (not an error, a verdict)."""


@dataclass(frozen=True)
class StageProgram:
    """A compiled stage pipeline the runtime loop can fold.

    `agg` is the live FusedPartialAggExec the program was compiled from:
    it owns the source plan, the output schema and the drain/emission
    helpers; everything the jit'd fold body needs (prepare fn, reduce
    kinds, dtypes) is captured here so the fold cache never keys on the
    plan instance.
    """
    agg: Any                       # FusedPartialAggExec
    prepare: Any                   # traced chain evaluator (fused._make_prepare)
    prepare_key: Any               # fused chain cache key
    kinds: Tuple[str, ...]         # reduce kinds per agg spec
    key_dtypes: Tuple[Any, ...]    # jnp dtypes of the group keys
    acc_dtypes: Tuple[Any, ...]    # jnp dtypes of the accumulators
    fingerprint: Tuple             # process-wide program identity
    # per-group-key SOURCE column index when the key is dict-encoded
    # utf8 (codes fold as int32; the loop captures each stream's last
    # dictionary to decode the drain); None entries are plain keys
    dict_keys: Tuple[Any, ...] = ()
    # the specs whose kind is "sum" over a decimal's unscaled integer:
    # the fold bounds those sums so that none can pass 64 bits unseen
    decimal_sums: Tuple[int, ...] = ()
    # projection lists of the chain's Expand (0: none): the fold runs
    # `prepare` once a list a batch, `prepare`'s third argument says which
    expand: int = 0

    @property
    def source(self):
        return self.agg._source

    @property
    def out_schema(self):
        return self.agg.schema


def stage_loop_mode() -> str:
    return config.STAGE_DEVICE_LOOP_ENABLE.get().strip().lower()


def stage_loop_active() -> bool:
    """'on' forces the loop wherever it compiles (tests on CPU
    hosts); 'auto' runs it only for device-resident compute, where the
    per-batch dispatch RTT it amortizes actually exists — on host
    placement the staged Arrow lanes win."""
    if getattr(_retry_local, "decline", 0) > 0:
        return False
    mode = stage_loop_mode()
    if mode == "on":
        return True
    if mode != "auto":
        return False
    from blaze_tpu.bridge.placement import host_resident
    return not host_resident()


# insertion-ordered; bounded like fused._PREPARE_CACHE
_SEEN_FINGERPRINTS: dict = {}
_SEEN_LIMIT = 256


def compile_fused_agg(agg) -> StageProgram:
    """StageProgram for one FusedPartialAggExec, or StageLoopIneligible
    with the reason (surfaced in explain / tracing)."""
    from blaze_tpu.plan.fused import FusedPartialAggExec
    if not isinstance(agg, FusedPartialAggExec):
        raise StageLoopIneligible(f"stage root {type(agg).__name__} is "
                                  "not a fused partial agg")
    if agg._ranges is not None:
        raise StageLoopIneligible("dense lane has its own windowed fold")
    dict_keys: Tuple[Any, ...] = ()
    if agg._has_var_keys:
        from blaze_tpu.plan.fused import _dict_key_sources
        admitted = _dict_key_sources(agg)
        if admitted is None:
            # a string key just evicted this stage from the device loop
            xla_stats.note_encoding(host_evictions_string=1)
            raise StageLoopIneligible("variable-width group keys")
        dict_keys = admitted
    if agg._prepare is None:
        raise StageLoopIneligible("filter/project chain did not trace")
    if not agg._group_exprs:
        raise StageLoopIneligible("no group keys")
    if not getattr(agg._source, "reexecutable", True):
        raise StageLoopIneligible("source is not re-executable: wholesale "
                                  "fallback could not re-run the partition")
    import jax.numpy as jnp
    kinds = tuple(rk for rk, _ok, _a in agg._specs)
    key_dtypes = tuple(
        jnp.int32 if dict_keys and dict_keys[i] is not None
        else e.data_type(agg._in_schema).jnp_dtype()
        for i, (e, _n) in enumerate(agg._group_exprs))
    acc_dtypes = tuple(agg._acc_dtypes())
    fingerprint = (agg._prepare_key, kinds,
                   tuple(str(d) for d in key_dtypes),
                   tuple(str(d) for d in acc_dtypes), dict_keys)
    if agg._decimal_specs:
        # (appended only then: a program without a decimal keeps the
        # fingerprint it had)
        fingerprint += (("decimal", agg._decimal_specs),)
    hit = fingerprint in _SEEN_FINGERPRINTS
    xla_stats.note_stage_program(cache_hit=hit)
    if not hit:
        if len(_SEEN_FINGERPRINTS) >= _SEEN_LIMIT:
            _SEEN_FINGERPRINTS.pop(next(iter(_SEEN_FINGERPRINTS)))
        _SEEN_FINGERPRINTS[fingerprint] = True
    decimal_sums = tuple(i for i in agg._decimal_specs if kinds[i] == "sum")
    return StageProgram(agg=agg, prepare=agg._prepare,
                        prepare_key=agg._prepare_key, kinds=kinds,
                        key_dtypes=key_dtypes, acc_dtypes=acc_dtypes,
                        fingerprint=fingerprint, dict_keys=dict_keys,
                        decimal_sums=decimal_sums, expand=agg._expand)


def try_compile(agg) -> Optional[StageProgram]:
    """compile_fused_agg, with ineligibility as None (the common caller
    shape: `prog = try_compile(agg); if prog is None: staged path`)."""
    try:
        return compile_fused_agg(agg)
    except StageLoopIneligible:
        return None


def compile_task_plan(plan) -> Optional[StageProgram]:
    """Stage-level entry for the scheduler: unwrap re-batching nodes and
    compile the stage root.  None = run the staged per-batch executor."""
    if not stage_loop_active():
        return None
    from blaze_tpu.plan.planner import CoalesceBatchesExec
    node = plan
    while isinstance(node, CoalesceBatchesExec):
        node = node.children[0]
    return try_compile(node)

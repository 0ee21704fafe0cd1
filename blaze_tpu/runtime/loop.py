"""Persistent device loop for compiled stage programs.

The staged executor dispatches one XLA program per batch (the fused
chain step) plus a host sync for the overflow scalar, so at a
millisecond per dispatch round trip (1.0 ms on a directly attached v5e,
CHANGES.md PR 21) the engine is dispatch-bound, not compute-bound.
This loop folds a CHUNK of bucket-padded batches per dispatch:
`lax.while_loop` runs chain + probe-insert + accumulate for every batch
of the chunk inside ONE program, carrying the agg hash table across
iterations with buffer donation, so Python-side dispatches per
partition drop from O(batches x operators) to O(chunks).  The chunk is
assembled by one program as well (`plan/fused.py` `_window_jit`: the
batches stacked, a tail chunk widened with masked-out batches to the one
width the fold is compiled for, the selected lanes a batch counted), so
a chunk is two dispatches and two readbacks.

A stage whose chain holds an Expand (plan/fused.py `_absorbable_chain`)
folds each batch once a projection list inside the same program
(`runtime.stage_loop_fold_expand`): a step is a (batch, list) pair, the
expanded rows never exist as batches, and a chunk is cut to as many
batches as keep its steps at the configured width.

Capacity is reserved BEFORE a chunk is folded, in every agg mode: at
the chunk boundary the host already holds the chunk's row counts and
the table's group count (they ride the overflow scalars' round trip),
so the table is sized for `groups + rows about to arrive` — a plain
allocation while it is empty, one rehash otherwise, over as many lanes
as that group count needs (`rehash_width`) — and overflow is a rare
backstop, not the growth policy.  The table is the largest object
a task keeps on its chip, so it is a memory-manager consumer of that
chip (`_TableCharge`): charged before each allocation and rehash (the
old and the new table together while both live), released after the
drain or the switch to pass-through.  A budget with no room for it
declines the partition like any other ineligibility, before anything
was emitted.

Partial-aggregation skipping (the AGG_TRIGGER_PARTIAL_SKIPPING analog,
ref agg_table.rs:108-122; the three `auron.tpu.partialAgg.skipping.*`
keys).  A PARTIAL program driven by `execute_loop` measures groups per
LIVE row: the fold returns the rows it inserted (after the chain's
filter, which the host cannot count beforehand) beside the table's
group count.  While fewer than `minRows` live rows are in, the fold
stops at the first batch boundary that reaches them and the host
resumes the SAME chunk there, as it does after an overflow; from then
on the cumulative ratio is looked at after every fold.  Once
`groups / live rows > ratio` the loop SWITCHES: it drains what the
table holds, releases it, and passes the rest of the partition through
un-aggregated in accumulator form (`_pass_through`), one elementwise
program per chunk over the same stacked window.  The FINAL aggregation
downstream merges raw rows exactly as it merges partial groups.  FINAL,
merge and complete programs never switch (nothing merges after them),
nor do programs with dictionary-encoded keys or an Expand, nor `run_partition`'s
direct caller (the device-to-device exchange wants ONE carry).

Discipline inherited from the staged path, kept intact:

  * An overflow is undone (hash_agg_step): the first batch that
    overflows gives its claims back and moves no accumulator, so the
    table is logically what it was (the same used slots, and the same
    keys, null bits and sums at them), and every later batch of the
    chunk is masked to a no-op; the host re-sizes + rehashes and resumes
    the SAME chunk at the overflow batch (`stage_loop_undone_steps`
    counts the steps taken back: the fold's and a rehash's).  Past
    `_MAX_SLOTS` every mode falls back wholesale.
  * Fallback only before the first emission.  Until a partition
    switches (and for ever, if it never does) the loop has emitted
    nothing, so `StageLoopFallback` and the staged re-run are lossless.
    A switched partition HAS emitted: from then on nothing is turned
    into a fallback, and a fault or error is the task's failure (the
    retry re-runs the partition whole, staged; first-wins commit keeps
    one output).
  * Cancellation/deadline (PR 7): the query token is checked between
    chunks (and per source batch by the metered stream), so teardown
    latency is bounded by one chunk, folded or passed through.
  * Fault injection (PR 4): the `device-loop` site fires at every chunk
    boundary; before the switch an injected fault becomes a wholesale
    fallback, after it a retryable task failure, never a divergent
    result.
"""

from __future__ import annotations

import functools
import math
import threading
from contextlib import contextmanager, nullcontext
from typing import NamedTuple

import jax
import jax.numpy as jnp

from blaze_tpu import config, faults
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.bridge.context import current_task
from blaze_tpu.bridge.xla_stats import meter_jit
from blaze_tpu.memory import MemConsumer, MemManager
from blaze_tpu.schema import TypeId
from blaze_tpu.parallel.stage import (hash_agg_step, init_hash_carry,
                                      join_key, key_valid_lanes,
                                      normalize_float_keys, rehash_width,
                                      row_contribution)
from blaze_tpu.xputil import to_host

# hard ceiling on the table size: past this the partition is cheaper to
# re-run staged (which streams and skips) than to hold on device
_MAX_SLOTS = 1 << 24

# Table sizing.  The table is re-sized when the groups it holds plus the
# rows about to arrive pass _TRIGGER_LOAD of its slots, to the power of
# two that puts them at _TARGET_LOAD.  The two are 2x apart so that a
# table never re-sizes at successive chunks of equal cardinality.
# Chosen from the fold's per-batch device time against slots and load
# on a TPU v5e (PERF.md section 6, PR 25).
_TRIGGER_LOAD = 0.25
_TARGET_LOAD = 0.125

# A decimal's int64 value lane: the partition is declined once the bound on
# its sums (the fold's `mass`, float32) reaches this.  A quarter of 2^63:
# the bound is kept in float32 and summed over the calls
_DECIMAL_MASS_LIMIT = float(1 << 61)

# the fold's `look` argument when no first look is pending: more live
# rows than a chunk can hold, so the fold never stops for it
_NO_LOOK = (1 << 31) - 1


class StageLoopFallback(RuntimeError):
    """The loop declined or failed BEFORE emitting anything; the caller
    re-runs the partition through the staged per-batch executor.  Like
    DeviceExchangeError, this is an optimization bailing out — never a
    new failure mode."""


@functools.lru_cache(maxsize=128)
def _slot_bytes(key_dtypes, kinds, acc_dtypes) -> int:
    """Bytes one slot of the table takes on the device, over all of the
    carry's arrays."""
    carry = jax.eval_shape(lambda: init_hash_carry(
        list(key_dtypes), kinds, list(acc_dtypes), 1))
    return sum(a.dtype.itemsize for a in jax.tree_util.tree_leaves(carry)
               if a.ndim)


class _TableCharge(MemConsumer):
    """A task's hash table as the memory manager sees it, on the task's
    chip.  It cannot be shed: a table in the middle of a fold has no
    lower tier, so `spill` releases nothing.  Nor does it press the
    chip's other consumers out: they belong to other tasks' threads.
    What it can do is decline: `hold` raises StageLoopFallback when the
    chip's budget has no room for the tables asked for, and the staged
    path, whose state spills, re-runs the partition."""

    def __init__(self, program):
        super().__init__("stage_loop_table")
        self.slot_bytes = _slot_bytes(tuple(program.key_dtypes),
                                      tuple(program.kinds),
                                      tuple(program.acc_dtypes))
        self.peak = 0

    def hold(self, *tables: int) -> None:
        """Charge tables of so many slots each, before they are made."""
        if self._manager is None:
            self.set_spillable(MemManager.get())
        manager, want = self._manager, sum(tables) * self.slot_bytes
        if (manager.chip_used(self.chip) - self.mem_used + want
                > manager.total):
            raise StageLoopFallback(
                f"memory budget of {manager.total} bytes has no room for "
                f"a table of {max(tables)} slots")
        self.update_mem_used(want)
        self.peak = max(self.peak, want)

    def spill(self) -> int:
        return 0

    def release(self) -> None:
        self._mem_used = 0
        self.unregister()


# fingerprint -> jit'd chunk fold or pass-through; bounded FIFO like
# fused's step caches
_FOLD_CACHE: dict = {}
_FOLD_CACHE_LOCK = threading.Lock()
_FOLD_LIMIT = 128

# -- regrow fences (overlapped exchange) ------------------------------------
# The overlapped exchange (plan/stages.py) keeps previous chunks'
# all-to-all collectives in flight while this loop folds the next chunk.
# A hash-table regrow is the one point where that is unsafe: the rehash
# doubles the live table while in-flight tickets still pin their
# send/receive buffers, and the overflow/rehash contract is atomic —
# so the overlap scheduler registers a fence that drains every in-flight
# ticket, and the loop runs all fences RIGHT BEFORE each regrow.

_FENCE_LOCK = threading.Lock()
_FENCES: list = []


@contextmanager
def exchange_fence(fn):
    """Register `fn` to run before every hash-table regrow for the
    duration of the `with` body.  Fences are global (not per-query):
    an extra drain of another query's tickets only adds waiting, never
    changes results."""
    with _FENCE_LOCK:
        _FENCES.append(fn)
    try:
        yield
    finally:
        with _FENCE_LOCK:
            _FENCES.remove(fn)


def _run_fences() -> None:
    with _FENCE_LOCK:
        fences = list(_FENCES)
    for fn in fences:
        fn()


def _cached(skey, build):
    # under a lock: a wave's tasks ask for the same program at the same
    # moment, and each must get the ONE wrapper whose traces the next
    # query finds again
    with _FOLD_CACHE_LOCK:
        fn = _FOLD_CACHE.get(skey)
        if fn is None:
            if len(_FOLD_CACHE) >= _FOLD_LIMIT:
                _FOLD_CACHE.pop(next(iter(_FOLD_CACHE)))
            fn = _FOLD_CACHE[skey] = build()
        return fn


def _batch_of(cols_stacked, b):
    return tuple(None if col is None else (col[0][b], col[1][b])
                 for col in cols_stacked)


def _fold_factory(program, donate: bool):
    prepare = program.prepare
    kinds = program.kinds
    decimal_sums = program.decimal_sums
    # a chain with an Expand folds each batch once a projection list: a
    # STEP is (batch, list), numbered batch-major, and `start`, `look`
    # and `resume` count steps (without an Expand a step is a batch)
    fan = program.expand or 1

    def fold_impl(carry, cols_stacked, masks, start, look):
        steps = masks.shape[0] * fan

        def body(state):
            i, c, ovf_seen, first_ovf, folded, rounds, undone, *mass = state
            b = i // fan
            which = (i % fan,) if program.expand else ()
            kd, kv, ad, av, m = prepare(_batch_of(cols_stacked, b), masks[b],
                                        *which)
            # once a step overflows, later steps fold as no-ops: the
            # table stays what it was before the overflow (hash_agg_step
            # takes an overflowing step's claims back), so the host can
            # regrow and resume mid-chunk
            live = jnp.logical_and(m, jnp.logical_not(ovf_seen))
            specs = [(k, d, v) for k, d, v in zip(kinds, ad, av)]
            new_c, ovf, _ng, step_rounds = hash_agg_step(
                c, list(zip(kd, kv)), specs, live)
            hit = ovf > 0
            first_ovf = jnp.where(hit & ~ovf_seen, i, first_ovf)
            # the rows of an overflowing step are not in the table
            nlive = jnp.sum(live, dtype=jnp.int32)
            folded += jnp.where(hit, 0, nlive)
            # a decimal sums as its unscaled integer in an int64 lane: no
            # group's sum can pass the live rows times the largest
            # magnitude among them, summed over every batch folded
            # (a program without a decimal sum carries no such bound)
            for j in decimal_sums:
                big = jnp.max(jnp.where(live & av[j], jnp.abs(ad[j]), 0))
                mass[0] += big.astype(jnp.float32) * nlive.astype(jnp.float32)
            return (i + 1, new_c, jnp.logical_or(ovf_seen, hit), first_ovf,
                    folded, rounds + step_rounds, undone + hit, *mass)

        def more(state):
            i, _c, _ovf_seen, _first_ovf, folded, *_rest = state
            # `look` live rows are in: stop at this step's boundary, so
            # the host can take its first look at groups per live row
            return (i < steps) & (folded < look)

        zero = jnp.asarray(0, jnp.int32)
        mass = (jnp.asarray(0, jnp.float32),) if decimal_sums else ()
        i, carry, ovf_seen, first_ovf, folded, rounds, undone, *mass = \
            jax.lax.while_loop(
                more, body, (start, carry, jnp.asarray(False), zero, zero,
                             jnp.zeros(2, jnp.int32), zero, *mass))
        # the table's group count, the live rows this call inserted and
        # the probe rounds it ran (full width, narrow width) ride the
        # overflow scalars' round trip: the host sizes the next chunk's
        # table from the first and judges the partial-skip ratio from the
        # first two.  `resume` is the step to go on from: the one that
        # overflowed, else the first one not folded (the chunk's steps
        # when nothing stopped the fold).  `undone` counts the steps
        # whose claims hash_agg_step took back
        resume = jnp.where(ovf_seen, first_ovf, i)
        # `mass`, beside the overflow scalars: what the decimal lanes'
        # sums are bounded by so far in this call (the host adds the
        # calls up and declines the partition before a sum could wrap)
        return (carry, ovf_seen, resume, carry.groups, folded, rounds, undone,
                *mass)

    kwargs = {"donate_argnums": (0,)} if donate else {}
    # (the function's name is what the trace matches a fold by; the
    # kernel's says which fold: `jit_fold_impl__runtime_stage_loop` or
    # `jit_fold_impl__runtime_stage_loop_fold_expand`)
    if program.expand:
        return _cached(
            (program.fingerprint, bool(donate)),
            lambda: meter_jit(fold_impl,
                              name="runtime.stage_loop_fold_expand",
                              **kwargs))
    return _cached(
        (program.fingerprint, bool(donate)),
        lambda: meter_jit(fold_impl, name="runtime.stage_loop", **kwargs))


def _passthrough_factory(program):
    """The chain alone over one stacked window, for a partition that
    stopped grouping: every batch from `start` on leaves as flat device
    columns in the partial output's accumulator form, with the mask of
    its live rows."""
    prepare = program.prepare
    kinds = program.kinds
    key_dtypes = program.key_dtypes
    acc_dtypes = program.acc_dtypes

    def passthrough_impl(cols_stacked, masks, start):
        def one(b):
            kd, kv, ad, av, m = prepare(_batch_of(cols_stacked, b), masks[b])
            live = jnp.logical_and(m, b >= start)
            kd, kv = zip(*normalize_float_keys(
                [(d.astype(dt), v) for d, v, dt in zip(kd, kv, key_dtypes)]))
            accs, acc_valid = zip(*(
                row_contribution(k, d, v, live, dt)
                for k, d, v, dt in zip(kinds, ad, av, acc_dtypes)))
            return kd, kv, accs, acc_valid, live

        out = jax.lax.map(one, jnp.arange(masks.shape[0], dtype=jnp.int32))
        return jax.tree_util.tree_map(lambda a: a.reshape(-1), out)

    return _cached(
        ("passthrough", program.fingerprint),
        lambda: meter_jit(passthrough_impl,
                          name="runtime.stage_loop_passthrough"))


def _donate_active() -> bool:
    """Donation only pays where buffers are device-resident; XLA CPU
    rejects it with a warning per call, so gate on backend."""
    return (config.STAGE_DEVICE_LOOP_DONATE.get()
            and jax.default_backend() != "cpu")


def loop_chunk_batches() -> int:
    """Configured chunk width, shrunk for degraded queries: the memory
    degradation ladder (serving/context.py) halves the chunk per shrink
    level — same policy as ops.base.effective_batch_size, floor 1."""
    from blaze_tpu.bridge.context import active_query
    chunk = max(1, config.STAGE_DEVICE_LOOP_CHUNK.get())
    q = active_query()
    if q is not None and q.capacity_shrink:
        chunk = max(1, chunk >> q.capacity_shrink)
    return chunk


def _slots_for(need: int, floor: int) -> int:
    """The power of two that holds `need` groups at _TARGET_LOAD, within
    [floor, _MAX_SLOTS]."""
    from blaze_tpu.plan.fused import _pow2
    return min(_MAX_SLOTS, max(floor, _pow2(math.ceil(need / _TARGET_LOAD))))


class _Unfolded(NamedTuple):
    """What a partition that switched to pass-through has not folded:
    the batches of the current (padded) window from `start` on, and the
    windows still to come."""
    cols_stacked: tuple
    masks: jax.Array
    count: int      # real batches of the window, before its padding
    start: int
    chunk: int      # the window's index in the partition
    windows: object


def _may_switch(program) -> bool:
    """Only a PARTIAL aggregation has a merge downstream that makes
    groups of passed-through rows."""
    agg = program.agg
    # (nor does a fold over an Expand switch: the pass-through program
    # lays a batch out once, not once a projection list)
    return (not agg._complete and not agg._grow and not program.expand
            and config.PARTIAL_AGG_SKIPPING_ENABLE.get())


def run_partition(program, partition: int, ctx: str = "",
                  source_stream=None, table=None):
    """Fold one partition through the stage program; returns the final
    HashAggCarry, which holds every row: this entry never switches to
    pass-through (its caller, the device-to-device exchange, drains ONE
    carry).  Raises StageLoopFallback on any ineligibility or failure —
    nothing has been emitted at that point, so the caller's staged
    re-run is lossless.  Cancellation (QueryCancelled / TaskKilledError
    / deadline) propagates untranslated.

    The table's capacity sequence is a function of the input alone (rows
    per batch, groups so far), never of timing: a repeat of the same
    partition walks the same sizes and loads no new program.

    `table` is the caller's charge for the carry (`charged_table`), held
    until the caller has drained it; without one the charge ends with
    the fold."""
    with (charged_table(program) if table is None
          else nullcontext(table)) as table:
        carry, _rest = _fold_partition(program, partition, ctx,
                                       source_stream, False, table)
    return carry


@contextmanager
def charged_table(program):
    """The memory manager's charge for one partition's table: whatever
    the body holds is released when it ends, however it ends."""
    table = _TableCharge(program)
    try:
        yield table
    finally:
        table.release()


def _fold_partition(program, partition: int, ctx: str, source_stream,
                    may_switch: bool, table: _TableCharge):
    """(carry, None), or (carry, _Unfolded) when `may_switch` and the
    table made more than `ratio` groups a live row: the carry holds what
    was folded until then and the rest is the caller's to pass through.
    Emits nothing either way, so StageLoopFallback is lossless here.
    The carry is charged to `table`, which the caller releases."""
    from blaze_tpu.plan.fused import _batch_windows, _pow2, _rehash_jit
    task = current_task()
    q = task.query
    if q is None:
        from blaze_tpu.bridge.context import active_query
        q = active_query()
    if q is not None and q.force_agg_passthrough:
        raise StageLoopFallback("query degraded to agg pass-through")
    # with an Expand a chunk's batches fold `fan` times each: the chunk is
    # cut to as many batches as keep its STEPS at the configured width, so
    # the table is sized for the rows a chunk's steps can bring and not
    # for `fan` chunks of them
    fan = program.expand or 1
    chunk = max(1, loop_chunk_batches() // fan)
    fold = _fold_factory(program, _donate_active())
    floor = _pow2(config.ON_DEVICE_AGG_CAPACITY.get())
    min_rows = min(_NO_LOOK,
                   max(1, config.PARTIAL_AGG_SKIPPING_MIN_ROWS.get()))
    ratio = config.PARTIAL_AGG_SKIPPING_RATIO.get()
    stream = (source_stream if source_stream is not None
              else program.agg.source_stream(partition))
    windows = _batch_windows(stream, chunk, pad_tail=True)
    batches = rows = lanes = fold_calls = regrows = reserves = undone = 0
    # (old table's slots, groups it held, new slots, lanes re-inserted)
    rehashes = []
    full_rounds = narrow_rounds = 0
    ci = groups = live_folded = 0
    decimal_mass = 0.0
    slots, carry = floor, None  # allocated at the first chunk, for it
    rest = None

    def fresh(n):
        with tracing.span("table_init", slots=n, device=task.device_id):
            return init_hash_carry(list(program.key_dtypes), program.kinds,
                                   list(program.acc_dtypes), n)

    def resized(want):
        """The table at `want` slots or more: a plain allocation while
        it holds nothing, one rehash otherwise.  Charged before it is
        made."""
        nonlocal undone
        while want <= _MAX_SLOTS:
            if carry is None or groups == 0:
                table.hold(want)
                return fresh(want), want
            table.hold(slots, want)
            # the live slots are compacted to `lanes` lanes before they
            # are re-inserted; `groups` is the table's own count, read
            # after the fold that filled it
            lanes = rehash_width(groups, slots)
            if groups > lanes:  # not an `assert`: -O must not drop it
                raise AssertionError(f"a rehash over {lanes} lanes would "
                                     f"drop groups of {groups}")
            with tracing.span("table_rehash", stage=ctx,
                              partition=partition, chunk=ci,
                              from_slots=slots, to_slots=want,
                              groups=groups, lanes=lanes,
                              device=task.device_id):
                _run_fences()  # drain in-flight overlapped exchanges
                rehashes.append((slots, groups, want, lanes))
                bigger, re_ovf, _, _ = _rehash_jit(program.kinds, want,
                                                   lanes)(carry)
                fits = int(to_host(re_ovf)) == 0
            if fits:
                table.hold(want)  # the old table goes with `carry`
                return bigger, want
            undone += 1  # the rehash is one step, and it took its claims back
            want *= 2  # rare probe clustering: double again
        raise StageLoopFallback(f"table would exceed {_MAX_SLOTS} slots")

    try:
        for cols_stacked, masks, batch_rows, count in windows:
            # chunk boundary: cooperative cancel, fault site, row counts.
            # The loop's host syncs are here and after each fold (the
            # overflow scalars with the table's group count and the
            # live rows inserted)
            task.check_running()
            faults.maybe_fail("device-loop", stage=ctx, chunk=ci)
            with tracing.span("stage_loop_chunk", stage=ctx,
                              partition=partition, chunk=ci,
                              batches=count, device=task.device_id) as attrs:
                # selected lanes per real batch, counted by the window's
                # program: before the chain's filter, so an upper bound
                # on the rows the fold will insert
                batch_rows = to_host(batch_rows).tolist()
                if fan > 1:
                    # (batch, projection list) steps, batch-major: each
                    # brings the batch's rows again
                    attrs.update(expand=fan, rows_in=sum(batch_rows[:count]),
                                 rows_out=fan * sum(batch_rows[:count]))
                    batch_rows = [r for r in batch_rows for _ in range(fan)]
                    count *= fan
                # reserve before fold
                need = groups + sum(batch_rows)
                if carry is None or need > slots * _TRIGGER_LOAD:
                    want = _slots_for(need, floor)
                    if want > slots:
                        reserves += 1
                    if carry is None or want > slots:
                        carry, slots = resized(want)
                start = 0
                while start < count:
                    # the first look: stop the fold once `min_rows` live
                    # rows are in, not at the chunk's end
                    look = (min_rows - live_folded
                            if may_switch and live_folded < min_rows
                            else _NO_LOOK)
                    carry, *scalars = fold(
                        carry, cols_stacked, masks,
                        jnp.asarray(start, jnp.int32),
                        jnp.asarray(look, jnp.int32))
                    fold_calls += 1
                    # the host waits for the fold here
                    ovf_seen, resume, ngroups, nlive, rounds, nundone, \
                        *mass = to_host(tuple(scalars))
                    undone += int(nundone)
                    if mass:
                        decimal_mass += float(mass[0])
                        if decimal_mass >= _DECIMAL_MASS_LIMIT:
                            xla_stats.note_decimal(overflow_groups=1)
                            raise StageLoopFallback(
                                "a decimal sum may pass 64 bits")
                    full_rounds += int(rounds[0])
                    narrow_rounds += int(rounds[1])
                    groups = int(ngroups)
                    live_folded += int(nlive)
                    start = int(resume)
                    if bool(ovf_seen):
                        # residual overflow (probe clustering below the
                        # trigger load): size for what is left of the
                        # chunk, at least double, and resume at the
                        # overflow batch
                        need = groups + sum(batch_rows[start:])
                        carry, slots = resized(
                            max(slots * 2, _slots_for(need, floor)))
                        regrows += 1
                    elif may_switch and live_folded >= min_rows:
                        xla_stats.note_partial_agg_probe(live_folded,
                                                         groups)
                        if groups > ratio * live_folded:
                            rest = _Unfolded(cols_stacked, masks, count,
                                             start, ci, windows)
                            break
                # rows handed to the fold, and no others
                rows += sum(batch_rows[:start])
                batches += min(start, count) // fan
                lanes += min(start, count) * int(masks.shape[1])
            if rest is not None:
                break
            ci += 1
            task.loop_chunks = ci
    except faults.InjectedFault as e:
        # scripted chaos at the device-loop site: wholesale fallback,
        # not a task retry — the chaos soak asserts THIS path converges
        raise StageLoopFallback(f"injected fault: {e}") from e
    if carry is None:
        table.hold(slots)
        carry = fresh(slots)  # empty partition
    if rest is not None:
        program.agg.metrics.add("partial_skipped", 1)
        xla_stats.note_partial_agg_skip(live_folded)
    xla_stats.note_stage_loop_task(
        chunks=fold_calls, batches=batches, rows=rows, lanes=lanes,
        regrows=regrows, reserves=reserves, undone_steps=undone,
        rehash_lanes=sum(r[0] for r in rehashes),
        rehash_groups=sum(r[1] for r in rehashes),
        rehash_new_slots=sum(r[2] for r in rehashes),
        rehash_probe_lanes=sum(r[3] for r in rehashes), slots=slots,
        table_bytes=table.peak, chip=task.device_id,
        full_rounds=full_rounds, narrow_rounds=narrow_rounds,
        dispatches_avoided=max(0, batches - fold_calls))
    if program.agg._decimal_specs:
        xla_stats.note_decimal(stage_loop_rows=rows, chip=task.device_id)
    if fan > 1:
        xla_stats.note_dict(chip=task.device_id, expand_rows_out=rows)
    program.agg._note_lane(batches)
    return carry, rest


def _pass_through(program, rest: _Unfolded, partition: int, ctx: str):
    """The rest of a switched partition, un-aggregated: per chunk ONE
    program runs the chain and lays each live row out as the group it
    would have been alone, and the live rows leave through the fused
    node's emission.  No table is held.  The partition has emitted by
    now, so nothing raised here may become a StageLoopFallback."""
    from blaze_tpu.plan.fused import _used_slots
    task = current_task()
    agg = program.agg
    passthrough = _passthrough_factory(program)

    def chunks():
        # the boundary checks of the window the switch fell in ran
        # before its fold; after its last batch nothing of it is left
        if rest.start < rest.count:
            yield (rest.chunk, rest.cols_stacked, rest.masks, rest.count,
                   rest.start)
        for ci, (cols_stacked, masks, _rows, count) in enumerate(
                rest.windows, rest.chunk + 1):
            task.check_running()
            faults.maybe_fail("device-loop", stage=ctx, chunk=ci)
            yield ci, cols_stacked, masks, count, 0

    for ci, cols_stacked, masks, count, start in chunks():
        with tracing.span("partial_passthrough", stage=ctx,
                          partition=partition, chunk=ci,
                          batches=count - start):
            keys, key_valid, accs, acc_valid, live = passthrough(
                cols_stacked, masks, jnp.asarray(start, jnp.int32))
            sel, n = _used_slots(live)
            rb = (agg._take_to_arrow(sel, n, [(k,) for k in keys], accs,
                                     acc_valid, key_valid=key_valid)
                  if n else None)
        xla_stats.note_partial_agg_rows(n)
        agg._note_lane(count - start)
        task.loop_chunks = ci + 1
        if rb is not None:
            yield from agg._emit_chunks(rb)


def _dict_stream_guard(stream, utf8_cols, held):
    """Wrap a dict-key stage's source stream: every utf8 source column
    must arrive dictionary-encoded (the prepare traced int32 code slots
    for them — a plain utf8 batch, e.g. after encoder overflow, has no
    device form and must fall back BEFORE the fold sees it), and the key
    sources leave under ONE dictionary a column (`held`, a
    `batch.DictStream` over them).  A source may bring batches under
    unrelated dictionaries (a `UnionExec` hands on one child's batches
    after another's, each child with its own encoder or build side):
    their codes are remapped where they lie before they reach the table,
    and `held.dicts` decodes the drain."""
    from blaze_tpu.batch import DictColumn
    for batch in stream:
        for ci in utf8_cols:
            c = batch.columns[ci]
            if not isinstance(c, DictColumn) or c.dictionary is None:
                raise StageLoopFallback(
                    "utf8 source column arrived without dictionary "
                    "encoding (encoder overflow or unencoded source)")
        yield held.under_one_dictionary(batch)


def execute_loop(program, partition: int, ctx: str = ""):
    """Generator form for FusedPartialAggExec.execute: fold, then drain
    through the shared emission path (ColumnBatch chunks); a PARTIAL
    program whose table made more than `ratio` groups a live row drains
    early and passes the rest of the partition through un-aggregated
    (module docstring).  Raises StageLoopFallback only BEFORE the first
    yield; after it, whatever goes wrong is raised as it is and fails
    the task."""
    dict_keys = getattr(program, "dict_keys", ())
    if any(s is not None for s in dict_keys):
        from blaze_tpu.schema import TypeId
        utf8_cols = {i for i, f in enumerate(program.source.schema)
                     if f.data_type.id == TypeId.UTF8}
        from blaze_tpu.batch import DictStream
        held = DictStream(only={s for s in dict_keys if s is not None})
        stream = _dict_stream_guard(program.agg.source_stream(partition),
                                    utf8_cols, held)
        # no switch: codes decode through the stream's LAST dictionary,
        # and the guard may decline the partition at any batch, which
        # is lossless only while nothing has been emitted
        with charged_table(program) as table:
            carry = run_partition(program, partition, ctx=ctx,
                                  source_stream=stream, table=table)
            key_dicts = [held.dicts.get(s) if s is not None else None
                         for s in dict_keys]
            yield from program.agg._emit_hash(carry, key_dicts=key_dicts)
        return
    with charged_table(program) as table:
        carry, rest = _fold_partition(program, partition, ctx, None,
                                      _may_switch(program), table)
        yield from program.agg._emit_hash(carry)
        del carry  # the table is released before the rest is read
    if rest is not None:
        yield from _pass_through(program, rest, partition, ctx)


def drain_device(program, carry):
    """D2D drain: compact the carry's used slots ON DEVICE and cast to
    the stage out-schema storage dtypes, so the partitioned output feeds
    DeviceExchange without the rows leaving the device (only the slot
    mask and the indices cross, fused._used_slots).  Returns (datas,
    valids, n) — lists of length-n device arrays in output column
    order."""
    from blaze_tpu.plan.fused import _used_slots
    if any(s is not None for s in getattr(program, "dict_keys", ())):
        # dict-key stages never reach here (utf8 output columns exclude
        # the boundary from DeviceExchange), but raw codes must not leak
        # into an exchange if that ever changes
        raise StageLoopFallback("dict-encoded keys cannot drain D2D")
    sel, count = _used_slots(carry.used)
    if count == 0:
        return [], [], 0
    fields = list(program.out_schema)
    datas, valids = [], []
    i = 0
    # the key columns and their validity, read from the table's lanes and
    # its `owner` over the used slots alone
    key_valid = key_valid_lanes(jnp.take(carry.owner, sel)[:count],
                                len(carry.keys))
    for lanes, kv in zip(carry.keys, key_valid):
        dt = fields[i].data_type.jnp_dtype()
        i += 1
        datas.append(join_key([jnp.take(lane, sel)[:count]
                               for lane in lanes]).astype(dt))
        valids.append(kv)
    for (_rk, out_kind, _a), acc, av in zip(program.agg._specs,
                                            carry.accs, carry.acc_valid):
        t = fields[i].data_type
        i += 1
        data = jnp.take(acc, sel)[:count].astype(t.jnp_dtype())
        datas.append(data)
        if out_kind == "count":
            valids.append(jnp.ones((count,), dtype=bool))
            continue
        valid = jnp.take(av, sel)[:count]
        if out_kind == "sum" and t.id == TypeId.DECIMAL \
                and t.precision <= 18:
            # a sum past its type's bound is NULL (CheckOverflow), as
            # the Arrow emission makes it (fused._to_arrow); uncounted
            # here, where nothing reads the rows back
            valid = valid & (jnp.abs(data) < 10 ** t.precision)
        valids.append(valid)
    return datas, valids, count

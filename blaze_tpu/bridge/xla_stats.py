"""XLA compile + host<->device transfer accounting.

The two TPU-specific hazards the profiler must surface (ROADMAP north
star; Flare and the Arrow-interface papers identify the analogous
native/JVM and host/device boundary costs):

* recompilation — every new (shape, dtype, static-arg) signature at a
  jit boundary triggers a fresh XLA compile, and compiles dominate a
  cold start on the chip.  `meter_jit` wraps `jax.jit` call sites so each
  dispatch is classified compile vs cache-hit, compile time accumulates
  per kernel, and shape churn (many distinct signatures on one kernel)
  is flagged.
* transfer volume and time — every crossing of the host/device boundary
  goes through `xputil.to_host` / `xputil.to_device`, which call
  `note_d2h`/`note_h2d` with the bytes moved and the nanoseconds the
  calling thread spent there.

Compile detection is portable across jax versions: the traced Python
function only RUNS when XLA is actually tracing (i.e. compiling) the
call; a cache hit never re-enters it.
"""

from __future__ import annotations

import functools
import os
import re
import sys
import sysconfig
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

_lock = threading.Lock()

# kernel name -> stats dict
_kernels: Dict[str, Dict[str, Any]] = {}
# h2d_ns is host staging + dispatch time only: device_put returns before
# the copy lands.  d2h_wait_ns is the time the caller was blocked, which
# includes waiting for the programs that produce the value.
_transfers = {"h2d_bytes": 0, "h2d_transfers": 0, "h2d_ns": 0,
              "d2h_bytes": 0, "d2h_transfers": 0, "d2h_wait_ns": 0}
# Task placement (bridge/context.TaskContext.device): tasks that ran
# under a task scope, how many of them on a chip other than 0, and bytes
# found on another chip than the task's and moved there outside the
# exchange's collective (xputil.on_task_chip; 0 where placement holds).
_placement = {"placed_tasks": 0, "placed_tasks_off_chip0": 0,
              "cross_chip_bytes": 0}
# the same by chip: device id -> {"tasks", "h2d_bytes", "d2h_bytes"}
_chips: Dict[int, Dict[str, int]] = {}
# batch-shaping + IO-pipeline counters (batch.bucket_capacity /
# ops.base.PrefetchIterator): how many capacity requests were quantized
# onto the bucket ladder (and the padding that cost), and how often the
# consumer actually waited on the prefetch queue (0 wait = IO fully
# overlapped with compute); and the rows `CoalesceStream` re-batched
# (ops/base.py), by the lane they left through: laid end to end on the chip
# by the tile program (kernels/tiles.py `lay_tile`) or joined by
# `ColumnBatch.concat`.  A batch that passed whole is in neither.  And the
# row groups of the parquet files a scan opened one by one
# (ops/scan.py `_decode_batches`), with those among them that it never
# decoded because their statistics proved that no row of theirs could meet
# the scan's predicate or its consumer's condition.  The last four by chip
# in `chip_stats()` too.
_pipeline = {"bucket_batches": 0, "bucket_pad_rows": 0,
             "prefetch_batches": 0, "prefetch_wait_ns": 0,
             "prefetch_waits": 0,
             "coalesce_tiled_rows": 0, "coalesce_concat_rows": 0,
             "scan_row_groups": 0, "scan_row_groups_pruned": 0}
_bucket_caps: set = set()

# Whole-stage expression-program accounting (exprs/program.py).  Programs
# are keyed by expression FINGERPRINT, not callable identity: every
# partition-local evaluator instance resolves to the ONE process-wide
# metered callable per fingerprint, so per-partition instances cannot
# report false recompiles (each jit cache — and its compile counters
# above — is shared through the program cache).
_exprs = {"expr_programs_built": 0, "expr_program_cache_hits": 0,
          "expr_program_evictions": 0,
          "expr_fused_batches": 0, "expr_eager_batches": 0}

# Fault-tolerance accounting (bridge/tasks.py retry loop, shuffle
# readers, plan/stages.py lineage recovery, faults.py injector): how
# many attempts tasks burned, how long retries waited, how often a
# shuffle block came back poisoned and what recovery re-ran.
_faults = {"task_attempts": 0, "task_retries": 0, "task_retry_wait_ns": 0,
           "task_failures": 0, "fetch_failures": 0, "stage_recoveries": 0,
           "recovered_map_tasks": 0, "faults_injected": 0,
           # device-tier failures that were NOT one of the engine's
           # declared degradations (a lowering/compile error above all):
           # the stage still falls back, but the error text is kept in
           # _fallback_errors so it cannot pass unseen with tracing off
           "unexpected_fallbacks": 0}
_FALLBACK_ERRORS_KEPT = 32
_fallback_errors: List[Dict[str, Any]] = []
_stage_loop_fallback_reasons: Dict[str, int] = {}

# Exchange-transport accounting (plan/stages.py DagScheduler,
# parallel/stage.py DeviceExchange): bytes moved through the on-device
# collective exchange vs the host file shuffle, collective dispatches,
# and how often the device lane bailed to the file fallback.
_shuffle = {"shuffle_device_bytes": 0, "shuffle_host_bytes": 0,
            "shuffle_device_rows": 0, "shuffle_device_row_bytes": 0,
            "shuffle_device_exchanges": 0,
            "shuffle_device_collectives": 0,
            "shuffle_device_fallbacks": 0,
            # where a device exchange took its rows from (noted at
            # dispatch): host columns cut evenly over the mesh, or the
            # chips the map output lay on; and ladder rungs climbed
            # after a destination bucket overflowed
            "shuffle_device_staged_rows": 0,
            "shuffle_device_placed_rows": 0,
            "shuffle_device_redispatches": 0,
            # overlapped exchange (PR 18): per-task tickets drained in
            # the background, and the host-side barrier — time from the
            # last fold completing to the first collective dispatch —
            # the overlap exists to eliminate (sync pays it per stage;
            # the overlapped path records 0)
            "shuffle_device_overlap_exchanges": 0,
            "shuffle_barrier_idle_ns": 0,
            # io.compression.codec coverage beyond shuffle frames:
            # worker-pool control frames and RSS partition puts
            # (raw size - wire size, summed; 0 when the codec is raw
            # or compression grew the payload and was skipped)
            "worker_frame_compressed_bytes_saved": 0,
            "rss_put_compressed_bytes_saved": 0,
            # a map task's committed output by the tier it took
            # (shuffle/writer.py): rows that stayed on the chip and their
            # bytes as columns and validity lanes, rows written to
            # `.data` files and those files' bytes, and of the resident
            # ones those a spill wrote to files later (a subset: they
            # were resident first)
            "shuffle_resident_rows": 0, "shuffle_resident_bytes": 0,
            "shuffle_file_rows": 0, "shuffle_file_bytes": 0,
            "shuffle_spilled_rows": 0, "shuffle_spilled_bytes": 0}

# Device-resident stage-loop accounting (runtime/loop.py,
# plan/stage_compiler.py): stage programs built vs served from the
# fingerprint cache, loop program calls (the O(1)-per-chunk dispatch
# the loop buys) vs the per-batch dispatches the staged path would have
# issued, rows folded device-side, overflow-driven table regrows (and
# the steps of the table whose claims were taken back for them), and
# wholesale fallbacks to the staged per-batch executor.
_stage_loop = {"stage_loop_programs_built": 0,
               "stage_loop_program_cache_hits": 0,
               "stage_loop_calls": 0, "stage_loop_chunks": 0,
               "stage_loop_batches": 0, "stage_loop_rows": 0,
               "stage_loop_lanes": 0,
               "stage_loop_tasks": 0, "stage_loop_regrows": 0,
               "stage_loop_undone_steps": 0,
               "stage_loop_reserves": 0, "stage_loop_rehash_lanes": 0,
               "stage_loop_rehash_groups": 0,
               "stage_loop_rehash_new_slots": 0,
               "stage_loop_rehash_probe_lanes": 0,
               "stage_loop_final_slots": 0, "stage_loop_table_bytes": 0,
               "stage_loop_full_rounds": 0, "stage_loop_narrow_rounds": 0,
               "stage_loop_max_slots": 0,
               "stage_loop_fallbacks": 0,
               "stage_loop_staged_dispatches_avoided": 0,
               "stage_loop_windows": 0, "stage_loop_windows_fused": 0}

# Adaptive partial-aggregation accounting (ops/agg/exec.py _AggState,
# plan/fused.py host lane): cardinality probes run, mode switches
# (ratio-triggered vs memory-pressure-triggered), and the rows that
# streamed through the pass-through lane un-aggregated.
_agg = {"partial_agg_skip_events": 0, "partial_agg_skipped_rows": 0,
        "partial_agg_probe_rows": 0, "partial_agg_probe_groups": 0,
        "partial_agg_switch_rows": 0, "partial_agg_spill_switches": 0,
        # rows the unfused, batch-at-a-time aggregation took (AggExec:
        # outside every fused lane and the stage loop)
        "agg_eager_rows": 0}

# Sort and sort-merge join on the device (ops/sort.py, ops/joins/exec.py):
# rows whose sort permutation came from the device and, of those, the rows
# of partitions that stayed on the chip while they were sorted (staged as
# device batches, their columns gathered by the permutation there: by chip
# in `chip_stats()` too), rows of both sides and pairs written by the
# device merge join, and equal-key runs the Python run cursor walked
# instead (ops/joins/smj.py: a partition the memory manager shed, or a join
# shape the device path states it does not take).
_sortmerge = {"sort_device_rows": 0, "sort_resident_rows": 0,
              "smj_device_rows": 0, "smj_device_pairs": 0,
              "smj_streamed_runs": 0}

# Window functions (ops/window.py): rows a `WindowExec` computed its
# functions over and, of those, the rows of sorted runs that never left the
# chip (the resident lane: flags from the key columns and every function's
# scan in one program, kernels/window.py); `window_partitions`: sorted RUNS
# handed to a lane (a task's partition of the exchange whole in the
# resident lane, a flushed chunk of it in the host lane), not SQL
# `PARTITION BY` groups, which the resident lane never reads back to count;
# and the least bytes the resident lane's scans had to move (key and
# argument columns read once, results written once).  By chip in
# `chip_stats()` too.
_window = {"window_rows": 0, "window_resident_rows": 0,
           "window_partitions": 0, "window_scan_bytes": 0}

# The hash joins' probe side (ops/joins/exec.py): probe rows handed to a
# join whose batches stay on the chip (`kernels/join.probe_gather`: inner,
# unique fixed-width build key) and to every other join, whose pairs and
# rows pass through the host; and, of the first, the rows whose build side
# the program addressed by the key itself (`JoinMap.direct_key`: one dense
# integer key, no hash and no search).  By chip in `chip_stats()` too.
_join = {"join_probe_device_rows": 0, "join_probe_host_rows": 0,
         "join_probe_direct_rows": 0}

# Strings as dictionary codes (batch.DictColumn) and the Expand a fused
# stage folds in place: rows an `ExpandExec` absorbed into a stage loop
# would have emitted (`expand_rows_out`: input rows x projections, never
# batches); rows x utf8 columns that crossed an operator boundary as int32
# codes (`dict_rows_coded`: a device probe's output, a fold's drain, an
# exchange block written or read, a resident sort or window) and that were
# decoded to strings (`dict_rows_decoded`: `DictColumn.to_arrow`, with a
# `dict_decode` instant under the operator's span); dictionaries that
# differed from their stream's and were unified on the host
# (`dict_unified`) and the rows whose codes were then remapped on the
# device (`dict_remap_rows`).  By chip in `chip_stats()` too.
_dicts = {"expand_rows_out": 0, "dict_rows_coded": 0,
          "dict_rows_decoded": 0, "dict_unified": 0, "dict_remap_rows": 0}

# Streaming-runtime accounting (streaming/executor.py StreamExecutor):
# committed epochs and their wall time, rows/records through the
# pipeline, late-record routing, checkpoint commits, recovery rounds
# and exactly-once sink outcomes.  The *_last entries are gauges (most
# recent observation), kept here so snapshot()/prometheus share one
# source: watermark delay (processing time - watermark), window-state
# retained bytes, and source lag (records staged but not yet polled).
_stream = {"stream_epochs": 0, "stream_epoch_wall_ns": 0,
           "stream_rows": 0, "stream_records": 0,
           "stream_late_records": 0, "stream_late_side_rows": 0,
           "stream_checkpoints": 0, "stream_checkpoint_bytes": 0,
           "stream_recoveries": 0, "stream_replayed_epochs": 0,
           "stream_sink_commits": 0, "stream_sink_dup_skips": 0,
           "stream_watermark_delay_ms_last": 0,
           "stream_window_state_bytes_last": 0,
           "stream_source_lag_records_last": 0}

# Worker-pool accounting (parallel/workers.py WorkerPool): processes
# spawned (incl. restarts), tasks shipped over the pipe, crashes (exit
# classified), hangs (liveness-deadline SIGKILLs), supervised restarts,
# slots blacklisted by the crash budget, and cancel escalations.
_workers = {"worker_spawns": 0, "worker_tasks": 0, "worker_crashes": 0,
            "worker_hangs": 0, "worker_restarts": 0,
            "worker_blacklisted": 0, "worker_cancels": 0,
            # child-process CPU actually burned running tasks (user+sys
            # os.times() delta shipped in each result frame); over a
            # wave's wall it says how many cores the children kept busy
            "worker_cpu_ns": 0}

# Speculative-execution accounting (bridge/tasks.py wave loop,
# shuffle/writer.py + shuffle/rss.py commit arbitration): waves that
# hedged at least one straggler, duplicate attempts launched, duplicates
# that won the first-wins commit, losers cancelled via the cooperative
# token, forced commit races (the speculation-loser-commit-race site),
# loser commits rejected at a shuffle tier, and double-accepts (must
# stay 0 — the duplicate_output_blocks invariant the soak asserts).
_speculation = {"speculation_waves": 0, "speculation_attempts": 0,
                "speculation_wins": 0, "speculation_losers_cancelled": 0,
                "speculation_commit_races": 0,
                "speculation_loser_commits_rejected": 0,
                "speculation_duplicate_commits": 0}

# Observability-plane accounting (PR 13): spans stitched in from worker
# children, flight-recorder dumps written, and query-profile LRU
# evictions (bridge/profiling.py store bound).
_obs = {"obs_spans_ingested": 0, "obs_flight_dumps": 0,
        "obs_profile_evictions": 0, "obs_spans_dropped": 0}

# Cross-query work sharing (blaze_tpu/cache/, serving single-flight,
# shared scan decode).  scan_share_hits = follower rides a leader's
# decode; scan_share_misses = leader decoded itself.
# cache_used_bytes_last is the result/subplan cache's live footprint.
_cache = {"result_cache_hits": 0, "result_cache_misses": 0,
          "result_cache_puts": 0, "result_cache_evictions": 0,
          "result_cache_invalidations": 0,
          "subplan_cache_hits": 0, "subplan_cache_misses": 0,
          "subplan_cache_puts": 0,
          "single_flight_coalesces": 0, "single_flight_promotions": 0,
          "scan_share_hits": 0, "scan_share_misses": 0,
          "scan_share_bytes_saved": 0,
          "cache_used_bytes_last": 0}

# Statistics feedback plane (plan/statstore.py, plan/advisor.py):
# observations ingested, ingests that merged onto an existing record
# (run 2+ of a fingerprint), advisor findings emitted into history,
# progress ETAs seeded from a statstore prior, and the store's current
# on-disk fingerprint count (gauge).
_stats = {"stats_ingests": 0, "stats_runs_merged": 0,
          "stats_advisor_findings": 0, "stats_eta_seeded": 0,
          "stats_fingerprints_last": 0}

# Adaptive query execution (plan/adaptive.py): runtime rewrites applied
# at stage boundaries, split by rule; plans seeded from statstore
# history at bind time; producer stages elided outright (their exchange
# never ran); and the estimated shuffle bytes those rewrites avoided.
_aqe = {"aqe_rewrites": 0, "aqe_broadcast_switches": 0,
        "aqe_partitions_coalesced": 0, "aqe_skew_splits": 0,
        "aqe_history_seeds": 0, "aqe_bytes_saved": 0,
        "aqe_stages_elided": 0}

# Encoding lanes (config.ENCODING_*): utf8 columns dictionary-encoded
# at scan decode, cross-batch dictionary-unify remaps at concat/exchange
# boundaries, decimal dispatches split by storage tier (scaled int32 /
# scaled int64 / two-limb int128), and host-lane evictions split by the
# column dtype that caused them — the per-column accounting the advisor
# reads instead of the old whole-stage "string somewhere -> host"
# verdict.
_encoding = {"dict_encoded_columns": 0, "dict_exchange_remaps": 0,
             "decimal_scaled_int32_dispatches": 0,
             "decimal_scaled_int64_dispatches": 0,
             "decimal_limb_dispatches": 0,
             "host_evictions_string": 0, "host_evictions_decimal": 0,
             "host_evictions_other": 0,
             # decimals as exact scaled integers (note_decimal): rows the
             # stage loop folded whose value lane is one, rows with a
             # decimal aggregate argument that an aggregation outside the
             # loop took, sums and averages sent to NULL or to the wide
             # path, expression batches with a decimal operand inside a
             # device program / through Arrow or numpy on the host
             "stage_loop_decimal_rows": 0, "agg_decimal_rows_host": 0,
             "decimal_overflow_groups": 0,
             "expr_decimal_device_batches": 0,
             "expr_decimal_host_batches": 0}

# Fleet-scope serving (blaze_tpu/fleet/): queries routed by the
# fingerprint-affine router, affinity hits (query landed on its
# rendezvous first choice — the replica whose result/subplan cache is
# warm), re-routes after replica death, end-to-end query retries,
# replica up/down transitions and heartbeat misses, torn socket frames
# survived, cross-replica hedges, and queries lost for good (must stay
# 0 — the kill-replica soak's core invariant).
# fleet_replicas_up_last is the router's current live-replica gauge.
_fleet = {"fleet_queries_routed": 0, "fleet_queries_completed": 0,
          "fleet_queries_lost": 0, "fleet_affinity_hits": 0,
          "fleet_affinity_misses": 0, "fleet_reroutes": 0,
          "fleet_retries": 0, "fleet_replica_down_events": 0,
          "fleet_replica_up_events": 0, "fleet_heartbeat_misses": 0,
          "fleet_torn_frames": 0, "fleet_hedges": 0,
          "fleet_hedge_wins": 0, "fleet_replicas_up_last": 0}

# Bounded raw-sample reservoirs feeding tail-latency percentiles (the
# statstore's per-fingerprint task sketch): successful task-attempt durations
# and run_tasks wave walls, in ns.  Lists, so NOT folded into
# snapshot()/delta() — read via duration_samples(), cleared by reset().
_task_duration_ns: List[int] = []
_wave_wall_ns: List[int] = []
_SAMPLE_CAP = 8192

# Prometheus histogram bucket upper bounds (seconds) for the task-
# latency and wave-wall exposition (bridge/profiling.py renders these
# as real `# TYPE ... histogram` families, not gauges).
HISTOGRAM_BUCKETS_S = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                       1.0, 2.5, 5.0, 10.0, 30.0)

# What JAX itself asked its backend for (jax.monitoring events): every
# program, the eager glue ones included, that `meter_jit` never sees.
# backend_compiles counts compile requests (a persistent-cache hit is
# also a request, answered by compile_cache_hits).  `program_loads_trimmed`
# counts the records the program ledger's cap has dropped
# (`_on_program_phase`); the ledger's totals are `program_load_summary()`'s,
# computed from the records, and have no counter beside them.
_backend = {"backend_compiles": 0, "backend_compile_ns": 0,
            "compile_cache_hits": 0, "program_loads_trimmed": 0}
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# the three phases JAX times for a program it is asked for, each with the
# function's name: tracing to a jaxpr, lowering to a module, and
# `compile_or_get_cached` (on a warm cache the look-up and the
# deserialisation)
_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           _BACKEND_COMPILE_EVENT: "backend"}
_backend_listening = False

# The program ledger: one record a phase of a request, for the life of
# the process, stamped on `time.perf_counter_ns` (the span tracer's clock,
# which the benchmark brings onto the device trace's).  Written only from
# JAX's monitoring callbacks, which run on the dispatching thread when a
# program is requested and never otherwise.  Oldest trimmed over the cap,
# counted in `program_loads_trimmed`.
_program_loads: List[Dict[str, Any]] = []
_PROGRAM_LOADS_CAP = 32768
_requesting = threading.local()  # .open: phases entered and not yet left;
                                 # .cache: what the persistent cache said
                                 # inside the open backend phase
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) \
    + os.sep
_SITE_SKIP = (os.path.abspath(__file__),
              os.path.join(_PKG_DIR, "xputil.py"))
_LIBRARY_DIRS = tuple(sorted({
    os.path.join(p, "") for k, p in sysconfig.get_paths().items()
    if k in ("stdlib", "platstdlib", "purelib", "platlib")}))

# Distinct signatures beyond this on one kernel = shape churn (the
# recompilation-storm smell: unpadded dynamic shapes hitting jit).
SHAPE_CHURN_THRESHOLD = 8


def _kernel_entry(name: str) -> Dict[str, Any]:
    entry = _kernels.get(name)
    if entry is None:
        entry = _kernels[name] = {
            "calls": 0, "compiles": 0, "cache_hits": 0,
            "compile_ns": 0, "dispatch_ns": 0, "signatures": set(),
        }
    return entry


def _signature(args, kwargs) -> tuple:
    def one(a):
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None:
            return ("arr", tuple(shape), str(dtype))
        if isinstance(a, (int, float, bool, str, bytes, type(None))):
            return ("lit", a)
        if isinstance(a, (tuple, list)):
            return ("seq", tuple(one(x) for x in a))
        return ("obj", type(a).__name__)
    return (tuple(one(a) for a in args),
            tuple(sorted((k, one(v)) for k, v in kwargs.items())))


_NOT_WORD = re.compile(r"[^A-Za-z0-9_]")


def program_name(fun_name: str, kernel: str) -> str:
    """The `__name__` handed to `jax.jit` for a metered kernel:
    `<function>__<kernel>`, so the profiler's `XLA Modules` events read
    `jit_<function>__<kernel>` and join `compile_report()["kernels"]` on
    the kernel name.  The function name stays in front: trace readers
    match on it (`^jit_fold_impl`).  `<lambda>` reads `_lambda`."""
    return (f"{_NOT_WORD.sub('_', fun_name).rstrip('_') or 'jit_fn'}"
            f"__{_NOT_WORD.sub('_', kernel)}")


_WRAPPED = re.compile(r"^\w+\(.*\)$")
_MODULE_NAME = re.compile(r"[^\w.-]")


def loaded_program_name(fun_name: str) -> str:
    """The name the device trace prints for a program JAX's monitoring
    events call `fun_name`: `_take` (the trace event) and `jit(_take)`
    (the other two) are both `jit__take`, as JAX names the module
    (`mlir.sanitize_name`, trailing `_` stripped)."""
    if not _WRAPPED.match(fun_name):
        fun_name = f"jit({fun_name})"
    return _MODULE_NAME.sub("_", fun_name).rstrip("_")


def program_kind(name: str) -> Tuple[str, Optional[str]]:
    """("metered", kernel) for a program named by `program_name`
    (`jit_<function>__<kernel>`), ("eager", None) for one-operation glue
    (`jit__take`: the function is `_take`, no `__` follows it)."""
    body = name.partition("_")[2]
    i = body.find("__", 1)
    return ("metered", body[i + 2:]) if i > 0 else ("eager", None)


def _call_site(frame) -> str:
    """`module:function:line` of the innermost frame from `frame` outward
    that lies in `blaze_tpu/` and is neither this file nor `xputil.py`;
    where the program's code is not on the stack, of the innermost frame
    outside the interpreter's and the installed packages' directories."""
    outside = None
    while frame is not None:
        code = frame.f_code
        path = code.co_filename
        if path.startswith(_PKG_DIR):
            if path not in _SITE_SKIP:
                return (f"{path[len(_PKG_DIR):]}:{code.co_name}:"
                        f"{frame.f_lineno}")
        elif outside is None and not path.startswith("<") \
                and not path.startswith(_LIBRARY_DIRS):
            outside = (f"{os.path.basename(path)}:{code.co_name}:"
                       f"{frame.f_lineno}")
        frame = frame.f_back
    return outside or "?"


def _note_cache_answer(**what) -> None:
    """What the persistent cache said inside this thread's open backend
    phase, kept for the phase's record."""
    cache = getattr(_requesting, "cache", None)
    if cache is None:
        cache = _requesting.cache = {}
    cache.update(what)


def _on_program_enter(event: str, _value: float, **_kw) -> None:
    """`record_scalar` at a phase's entry: how deep this thread's
    requests nest."""
    if event in _PHASES:
        try:
            _requesting.open.append(event)
        except AttributeError:
            _requesting.open = [event]


def _on_program_phase(event: str, secs: float, **kw) -> None:
    """A phase of a request ended on this thread: one ledger record and,
    while tracing is on, one `xla_compile` span."""
    phase = _PHASES.get(event)
    if phase is None:
        if event == _CACHE_RETRIEVAL_EVENT:
            _note_cache_answer(retrieval_ns=int(secs * 1e9))
        return
    t1 = time.perf_counter_ns()
    ns = int(secs * 1e9)
    still_open = getattr(_requesting, "open", None)
    if still_open:
        # an entry without its end (a listener registered mid-phase) sits
        # above ours: drop it with ours
        while still_open and still_open.pop() != event:
            pass
    depth = len(still_open) if still_open else 0
    name = loaded_program_name(str(kw.get("fun_name", "")))
    kind, kernel = program_kind(name)
    record = {"program": name, "phase": phase, "t0_ns": t1 - ns, "t1_ns": t1,
              "tid": threading.get_ident(), "depth": depth, "kind": kind,
              "site": _call_site(sys._getframe(1))}
    if phase == "backend":
        cache = getattr(_requesting, "cache", None) or {}
        _requesting.cache = None
        record["cache_hit"] = bool(cache.get("hit"))
        if cache.get("hit"):
            record["retrieval_ns"] = cache.get("retrieval_ns", 0)
    with _lock:
        if phase == "backend":
            _backend["backend_compiles"] += 1
            _backend["backend_compile_ns"] += ns
        _program_loads.append(record)
        over = len(_program_loads) - _PROGRAM_LOADS_CAP
        if over > 0:
            del _program_loads[:over]
            _backend["program_loads_trimmed"] += over
    from blaze_tpu.bridge import tracing
    if tracing.enabled():
        attrs = {"program": name, "phase": phase, "site": record["site"],
                 "ns": ns, "source": "backend"}
        if phase == "backend":
            attrs["cache_hit"] = record["cache_hit"]
        if kernel is not None:
            attrs["kernel"] = kernel
        tracing.emit_span("xla_compile", ns, **attrs)


def _on_cache_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        with _lock:
            _backend["compile_cache_hits"] += 1
        _note_cache_answer(hit=True)


def listen_backend_compiles() -> None:
    """Register the three jax.monitoring listeners once per process
    (called from `import blaze_tpu`; `reset()` zeroes the counters and
    the ledger and leaves them registered)."""
    global _backend_listening
    with _lock:
        if _backend_listening:
            return
        _backend_listening = True
    import jax
    jax.monitoring.register_scalar_listener(_on_program_enter)
    jax.monitoring.register_event_duration_secs_listener(_on_program_phase)
    jax.monitoring.register_event_listener(_on_cache_event)


def program_loads(since_ns: Optional[int] = None,
                  until_ns: Optional[int] = None) -> List[Dict[str, Any]]:
    """The ledger's records (copies, oldest first) that ended after
    `since_ns` and at or before `until_ns`, both on
    `time.perf_counter_ns`: one a phase (`trace`, `lower`, `backend`) of
    every program this process asked JAX for, with `program` (as the
    device trace prints it), `t0_ns` / `t1_ns`, `tid`, `depth` (0: a
    top-level request of its thread), `kind` (`metered` / `eager`), `site`
    (`module:function:line` of the caller in `blaze_tpu/`) and, for a
    backend phase, `cache_hit` with `retrieval_ns`."""
    with _lock:
        return [dict(r) for r in _program_loads
                if (since_ns is None or r["t1_ns"] > since_ns)
                and (until_ns is None or r["t1_ns"] <= until_ns)]


def program_load_summary(until_ns: Optional[int] = None,
                         since_ns: Optional[int] = None,
                         top: int = 20) -> Dict[str, Any]:
    """What the records of `program_loads(since_ns, until_ns)` add up to:
    thread-seconds by phase and the persistent cache's retrieval seconds
    (top-level requests only, so nothing is counted twice), `wall_s` the
    union over threads and phases (seconds in which at least one thread
    was getting a program), requests by kind and how many the cache
    answered (every backend phase, nested or not), the `top` (program,
    call site) pairs with most thread-seconds (`seconds`, and of them
    `trace_s` / `lower_s` / `backend_s`; `requests`), and `trimmed`: records the
    cap has dropped since the last `reset()` (above 0, the oldest are
    missing here)."""
    records = program_loads(since_ns, until_ns)
    out: Dict[str, Any] = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                           "cache_retrieval_s": 0.0, "requests_eager": 0,
                           "requests_metered": 0, "cache_hits": 0}
    pairs: Dict[tuple, Dict[str, Any]] = {}
    intervals = []
    for r in records:
        if r["phase"] == "backend":
            out[f"requests_{r['kind']}"] += 1
            out["cache_hits"] += r["cache_hit"]
        if r["depth"]:
            continue
        secs = (r["t1_ns"] - r["t0_ns"]) / 1e9
        out[f"{r['phase']}_s"] += secs
        out["cache_retrieval_s"] += r.get("retrieval_ns", 0) / 1e9
        intervals.append((r["t0_ns"], r["t1_ns"]))
        pair = pairs.setdefault((r["program"], r["site"]), {
            "seconds": 0.0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "requests": 0})
        pair["seconds"] += secs
        pair[f"{r['phase']}_s"] += secs
        pair["requests"] += r["phase"] == "backend"
    from blaze_tpu.bridge.history import _merged_busy_ns
    out["wall_s"] = _merged_busy_ns(intervals) / 1e9
    out["top"] = [{"program": p, "site": s, **v} for (p, s), v in sorted(
        pairs.items(), key=lambda kv: -kv[1]["seconds"])[:top]]
    with _lock:
        out["trimmed"] = _backend["program_loads_trimmed"]
    return out


def meter_jit(fun: Callable, *, name: Optional[str] = None,
              **jit_kwargs) -> Callable:
    """`jax.jit` with compile/cache-hit accounting.

    Drop-in for `jax.jit(fun, **kwargs)` — supports static_argnums /
    static_argnames / donate_argnums.  Each call is timed; a call during
    which the traced body executed is a compile, otherwise a cache hit.
    The device program is named after the kernel (`program_name`).
    Under a task that has a chip, an operand found on another chip is
    moved to the task's and counted (`xputil.on_task_chip`).
    """
    import jax

    from blaze_tpu.xputil import on_task_chip

    kname = name or getattr(fun, "__name__", "jit_fn")
    traced = threading.local()

    @functools.wraps(fun)
    def _noting(*args, **kwargs):
        traced.hit = True
        return fun(*args, **kwargs)

    _noting.__name__ = program_name(
        getattr(fun, "__name__", "jit_fn"), kname)
    _noting.__qualname__ = _noting.__name__
    jitted = jax.jit(_noting, **jit_kwargs)

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        traced.hit = False
        args, kwargs = on_task_chip((args, kwargs))
        t0 = time.perf_counter_ns()
        out = jitted(*args, **kwargs)
        dt = time.perf_counter_ns() - t0
        compiled = getattr(traced, "hit", False)
        with _lock:
            entry = _kernel_entry(kname)
            entry["calls"] += 1
            entry["dispatch_ns"] += dt
            try:
                entry["signatures"].add(_signature(args, kwargs))
            except TypeError:
                pass  # unhashable leaf: skip churn tracking for this call
            if compiled:
                entry["compiles"] += 1
                entry["compile_ns"] += dt
            else:
                entry["cache_hits"] += 1
        return out

    wrapper._blaze_metered_jit = kname  # introspection / tests
    wrapper._blaze_jitted = jitted      # .lower() for the naming test
    return wrapper


# what the stage loop's tables cost each chip, in the order
# note_stage_loop_task fills them
_CHIP_TABLE_KEYS = ("stage_loop_rehash_lanes", "stage_loop_rehash_groups",
                    "stage_loop_rehash_new_slots",
                    "stage_loop_rehash_probe_lanes",
                    "stage_loop_final_slots", "stage_loop_table_bytes")


def _chip_entry(chip: int) -> Dict[str, int]:
    entry = _chips.get(chip)
    if entry is None:
        entry = _chips[chip] = {"tasks": 0, "h2d_bytes": 0, "d2h_bytes": 0,
                                "join_probe_device_rows": 0,
                                "join_probe_host_rows": 0,
                                "join_probe_direct_rows": 0,
                                "stage_loop_windows": 0,
                                "stage_loop_windows_fused": 0,
                                "stage_loop_lanes": 0,
                                "stage_loop_undone_steps": 0,
                                "stage_loop_decimal_rows": 0,
                                "sort_resident_rows": 0,
                                "coalesce_tiled_rows": 0,
                                "coalesce_concat_rows": 0,
                                "scan_row_groups": 0,
                                "scan_row_groups_pruned": 0,
                                **{k: 0 for k in _window},
                                **{k: 0 for k in _dicts},
                                **{k: 0 for k in _CHIP_TABLE_KEYS}}
    return entry


def note_h2d(nbytes: int, ns: int = 0, chip: int = 0) -> None:
    if nbytes <= 0:
        return
    with _lock:
        _transfers["h2d_bytes"] += int(nbytes)
        _transfers["h2d_transfers"] += 1
        _transfers["h2d_ns"] += int(ns)
        _chip_entry(chip)["h2d_bytes"] += int(nbytes)


def note_d2h(nbytes: int, wait_ns: int = 0, chip: int = 0) -> None:
    if nbytes <= 0:
        return
    with _lock:
        _transfers["d2h_bytes"] += int(nbytes)
        _transfers["d2h_transfers"] += 1
        _transfers["d2h_wait_ns"] += int(wait_ns)
        _chip_entry(chip)["d2h_bytes"] += int(nbytes)


def note_join_probe(chip: int, on_device: bool, rows: int,
                    direct: bool = False) -> None:
    """`rows` probe rows reached a hash join on `chip`, through the
    device-resident probe or the other; `direct`: the device-resident
    probe read the build row at the key's own offset."""
    keys = ["join_probe_device_rows" if on_device else "join_probe_host_rows"]
    if direct:
        keys.append("join_probe_direct_rows")
    with _lock:
        for key in keys:
            _join[key] += int(rows)
            _chip_entry(chip)[key] += int(rows)


def join_stats() -> dict:
    with _lock:
        return dict(_join)


def note_task_placed(chip: int) -> None:
    """One task's operator chain started on `chip`."""
    with _lock:
        _placement["placed_tasks"] += 1
        _placement["placed_tasks_off_chip0"] += chip != 0
        _chip_entry(chip)["tasks"] += 1


def note_cross_chip(nbytes: int) -> None:
    """Bytes moved to the task's chip from another, outside the
    collective (xputil.on_task_chip)."""
    with _lock:
        _placement["cross_chip_bytes"] += int(nbytes)


def placement_stats() -> dict:
    with _lock:
        return dict(_placement)


def chip_stats() -> Dict[int, Dict[str, int]]:
    """device id -> {"tasks", "h2d_bytes", "d2h_bytes",
    "join_probe_device_rows", "join_probe_host_rows",
    "join_probe_direct_rows",
    "stage_loop_windows", "stage_loop_windows_fused", "stage_loop_lanes",
    "stage_loop_undone_steps", "stage_loop_decimal_rows", "sort_resident_rows",
    "coalesce_tiled_rows", "coalesce_concat_rows", "scan_row_groups",
    "scan_row_groups_pruned", the four window
    counters (`_window`), the five dictionary and Expand counters
    (`_dicts`) and the stage loop's table counters
    (_CHIP_TABLE_KEYS)} since the last reset: what each chip was given to
    do."""
    with _lock:
        return {chip: dict(e) for chip, e in sorted(_chips.items())}


def note_bucket(capacity: int, pad_rows: int) -> None:
    """One capacity request quantized onto the bucket ladder
    (batch.bucket_capacity)."""
    with _lock:
        _pipeline["bucket_batches"] += 1
        _pipeline["bucket_pad_rows"] += max(0, int(pad_rows))
        _bucket_caps.add(int(capacity))


def note_coalesce(chip: int, tiled: bool, rows: int) -> None:
    """`rows` rows left a `CoalesceStream` on `chip` re-batched, through
    the tile program or through `ColumnBatch.concat`."""
    key = "coalesce_tiled_rows" if tiled else "coalesce_concat_rows"
    with _lock:
        _pipeline[key] += int(rows)
        _chip_entry(chip)[key] += int(rows)


def note_scan_groups(chip: int, groups: int, pruned: int) -> None:
    """A scan on `chip` opened a parquet file of `groups` row groups and
    left `pruned` of them undecoded on their statistics."""
    with _lock:
        for key, n in (("scan_row_groups", groups),
                       ("scan_row_groups_pruned", pruned)):
            _pipeline[key] += int(n)
            _chip_entry(chip)[key] += int(n)


def note_prefetch(batches: int = 0, wait_ns: int = 0) -> None:
    """Prefetch-queue accounting from the consumer side: `batches` =
    items delivered through a prefetch queue, `wait_ns` = time the
    consumer blocked on the queue (the un-overlapped IO residue)."""
    with _lock:
        _pipeline["prefetch_batches"] += int(batches)
        if wait_ns > 0:
            _pipeline["prefetch_wait_ns"] += int(wait_ns)
            _pipeline["prefetch_waits"] += 1


def note_expr_program(built: bool = False, cache_hit: bool = False,
                      evicted: bool = False) -> None:
    """One program-cache resolution (exprs/program.py get_program)."""
    with _lock:
        if built:
            _exprs["expr_programs_built"] += 1
        if cache_hit:
            _exprs["expr_program_cache_hits"] += 1
        if evicted:
            _exprs["expr_program_evictions"] += 1


def note_expr_dispatch(fused: int = 0, eager: int = 0) -> None:
    """Per-batch dispatch accounting: `fused` batches went through a
    compiled expression program, `eager` fell back to the interpreted
    evaluator (host-only exprs, ANSI mode, non-device columns)."""
    with _lock:
        _exprs["expr_fused_batches"] += int(fused)
        _exprs["expr_eager_batches"] += int(eager)


def note_task_attempts(attempts: int = 1, retry_wait_ns: int = 0,
                       failed: bool = False) -> None:
    """One task reached a terminal state after `attempts` tries, having
    slept `retry_wait_ns` in backoff (bridge/tasks.py)."""
    with _lock:
        _faults["task_attempts"] += int(attempts)
        _faults["task_retries"] += max(0, int(attempts) - 1)
        _faults["task_retry_wait_ns"] += int(retry_wait_ns)
        if failed:
            _faults["task_failures"] += 1


def note_fetch_failure() -> None:
    """One shuffle block failed verification/fetch (FetchFailedError)."""
    with _lock:
        _faults["fetch_failures"] += 1


def note_stage_recovery(map_tasks: int = 1) -> None:
    """One lineage-recovery round re-ran `map_tasks` producer tasks."""
    with _lock:
        _faults["stage_recoveries"] += 1
        _faults["recovered_map_tasks"] += int(map_tasks)


def note_fault_injected() -> None:
    """The chaos injector fired one scripted fault (faults.py)."""
    with _lock:
        _faults["faults_injected"] += 1


def note_unexpected_fallback(site: str, exc: BaseException,
                             **where: Any) -> None:
    """A device tier fell back on an exception the engine does not
    declare as a degradation.  Keeps the newest error texts for
    `fallback_errors()`; the caller logs the traceback at ERROR."""
    rec = {"site": site, "error": type(exc).__name__,
           "message": str(exc)[:2000], **where}
    with _lock:
        _faults["unexpected_fallbacks"] += 1
        _fallback_errors.append(rec)
        del _fallback_errors[:-_FALLBACK_ERRORS_KEPT]


def fallback_errors() -> List[Dict[str, Any]]:
    """The newest unexpected device-tier fallback errors, oldest first."""
    with _lock:
        return [dict(r) for r in _fallback_errors]


def fault_stats() -> dict:
    with _lock:
        return dict(_faults)


def note_worker_spawn(restart: bool = False) -> None:
    """One worker process forked (restart=True when replacing a crash)."""
    with _lock:
        _workers["worker_spawns"] += 1
        if restart:
            _workers["worker_restarts"] += 1


def note_worker_task() -> None:
    """One task shipped over the pipe to a pool worker."""
    with _lock:
        _workers["worker_tasks"] += 1


def note_worker_crash(hang: bool = False) -> None:
    """A worker died mid-task (hang=True: liveness-deadline SIGKILL)."""
    with _lock:
        _workers["worker_crashes"] += 1
        if hang:
            _workers["worker_hangs"] += 1


def note_worker_blacklisted() -> None:
    """A slot exhausted its crash budget and was blacklisted."""
    with _lock:
        _workers["worker_blacklisted"] += 1


def note_worker_cancel() -> None:
    """A cancel/deadline escalated into the child (SIGTERM->SIGKILL)."""
    with _lock:
        _workers["worker_cancels"] += 1


def worker_stats() -> dict:
    with _lock:
        return dict(_workers)


def note_speculation(waves: int = 0, attempts: int = 0, wins: int = 0,
                     losers_cancelled: int = 0, commit_races: int = 0,
                     loser_commits_rejected: int = 0,
                     duplicate_commits: int = 0) -> None:
    """Speculative-execution events (bridge/tasks.py wave loop and the
    per-tier commit arbitration in shuffle/writer.py, shuffle/rss.py)."""
    with _lock:
        _speculation["speculation_waves"] += waves
        _speculation["speculation_attempts"] += attempts
        _speculation["speculation_wins"] += wins
        _speculation["speculation_losers_cancelled"] += losers_cancelled
        _speculation["speculation_commit_races"] += commit_races
        _speculation["speculation_loser_commits_rejected"] += \
            loser_commits_rejected
        _speculation["speculation_duplicate_commits"] += duplicate_commits


def speculation_stats() -> dict:
    with _lock:
        return dict(_speculation)


def note_task_duration(ns: int) -> None:
    """One successful task attempt's wall time (speculation's straggler
    cutoff and the statstore's task percentiles feed from here)."""
    with _lock:
        if len(_task_duration_ns) < _SAMPLE_CAP:
            _task_duration_ns.append(int(ns))


def note_wave_wall(ns: int) -> None:
    """One run_tasks wave's wall time, submit to last result."""
    with _lock:
        if len(_wave_wall_ns) < _SAMPLE_CAP:
            _wave_wall_ns.append(int(ns))


def duration_samples() -> Dict[str, List[int]]:
    """Raw ns samples: {"task_ns": [...], "wave_ns": [...]}.  Bounded at
    _SAMPLE_CAP each; callers slice by remembered length for per-leg
    percentiles."""
    with _lock:
        return {"task_ns": list(_task_duration_ns),
                "wave_ns": list(_wave_wall_ns)}


def note_obs(spans_ingested: int = 0, flight_dumps: int = 0,
             profile_evictions: int = 0, spans_dropped: int = 0) -> None:
    with _lock:
        _obs["obs_spans_ingested"] += spans_ingested
        _obs["obs_flight_dumps"] += flight_dumps
        _obs["obs_profile_evictions"] += profile_evictions
        _obs["obs_spans_dropped"] += spans_dropped


def obs_stats() -> dict:
    with _lock:
        return dict(_obs)


def note_cache(**deltas: int) -> None:
    """Work-sharing plane mutator: kwargs name `_cache` keys; gauges
    (`*_last`) are set absolutely, counters are incremented."""
    with _lock:
        for k, v in deltas.items():
            if k not in _cache:
                continue
            if k.endswith("_last"):
                _cache[k] = int(v)
            else:
                _cache[k] += int(v)


def cache_stats() -> dict:
    with _lock:
        return dict(_cache)


def note_stats(**deltas: int) -> None:
    """Stats-plane mutator: kwargs name `_stats` keys with or without
    the `stats_` prefix; gauges (`*_last`) are set absolutely, counters
    are incremented (the note_cache contract)."""
    with _lock:
        for k, v in deltas.items():
            key = k if k.startswith("stats_") else f"stats_{k}"
            if key not in _stats:
                continue
            if key.endswith("_last"):
                _stats[key] = int(v)
            else:
                _stats[key] += int(v)


def statstore_stats() -> dict:
    with _lock:
        return dict(_stats)


def note_aqe(**deltas: int) -> None:
    """AQE-plane mutator: kwargs name `_aqe` keys with or without the
    `aqe_` prefix; gauges (`*_last`) are set absolutely, counters are
    incremented (the note_stats contract)."""
    with _lock:
        for k, v in deltas.items():
            key = k if k.startswith("aqe_") else f"aqe_{k}"
            if key not in _aqe:
                continue
            if key.endswith("_last"):
                _aqe[key] = int(v)
            else:
                _aqe[key] += int(v)


def aqe_stats() -> dict:
    with _lock:
        return dict(_aqe)


def note_encoding(**deltas: int) -> None:
    """Encoding-plane mutator (dict/decimal device lanes): kwargs name
    `_encoding` keys exactly; gauges (`*_last`) are set absolutely,
    counters are incremented (the note_stats contract)."""
    with _lock:
        for key, v in deltas.items():
            if key not in _encoding:
                continue
            if key.endswith("_last"):
                _encoding[key] = int(v)
            else:
                _encoding[key] += int(v)


def note_decimal(stage_loop_rows: int = 0, agg_rows_host: int = 0,
                 overflow_groups: int = 0, expr_device_batches: int = 0,
                 expr_host_batches: int = 0, chip: int = 0) -> None:
    """Decimals as exact scaled integers.  `stage_loop_rows`: rows a
    stage-loop task folded whose value lane is a scaled integer (kept by
    `chip` too); `agg_rows_host`: rows with a decimal aggregate argument
    that an aggregation outside the stage loop took; `overflow_groups`:
    sums or averages sent to NULL past their type's bound, or to the
    wide path past 64 bits; `expr_device_batches` / `expr_host_batches`:
    expression batches with a decimal operand evaluated inside a device
    program, and through Arrow or numpy on the host."""
    with _lock:
        _encoding["stage_loop_decimal_rows"] += int(stage_loop_rows)
        _encoding["agg_decimal_rows_host"] += int(agg_rows_host)
        _encoding["decimal_overflow_groups"] += int(overflow_groups)
        _encoding["expr_decimal_device_batches"] += int(expr_device_batches)
        _encoding["expr_decimal_host_batches"] += int(expr_host_batches)
        if stage_loop_rows:
            _chip_entry(chip)["stage_loop_decimal_rows"] += \
                int(stage_loop_rows)


def encoding_stats() -> dict:
    with _lock:
        return dict(_encoding)


def note_fleet(**deltas: int) -> None:
    """Fleet-plane mutator: kwargs name `_fleet` keys with or without
    the `fleet_` prefix; gauges (`*_last`) are set absolutely, counters
    are incremented (the note_stats contract)."""
    with _lock:
        for k, v in deltas.items():
            key = k if k.startswith("fleet_") else f"fleet_{k}"
            if key not in _fleet:
                continue
            if key.endswith("_last"):
                _fleet[key] = int(v)
            else:
                _fleet[key] += int(v)


def fleet_stats() -> dict:
    with _lock:
        return dict(_fleet)


def _histogram(samples_ns: List[int]) -> Dict[str, Any]:
    """Cumulative-bucket Prometheus histogram over an ns reservoir:
    {"buckets": [(le_seconds, cumulative_count), ...], "sum": seconds,
    "count": n}.  Buckets are HISTOGRAM_BUCKETS_S plus +Inf."""
    counts = [0] * len(HISTOGRAM_BUCKETS_S)
    total = 0.0
    for ns in samples_ns:
        s = ns / 1e9
        total += s
        for bi, le in enumerate(HISTOGRAM_BUCKETS_S):
            if s <= le:
                counts[bi] += 1  # every bucket with s <= le: cumulative
    return {"buckets": list(zip(HISTOGRAM_BUCKETS_S, counts)),
            "sum": total, "count": len(samples_ns)}


def latency_histograms() -> Dict[str, Dict[str, Any]]:
    """Histogram views of the duration reservoirs for /metrics.prom:
    task-attempt latency and run_tasks wave wall, in seconds."""
    with _lock:
        task = list(_task_duration_ns)
        wave = list(_wave_wall_ns)
    return {"task_duration_seconds": _histogram(task),
            "wave_wall_seconds": _histogram(wave)}


def note_device_exchange(rows: int, nbytes: int,
                         collectives: int = 1, row_bytes: int = 0) -> None:
    """One map->reduce repartition completed over device collectives:
    `rows` real rows exchanged, `nbytes` buffer bytes that rode the
    all-to-all (padded send buffers — what actually moved), the number
    of collective ops the program issued, and `row_bytes`, the real
    rows' own bytes as they ride (columns, validity, partition id, row
    mask; no padding)."""
    with _lock:
        _shuffle["shuffle_device_exchanges"] += 1
        _shuffle["shuffle_device_rows"] += int(rows)
        _shuffle["shuffle_device_bytes"] += int(nbytes)
        _shuffle["shuffle_device_row_bytes"] += int(row_bytes)
        _shuffle["shuffle_device_collectives"] += int(collectives)


def note_exchange_source(staged: int = 0, placed: int = 0) -> None:
    """One device exchange dispatched: `staged` rows entered the
    collective from host columns (`DeviceExchange.dispatch`), `placed`
    rows from the chips they lay on (`dispatch_placed`)."""
    with _lock:
        _shuffle["shuffle_device_staged_rows"] += int(staged)
        _shuffle["shuffle_device_placed_rows"] += int(placed)


def note_exchange_redispatch() -> None:
    """A destination bucket overflowed and the exchange was dispatched
    again at the ladder's next rung."""
    with _lock:
        _shuffle["shuffle_device_redispatches"] += 1


def note_host_exchange(nbytes: int) -> None:
    """One producer stage's map outputs landed in host shuffle files
    (`nbytes` = total .data bytes across its map tasks)."""
    with _lock:
        _shuffle["shuffle_host_bytes"] += int(nbytes)


def note_exchange_tier(tier: str, rows: int, nbytes: int) -> None:
    """One map task committed `rows` rows through `tier`: `resident`
    (on the chip), `file` (its `.data` file), or `spilled` (a resident
    output written to files under memory pressure)."""
    with _lock:
        _shuffle[f"shuffle_{tier}_rows"] += int(rows)
        _shuffle[f"shuffle_{tier}_bytes"] += int(nbytes)


def note_device_shuffle_fallback() -> None:
    """A device-resident exchange aborted (fault, overflow, capacity)
    and the stage re-ran through the file shuffle."""
    with _lock:
        _shuffle["shuffle_device_fallbacks"] += 1


def note_exchange_overlap() -> None:
    """One overlapped exchange ticket drained: its collective and
    partition split ran concurrently with a later task's fold."""
    with _lock:
        _shuffle["shuffle_device_overlap_exchanges"] += 1


def note_barrier_idle(ns: int) -> None:
    """Host-side fold-end -> first-collective-dispatch gap for one
    producer stage's device exchange (the barrier the overlapped
    exchange eliminates; clamped >= 0 by callers)."""
    with _lock:
        _shuffle["shuffle_barrier_idle_ns"] += int(ns)


def note_frame_compression(kind: str, saved: int) -> None:
    """io.compression.codec saved `saved` bytes on one frame: kind
    'worker' = a worker-pool control frame (task/result/heartbeat),
    'rss' = an RSS partition put."""
    key = ("worker_frame_compressed_bytes_saved" if kind == "worker"
           else "rss_put_compressed_bytes_saved")
    with _lock:
        _shuffle[key] += int(saved)


def note_worker_cpu(ns: int) -> None:
    """Child-process CPU (user+sys) reported in one result frame."""
    with _lock:
        _workers["worker_cpu_ns"] += int(ns)


def shuffle_stats() -> dict:
    with _lock:
        return dict(_shuffle)


def note_stage_program(cache_hit: bool) -> None:
    """A StageProgram lookup: built fresh (new stage fingerprint /
    capacity rung / dtype signature) or served from the process LRU."""
    with _lock:
        if cache_hit:
            _stage_loop["stage_loop_program_cache_hits"] += 1
        else:
            _stage_loop["stage_loop_programs_built"] += 1


def note_stage_loop_task(chunks: int, batches: int, rows: int, lanes: int,
                         regrows: int, reserves: int, undone_steps: int,
                         rehash_lanes: int,
                         slots: int, dispatches_avoided: int,
                         full_rounds: int, narrow_rounds: int,
                         rehash_groups: int, rehash_new_slots: int,
                         rehash_probe_lanes: int, table_bytes: int,
                         chip: int) -> None:
    """One map task completed through the device-resident stage loop:
    `chunks` loop program calls folded `batches` batches / `rows` rows,
    over `lanes` lanes: for every batch handed to a fold its window's
    capacity, so rows over lanes is how full the folds ran (a step of
    the fold costs by its lanes, not by its live rows; kept by `chip`
    too).
    The agg table's capacity was raised at `reserves` chunk boundaries
    before the fold and `regrows` times after an overflow;
    `undone_steps` steps of the table (the fold's, counted on the
    device, and a rehash's) overflowed and took their claims back, kept
    by `chip` too: it reads 0 wherever the table was sized before the
    fold.  Each rehash
    pushed the old table's slots (`rehash_lanes`, summed) holding
    `rehash_groups` groups into a table of `rehash_new_slots` slots,
    re-inserting them over `rehash_probe_lanes` lanes (summed: the width
    the live slots were compacted to, or the old table's slots where the
    rehash ran uncompacted; old slots over these is the compaction's
    ratio), and the table ended at `slots` (`stage_loop_final_slots`
    sums them, so a window's delta over `stage_loop_tasks` is the mean
    table;
    `stage_loop_max_slots` is the high-water mark since reset(), which
    a warm window's delta reads as 0).  `table_bytes` is the most the
    memory manager held against the task's table at one time (old and
    new table together while a rehash ran).  The table counters are kept
    by `chip` too.  The table's probe ran `full_rounds` rounds over a
    whole batch's lanes and `narrow_rounds` over the compacted rows a
    batch still had unplaced (parallel/stage.py hash_agg_step).  The
    staged per-batch path would have issued `dispatches_avoided` extra
    Python dispatches."""
    table = dict(zip(_CHIP_TABLE_KEYS, map(int, (
        rehash_lanes, rehash_groups, rehash_new_slots, rehash_probe_lanes,
        slots, table_bytes))))
    with _lock:
        _stage_loop["stage_loop_tasks"] += 1
        _stage_loop["stage_loop_calls"] += int(chunks)
        _stage_loop["stage_loop_chunks"] += int(chunks)
        _stage_loop["stage_loop_batches"] += int(batches)
        _stage_loop["stage_loop_rows"] += int(rows)
        _stage_loop["stage_loop_regrows"] += int(regrows)
        _stage_loop["stage_loop_reserves"] += int(reserves)
        entry = _chip_entry(chip)
        for k, v in (("stage_loop_lanes", int(lanes)),
                     ("stage_loop_undone_steps", int(undone_steps)),
                     *table.items()):
            _stage_loop[k] += v
            entry[k] += v
        _stage_loop["stage_loop_full_rounds"] += int(full_rounds)
        _stage_loop["stage_loop_narrow_rounds"] += int(narrow_rounds)
        _stage_loop["stage_loop_max_slots"] = max(
            _stage_loop["stage_loop_max_slots"], int(slots))
        _stage_loop["stage_loop_staged_dispatches_avoided"] += \
            int(dispatches_avoided)


def note_stage_loop_window(fused: bool, chip: int) -> None:
    """One window of source batches assembled for a fold
    (plan/fused.py `_batch_windows`): stacked, widened and counted by
    the one window program.  `fused` says that nothing ran beside it: no
    batch of the window had to be padded to the others' capacity first,
    array by array.  Kept by `chip` too."""
    with _lock:
        for counters in (_stage_loop, _chip_entry(chip)):
            counters["stage_loop_windows"] += 1
            counters["stage_loop_windows_fused"] += int(fused)


def note_stage_loop_fallback(reason: str = "") -> None:
    """A stage-loop task aborted (ineligible chain, injected fault,
    overflow past the cap) and re-ran through the staged per-batch
    executor.  The reason text is tallied so a run can tell a declared
    degradation from anything else without tracing on."""
    with _lock:
        _stage_loop["stage_loop_fallbacks"] += 1
        if reason in _stage_loop_fallback_reasons \
                or len(_stage_loop_fallback_reasons) < _FALLBACK_ERRORS_KEPT:
            _stage_loop_fallback_reasons[reason] = \
                _stage_loop_fallback_reasons.get(reason, 0) + 1


def stage_loop_fallback_reasons() -> Dict[str, int]:
    """reason text -> count of stage-loop fallbacks since reset()."""
    with _lock:
        return dict(_stage_loop_fallback_reasons)


def stage_loop_stats() -> dict:
    with _lock:
        return dict(_stage_loop)


def note_partial_agg_probe(rows: int, groups: int) -> None:
    """One cardinality probe over `rows` buffered rows that resolved
    `groups` distinct groups (the skip decision's evidence)."""
    with _lock:
        _agg["partial_agg_probe_rows"] += int(rows)
        _agg["partial_agg_probe_groups"] += int(groups)


def note_partial_agg_skip(switch_row: int, on_spill: bool = False) -> None:
    """One partial agg switched to pass-through after consuming
    `switch_row` rows; `on_spill` when memory pressure (not the ratio
    probe) forced the switch."""
    with _lock:
        _agg["partial_agg_skip_events"] += 1
        _agg["partial_agg_switch_rows"] += int(switch_row)
        if on_spill:
            _agg["partial_agg_spill_switches"] += 1


def note_partial_agg_rows(rows: int) -> None:
    """Rows streamed through the pass-through lane un-aggregated."""
    with _lock:
        _agg["partial_agg_skipped_rows"] += int(rows)


def note_agg_eager(rows: int) -> None:
    """Rows an unfused `AggExec` took, a batch at a time."""
    with _lock:
        _agg["agg_eager_rows"] += int(rows)


def agg_stats() -> dict:
    with _lock:
        return dict(_agg)


def note_sortmerge(**deltas: int) -> None:
    """kwargs name `_sortmerge` keys; all are counters."""
    with _lock:
        for k, v in deltas.items():
            _sortmerge[k] += int(v)


def note_sort_resident(rows: int, chip: int) -> None:
    """A partition of `rows` rows sorted on `chip` without leaving it."""
    with _lock:
        _sortmerge["sort_device_rows"] += int(rows)
        _sortmerge["sort_resident_rows"] += int(rows)
        _chip_entry(chip)["sort_resident_rows"] += int(rows)


def sortmerge_stats() -> dict:
    with _lock:
        return dict(_sortmerge)


def note_window(rows: int, chip: int, resident: bool,
                scan_bytes: int = 0) -> None:
    """A `WindowExec` lane computed its functions over one sorted run of
    `rows` rows on `chip`; `resident`: the run never left the chip, and
    its scans had `scan_bytes` to move at the least."""
    deltas = {"window_rows": rows, "window_partitions": 1,
              "window_resident_rows": rows if resident else 0,
              "window_scan_bytes": scan_bytes}
    with _lock:
        entry = _chip_entry(chip)
        for k, v in deltas.items():
            _window[k] += int(v)
            entry[k] += int(v)


def window_stats() -> dict:
    with _lock:
        return dict(_window)


def note_dict(chip: Optional[int] = None, **deltas: int) -> None:
    """Dictionary-code and Expand accounting: kwargs name `_dicts` keys
    (all sums); `chip` None reads the current task's."""
    if chip is None:
        from blaze_tpu.bridge.context import current_task
        chip = current_task().device_id
    with _lock:
        entry = _chip_entry(chip)
        for k, v in deltas.items():
            _dicts[k] += int(v)
            entry[k] += int(v)


def dict_stats() -> dict:
    with _lock:
        return dict(_dicts)


def note_stream_epoch(wall_ns: int, rows: int = 0,
                      records: int = 0) -> None:
    """One committed micro-batch epoch: wall time, sink rows emitted,
    source records consumed."""
    with _lock:
        _stream["stream_epochs"] += 1
        _stream["stream_epoch_wall_ns"] += int(wall_ns)
        _stream["stream_rows"] += int(rows)
        _stream["stream_records"] += int(records)


def note_stream_late(records: int, side_rows: int = 0) -> None:
    """Late records seen past the watermark; side_rows counts the ones
    routed to the late-side output (policy `side`)."""
    with _lock:
        _stream["stream_late_records"] += int(records)
        _stream["stream_late_side_rows"] += int(side_rows)


def note_stream_checkpoint(nbytes: int = 0) -> None:
    with _lock:
        _stream["stream_checkpoints"] += 1
        _stream["stream_checkpoint_bytes"] += int(nbytes)


def note_stream_recovery(replayed_epochs: int = 0) -> None:
    """One recovery round: restore from the last committed manifest."""
    with _lock:
        _stream["stream_recoveries"] += 1
        _stream["stream_replayed_epochs"] += int(replayed_epochs)


def note_stream_sink(committed: int = 0, dup_skips: int = 0) -> None:
    """Exactly-once sink outcomes: first-wins commits vs replayed
    attempts skipped because the epoch manifest already existed."""
    with _lock:
        _stream["stream_sink_commits"] += int(committed)
        _stream["stream_sink_dup_skips"] += int(dup_skips)


def note_stream_gauges(watermark_delay_ms: Optional[int] = None,
                       window_state_bytes: Optional[int] = None,
                       source_lag_records: Optional[int] = None) -> None:
    """Latest-observation gauges (watermark delay, retained window-state
    bytes, unread source records)."""
    with _lock:
        if watermark_delay_ms is not None:
            _stream["stream_watermark_delay_ms_last"] = \
                int(watermark_delay_ms)
        if window_state_bytes is not None:
            _stream["stream_window_state_bytes_last"] = \
                int(window_state_bytes)
        if source_lag_records is not None:
            _stream["stream_source_lag_records_last"] = \
                int(source_lag_records)


def stream_stats() -> dict:
    with _lock:
        return dict(_stream)


def expr_stats() -> dict:
    """Expression-program counters; `expr_cache_hit_rate` is hits over
    cache resolutions (the recompile-guard's steady-state signal)."""
    with _lock:
        d = dict(_exprs)
    lookups = d["expr_programs_built"] + d["expr_program_cache_hits"]
    d["expr_cache_hit_rate"] = (
        d["expr_program_cache_hits"] / lookups if lookups else 0.0)
    return d


def pipeline_stats() -> dict:
    """Bucket + prefetch counters; `bucket_capacities` is the distinct
    ladder rungs observed (the static-shape universe jit kernels see)."""
    with _lock:
        d = dict(_pipeline)
        d["distinct_buckets"] = len(_bucket_caps)
        d["bucket_capacities"] = sorted(_bucket_caps)
        return d


def compile_report() -> dict:
    """Per-kernel compile stats + totals, JSON-ready.  Covers the
    kernels wrapped by `meter_jit` only: `total_compiles` in
    `snapshot()` is their sum.  What JAX compiled in all, eager glue
    programs included, is `backend_compiles` (`backend_stats()`)."""
    with _lock:
        kernels = {}
        totals = {"calls": 0, "compiles": 0, "cache_hits": 0,
                  "compile_ns": 0}
        for kname, e in sorted(_kernels.items()):
            sigs = len(e["signatures"])
            kernels[kname] = {
                "calls": e["calls"], "compiles": e["compiles"],
                "cache_hits": e["cache_hits"],
                "compile_ns": e["compile_ns"],
                "dispatch_ns": e["dispatch_ns"],
                "distinct_signatures": sigs,
                "shape_churn": sigs > SHAPE_CHURN_THRESHOLD,
            }
            for k in totals:
                totals[k] += e[k]
        return {"kernels": kernels, "totals": totals}


def transfer_stats() -> dict:
    with _lock:
        return dict(_transfers)


def backend_stats() -> dict:
    with _lock:
        return dict(_backend)


def counter_families() -> Dict[str, Dict[str, int]]:
    """Every flat counter key, grouped by plane.  The single source the
    Prometheus exposition (bridge/profiling.py) and the history rollup
    (bridge/history.py) both iterate, so a new family cannot land in one
    surface and silently miss the other
    (tests/test_history_conformance.py).  Keys ending in `_last` are
    point-in-time gauges, everything else is a monotone counter."""
    with _lock:
        return {
            "transfers": dict(_transfers),
            "placement": dict(_placement),
            "pipeline": dict(_pipeline),
            "exprs": dict(_exprs),
            "faults": dict(_faults),
            "shuffle": dict(_shuffle),
            "stage_loop": dict(_stage_loop),
            "agg": dict(_agg),
            "join": dict(_join),
            "stream": dict(_stream),
            "workers": dict(_workers),
            "speculation": dict(_speculation),
            "obs": dict(_obs),
            "cache": dict(_cache),
            "stats": dict(_stats),
            "aqe": dict(_aqe),
            "encoding": dict(_encoding),
            "fleet": dict(_fleet),
            "backend": dict(_backend),
        }


def snapshot() -> dict:
    """Flat counter snapshot for before/after deltas (explain_analyze)."""
    rep = compile_report()
    flat = transfer_stats()
    flat.update(placement_stats())
    for chip, entry in chip_stats().items():
        flat.update({f"chip{chip}_{k}": v for k, v in entry.items()})
    ps = pipeline_stats()
    ps.pop("bucket_capacities", None)  # list: not delta-able
    flat.update(ps)
    es = expr_stats()
    es.pop("expr_cache_hit_rate", None)  # ratio: not delta-able
    flat.update(es)
    flat.update(fault_stats())
    flat.update(agg_stats())
    flat.update(shuffle_stats())
    flat.update(stage_loop_stats())
    flat.update(sortmerge_stats())
    flat.update(window_stats())
    flat.update(dict_stats())
    flat.update(join_stats())
    flat.update(stream_stats())
    flat.update(worker_stats())
    flat.update(speculation_stats())
    flat.update(obs_stats())
    flat.update(cache_stats())
    flat.update(statstore_stats())
    flat.update(aqe_stats())
    flat.update(encoding_stats())
    flat.update(fleet_stats())
    flat.update(backend_stats())
    flat.update({f"total_{k}": v for k, v in rep["totals"].items()})
    return flat


def delta(before: dict) -> dict:
    now = snapshot()
    return {k: now.get(k, 0) - before.get(k, 0) for k in now}


def reset() -> None:
    """Test helper: clear all counters."""
    with _lock:
        _kernels.clear()
        for k in _transfers:
            _transfers[k] = 0
        for k in _placement:
            _placement[k] = 0
        _chips.clear()
        for k in _pipeline:
            _pipeline[k] = 0
        for k in _exprs:
            _exprs[k] = 0
        for k in _faults:
            _faults[k] = 0
        for k in _agg:
            _agg[k] = 0
        for k in _shuffle:
            _shuffle[k] = 0
        for k in _stage_loop:
            _stage_loop[k] = 0
        for k in _sortmerge:
            _sortmerge[k] = 0
        for k in _window:
            _window[k] = 0
        for k in _join:
            _join[k] = 0
        for k in _dicts:
            _dicts[k] = 0
        for k in _stream:
            _stream[k] = 0
        for k in _workers:
            _workers[k] = 0
        for k in _speculation:
            _speculation[k] = 0
        for k in _obs:
            _obs[k] = 0
        for k in _cache:
            _cache[k] = 0
        for k in _stats:
            _stats[k] = 0
        for k in _aqe:
            _aqe[k] = 0
        for k in _encoding:
            _encoding[k] = 0
        for k in _fleet:
            _fleet[k] = 0
        for k in _backend:
            _backend[k] = 0
        _program_loads.clear()
        _fallback_errors.clear()
        _stage_loop_fallback_reasons.clear()
        _task_duration_ns.clear()
        _wave_wall_ns.clear()
        _bucket_caps.clear()

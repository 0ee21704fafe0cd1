"""Layered typed configuration.

Mirrors the reference's config system: JVM-side `ConfigOption` schema objects
(ref: auron-core/.../configuration/ConfigOption.java) with ~70 `spark.auron.*`
keys defined in SparkAuronConfiguration, read lazily by the native side through
`define_conf!` proxies (ref: auron-jni-bridge/src/conf.rs:20-63).

Here the host engine (Spark bridge or test harness) supplies a plain dict of
key→string overrides; operators read typed values through module-level
`ConfigOption` objects.  A single `conf` session object is the source of truth,
like the reference's single JVM SparkConf.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

_REGISTRY: Dict[str, "ConfigOption"] = {}


@dataclass(frozen=True)
class ConfigOption:
    """Typed config key with default, alt-keys and doc (ref ConfigOption.java)."""

    key: str
    default: Any
    parse: Callable[[str], Any]
    doc: str = ""
    alt_keys: tuple = ()
    category: str = "core"

    def __post_init__(self):
        _REGISTRY[self.key] = self

    def get(self, session: Optional["ConfSession"] = None) -> Any:
        return (session or conf).get(self)


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


def int_conf(key: str, default: int, doc: str = "", category: str = "core",
             alt_keys: tuple = ()) -> ConfigOption:
    return ConfigOption(key, default, int, doc, alt_keys, category)


def float_conf(key: str, default: float, doc: str = "", category: str = "core",
               alt_keys: tuple = ()) -> ConfigOption:
    return ConfigOption(key, default, float, doc, alt_keys, category)


def bool_conf(key: str, default: bool, doc: str = "", category: str = "core",
              alt_keys: tuple = ()) -> ConfigOption:
    return ConfigOption(key, default, _parse_bool, doc, alt_keys, category)


def str_conf(key: str, default: str, doc: str = "", category: str = "core",
             alt_keys: tuple = ()) -> ConfigOption:
    return ConfigOption(key, default, str, doc, alt_keys, category)


class ConfSession:
    """Mutable override store; thread-safe; env `BLAZE_TPU_<KEY>` wins lowest."""

    def __init__(self, overrides: Optional[Dict[str, str]] = None):
        self._lock = threading.Lock()
        self._overrides: Dict[str, str] = dict(overrides or {})

    def set(self, key: str, value: Any) -> None:
        with self._lock:
            self._overrides[key] = str(value)

    def unset(self, key: str) -> None:
        with self._lock:
            self._overrides.pop(key, None)

    def update(self, kv: Dict[str, Any]) -> None:
        with self._lock:
            for k, v in kv.items():
                self._overrides[k] = str(v)

    def get(self, opt: ConfigOption) -> Any:
        with self._lock:
            for k in (opt.key, *opt.alt_keys):
                if k in self._overrides:
                    return opt.parse(self._overrides[k])
        hosted = host_conf_lookup(opt)
        if hosted is not None:
            return opt.parse(hosted)
        for k in (opt.key, *opt.alt_keys):
            env_key = "BLAZE_TPU_" + k.upper().replace(".", "_")
            if env_key in os.environ:
                return opt.parse(os.environ[env_key])
        return opt.default

    def is_set(self, opt: ConfigOption) -> bool:
        with self._lock:
            if any(k in self._overrides for k in (opt.key, *opt.alt_keys)):
                return True
        return any("BLAZE_TPU_" + k.upper().replace(".", "_") in os.environ
                   for k in (opt.key, *opt.alt_keys))

    def snapshot(self) -> Dict[str, str]:
        with self._lock:
            return dict(self._overrides)

    def replace(self, overrides: Dict[str, str]) -> None:
        """Swap the whole override map (worker children apply the
        parent's snapshot per task; update() would leak keys the parent
        has since unset)."""
        with self._lock:
            self._overrides = {k: str(v) for k, v in overrides.items()}


class _Scoped:
    """Context manager restoring overridden keys on exit (test helper)."""

    def __init__(self, session: ConfSession, kv: Dict[str, Any]):
        self._session = session
        self._kv = kv
        self._saved: Dict[str, Optional[str]] = {}

    def __enter__(self):
        snap = self._session.snapshot()
        for k, v in self._kv.items():
            self._saved[k] = snap.get(k)
            self._session.set(k, v)
        return self._session

    def __exit__(self, *exc):
        for k, old in self._saved.items():
            if old is None:
                self._session.unset(k)
            else:
                self._session.set(k, old)
        return False


#: Global session (the host bridge replaces/overlays this per task).
conf = ConfSession()

#: Host-engine conf resolver installed through the C-ABI callback surface
#: (the define_conf! lazy JVM reads, auron-jni-bridge/src/conf.rs:20-63).
#: Lookups are memoized per key like the reference's lazy proxies — the
#: cross-ABI round trip must not sit in per-batch hot paths.
_host_conf_provider: Optional[Callable[[str], Optional[str]]] = None
_host_conf_cache: Dict[str, Optional[str]] = {}


def set_host_conf_provider(fn: Optional[Callable[[str], Optional[str]]]
                           ) -> None:
    global _host_conf_provider
    _host_conf_provider = fn
    _host_conf_cache.clear()


def host_conf_lookup(opt: "ConfigOption") -> Optional[str]:
    fn = _host_conf_provider
    if fn is None:
        return None
    for k in (opt.key, *opt.alt_keys):
        if k in _host_conf_cache:
            v = _host_conf_cache[k]
        else:
            v = fn(k)
            _host_conf_cache[k] = v
        if v is not None:
            return v
    return None


def scoped(**kv: Any) -> _Scoped:
    """`with scoped(**{"auron.batch.size": 1024}): ...`"""
    return _Scoped(conf, {k.replace("_", "."): v for k, v in kv.items()} if all(
        "." not in k for k in kv) else kv)


def describe_all() -> List[Dict[str, Any]]:
    """Doc generator feed (ref SparkAuronConfigurationDocGenerator.java)."""
    return [
        {"key": o.key, "default": o.default, "doc": o.doc,
         "category": o.category, "alt_keys": o.alt_keys}
        for o in sorted(_REGISTRY.values(), key=lambda o: o.key)
    ]


def generate_docs() -> str:
    """Render the configuration reference as markdown, grouped by category
    (the SparkAuronConfigurationDocGenerator analog)."""
    by_cat: Dict[str, List[Dict[str, Any]]] = {}
    for o in describe_all():
        by_cat.setdefault(o["category"], []).append(o)
    lines = ["# Configuration", ""]
    for cat in sorted(by_cat):
        lines.append(f"## {cat}")
        lines.append("")
        lines.append("| key | default | description |")
        lines.append("|---|---|---|")
        for o in by_cat[cat]:
            doc = o["doc"]
            if o["alt_keys"]:
                alts = ", ".join(f"`{k}`" for k in o["alt_keys"])
                doc = f"{doc} (aliases: {alts})"
            lines.append(f"| `{o['key']}` | `{o['default']}` | "
                         f"{doc} |")
        lines.append("")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Core option schema.  Keys keep the reference's names (conf.rs:32-63 /
# SparkAuronConfiguration) so a host bridge can pass them straight through.
# ---------------------------------------------------------------------------

BATCH_SIZE = int_conf(
    "auron.batch.size", 32768,
    "Static rows-per-batch tile; device buffers are padded to this "
    "capacity.  Larger than the reference's 10000 default: per-batch "
    "orchestration is the host-side fixed cost here, and HBM/host RAM "
    "fit 32K-row tiles comfortably.")
MEMORY_FRACTION = float_conf(
    "auron.memory.fraction", 0.6,
    "Fraction of the device HBM budget granted to the memory manager.")
SMJ_FALLBACK_ENABLE = bool_conf(
    "auron.smjfallback.enable", False,
    "Fall back from hash join to sort-merge join when the build side "
    "exceeds the rows/mem thresholds "
    "(ref SparkAuronConfiguration.java:231).")
SMJ_FALLBACK_ROWS_THRESHOLD = int_conf(
    "auron.smjfallback.rows.threshold", 10_000_000,
    "Build-side row count that triggers hash->SMJ fallback.")
SMJ_FALLBACK_MEM_THRESHOLD = int_conf(
    "auron.smjfallback.mem.threshold", 134217728,
    "Build-side bytes that trigger hash->SMJ fallback (128MB default).")
PARTIAL_AGG_SKIPPING_ENABLE = bool_conf(
    "auron.tpu.partialAgg.skipping.enable", True,
    "Pass rows through un-aggregated when partial-agg cardinality is too high "
    "(ref agg_table.rs:108-122 AGG_TRIGGER_PARTIAL_SKIPPING).",
    alt_keys=("auron.partialAggSkipping.enable",))
PARTIAL_AGG_SKIPPING_RATIO = float_conf(
    "auron.tpu.partialAgg.skipping.ratio", 0.9,
    "Groups-emitted/rows-consumed ratio beyond which partial agg switches "
    "to pass-through (reference default 0.9, SparkAuronConfiguration.java).",
    alt_keys=("auron.partialAggSkipping.ratio",))
PARTIAL_AGG_SKIPPING_MIN_ROWS = int_conf(
    "auron.tpu.partialAgg.skipping.minRows", 50000,
    "Probe window: rows observed before the one-shot cardinality probe "
    "runs (the reference defaults to 5x its 10000-row batch size).",
    alt_keys=("auron.partialAggSkipping.minRows",))
SPILL_COMPRESSION_CODEC = str_conf(
    "auron.spill.compression.codec", "zstd", "Codec for spill files + shuffle IPC.")
SHUFFLE_COMPRESSION_TARGET_BUF_SIZE = int_conf(
    "auron.shuffle.compression.target.buf.size", 4194304,
    "Target frame size for compressed shuffle IPC blocks.")
UDF_WRAPPER_NUM_THREADS = int_conf(
    "auron.udfWrapper.numThreads", 1, "Host threads serving UDF fallback eval.")
TOKIO_WORKER_THREADS_PER_CPU = int_conf(
    "auron.tokio.worker.threads.per.cpu", 1,
    "Host async worker threads per CPU core for the task runtime "
    "(ref rt.rs:108-112; our executor is a thread pool feeding the device).")
PARQUET_ENABLE_PAGE_FILTERING = bool_conf(
    "auron.parquet.enable.pageFiltering", True,
    "Row-group/page pruning with min-max stats on scan (ref conf.rs:43): "
    "by the scan's own predicate and, for one read, by what its consumer "
    "says of the rows it will use (a fused aggregation's leading filters, "
    "a FilterExec's conjuncts, a join's build-key range), on the host's "
    "path and on the chip's alike.  A row group without statistics is "
    "read.")
PARQUET_ENABLE_BLOOM_FILTER = bool_conf(
    "auron.parquet.enable.bloomFilter", False,
    "Parquet bloom-filter pruning on scan (ref conf.rs:44).")
IGNORE_CORRUPTED_FILES = bool_conf(
    "auron.files.ignoreCorruptFiles", False, "Skip unreadable input files.",
    alt_keys=("auron.ignore.corrupted.files",))
INPUT_BATCH_PREFETCH = int_conf(
    "auron.input.batch.prefetch", 2,
    "Host->device double-buffering depth (the sync_channel(1) analog, rt.rs:142).")
BATCH_BUCKETING_ENABLE = bool_conf(
    "auron.tpu.batch.bucketing", True,
    "Quantize device-buffer capacities onto the geometric bucket ladder "
    "(batch.bucket_capacity) so every jit'd kernel sees a bounded set of "
    "static shapes and compiles at most once per (kernel, bucket); off, "
    "capacities lane-round per batch and each ragged tail size compiles "
    "its own program.")
BATCH_BUCKET_MIN = int_conf(
    "auron.tpu.batch.bucket.min", 128,
    "Smallest rung of the capacity bucket ladder (rounded up to the "
    "128-lane tile).")
BATCH_BUCKET_GROWTH = float_conf(
    "auron.tpu.batch.bucket.growth", 2.0,
    "Geometric growth factor between bucket-ladder rungs; 2.0 gives the "
    "128*2^k ladder (memory overhead bounded by the factor, kernel "
    "variants bounded by log_growth(max_rows)).")
IO_PREFETCH_ENABLE = bool_conf(
    "auron.tpu.io.prefetch", True,
    "Async pipelined executor at host-IO edges (ops/base.py "
    "PrefetchIterator): parquet row-group decode, shuffle IPC segment "
    "reads and map-side materialization run on a bounded background "
    "worker so the device never idles on host IO.  Kill-switch for "
    "debugging; depth comes from auron.tpu.io.prefetch.depth.")
IO_PREFETCH_DEPTH = int_conf(
    "auron.tpu.io.prefetch.depth", 2,
    "Bounded queue depth of the IO prefetcher; <= 0 degrades to a "
    "synchronous passthrough (same as disabling the kill-switch).")
ON_DEVICE_AGG_CAPACITY = int_conf(
    "auron.tpu.agg.table.capacity", 1 << 18,
    "Group slots the device hash aggregation table starts from.  The "
    "stage loop (runtime/loop.py) treats it as a floor: at each chunk "
    "boundary it sizes the table, in every agg mode, for the groups held "
    "plus the rows about to arrive (powers of two up to 2^24 slots).  "
    "The staged per-batch path (plan/fused.py) starts here too; there "
    "overflow doubles the table in exact modes and degrades to "
    "pass-through partials in partial mode.")
FUSED_STAGE_ENABLE = bool_conf(
    "auron.tpu.fused.stage.enable", True,
    "Rewrite eligible scan->filter->partial-agg subtrees into single-XLA-"
    "program fused stages (plan/fused.py fuse_plan).")
FUSED_FOLD_WINDOW = int_conf(
    "auron.tpu.fused.fold.window", 1,
    "Source batches folded through ONE XLA program in the fused dense "
    "path (fori_loop over stacked inputs): divides dispatch count and "
    "keeps the group-table carry in place inside the program.")
FUSED_STAGE_CAPACITY = int_conf(
    "auron.tpu.fused.stage.capacity", 1 << 24,
    "Max dense group-table slots (product of key ranges) for the fused "
    "dense-group-id path before falling back to the sorted table.")
AGG_MXU_ENABLE = bool_conf(
    "auron.tpu.mxuAgg.enable", True,
    "Aggregate compact dense group tables as MXU one-hot matmuls "
    "(kernels/mxu_agg.py) instead of scatters when stats prove "
    "eligibility — the TPU fast path (~4x the best scatter kernel).")
AGG_MXU_MAX_SLOTS = int_conf(
    "auron.tpu.mxuAgg.maxSlots", 1 << 17,
    "Dense-table slot cap for the MXU aggregation strategy; beyond it "
    "the per-row matmul cost outgrows the scatter path.")
AGG_MXU_FORCE = bool_conf(
    "auron.tpu.mxuAgg.force", False,
    "Run the MXU agg strategy on non-TPU backends through its scatter "
    "reference formulation (integration tests).")
AGG_MXU_DECIMAL_SCALE = int_conf(
    "auron.tpu.mxuAgg.decimalScale", 100,
    "Fixed-point scale probed for float sum columns on the MXU path "
    "(100 = two decimals, the TPC-DS money shape); rows that fail the "
    "exactness verify fall the stage back to the scatter path.")
SORT_SPILL_BATCHES = int_conf(
    "auron.tpu.sort.inmem.batches", 64,
    "Batches buffered in device memory before external sort spills a run.")
UDF_FALLBACK_ENABLE = bool_conf(
    "auron.udf.fallback.enable", True,
    "Wrap unsupported expressions as host-evaluated UDFs during plan "
    "conversion (convertExprWithFallback, NativeConverters.scala:399) "
    "instead of rejecting the subtree.")
PLACEMENT = str_conf(
    "auron.tpu.placement", "auto",
    "Stage-compute placement: 'auto' measures the accelerator dispatch "
    "round trip once and moves stage compute to the host XLA backend "
    "(with a WARNING) only when it exceeds placement.rtt.threshold.ms; "
    "'device' forces the accelerator and raises where jax found none; "
    "'host' forces host XLA (bridge/placement.py — the "
    "removeInefficientConverts analog for the host<->device boundary).")
PLACEMENT_RTT_THRESHOLD_MS = float_conf(
    "auron.tpu.placement.rtt.threshold.ms", 5.0,
    "Auto-placement cutoff: a measured per-dispatch round trip above "
    "this moves stages onto host XLA.  A directly attached chip "
    "measures well under a millisecond.")
FUSED_DICT_DEVICE_ENABLE = bool_conf(
    "auron.tpu.fused.dictDevice", True,
    "Device path for var-width (utf8/binary) group keys in fused "
    "stages: every key column dictionary-encodes to dense i32 codes "
    "against an accumulated per-key dictionary, the device groups by "
    "the packed code id with the sort-free dense kernel, and keys "
    "decode back through the dictionaries at emit (SURVEY §7 "
    "hard-part #1; parquet dictionary-code strategy).")
FUSED_DICT_DEVICE_MAX_SLOTS = int_conf(
    "auron.tpu.fused.dictDevice.maxSlots", 1 << 22,
    "Dense code-table ceiling for the dict-device strategy; growth "
    "past it falls back to the host-vectorized aggregation.")
ENCODING_DICT_ENABLE = bool_conf(
    "auron.tpu.encoding.dict.enable", True,
    "Dictionary-encode utf8 columns at scan decode: the device lanes "
    "see only the int32 code column, so group-by/join keys, equality "
    "filters and IN-list predicates ride the existing int lanes "
    "(expr programs, device stage loop, hash kernels); strings decode "
    "back to utf8 only at host materialization.  Operations the codes "
    "cannot answer (substring, LIKE, concat) fall back eager per "
    "EXPRESSION, not per stage.  A utf8 column is then a DictColumn from "
    "where it enters a query to where a row is shown: broadcast build "
    "sides are encoded against sorted dictionaries, a fused stage's "
    "string group keys are key lanes of the stage loop's table, the "
    "exchange carries codes and the resident sort and window take them.  "
    "On by default since PR 48; the disabled path is byte-identical to "
    "pre-encoding behavior.", category="encoding")
ENCODING_DICT_MAX_ENTRIES = int_conf(
    "auron.tpu.encoding.dict.maxEntries", 1 << 16,
    "Per-column dictionary cardinality ceiling for scan-side string "
    "encoding.  A column whose running per-stream dictionary would "
    "exceed it stops encoding for the remainder of that stream (later "
    "batches stay plain utf8; downstream consumers decode losslessly).",
    category="encoding")
ENCODING_DECIMAL_ENABLE = bool_conf(
    "auron.tpu.encoding.decimal.enable", True,
    "Lower decimal128 columns as scaled-integer arithmetic on the "
    "device lanes: precisions <= 18 run as scaled int64 (or int32, see "
    "encoding.decimal.int32) through expr programs, the stage loop and "
    "DeviceExchange; unequal-scale comparisons, and a wider operand of "
    "a multiply or a comparison, go through the two-limb int128 "
    "kernels (kernels/decimal128.py).  Overflow promotes to the eager "
    "host path — never silently wraps.  Results are bit-identical to "
    "host Arrow decimal arithmetic, ANSI and non-ANSI.  On by default "
    "since TPC-DS's money columns are decimal(7,2); off keeps every "
    "decimal expression on the host.", category="encoding")
ENCODING_DECIMAL_INT32 = bool_conf(
    "auron.tpu.encoding.decimal.int32", True,
    "With encoding.decimal.enable, store decimals of precision <= 9 as "
    "scaled int32 on device (TPU v5e emulates 64-bit integer ops ~10x "
    "slower, so the narrowest exact width wins).  A single add/sub of "
    "two p<=9 operands cannot exceed int32 range; results widen to the "
    "declared int64 output dtype.", category="encoding")
COLUMN_PRUNING_ENABLE = bool_conf(
    "auron.tpu.columnPruning", True,
    "Engine-side column-pruning pass over decoded plans (the Catalyst "
    "ColumnPruning analog, plan/column_pruning.py): scans narrow to the "
    "columns referenced above them.  Plans from Spark arrive pruned "
    "already; this recovers the behavior for directly-authored IR.")
FUSED_HOST_COLLECT_ROWS = int_conf(
    "auron.tpu.fused.hostVectorized.collectRows", 1 << 21,
    "Buffered input rows before the host-vectorized agg re-merges into "
    "its running acc table (bounds memory by distinct groups; the "
    "InMemTable spill-trigger analog).")
SCAN_EAGER_FILE_BYTES = int_conf(
    "auron.tpu.scan.eagerFileBytes", 128 << 20,
    "Local parquet files up to this size decode eagerly per file "
    "(multithreaded read_row_groups, re-sliced zero-copy to the batch "
    "size); larger files stream through iter_batches for bounded "
    "memory.")
SHUFFLE_FILE_CODEC = str_conf(
    "auron.tpu.shuffle.localFileCodec", "raw",
    "Frame codec for staged rows written to local shuffle .data files "
    "(page-cache-backed disk: compression costs critical-path CPU and "
    "saves nothing; frames stay self-describing so any reader handles "
    "any mix).  Set to lz4 when .data segments are mostly fetched "
    "across the network.  Spill frames and RSS pushes always use "
    "io.compression.codec.")
DAG_SINGLE_TASK_BYTES = int_conf(
    "auron.tpu.dag.singleTaskBytes", 64 << 20,
    "Queries whose total file-scan input is at or below this run as ONE "
    "wire task with in-process exchanges (the Spark-AQE coalesce-to-one-"
    "partition analog); per-task fixed costs dominate below it.  0 "
    "disables the fast path.")
JOIN_RUNTIME_FILTER_ENABLE = bool_conf(
    "auron.tpu.join.runtimeFilter", True,
    "Drop probe rows outside the build side's join-key [min, max] before "
    "hash-probing (the runtime-filter join analog; ref bloom_filter agg "
    "+ bloom_filter_might_contain.rs).  On the host's path and on the "
    "chip's the range also prunes the row groups of a parquet scan the "
    "join probes (under auron.parquet.enable.pageFiltering; inner and "
    "probe-side semi joins, integer and date keys that are plain "
    "columns); on the chip's path the probe program is the row filter.")
FUSED_HOST_EAGER_SCAN_BYTES = int_conf(
    "auron.tpu.fused.hostVectorized.eagerScanBytes", 128 << 20,
    "Parquet inputs up to this size read eagerly (pq.read_table + "
    "vectorized filter) inside the host-vectorized fused stage; larger "
    "inputs stream through the dataset scanner for bounded memory.")
FUSED_HOST_VECTORIZED_ENABLE = bool_conf(
    "auron.tpu.fused.hostVectorized", True,
    "Under host placement, run eligible fused aggregations through "
    "Arrow's multithreaded C++ hash aggregation instead of XLA-CPU "
    "programs (plan/fused.py _execute_host_vectorized).")
HOST_TASK_PARALLELISM = int_conf(
    "auron.tpu.host.taskParallelism", 1,
    "Concurrent task slots under host placement.  Host tasks are "
    "Python-orchestrated around intra-op-parallel C++ kernels, so serial "
    "tasks with all cores inside each kernel beat GIL-contended task "
    "concurrency (the TASK_CPUS analog for the host path).")
EXPR_FUSE = bool_conf(
    "auron.tpu.expr.fuse", True,
    "Whole-stage expression compilation (exprs/program.py): lower each "
    "Filter/Project/FilterProject expression chain into ONE jit'd XLA "
    "program — mask computation, selection and projection fused — cached "
    "process-wide by expression fingerprint so repeated queries and all "
    "partitions share the compiled executable.  Host-only expressions "
    "(strings, UDFs, decimals, ANSI mode) fall back to the eager "
    "evaluator automatically; this is the kill-switch.")
EXPR_CACHE_SIZE = int_conf(
    "auron.tpu.expr.cache.size", 256,
    "Bounded LRU capacity of the cross-query expression-program cache "
    "(distinct (fingerprint, dtype-signature) entries; each entry also "
    "holds jit's per-bucket-capacity executables).")
EXPR_DONATE = bool_conf(
    "auron.tpu.expr.donate", False,
    "Donate input buffers to fused expression programs "
    "(jit donate_argnums) so XLA may reuse them in place.  Off by "
    "default: filter output batches alias their input columns and "
    "memory scans re-yield the same buffers across executes, so "
    "donation is only safe when the producer guarantees single-use "
    "batches.")
EXPR_CONST_FOLD = bool_conf(
    "auron.tpu.expr.constFold", True,
    "Fold literal-only subexpressions (lit(2)*lit(3), casts of "
    "literals) to a single Literal at plan-decode time (exprs/fold.py) "
    "— smaller traced programs and stabler program fingerprints.")
COLLAPSE_FILTER_PROJECT = bool_conf(
    "auron.tpu.plan.collapseFilterProject", True,
    "Planner rewrite (plan/planner.py collapse_filter_project): merge "
    "adjacent Filter->Project chains into one FilterProjectExec and "
    "Project->Project into a single Project by substituting bound "
    "references, so the fused expression program sees the whole chain "
    "as one XLA-compiled stage.")
FAULTS_ENABLE = bool_conf(
    "auron.tpu.faults.enable", False,
    "Activate the deterministic fault-injection registry (faults.py) "
    "from auron.tpu.faults.rules/.seed — chaos testing only; production "
    "queries leave this off.", category="fault-tolerance")
FAULTS_SEED = int_conf(
    "auron.tpu.faults.seed", 0,
    "Seed for injection decisions: the k-th evaluation of a site fires "
    "as a pure function of (seed, site, k), so a fixed seed reproduces "
    "the exact failure schedule.", category="fault-tolerance")
FAULTS_RULES = str_conf(
    "auron.tpu.faults.rules", "",
    "Comma-separated injection rules: `site=p` (probability), "
    "`site=p*max` (capped fires), `site@k1+k2` (exact occurrences), "
    "optional `:corrupt` action suffix (flip a frame byte instead of "
    "raising).  Sites: task-start, shuffle-write, shuffle-read, "
    "ipc-decode, mem-pressure, device-collective, device-loop, admit, "
    "cancel-race, quota-breach, stream-epoch, checkpoint-commit, "
    "worker-crash, worker-hang, worker-slow, "
    "speculation-loser-commit-race.  Site names are validated at parse "
    "time (faults.register_site declares dynamic sites).",
    category="fault-tolerance")
FAULTS_WORKER_SLOW_MS = int_conf(
    "auron.tpu.faults.workerSlowMs", 50,
    "Delay injected by a firing worker-slow fault site: the child "
    "stalls this long while still heartbeating (slow != dead).  The "
    "speculation soak raises it so a hedged duplicate has real wall "
    "time to win back.", category="fault-tolerance")
TASK_MAX_ATTEMPTS = int_conf(
    "auron.tpu.task.maxAttempts", 4,
    "Bounded per-task attempts for retryable failures (transient IO, "
    "injected faults) — the spark.task.maxFailures analog.  Fatal "
    "errors (plan/serde/logic) and FetchFailedError never retry "
    "in place; 1 disables retry.", category="fault-tolerance")
TASK_RETRY_BACKOFF_MS = int_conf(
    "auron.tpu.task.backoff", 100,
    "Base backoff between task attempts in ms; attempt n sleeps "
    "base*2^(n-1) with up to +25% jitter, capped at 10s.",
    category="fault-tolerance")
STAGE_MAX_RECOVERIES = int_conf(
    "auron.tpu.stage.maxRecoveries", 3,
    "Lineage-recovery rounds per query: each FetchFailedError re-runs "
    "only the poisoned producer map task and restarts the consuming "
    "stage; beyond this many rounds the failure propagates (the "
    "spark.stage.maxConsecutiveAttempts analog).",
    category="fault-tolerance")
WORKERS_ENABLE = bool_conf(
    "auron.tpu.workers.enable", False,
    "Route map tasks through the supervised worker-process pool "
    "(parallel/workers.py) instead of in-process threads: a native "
    "segfault / OOM-kill / hung dispatch costs ONE worker process and a "
    "retry, not the whole query service.  Off by default — the thread "
    "path stays the seed-verified baseline.", category="fault-tolerance")
WORKERS_COUNT = int_conf(
    "auron.tpu.workers.count", 2,
    "Long-lived worker processes in the pool (the executor-count "
    "analog).  Each worker runs one task at a time; crashed workers are "
    "restarted with backoff until the crash budget blacklists them.",
    category="fault-tolerance")
WORKERS_HEARTBEAT_MS = int_conf(
    "auron.tpu.workers.heartbeatMs", 100,
    "Worker heartbeat period while running a task.  Heartbeats ride the "
    "same CRC-framed pipe as results, so a wedged child (native hang, "
    "GIL-free deadlock) stops producing them.",
    category="fault-tolerance")
WORKERS_LIVENESS_MS = int_conf(
    "auron.tpu.workers.livenessMs", 2000,
    "Liveness deadline: a busy worker silent for this long is declared "
    "hung, SIGKILLed, and its task re-dispatched as WorkerCrashed "
    "(the spark.network.timeout / executor-heartbeat analog).  Must "
    "comfortably exceed heartbeatMs.", category="fault-tolerance")
WORKERS_CRASH_BUDGET = int_conf(
    "auron.tpu.workers.crashBudget", 3,
    "Crashes a worker slot survives before it is blacklisted (never "
    "restarted, never receives tasks again) — the repeat-offender "
    "analog of Spark's excludeOnFailure.", category="fault-tolerance")
WORKERS_RESTART_BACKOFF_MS = int_conf(
    "auron.tpu.workers.restartBackoffMs", 50,
    "Base delay before respawning a crashed worker; doubles per "
    "accumulated crash on that slot so a crash-looping environment "
    "backs off instead of spinning fork+die.", category="fault-tolerance")
WORKERS_DRAIN_MS = int_conf(
    "auron.tpu.workers.drainMs", 1000,
    "Graceful-drain budget at pool shutdown: workers get a shutdown "
    "message and this long to exit cleanly before SIGTERM, then "
    "SIGKILL.", category="fault-tolerance")
SPECULATION_ENABLE = bool_conf(
    "auron.tpu.speculation.enable", False,
    "Speculative execution (the spark.speculation analog): once the "
    "quantile share of a wave's tasks has finished, a task running "
    "longer than multiplier x the wave's median successful duration "
    "gets a duplicate attempt with a fresh attempt id; the first "
    "attempt to commit wins and the loser is cancelled via the "
    "cooperative token.  Off by default — with it off the wave loop "
    "runs exactly one attempt per task.", category="fault-tolerance")
SPECULATION_QUANTILE = float_conf(
    "auron.tpu.speculation.quantile", 0.75,
    "Share of a wave's tasks that must have finished before any "
    "straggler is hedged (spark.speculation.quantile).",
    category="fault-tolerance")
SPECULATION_MULTIPLIER = float_conf(
    "auron.tpu.speculation.multiplier", 1.5,
    "A running task is a straggler when its elapsed time exceeds this "
    "multiple of the wave's median successful task duration "
    "(spark.speculation.multiplier).", category="fault-tolerance")
SPECULATION_MIN_MS = int_conf(
    "auron.tpu.speculation.minRuntimeMs", 100,
    "Floor on the straggler cutoff: tasks are never speculated before "
    "running at least this long, so sub-millisecond waves don't hedge "
    "on scheduling noise (spark.speculation.minTaskRuntime).",
    category="fault-tolerance")
SHUFFLE_CHECKSUM_ENABLE = bool_conf(
    "auron.tpu.shuffle.checksum", True,
    "CRC32C checksum on every shuffle/spill IPC frame (4 bytes/frame, "
    "verified on read).  A mismatched frame raises FetchFailedError "
    "with the writing map task's identity so the scheduler can re-run "
    "exactly that task instead of failing the query.",
    category="fault-tolerance")
MESH_DEVICES = int_conf(
    "auron.tpu.mesh.devices", 0,
    "Devices in the 1-D data-parallel mesh that runs device-resident "
    "stage execution (parallel/mesh.py make_mesh).  0 = every visible "
    "device.  The mesh sizes the device exchange AND bounds task "
    "placement: task p of every stage runs on device p mod N of it "
    "(parallel/mesh.py task_device), so 1 keeps every task on the first "
    "device.  On CPU hosts, XLA_FLAGS="
    "--xla_force_host_platform_device_count=N provides N virtual "
    "devices for the same code path.", category="scale-out")
SHUFFLE_DEVICE = str_conf(
    "auron.tpu.shuffle.device", "auto",
    "Device-resident map->reduce exchange: 'auto' moves eligible hash "
    "repartitions (fixed-width row schema, column-reference keys) over "
    "mesh collectives when compute is device-resident (bridge/"
    "placement) and >1 device is visible, 'on' forces the attempt "
    "regardless of placement, 'off' never takes the collective.  "
    "Any device-lane "
    "failure — injected fault, capacity overflow, unsupported shape — "
    "falls back to the file shuffle for that stage (counted as "
    "shuffle_device_fallbacks), so lineage recovery keeps working.  "
    "This key speaks of the mesh collective alone.  Where it declines "
    "and compute is device-resident on ONE device, the scheduler keeps "
    "a map task's output on the chip for the reduce tasks of the same "
    "process (the resident tier, plan/stages.py _resident_tier); no key "
    "selects that tier: it is taken where writer and reader share a "
    "chip and a process and nothing else needs the output as files "
    "(worker pool, shuffle service, speculation, the subplan cache, "
    "adaptive re-planning, a broadcast reader), for the columns the "
    "chip carries, and it spills to the file shuffle's own files under "
    "memory pressure.",
    category="scale-out")
SHUFFLE_DEVICE_MAX_BYTES = int_conf(
    "auron.tpu.shuffle.device.maxBytes", 1 << 30,
    "Estimated per-exchange payload above which the device lane "
    "declines and the stage spills to the file shuffle (device "
    "exchanges buffer whole map outputs; the file path streams).",
    category="scale-out")
MESH_EXCHANGE_SKEW = float_conf(
    "auron.tpu.mesh.exchangeSkew", 2.0,
    "Headroom factor on the per-destination send-buffer capacity of "
    "the collective exchange (capacity ladder rung >= skew * "
    "rows/destination).  Skewed key distributions that still overflow "
    "re-dispatch at the next ladder rung.", category="scale-out")
EXCHANGE_OVERLAP_ENABLE = bool_conf(
    "auron.tpu.exchange.overlap.enable", False,
    "Double-buffer the device exchange: each map task's all-to-all is "
    "DISPATCHED (unawaited device futures) as soon as its fold "
    "finishes and DRAINED on a background thread, so task k's "
    "collective + partition re-encode overlap task k+1's stage-loop "
    "fold (ROADMAP item 4 — the ledger's barrier_idle category is the "
    "target).  Overlap is fenced at hash-table regrow boundaries "
    "(runtime/loop.py exchange_fence) to keep the atomic "
    "overflow/rehash contract, and any dispatch/drain failure falls "
    "back wholesale to the file shuffle exactly like the synchronous "
    "lane.  Off (default) keeps the byte-identical synchronous "
    "exchange.", category="scale-out")
EXCHANGE_OVERLAP_DEPTH = int_conf(
    "auron.tpu.exchange.overlap.depth", 2,
    "In-flight exchange tickets allowed before the next dispatch "
    "blocks (double-buffering = 2).  Bounds device send/receive "
    "buffers held live concurrently; <= 1 degrades to dispatch-then-"
    "drain per task with the drain still off the fold thread.",
    category="scale-out")
STAGE_DEVICE_LOOP_ENABLE = str_conf(
    "auron.tpu.stage.deviceLoop.enable", "auto",
    "Device-resident stage loop (runtime/loop.py): compile an eligible "
    "map-stage pipeline (filter -> project -> partial hash-agg) into ONE "
    "jit'd program whose body fori_loops over a chunk of bucket-padded "
    "batches, so Python dispatch cost is paid per chunk instead of per "
    "batch x operator.  'auto' runs it for device-resident compute — "
    "where the per-batch dispatch RTT it amortizes exists — on stages "
    "that compile (plan/stage_compiler.py eligibility: fixed-width "
    "dtypes, traceable exprs, hash-lane agg); 'on' forces it wherever "
    "it compiles, regardless of placement (tests on CPU hosts); "
    "'off' always uses the staged per-batch executor.  Any loop "
    "failure — injected fault, overflow past the "
    "table cap, untraceable chain — falls back wholesale to the staged "
    "path for that task (counted as stage_loop_fallbacks), preserving "
    "lineage recovery and cancellation semantics.", category="scale-out")
STAGE_DEVICE_LOOP_CHUNK = int_conf(
    "auron.tpu.stage.deviceLoop.chunkBatches", 8,
    "Batches folded per stage-loop program call.  Cancellation/deadline "
    "tokens and fault-injection sites are checked between chunks, so "
    "teardown latency is bounded by one chunk; degraded queries "
    "(capacity_shrink) halve the chunk per shrink level, floor 1.",
    category="scale-out")
STAGE_DEVICE_LOOP_DONATE = bool_conf(
    "auron.tpu.stage.deviceLoop.donate", True,
    "Donate the agg-carry buffers (hash table keys/accumulators) to the "
    "stage-loop program so XLA updates them in place across chunk calls "
    "instead of allocating a fresh table per chunk.  Disable when "
    "debugging with jax_check_tracer_leaks or on backends that reject "
    "donation (harmless: XLA warns and copies).", category="scale-out")
SHUFFLE_SERVICE = str_conf(
    "auron.tpu.shuffle.service", "",
    "Elastic shuffle tier endpoint (shuffle/rss.py, the "
    "Celeborn/Uniffle analog): a shared-storage directory root, or "
    "`socket://host:port` for the socket backend — map tasks push "
    "partition frames to an RSS server over CRC32C control frames, so "
    "map outputs survive their producing replica and reducers on ANY "
    "replica can fetch them.  Empty (default) keeps the local file "
    "shuffle; any service-tier failure falls back to files for that "
    "stage.", category="scale-out")
FLEET_REPLICA_ID = str_conf(
    "auron.tpu.fleet.replicaId", "",
    "Identity of THIS process within a serving fleet (fleet/replica.py)."
    "  Stamped on every history event the replica's queries emit, so the"
    " history rollup can aggregate per-replica query counts.  Empty "
    "(default) = not a fleet replica; nothing is stamped and the "
    "disabled path is byte-identical.", category="fleet")
FLEET_HEARTBEAT_MS = int_conf(
    "auron.tpu.fleet.heartbeatMs", 250,
    "Router→replica ping cadence (fleet/router.py).  Only read once a "
    "FleetRouter is constructed; no fleet, no effect.", category="fleet")
FLEET_LIVENESS_MS = int_conf(
    "auron.tpu.fleet.livenessMs", 2000,
    "A replica whose last successful heartbeat is older than this is "
    "marked DOWN (the worker-pool liveness deadline at fleet scope): "
    "queries stop routing to it and its in-flight queries are retried "
    "end-to-end on the next replica in rendezvous order.",
    category="fleet")
FLEET_PROBE_BACKOFF_MS = int_conf(
    "auron.tpu.fleet.probeBackoffMs", 200,
    "Base of the exponential backoff between liveness probes of a DOWN "
    "replica (200ms, 400ms, 800ms, ... like the worker-pool respawn "
    "backoff).  A probe that answers marks the replica UP again.",
    category="fleet")
FLEET_PROBE_BACKOFF_MAX_MS = int_conf(
    "auron.tpu.fleet.probeBackoffMaxMs", 10_000,
    "Ceiling on the down-replica probe backoff.", category="fleet")
FLEET_RETRIES = int_conf(
    "auron.tpu.fleet.retries", 2,
    "End-to-end re-routes per query after a replica dies mid-flight "
    "(connection reset or liveness miss).  Safe at every count because "
    "attempt commit is first-wins on every shuffle tier — a retried "
    "query can never double-commit blocks.", category="fleet")
FLEET_DRAIN_MS = int_conf(
    "auron.tpu.fleet.drainMs", 2000,
    "Graceful-drain window on replica SIGTERM: stop accepting new "
    "connections, let in-flight queries finish up to this long, then "
    "exit 0.  SIGKILL (crash) skips the drain — that is what the "
    "router's retry path is for.", category="fleet")
FLEET_HEDGE_ENABLE = bool_conf(
    "auron.tpu.fleet.hedge.enable", False,
    "Hedge straggling queries across replicas (speculative execution "
    "at fleet scope): a routed query running past hedge.multiplier x "
    "the router's observed median wall is re-submitted to the next "
    "replica in rendezvous order; first result wins, the loser is "
    "cancelled.  Duplicate-safe for the same reason router retry is — "
    "first-wins attempt commit on every tier.  Off by default.",
    category="fleet")
FLEET_HEDGE_MULTIPLIER = float_conf(
    "auron.tpu.fleet.hedge.multiplier", 3.0,
    "Straggler threshold for cross-replica hedging, as a multiple of "
    "the router's median completed-query wall (the speculation "
    "multiplier at fleet scope).", category="fleet")
FLEET_HEDGE_MIN_MS = int_conf(
    "auron.tpu.fleet.hedge.minMs", 50,
    "Floor on the hedge trigger: a query younger than this is never "
    "hedged, whatever the median says (guards against hedging every "
    "query when the mix is uniformly fast).", category="fleet")
SERVING_MAX_CONCURRENT = int_conf(
    "auron.tpu.serving.maxConcurrent", 4,
    "Queries executing simultaneously in the QueryService "
    "(serving/service.py); admitted queries beyond this wait in the "
    "bounded queue.", category="serving")
SERVING_MAX_QUEUE = int_conf(
    "auron.tpu.serving.maxQueue", 32,
    "Bounded admission queue depth: submissions past it are shed "
    "immediately with QueryRejected(kind='queue-full') — the service "
    "never wedges under overload.", category="serving")
SERVING_TENANT_MAX_INFLIGHT = int_conf(
    "auron.tpu.serving.tenant.maxInflight", 8,
    "Per-tenant in-flight cap (queued + running): submissions past it "
    "are shed with QueryRejected(kind='tenant-quota'), so one tenant "
    "cannot monopolize the queue.", category="serving")
SERVING_ADMIT_MEM_BYTES = int_conf(
    "auron.tpu.serving.admitMemBytes", 0,
    "Estimated-input-bytes admission ceiling: a query whose scan "
    "footprint estimate exceeds this is shed with QueryRejected"
    "(kind='memory') instead of admitted to OOM later.  0 disables; "
    "un-stat-able inputs (remote FS, memory tables) always admit.",
    category="serving")
QUERY_DEADLINE_MS = int_conf(
    "auron.tpu.query.deadlineMs", 0,
    "Default per-query deadline in ms, applied at submission when the "
    "caller doesn't pass one: past it the query is cancelled "
    "cooperatively (DeadlineExceeded) within one batch boundary and "
    "fully torn down.  0 = no deadline.", category="serving")
QUERY_MEM_QUOTA = int_conf(
    "auron.tpu.query.memQuota", 0,
    "Default per-query memory quota in bytes over the unified "
    "MemManager: a breaching query first sheds its own state and "
    "climbs the degradation ladder (partial-agg pass-through, then "
    "batch-capacity shrink) and is killed (QueryMemoryExceeded) only "
    "when degradation cannot bring it under.  0 = no quota.",
    category="serving")
SERVING_SINGLE_FLIGHT = bool_conf(
    "auron.tpu.serving.singleFlight", False,
    "Coalesce identical in-flight queries in the QueryService: when a "
    "submitted plan's fingerprint+snapshot matches one already queued "
    "or running, the new query becomes a waiter on the leader's result "
    "(one execution, N answers).  A cancelled leader promotes the first "
    "live waiter to executor; deadline/quota kills stay per-query.",
    category="serving")
SERVING_USE_WORKERS = bool_conf(
    "auron.tpu.serving.useWorkerPool", False,
    "Route serving-mode map tasks (queries carrying a QueryContext) "
    "onto the process-isolated worker pool even when "
    "auron.tpu.workers.enable is off, so concurrent admitted queries "
    "get true parallelism instead of time-slicing one interpreter.  "
    "Off by default: solo/batch runs keep the in-process path.",
    category="serving")
CACHE_ENABLE = bool_conf(
    "auron.tpu.cache.enable", False,
    "Master switch for the cross-query work-sharing cache "
    "(blaze_tpu/cache/): semantic result + subplan reuse keyed by "
    "canonical plan fingerprint and source snapshot version.  Off "
    "(default) keeps execution byte-identical to the uncached path "
    "with zero steady-state overhead.", category="cache")
CACHE_MAX_BYTES = int_conf(
    "auron.tpu.cache.maxBytes", 256 << 20,
    "Byte budget for the shared result/subplan cache.  The cache is a "
    "MemConsumer under the unified MemManager, so global memory "
    "pressure evicts cached entries (LRU) before live queries spill.",
    category="cache")
CACHE_SUBPLAN = bool_conf(
    "auron.tpu.cache.subplan", True,
    "Also cache exchange-boundary subplan outputs (leaf map-stage "
    "shuffle blocks): a later query whose producing subtree matches "
    "skips the whole map stage and replays the cached partition "
    "blocks.  Only read when auron.tpu.cache.enable is on.",
    category="cache")
CACHE_SCAN_SHARE = bool_conf(
    "auron.tpu.cache.scanShare", False,
    "Deduplicate CONCURRENT ParquetScan decode at (file, row-groups, "
    "column-superset) granularity: one leader decodes, followers ride "
    "the published batches (refcounted, dropped when the last reader "
    "releases — no retained memory).  Only read when "
    "auron.tpu.cache.enable is on.", category="cache")
CACHE_SCAN_SHARE_MAX_BYTES = int_conf(
    "auron.tpu.cache.scanShare.maxBytes", 64 << 20,
    "Per-file ceiling for shared scan decode: files larger than this "
    "stream through the normal per-consumer path instead of being "
    "buffered for followers.", category="cache")
CASE_SENSITIVE = bool_conf("spark.sql.caseSensitive", False, "Column name matching.")
ANSI_ENABLED = bool_conf(
    "spark.sql.ansi.enabled", False,
    "ANSI SQL mode: Cast raises on malformed/overflowing input instead of "
    "producing NULL; TryCast still nulls (ref cast.rs TryCastExpr).")

# ---------------------------------------------------------------------------
# Remaining SparkAuronConfiguration families (same key names; ~70 total).
# "convert"-category switches gate the plan-translation layer
# (plan/convert.py); the rest are read by the named operators.
# ---------------------------------------------------------------------------

ENABLED = bool_conf(
    "auron.enabled", True, "Master switch for native conversion.",
    category="convert")
UI_ENABLED = bool_conf(
    "auron.ui.enabled", True,
    "Expose the profiling/metrics HTTP endpoints (bridge/profiling.py).",
    category="observability")
PROCESS_VMRSS_MEMORY_FRACTION = float_conf(
    "auron.process.vmrss.memoryFraction", 0.9,
    "Process-RSS fraction usable before the memory manager refuses growth "
    "(MemManager.init_from_conf).", category="memory")
ON_HEAP_SPILL_MEMORY_FRACTION = float_conf(
    "auron.onHeapSpill.memoryFraction", 0.9,
    "Fraction of the host budget the spill tiers may pin in RAM before "
    "moving runs to disk.", category="memory")
ENABLE_CASECONVERT_FUNCTIONS = bool_conf(
    "auron.enable.caseconvert.functions", False,
    "Allow upper/lower conversion through the native path (locale-exact "
    "parity gate).", category="convert")
INPUT_BATCH_STATISTICS = bool_conf(
    "auron.enableInputBatchStatistics", False,
    "Record per-batch row/byte statistics in the runtime metric tree.",
    category="observability")
TRACE_ENABLE = bool_conf(
    "auron.tpu.trace.enable", False,
    "Collect execution spans process-wide without an explicit "
    "start_tracing() call (bridge/tracing.py).  Probed once lazily; "
    "disabled tracing stays a near-free boolean check at every span site.",
    category="observability")
FLIGHT_RECORDER_ENABLE = bool_conf(
    "auron.tpu.flightRecorder.enable", True,
    "Dump a post-mortem JSON artifact (recent spans, counter deltas, "
    "config snapshot) when a query dies with a fatal classification — "
    "quota kill, deadline, pool-unavailable, stream recovery exhaustion "
    "(bridge/context.py flight recorder).", category="observability")
FLIGHT_RECORDER_DIR = str_conf(
    "auron.tpu.flightRecorder.dir", "",
    "Directory for flight-recorder dumps; empty uses "
    "<system tempdir>/blaze_flight.", category="observability")
FLIGHT_RECORDER_SPANS = int_conf(
    "auron.tpu.flightRecorder.maxSpans", 256,
    "Most-recent span count retained in each flight-recorder dump.",
    category="observability")
PROFILE_STORE_MAX = int_conf(
    "auron.tpu.profile.maxEntries", 64,
    "LRU capacity of the in-memory query-profile store served at "
    "/profile/<qid>; evictions are counted in obs_profile_evictions.",
    category="observability")
HISTORY_ENABLE = bool_conf(
    "auron.tpu.history.enable", False,
    "Write the persistent per-query JSONL event log (admission, stage "
    "completion, recovery, final metric tree + attribution) replayed by "
    "the /history endpoints (bridge/history.py).  Probed once lazily; "
    "disabled history stays a near-free boolean check at every emit "
    "site — zero hot-path writes.", category="observability")
HISTORY_DIR = str_conf(
    "auron.tpu.history.dir", "",
    "Directory for query event logs; empty uses "
    "<system tempdir>/blaze_history.", category="observability")
HISTORY_MAX_EVENTS = int_conf(
    "auron.tpu.history.maxEventsPerQuery", 512,
    "Event-log bound per query; events beyond it are dropped (the "
    "terminal event always lands and carries the drop count).",
    category="observability")
HISTORY_MAX_QUERIES = int_conf(
    "auron.tpu.history.maxQueries", 256,
    "Retention: most-recent query logs kept on disk; admission prunes "
    "the oldest beyond this.", category="observability")
SENTINEL_THRESHOLD = float_conf(
    "auron.tpu.sentinel.threshold", 0.10,
    "Default relative noise floor for the regression sentinel "
    "(blaze_tpu/tools/sentinel.py): metric drift below this fraction "
    "of baseline is not a regression.", category="observability")
STATS_ENABLE = bool_conf(
    "auron.tpu.stats.enable", False,
    "Enable the statistics feedback plane: the per-fingerprint "
    "observed-stats store (plan/statstore.py), the advisor findings "
    "derived from it, and the live /query/<qid>/progress registry.  "
    "Probed once lazily; disabled it stays a near-free boolean check — "
    "zero writes, zero allocation on the query path.",
    category="observability")
STATS_DIR = str_conf(
    "auron.tpu.stats.dir", "",
    "Directory for the per-fingerprint statistics store; empty uses "
    "<history dir>/stats.", category="observability")
STATS_MAX_FINGERPRINTS = int_conf(
    "auron.tpu.stats.maxFingerprints", 256,
    "Retention bound for the statistics store: most-recently-updated "
    "fingerprint records kept on disk; ingest prunes the oldest beyond "
    "this.", category="observability")
STATS_SKETCH_CENTROIDS = int_conf(
    "auron.tpu.stats.sketchCentroids", 64,
    "Centroid budget per quantile sketch in the statistics store.  "
    "Larger is sharper (lower quantile error) and bigger on disk; "
    "merges collapse the closest adjacent centroids past this bound.",
    category="observability")
STATS_ADVISOR_BROADCAST_BYTES = int_conf(
    "auron.tpu.stats.advisor.broadcastBytes", 8 << 20,
    "Advisor threshold: a shuffle boundary whose p50 total bytes fits "
    "under this is flagged as a broadcast candidate.",
    category="observability")
STATS_ADVISOR_SKEW_FACTOR = float_conf(
    "auron.tpu.stats.advisor.skewFactor", 4.0,
    "Advisor threshold: a partition whose bytes exceed this multiple "
    "of the boundary's median partition bytes is flagged as a "
    "skew-split candidate.", category="observability")
AQE_ENABLE = bool_conf(
    "auron.tpu.aqe.enable", False,
    "Enable adaptive query execution (plan/adaptive.py): the "
    "DagScheduler re-plans not-yet-dispatched consumer stages from the "
    "exact map-output bytes of committed producers — broadcast-join "
    "switch, reduce-partition coalescing, and skew-split.  Probed once "
    "lazily; disabled AQE stays a near-free boolean check at the stage "
    "boundary and the executed plan is byte-identical to the static "
    "plan.", category="observability")
AQE_BROADCAST_THRESHOLD = int_conf(
    "auron.tpu.aqe.broadcastThreshold", -1,
    "Observed build-side map-output bytes under this rewrite a "
    "shuffle-hash join to a broadcast build at runtime; -1 inherits "
    "auron.tpu.stats.advisor.broadcastBytes so the advisor and the AQE "
    "pass can never disagree.", category="observability")
AQE_COALESCE_TARGET = int_conf(
    "auron.tpu.aqe.coalesceTargetBytes", 16 << 20,
    "Target bytes per reduce partition after coalescing: adjacent "
    "partitions are merged greedily until the next would push a group "
    "past this.  Also the history-seeded partition-count target at "
    "plan bind time.", category="observability")
AQE_SKEW_FACTOR = float_conf(
    "auron.tpu.aqe.skewFactor", -1.0,
    "A reduce partition whose bytes exceed this multiple of the "
    "boundary median is split across replicated-build sub-tasks; "
    "<= 0 inherits auron.tpu.stats.advisor.skewFactor.",
    category="observability")
AQE_SKEW_MAX_SPLITS = int_conf(
    "auron.tpu.aqe.skewMaxSplits", 8,
    "Upper bound on the sub-tasks a single skewed partition is split "
    "into (each replicates the build side once).",
    category="observability")
AQE_HISTORY_SEED = bool_conf(
    "auron.tpu.aqe.historySeed", False,
    "Seed the plan at bind time from the statistics store's "
    "per-fingerprint quantiles (requires auron.tpu.stats.enable): "
    "pre-broadcast historically-small build sides, shrink partition "
    "counts toward coalesceTargetBytes, and pre-select the partial-agg "
    "skip strategy when history shows high group cardinality.",
    category="observability")
UDAF_FALLBACK_ENABLE = bool_conf(
    "auron.udafFallback.enable", True,
    "Allow typed-imperative UDAFs to run through the host round-trip "
    "(ops/agg/functions.py HostUDAF); disabled -> plans with UDAFs are "
    "rejected.", category="operator")
SUGGESTED_UDAF_MEM_USED_SIZE = int_conf(
    "auron.suggested.udaf.memUsedSize", 8192,
    "Per-row memory estimate charged for buffered UDAF state.",
    category="operator")
UDAF_FALLBACK_NUM_TRIGGER_SORT_AGG = int_conf(
    "auron.udafFallback.num.udafs.trigger.sortAgg", 1,
    "UDAF count at which the converter emits SortAgg instead of HashAgg.",
    category="convert")
UDAF_FALLBACK_TYPED_IMPERATIVE_ROW_SIZE = int_conf(
    "auron.udafFallback.typedImperativeEstimatedRowSize", 256,
    "Estimated serialized row size for typed-imperative UDAF buffers.",
    category="operator")
CAST_TRIM_STRING = bool_conf(
    "auron.cast.trimString", True,
    "Trim whitespace before string->numeric/date casts (Spark behavior).",
    category="operator")
PARTIAL_AGG_SKIPPING_PROBE_ROWS = int_conf(
    "auron.tpu.partialAgg.skipping.probeRows", 16384,
    "Uniform-sample size for the cardinality-ratio probe that drives "
    "partial-agg skipping (minRows still gates WHEN the probe may run; "
    "this bounds what it costs).  The sample is strided across the "
    "whole buffer, so repeated keys depress the ratio and the skip "
    "decision errs toward keeping the aggregation.",
    category="operator",
    alt_keys=("auron.tpu.partialAggSkipping.probeRows",))
PARTIAL_AGG_SKIPPING_ON_SPILL = bool_conf(
    "auron.tpu.partialAgg.skipping.onSpill", False,
    "Under memory pressure, switch an eligible partial agg to pass-through "
    "instead of spilling its buffer (skip-before-spill; off keeps the "
    "reference's spill-before-skip ordering).", category="operator",
    alt_keys=("auron.partialAggSkipping.skipSpill",))
#: Back-compat alias (pre-rename name).
PARTIAL_AGG_SKIPPING_SKIP_SPILL = PARTIAL_AGG_SKIPPING_ON_SPILL
PARQUET_MAX_OVER_READ_SIZE = int_conf(
    "auron.parquet.maxOverReadSize", 16384,
    "Coalesce adjacent column-chunk reads separated by at most this many "
    "bytes.", category="scan")
PARQUET_METADATA_CACHE_SIZE = int_conf(
    "auron.parquet.metadataCacheSize", 1024,
    "Parquet footer/metadata entries cached across scans and bound "
    "discovery (ops/scan.py parquet_metadata).", category="scan")
IO_COMPRESSION_CODEC = str_conf(
    "io.compression.codec", "lz4",
    "Shuffle IPC frame codec: lz4 (reference default, Arrow C++ "
    "lz4-frame) | zstd | raw.  Unset, auron.spill.compression.codec "
    "applies.  lz4 falls back to raw when Arrow lacks the codec.",
    category="shuffle")
IO_COMPRESSION_ZSTD_LEVEL = int_conf(
    "io.compression.zstd.level", 1,
    "zstd level for shuffle/spill frames.", category="shuffle")
IO_COMPRESSION_WORKER_FRAMES = bool_conf(
    "auron.tpu.io.compression.workerFrames", False,
    "Compress worker-pool control frames (task/result/heartbeat "
    "pickles riding the CRC32C pipe protocol) with io.compression."
    "codec.  The codec byte has always been in the frame header, so "
    "either end decodes any mix — a parent with this on talks to an "
    "old child and vice versa.  Savings are counted in "
    "worker_frame_compressed_bytes_saved; RSS partition puts "
    "already carry IPC-compressed payloads and are accounted "
    "separately (rss_put_compressed_bytes_saved).",
    category="shuffle")
FORCE_SHUFFLED_HASH_JOIN = bool_conf(
    "auron.forceShuffledHashJoin", False,
    "Convert every sort-merge join into a shuffled hash join.",
    category="convert")
PARSE_JSON_ERROR_FALLBACK = bool_conf(
    "auron.parseJsonError.fallback", True,
    "get_json_object parse failures fall back to the host engine instead "
    "of returning null.", category="operator")
SUGGESTED_MERGING_BATCH_MEM_SIZE = int_conf(
    "auron.suggested.batch.memSize.multiwayMerging", 1 << 20,
    "Target bytes per output chunk in k-way merges (ops/sort.py).",
    category="operator")
ORC_FORCE_POSITIONAL_EVOLUTION = bool_conf(
    "auron.orc.force.positional.evolution", False,
    "Match ORC columns by position instead of name.", category="scan")
ORC_TIMESTAMP_USE_MICROSECOND = bool_conf(
    "auron.orc.timestamp.use.microsecond", True,
    "Read ORC timestamps at microsecond resolution (the engine-wide "
    "timestamp unit).", category="scan")
ORC_SCHEMA_CASE_SENSITIVE = bool_conf(
    "auron.orc.schema.caseSensitive.enable", False,
    "Case-sensitive ORC schema matching.", category="scan")
FORCE_SHORT_CIRCUIT_AND_OR = bool_conf(
    "auron.forceShortCircuitAndOr", True,
    "Flatten AND predicate trees into sequential short-circuit conjuncts "
    "in filters (exprs/evaluator.py; the reference defaults this off "
    "because its SC nodes bypass Hive-UDF checks — here the flattened "
    "form is the native fast path).", category="operator")
DECIMAL_ARITH_OP_ENABLED = bool_conf(
    "auron.decimal.arithOp.enabled", True,
    "Allow native decimal +-*/ (precision-tracking arithmetic).",
    category="convert")
DATETIME_EXTRACT_ENABLED = bool_conf(
    "auron.datetime.extract.enabled", True,
    "Allow native year/month/day/hour extraction.", category="convert")
UDF_JSON_ENABLED = bool_conf(
    "auron.udf.UDFJson.enabled", True,
    "Convert Hive UDFJson (get_json_object) natively.", category="convert")
UDF_BRICKHOUSE_ENABLED = bool_conf(
    "auron.udf.brickhouse.enabled", False,
    "Convert brickhouse collect/combine_unique UDAFs natively.",
    category="convert")
UDF_SINGLE_CHILD_FALLBACK_ENABLED = bool_conf(
    "auron.udf.singleChildFallback.enabled", False,
    "Wrap single-child unsupported expressions in a UDF fallback instead "
    "of rejecting the subtree.", category="convert")

# per-operator conversion switches (ref AuronConverters.scala:98-128)
_OPERATOR_SWITCHES = {}
for _op in ("scan", "paimon.scan", "iceberg.scan", "hudi.scan", "project",
            "filter", "sort", "union", "smj", "shj",
            "native.join.condition", "bhj", "bnlj", "local.limit",
            "global.limit", "take.ordered.and.project", "collectLimit",
            "aggr", "expand", "window", "window.group.limit", "generate",
            "local.table.scan", "data.writing", "data.writing.parquet",
            "data.writing.orc", "scan.parquet", "scan.parquet.timestamp",
            "scan.orc", "scan.orc.timestamp", "broadcastExchange",
            "shuffleExchange"):
    _OPERATOR_SWITCHES[_op] = bool_conf(
        f"auron.enable.{_op}", True,
        f"Allow converting {_op} nodes to the native engine.",
        category="convert")


def operator_enabled(op: str) -> bool:
    """Converter gate lookup (ref per-op enable flags,
    AuronConverters.scala:98-128)."""
    opt = _OPERATOR_SWITCHES.get(op)
    return True if opt is None else opt.get()


# -- streaming runtime (blaze_tpu/streaming/) --------------------------------
STREAM_EPOCH_INTERVAL_MS = int_conf(
    "auron.tpu.stream.epoch.intervalMs", 0,
    "Target pacing between micro-batch epochs of the streaming runtime "
    "(streaming/executor.py).  0 = run epochs back-to-back (drain mode, "
    "the test default); >0 sleeps out the remainder of the "
    "interval after each epoch, like Flink's checkpoint interval.",
    category="streaming")
STREAM_CHECKPOINT_DIR = str_conf(
    "auron.tpu.stream.checkpoint.dir", "",
    "Directory for streaming checkpoint manifests (ckpt-NNNNNN.json: "
    "per-partition source offsets, watermark, window-state snapshot, "
    "sink attempt).  Empty = the StreamExecutor creates a private "
    "tempdir torn down with the query.", category="streaming")
STREAM_WATERMARK_LATENESS_MS = int_conf(
    "auron.tpu.stream.watermark.latenessMs", 0,
    "Allowed event-time lateness: the watermark trails the minimum "
    "per-partition max event time by this many ms, so records up to "
    "this late still land in their window before it fires.",
    category="streaming")
STREAM_LATE_SIDE_POLICY = str_conf(
    "auron.tpu.stream.lateSide.policy", "drop",
    "Where records older than the watermark go: `drop` discards them "
    "(counted as stream_late_records), `side` routes them to the "
    "executor's late-side output for the caller to reprocess, `accept` "
    "folds them into the pane's retained accumulator so a re-opened "
    "window re-emits corrected cumulative values (downstream must "
    "tolerate updates; fired accumulators stay in window state).",
    category="streaming")
STREAM_MAX_RECOVERIES = int_conf(
    "auron.tpu.stream.maxRecoveries", 3,
    "Bounded checkpoint-recovery rounds per streaming query: each "
    "retryable epoch failure replays from the last committed manifest "
    "at most this many times before the error propagates.",
    category="streaming")

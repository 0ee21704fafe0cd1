"""Benchmark: TPC-DS q01 inner pipeline, SF1, END-TO-END through the engine.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}
On ANY failure (backend down, hung compile, mid-run UNAVAILABLE) it still
prints one JSON line — with an "error" field — and exits nonzero fast.

Harness structure (the bench must end, with an error line, even when a
backend hangs inside init/compile):

  supervisor (this process; never imports jax)
    ├ probe: child process runs a tiny jit on the default backend under a
    │        hard timeout, retried N times (first TPU init is slow)
    └ ONE attempt: a child process runs the real benchmark under a hard
      deadline; on timeout the whole process GROUP is SIGKILLed (no
      orphans).  The supervisor relays the child's JSON line, or prints
      its own error line and exits nonzero — a failed phase fails the run.

  child (--child): the benchmark body.  All engine tasks run as futures
  with timeouts — a thread stuck in backend init converts to a TimeoutError
  instead of wedging ThreadPoolExecutor.map; the child exits via os._exit
  so stuck non-daemon threads can never turn an error into a hang.

Workload (BASELINE.md config #1): the q01 `ctr` aggregation over SF1
store_returns (287,514 rows), executed the way a Spark stage pair would
drive this engine:

  stage 1 (xM map tasks): parquet_scan -> filter(returned_date_sk in the
      d_year=2000 key range, the DPP-pushed form of the date_dim join)
      -> hash_agg PARTIAL sum(return_amt) by (customer, store)
      -> shuffle_writer hash(cust, store) -> .data/.index files
  stage 2 (xR reduce tasks): ipc_reader(file segments) -> hash_agg FINAL

Every task is delivered as protobuf TaskDefinition bytes through
NativeExecutionRuntime — the full wire path: plan decode, fused-stage
rewrite (plan/fused.py dense group-id path), parquet decode, H2D, device
filter+aggregation, Spark-compatible murmur3 hash partitioning, framed IPC
shuffle files, reduce-side merge.  Wall-clock covers ALL of it, including
the dimension-table lookup that derives the date range.

Extras: a q06-shaped hash-join stage (store_returns ⋈ date_dim on
date_sk, filter+join+agg) is also timed, as `join_*` fields — joins are
the reference's bread and butter (BASELINE config #2) and were previously
unmeasured (VERDICT r2 weak #4).

Baseline: the identical queries on pyarrow's multithreaded C++ kernels,
the stand-in for Auron's CPU-native engine.  Correctness is asserted
against it every run.  NOTE the baseline is a FLOOR, not a peer: it runs
one in-process pass with no shuffle files, no partial/final aggregation
split, no task protocol — work Auron-CPU itself pays (its 2.02x headline
is vs Spark-JVM, a far weaker baseline).  vs_baseline ~= 1.0 here means
the engine's whole distribution machinery costs nothing over raw C++
kernels.

Partitioning is Spark-faithful: maps = input / 128MB
(spark.sql.files.maxPartitionBytes), reduces sized by AQE advisory
coalescing — so SF1 runs 1 map/1 reduce exactly as spark-local would.

Device-compute fields: `device_rows_per_sec` measures the DENSE fused
kernel folded 128x over an HBM-resident batch in ONE XLA program (1
dispatch, so the per-dispatch round trip cancels).  The hash-strategy
kernel is reported separately (`device_hash_rows_per_sec`).  Host-XLA
equivalents of both kernels are recorded for a chip-vs-host comparison.

Roofline sanity: the line also reports achieved input-bytes/s over the
chip's published HBM peak (CHIP_PEAKS, keyed by device_kind; null for a
device that is not in the table).  Anything above 1 means broken timing.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

# Published chip peaks, keyed by jax.devices()[0].device_kind.  Source:
# Google Cloud documentation, "TPU v5e" (16 GB of HBM at 819 GB/s per
# chip).  A device that is not in the table has NO peak: the roofline
# fields come out null, never a v5e's number.
CHIP_PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_sec": 819e9},
}


def _device_kind():
    import jax
    return jax.devices()[0].device_kind


def _hbm_peak_bytes_s():
    return CHIP_PEAKS.get(_device_kind(), {}).get("hbm_bytes_per_sec")


SCALE = float(os.environ.get("BLAZE_BENCH_SCALE", "1.0"))
N_FILES = int(os.environ.get("BLAZE_BENCH_FILES", "4"))

# Partition counts follow what Spark would actually schedule for this
# input.  Maps: FilePartition packing under maxSplitBytes =
# min(maxPartitionBytes=128MB, max(openCostInBytes=4MB, bytesPerCore))
# with bytesPerCore = (totalBytes + #files*openCost) / defaultParallelism
# — the exact formula the reference re-implements engine-side
# (NativeIcebergTableScanExec.scala:318-325, NativePaimonTableScanExec
# .scala:237-241); on a small input it is bytesPerCore, not 128MB, that
# governs, so spark-local[N] fans maps out to the cores.  Reduces: AQE
# coalescing toward advisoryPartitionSizeInBytes=64MB, but
# coalescePartitions.parallelismFirst=true (the Spark default) keeps at
# least defaultParallelism partitions as long as each clears
# minPartitionSize=1MB.  Overridable for scaling studies.
_SF1_BYTES = 6_100_000  # measured SF1 store_returns footprint
_OPEN_COST = 4 << 20    # spark.sql.files.openCostInBytes default
_CORES = os.cpu_count() or 2  # local[*] defaultParallelism

def _spark_partitions(scale: float):
    est_bytes = int(_SF1_BYTES * scale)
    total = est_bytes + N_FILES * _OPEN_COST
    max_split = min(128 << 20, max(_OPEN_COST, total // _CORES))
    # whole-file granularity: our FileScanExecConf groups whole files
    maps = min(N_FILES, max(1, -(-total // max_split)))
    shuffle_est = est_bytes // 3
    reduces = max(1, -(-shuffle_est // (64 << 20)))
    reduces = max(reduces, min(_CORES, max(1, shuffle_est >> 20)))
    return maps, reduces

_DEF_MAPS, _DEF_REDUCES = _spark_partitions(SCALE)
N_MAPS = int(os.environ.get("BLAZE_BENCH_MAPS", str(_DEF_MAPS)))
N_REDUCES = int(os.environ.get("BLAZE_BENCH_REDUCES", str(_DEF_REDUCES)))
ITERS = int(os.environ.get("BLAZE_BENCH_ITERS", "5"))
SF10 = os.environ.get("BLAZE_BENCH_SF10", "1") == "1" and SCALE == 1.0
DEVICE_LOOP = os.environ.get("BLAZE_BENCH_DEVICE_LOOP", "1") == "1"

PROBE_TIMEOUT_S = float(os.environ.get("BLAZE_BENCH_PROBE_TIMEOUT", "150"))
PROBE_TRIES = int(os.environ.get("BLAZE_BENCH_PROBE_TRIES", "2"))
ATTEMPT_TIMEOUT_S = float(os.environ.get("BLAZE_BENCH_ATTEMPT_TIMEOUT",
                                         "900"))
STAGE_TIMEOUT_S = float(os.environ.get("BLAZE_BENCH_STAGE_TIMEOUT", "300"))

METRIC_NAME = "tpcds_q01_sf%g_e2e_rows_per_sec" % SCALE


# ===========================================================================
# supervisor side (no jax imports anywhere on these paths)
# ===========================================================================

def _error_line(msg: str, **extras) -> None:
    """The contract holds even in failure: one JSON line, then exit fast."""
    rec = {"metric": METRIC_NAME, "value": 0, "unit": "rows/s",
           "vs_baseline": 0, "error": msg[-2000:]}
    rec.update(extras)
    print(json.dumps(rec))
    sys.stdout.flush()


def _write_bench(path: str, rec: dict) -> dict:
    """Every BENCH_*.json artifact lands through the unified
    schema-versioned writer (blaze_tpu.tools.bench_schema), so the
    regression sentinel can parse any leg's output uniformly.  Lazy
    import: the supervisor side must stay free of blaze_tpu (jax)."""
    from blaze_tpu.tools.bench_schema import write_bench_artifact
    return write_bench_artifact(path, rec)


_PROBE_CODE = r"""
import os
import jax
import jax.numpy as jnp
x = jax.jit(lambda a: (a * 2).sum())(jnp.arange(128))
x.block_until_ready()
print("PROBE_OK", jax.default_backend(), len(jax.devices()))
"""


def _run_group(args, timeout_s, env=None):
    """Run a child in its own process group; SIGKILL the whole group on
    timeout so a thread wedged in backend init can't orphan anything."""
    p = subprocess.Popen(args, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True,
                         start_new_session=True, env=env,
                         cwd=os.path.dirname(os.path.abspath(__file__)))
    try:
        out, err = p.communicate(timeout=timeout_s)
        return p.returncode, out, err, False
    except subprocess.TimeoutExpired:
        try:
            os.killpg(os.getpgid(p.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            p.kill()
        out, err = p.communicate()
        return -9, out, err, True


def _probe_backend():
    """Returns (platform, n_devices) or raises after bounded retries."""
    last = ""
    for i in range(PROBE_TRIES):
        rc, out, err, timed_out = _run_group(
            [sys.executable, "-c", _PROBE_CODE], PROBE_TIMEOUT_S)
        for ln in out.splitlines():
            if ln.startswith("PROBE_OK"):
                _, platform, n = ln.split()
                return platform, int(n)
        last = ("probe attempt %d: %s" %
                (i + 1, "hang killed after %gs" % PROBE_TIMEOUT_S
                 if timed_out else (err or out).strip()[-500:]))
        time.sleep(2)
    raise RuntimeError("backend probe failed: " + last)


def supervise() -> int:
    t0 = time.perf_counter()
    try:
        platform, n_dev = _probe_backend()
    except RuntimeError as e:
        _error_line(str(e), stage="probe",
                    harness_wall_s=round(time.perf_counter() - t0, 1))
        return 1

    # ONE attempt: a failed run is a failed run, never retried into exit 0
    rc, out, err, timed_out = _run_group(
        [sys.executable, os.path.abspath(__file__), "--child"],
        ATTEMPT_TIMEOUT_S)
    line = None
    for ln in reversed(out.splitlines()):
        if ln.startswith("{"):
            line = ln
            break
    last_err = ""
    if rc == 0 and line is not None:
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            rec = None
            last_err = "unparseable output %r" % line[:200]
        if rec is not None and "error" not in rec:
            rec["platform"] = platform
            rec["n_devices"] = n_dev
            print(json.dumps(rec))
            sys.stdout.flush()
            return 0
        if rec is not None:
            last_err = rec["error"]
    elif timed_out:
        last_err = "killed after %gs deadline" % ATTEMPT_TIMEOUT_S
    else:
        last_err = "rc=%d %s" % (rc, line or (err or out).strip()[-800:])
    _error_line(last_err, stage="bench", platform=platform,
                harness_wall_s=round(time.perf_counter() - t0, 1))
    return 1


# ===========================================================================
# child side — the benchmark body
# ===========================================================================

SR_SCHEMA_D = {"fields": [
    {"name": "sr_returned_date_sk", "type": {"id": "int64"},
     "nullable": True},
    {"name": "sr_customer_sk", "type": {"id": "int64"}, "nullable": True},
    {"name": "sr_store_sk", "type": {"id": "int64"}, "nullable": True},
    {"name": "sr_return_amt", "type": {"id": "float64"}, "nullable": True},
    {"name": "sr_ticket_number", "type": {"id": "int64"}, "nullable": True},
]}
PARTIAL_SCHEMA_D = {"fields": [
    {"name": "ctr_customer_sk", "type": {"id": "int64"}, "nullable": True},
    {"name": "ctr_store_sk", "type": {"id": "int64"}, "nullable": True},
    {"name": "ctr_total_return.sum", "type": {"id": "float64"},
     "nullable": True},
]}
DD_SCHEMA_D = {"fields": [
    {"name": "d_date_sk", "type": {"id": "int64"}, "nullable": True},
    {"name": "d_year", "type": {"id": "int64"}, "nullable": True},
]}


def _tasks(fn, n, what):
    """Run n tasks on a pool, but never wait unboundedly: a task wedged in
    backend init becomes a TimeoutError (VERDICT r2 weak #1)."""
    from blaze_tpu.bridge.tasks import run_tasks
    return run_tasks(fn, n, STAGE_TIMEOUT_S, what)


def _record_tree(tree) -> None:
    """Feed finalize()-time operator metric trees to the observability
    store so the run's profile can be persisted next to the BENCH json.
    In-process tasks only: process-pool workers record in their own
    interpreter and those trees are not collected here."""
    from blaze_tpu.bridge import profiling
    profiling.record_metrics(tree.to_dict())


def _observed_placement(pi):
    """(compute_placement, per-stage breakdown) derived from EVIDENCE of
    the run instead of the session-level policy: the recorded metric
    trees carry per-operator lane counters (agg host_lane/device_lane
    batches) and xla_stats records stage-loop engagement.  The old
    session-level field reported the launch placement even when the
    actual lanes ran elsewhere — per-stage observation keeps the
    headline honest."""
    from blaze_tpu.bridge import profiling, xla_stats

    def fold(node, acc):
        vals = node.get("values", {}) or {}
        acc[0] += int(vals.get("device_lane_batches", 0))
        acc[1] += int(vals.get("host_lane_batches", 0))
        for ch in node.get("children", []) or []:
            fold(ch, acc)
        return acc

    kind = pi.device_kind if pi else "unknown"
    per_stage = {}
    for tree in profiling.recent_metrics():
        root = tree.get("name") or "stage"
        dev, host = fold(tree, [0, 0])
        s = per_stage.setdefault(root, {"device_lane_batches": 0,
                                        "host_lane_batches": 0})
        s["device_lane_batches"] += dev
        s["host_lane_batches"] += host
    for s in per_stage.values():
        d, h = s["device_lane_batches"], s["host_lane_batches"]
        s["placement"] = (kind if d and not h
                          else "host" if h and not d
                          else f"mixed({kind}+host)" if d else kind)
    sl = xla_stats.stage_loop_stats()
    dev_total = sum(s["device_lane_batches"] for s in per_stage.values())
    host_total = sum(s["host_lane_batches"] for s in per_stage.values())
    if sl.get("stage_loop_tasks"):
        overall = f"device-loop({kind})"
    elif dev_total and host_total:
        overall = f"mixed({kind}+host)"
    elif host_total:
        overall = "host"
    else:
        overall = kind
    return overall, per_stage


def _persist_profile() -> None:
    """Write the per-operator/XLA profile of this bench run alongside the
    BENCH_*.json output line (BLAZE_BENCH_PROFILE_PATH overrides)."""
    from blaze_tpu.bridge import profiling, xla_stats
    path = os.environ.get(
        "BLAZE_BENCH_PROFILE_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_PROFILE.json"))
    rec = {"metric": METRIC_NAME,
           "xla": xla_stats.compile_report(),
           "transfers": xla_stats.transfer_stats(),
           "pipeline": xla_stats.pipeline_stats(),
           "metric_trees": profiling.recent_metrics()}
    _write_bench(path, rec)


# ---- process-pool execution for host-placed stages ------------------------
# Spark's executors are separate JVMs with true thread parallelism; the
# analogous host deployment here is a pool of worker PROCESSES (each its
# own GIL) that persist across queries like executors persist across
# stages.  Tasks arrive as plan/file descriptors (picklable), exactly the
# TaskDefinition contract; the pool is only used when stage compute is
# host-placed (device placement keeps the in-process thread path: one
# process per chip).

_PROC_POOL = None


def _worker_init(batch_size):
    # host-placed stage tasks: the pool exists only when the parent's
    # compute is on host XLA, and its workers say where they run
    os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported here
    print("bench pool worker %d: JAX_PLATFORMS=cpu" % os.getpid(),
          file=sys.stderr)
    from blaze_tpu import config as C
    C.conf.set(C.BATCH_SIZE.key, batch_size)
    C.conf.set(C.PLACEMENT.key, "host")


def _get_pool():
    global _PROC_POOL
    if _PROC_POOL is None:
        import multiprocessing as mp
        ctx = mp.get_context("spawn")
        _PROC_POOL = ctx.Pool(
            _CORES, initializer=_worker_init,
            initargs=(int(os.environ.get("BLAZE_BENCH_BATCH", 65536)),))
    return _PROC_POOL


def _shutdown_pool():
    """MUST run before the child's os._exit: workers inherit the
    supervisor's stdout pipe, and orphaned workers holding its write
    end would turn every successful run into a reported hang."""
    global _PROC_POOL
    if _PROC_POOL is not None:
        _PROC_POOL.terminate()
        _PROC_POOL.join()
        _PROC_POOL = None


def _use_proc_pool() -> bool:
    if os.environ.get("BLAZE_BENCH_PROC_POOL", "1") != "1":
        return False
    from blaze_tpu.bridge.placement import placement_info
    pi = placement_info()
    return pi is not None and pi.device_kind == "cpu"


def _proc_tasks(fn, args_list, what):
    pool = _get_pool()
    results = [pool.apply_async(fn, (a,)) for a in args_list]
    deadline = time.monotonic() + STAGE_TIMEOUT_S  # ONE shared budget
    out = []
    errors = []
    for i, r in enumerate(results):
        try:
            out.append(r.get(timeout=max(0.1, deadline - time.monotonic())))
        except Exception as e:  # surface the first REAL failure last
            errors.append((i, e))
    if errors:
        i, e = errors[0]
        raise RuntimeError(f"{what}: task {i} failed: {e!r}") from e
    return out


def _proc_map_task(args):
    sr_paths, lo, hi, m, tmpdir, n_maps, n_reduces = args
    from blaze_tpu.bridge.runtime import NativeExecutionRuntime
    from blaze_tpu.plan.proto_serde import task_definition_to_bytes
    td = task_definition_to_bytes(
        stage1_td(sr_paths, lo, hi, m, tmpdir, n_maps, n_reduces))
    rt = NativeExecutionRuntime(td).start()
    try:
        for _ in rt.batches():
            pass
    finally:
        rt.finalize()
    return None


def _proc_reduce_task(args):
    blocks, r, n_reduces = args  # blocks: [(path, offset, length), ...]
    import pyarrow as pa
    from blaze_tpu.bridge.resource import put_resource
    from blaze_tpu.bridge.runtime import NativeExecutionRuntime
    from blaze_tpu.plan.proto_serde import task_definition_to_bytes
    from blaze_tpu.shuffle.reader import FileSegmentBlock

    def blocks_for(_partition):
        return [FileSegmentBlock(p, off, length)
                for p, off, length in blocks]

    put_resource("bench_q01_shuffle", blocks_for)
    td = task_definition_to_bytes(stage2_td(r, n_reduces))
    rt = NativeExecutionRuntime(td).start()
    groups = 0
    total = 0.0
    try:
        for rb in rt.batches():
            groups += rb.num_rows
            s = pa.compute.sum(rb.column(2)).as_py()
            total += s if s is not None else 0.0
    finally:
        rt.finalize()
    return groups, total


def ensure_dataset(scale: float = SCALE):
    """Generate + cache the SF-scaled q01 tables as parquet."""
    import pyarrow.parquet as pq
    from blaze_tpu.itest.tpcds_data import gen_date_dim, gen_store_returns
    # "d3" = date-ordered fact layout (dsdgen emits fact rows in date
    # order; see itest/tpcds_data._date_ordered) — distinct cache key so
    # stale uniform-random caches regenerate
    root = f"/tmp/blaze_tpu_bench/sf{scale:g}_f{N_FILES}_d3"
    marker = os.path.join(root, ".done")
    sr_paths = [os.path.join(root, f"store_returns_{i}.parquet")
                for i in range(N_FILES)]
    dd_path = os.path.join(root, "date_dim.parquet")
    if not os.path.exists(marker):
        os.makedirs(root, exist_ok=True)
        sr = gen_store_returns(scale)
        rows = sr.num_rows
        per = -(-rows // N_FILES)
        for i, p in enumerate(sr_paths):
            pq.write_table(sr.slice(i * per, per), p,
                           row_group_size=1 << 16)
        pq.write_table(gen_date_dim(scale), dd_path)
        open(marker, "w").write("ok")
    return sr_paths, dd_path


def _scratch_dir(prefix):
    """Shuffle scratch on the RAM disk when available (one shared
    heuristic with the production scheduler: stages.py)."""
    import tempfile
    from blaze_tpu.plan.stages import _shuffle_scratch_base
    return tempfile.mkdtemp(prefix=prefix, dir=_shuffle_scratch_base())


def _file_groups(paths, n_groups):
    """FilePartition packing: files round-robin into map partitions."""
    groups = [[] for _ in range(n_groups)]
    for i, p in enumerate(paths):
        groups[i % n_groups].append(p)
    return groups


def date_sk_range(dd_path: str):
    """The d_year=2000 date-key range (what Spark's DPP/broadcast would
    push into the fact-table scan)."""
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    dd = pq.read_table(dd_path, columns=["d_date_sk", "d_year"])
    keys = dd.filter(pc.equal(dd["d_year"], 2000))["d_date_sk"]
    return int(pc.min(keys).as_py()), int(pc.max(keys).as_py())


def _col(name):
    return {"kind": "column", "name": name}


def _lit(v):
    return {"kind": "literal", "value": v, "type": {"id": "int64"}}


def stage1_td(sr_paths, lo, hi, map_id, tmpdir, n_maps=None,
              n_reduces=None):
    n_maps = n_maps or N_MAPS
    n_reduces = n_reduces or N_REDUCES
    # the wire carries ONE file group per task (FileScanExecConf):
    # this task's group stays, siblings blank out
    file_groups = [g if i == map_id else []
                   for i, g in enumerate(_file_groups(sr_paths, n_maps))]
    plan = {
        "kind": "shuffle_writer",
        "partitioning": {"kind": "hash",
                         "exprs": [{"kind": "column", "index": 0},
                                   {"kind": "column", "index": 1}],
                         "num_partitions": n_reduces},
        "data_file": os.path.join(tmpdir, f"shuffle_{map_id}.data"),
        "index_file": os.path.join(tmpdir, f"shuffle_{map_id}.index"),
        "input": {
            "kind": "hash_agg",
            "groupings": [{"expr": _col("sr_customer_sk"),
                           "name": "ctr_customer_sk"},
                          {"expr": _col("sr_store_sk"),
                           "name": "ctr_store_sk"}],
            "aggs": [{"fn": "sum", "mode": "partial",
                      "name": "ctr_total_return",
                      "args": [_col("sr_return_amt")]}],
            "input": {
                "kind": "filter",
                "predicates": [
                    {"kind": "binary", "op": ">=",
                     "l": _col("sr_returned_date_sk"), "r": _lit(lo)},
                    {"kind": "binary", "op": "<=",
                     "l": _col("sr_returned_date_sk"), "r": _lit(hi)}],
                "input": {"kind": "parquet_scan", "schema": SR_SCHEMA_D,
                          # Catalyst prunes unused columns before the plan
                          # reaches the engine (NativeParquetScanBase
                          # projection); mirror that contract
                          "projection": ["sr_returned_date_sk",
                                         "sr_customer_sk", "sr_store_sk",
                                         "sr_return_amt"],
                          "file_groups": file_groups}}}}
    return {"stage_id": 1, "partition_id": map_id,
            "num_partitions": n_maps, "plan": plan}


def stage2_td(reduce_id, n_reduces=None):
    n_reduces = n_reduces or N_REDUCES
    plan = {
        "kind": "hash_agg",
        "groupings": [{"expr": {"kind": "column", "index": 0},
                       "name": "ctr_customer_sk"},
                      {"expr": {"kind": "column", "index": 1},
                       "name": "ctr_store_sk"}],
        "aggs": [{"fn": "sum", "mode": "final", "name": "ctr_total_return",
                  "args": [{"kind": "column", "index": 2}]}],
        "input": {"kind": "ipc_reader", "resource_id": "bench_q01_shuffle",
                  "schema": PARTIAL_SCHEMA_D,
                  "num_partitions": n_reduces}}
    return {"stage_id": 2, "partition_id": reduce_id,
            "num_partitions": n_reduces, "plan": plan}


def run_engine(sr_paths, dd_path, tmpdir, n_maps=None, n_reduces=None):
    """One full q01-inner execution; returns (n_groups, total_sum).

    Tasks within a stage run on a thread pool (spark local[N]: one task
    per executor core; the engine's device work is async-dispatched, so
    concurrent tasks overlap their host round trips)."""
    import pyarrow as pa
    from blaze_tpu.bridge.resource import put_resource
    from blaze_tpu.bridge.runtime import NativeExecutionRuntime
    from blaze_tpu.plan.proto_serde import task_definition_to_bytes
    from blaze_tpu.shuffle.reader import FileSegmentBlock
    from blaze_tpu.shuffle.exchange import read_index_file

    lo, hi = date_sk_range(dd_path)
    n_maps = n_maps or N_MAPS
    n_reduces = n_reduces or N_REDUCES

    # the pool pays per-task IPC; single-task STAGES keep the
    # zero-overhead in-process path (gated per stage)
    pool_ok = _use_proc_pool()
    if pool_ok and n_maps >= 2:
        _proc_tasks(_proc_map_task,
                    [(sr_paths, lo, hi, m, tmpdir, n_maps, n_reduces)
                     for m in range(n_maps)], "q01 map stage")
    else:
        def run_map(m):
            td = task_definition_to_bytes(
                stage1_td(sr_paths, lo, hi, m, tmpdir, n_maps, n_reduces))
            rt = NativeExecutionRuntime(td).start()
            try:
                for _ in rt.batches():
                    pass
            finally:
                _record_tree(rt.finalize())

        _tasks(run_map, n_maps, "q01 map stage")

    # ---- register reduce-side block map (the MapOutputTracker analog) ----
    offsets = [read_index_file(os.path.join(tmpdir, f"shuffle_{m}.index"))
               for m in range(n_maps)]

    def seg_list(partition):
        out = []
        for m in range(n_maps):
            off = offsets[m]
            length = off[partition + 1] - off[partition]
            if length > 0:
                out.append((os.path.join(tmpdir, f"shuffle_{m}.data"),
                            off[partition], length))
        return out

    if pool_ok and n_reduces >= 2:
        results = _proc_tasks(
            _proc_reduce_task,
            [(seg_list(r), r, n_reduces) for r in range(n_reduces)],
            "q01 reduce stage")
        return sum(g for g, _ in results), sum(t for _, t in results)

    def blocks_for(partition):
        return [FileSegmentBlock(p, off, length)
                for p, off, length in seg_list(partition)]

    put_resource("bench_q01_shuffle", blocks_for)

    def run_reduce(r):
        td = task_definition_to_bytes(stage2_td(r, n_reduces))
        rt = NativeExecutionRuntime(td).start()
        groups = 0
        total = 0.0
        try:
            for rb in rt.batches():
                groups += rb.num_rows
                s = pa.compute.sum(rb.column(2)).as_py()
                total += s if s is not None else 0.0
        finally:
            _record_tree(rt.finalize())
        return groups, total

    results = _tasks(run_reduce, n_reduces, "q01 reduce stage")
    return sum(g for g, _ in results), sum(t for _, t in results)


def run_baseline(sr_paths, dd_path, pushdown: bool = False):
    """Identical query on pyarrow (multithreaded C++ columnar kernels).

    pushdown=False is the recorded `vs_baseline` denominator (same
    definition since round 1): one in-process read+filter+group pass.
    pushdown=True additionally hands pyarrow the date predicate for its
    own row-group pruning — reported as `pushdown_baseline_wall_s` so the
    engine's scan-pruning advantage is visible, not hidden."""
    import pyarrow.compute as pc
    import pyarrow.parquet as pq

    lo, hi = date_sk_range(dd_path)
    filters = ([("sr_returned_date_sk", ">=", lo),
                ("sr_returned_date_sk", "<=", hi)] if pushdown else None)
    t = pq.read_table(sr_paths,
                      columns=["sr_returned_date_sk", "sr_customer_sk",
                               "sr_store_sk", "sr_return_amt"],
                      filters=filters)
    mask = pc.and_(pc.greater_equal(t["sr_returned_date_sk"], lo),
                   pc.less_equal(t["sr_returned_date_sk"], hi))
    f = t.filter(mask)
    agg = f.group_by(["sr_customer_sk", "sr_store_sk"]).aggregate(
        [("sr_return_amt", "sum")])
    total = pc.sum(agg["sr_return_amt_sum"]).as_py()
    return agg.num_rows, float(total if total is not None else 0.0)


# ---- q06-shaped join stage (BASELINE config #2 shape) ---------------------

def join_td(sr_paths, dd_path, map_id, n_maps=None):
    """store_returns ⋈ date_dim on returned_date_sk, d_year=2000 filter on
    the build side, count+sum aggregate — the broadcast-join stage shape."""
    n_maps = n_maps or N_MAPS
    file_groups = [g if i == map_id else []
                   for i, g in enumerate(_file_groups(sr_paths, n_maps))]
    dd_groups = [[] for _ in range(n_maps)]
    dd_groups[map_id] = [dd_path]
    plan = {
        "kind": "hash_agg",
        "groupings": [],
        "aggs": [{"fn": "count", "mode": "partial", "name": "cnt",
                  "args": [_col("sr_ticket_number")]},
                 {"fn": "sum", "mode": "partial", "name": "amt",
                  "args": [_col("sr_return_amt")]}],
        "input": {
            "kind": "broadcast_join",
            "join_type": "inner",
            "left_keys": [_col("sr_returned_date_sk")],
            "right_keys": [_col("d_date_sk")],
            "left": {"kind": "parquet_scan", "schema": SR_SCHEMA_D,
                     "projection": ["sr_returned_date_sk",
                                    "sr_return_amt", "sr_ticket_number"],
                     "file_groups": file_groups},
            "right": {"kind": "filter",
                      "predicates": [{"kind": "binary", "op": "==",
                                      "l": _col("d_year"),
                                      "r": _lit(2000)}],
                      "input": {"kind": "parquet_scan",
                                "schema": DD_SCHEMA_D,
                                "file_groups": dd_groups}},
            "build_side": "right"}}
    return {"stage_id": 3, "partition_id": map_id,
            "num_partitions": n_maps, "plan": plan}


def _proc_join_task(args):
    sr_paths, dd_path, m, n_maps = args
    import pyarrow as pa
    from blaze_tpu.bridge.runtime import NativeExecutionRuntime
    from blaze_tpu.plan.proto_serde import task_definition_to_bytes
    td = task_definition_to_bytes(join_td(sr_paths, dd_path, m, n_maps))
    rt = NativeExecutionRuntime(td).start()
    cnt, amt = 0, 0.0
    try:
        for rb in rt.batches():
            cnt += pa.compute.sum(rb.column(0)).as_py() or 0
            amt += pa.compute.sum(rb.column(1)).as_py() or 0.0
    finally:
        rt.finalize()
    return cnt, amt


def run_join_engine(sr_paths, dd_path, n_maps=None):
    import pyarrow as pa
    from blaze_tpu.bridge.runtime import NativeExecutionRuntime
    from blaze_tpu.plan.proto_serde import task_definition_to_bytes

    n_maps = n_maps or N_MAPS

    if _use_proc_pool() and n_maps >= 2:
        results = _proc_tasks(
            _proc_join_task,
            [(sr_paths, dd_path, m, n_maps) for m in range(n_maps)],
            "q06-shaped join stage")
        return (sum(c for c, _ in results), sum(a for _, a in results))

    def run_map(m):
        td = task_definition_to_bytes(join_td(sr_paths, dd_path, m, n_maps))
        rt = NativeExecutionRuntime(td).start()
        cnt, amt = 0, 0.0
        try:
            for rb in rt.batches():
                cnt += pa.compute.sum(rb.column(0)).as_py() or 0
                amt += pa.compute.sum(rb.column(1)).as_py() or 0.0
        finally:
            _record_tree(rt.finalize())
        return cnt, amt

    results = _tasks(run_map, n_maps, "q06-shaped join stage")
    return sum(c for c, _ in results), sum(a for _, a in results)


def run_join_baseline(sr_paths, dd_path):
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    sr = pq.read_table(sr_paths,
                       columns=["sr_returned_date_sk", "sr_ticket_number",
                                "sr_return_amt"])
    dd = pq.read_table(dd_path, columns=["d_date_sk", "d_year"])
    dd = dd.filter(pc.equal(dd["d_year"], 2000))
    j = sr.join(dd, keys="sr_returned_date_sk", right_keys="d_date_sk",
                join_type="inner")
    cnt = pc.count(j["sr_ticket_number"]).as_py()
    amt = pc.sum(j["sr_return_amt"]).as_py()
    return int(cnt or 0), float(amt or 0.0)


def child_main():
    import shutil
    import tempfile

    import numpy as np

    # large tiles cut per-batch dispatches and host round trips; device
    # HBM fits them easily
    from blaze_tpu import config
    config.conf.set(config.BATCH_SIZE.key,
                    int(os.environ.get("BLAZE_BENCH_BATCH", 65536)))

    sr_paths, dd_path = ensure_dataset()
    input_bytes = sum(os.path.getsize(p) for p in sr_paths)
    n_rows = sum(_parquet_rows(p) for p in sr_paths)

    # Warm both sides, then time them INTERLEAVED (B,E,B,E,...): the
    # shared 2-CPU box is noisy, and separate timing blocks let one
    # descheduled stretch define a whole side of the ratio.  Alternating
    # samples expose both sides to the same load; each side reports its
    # MINIMUM (the standard least-noise estimator — a descheduled stretch
    # can only inflate a sample, never deflate it), applied symmetrically
    # to both sides of every ratio.
    want_groups, want_total = run_baseline(sr_paths, dd_path)  # warm
    warmdir = _scratch_dir("blaze_bench_")
    try:  # engine warmup compiles the fused stage
        run_engine(sr_paths, dd_path, warmdir)
    finally:
        shutil.rmtree(warmdir, ignore_errors=True)
    # warm side-by-side done: every kernel/bucket the timed loop can hit
    # is compiled now — compiles observed from here on are steady-state
    # recompiles (shape churn the bucket ladder failed to absorb; 0 is
    # the design point)
    from blaze_tpu.bridge import xla_stats
    xla_warm = xla_stats.snapshot()
    cpu_times = []
    times = []
    pd_times = []
    for _ in range(max(9, ITERS)):
        t0 = time.perf_counter()
        want_groups, want_total = run_baseline(sr_paths, dd_path)
        cpu_times.append(time.perf_counter() - t0)
        # transparency figure, SAME loop + sample count: the baseline
        # WITH pyarrow's own predicate pushdown (row-group pruning) —
        # the engine's scan-pruning edge is the gap between the two
        # baseline walls
        t0 = time.perf_counter()
        run_baseline(sr_paths, dd_path, pushdown=True)
        pd_times.append(time.perf_counter() - t0)
        tmpdir = _scratch_dir("blaze_bench_")
        try:
            t0 = time.perf_counter()
            got_groups, got_total = run_engine(sr_paths, dd_path, tmpdir)
            times.append(time.perf_counter() - t0)
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
        assert got_groups == want_groups, (got_groups, want_groups)
        assert abs(got_total - want_total) / max(abs(want_total), 1) < 1e-9, \
            (got_total, want_total)
    cpu_s = float(np.min(cpu_times))
    tpu_s = float(np.min(times))
    pushdown_cpu_s = float(np.min(pd_times))
    steady_recompiles = int(xla_stats.delta(xla_warm)["total_compiles"])

    # prefetch-off twin of the engine loop: IO pipeline executor disabled
    # via its kill-switch, min over the same-shaped sample loop — the
    # decode/compute overlap win is prefetch_off_wall_s vs wall_s
    pf_off_times = []
    config.conf.set(config.IO_PREFETCH_ENABLE.key, False)
    try:
        for _ in range(max(5, ITERS)):
            tmpdir = _scratch_dir("blaze_bench_")
            try:
                t0 = time.perf_counter()
                run_engine(sr_paths, dd_path, tmpdir)
                pf_off_times.append(time.perf_counter() - t0)
            finally:
                shutil.rmtree(tmpdir, ignore_errors=True)
    finally:
        config.conf.unset(config.IO_PREFETCH_ENABLE.key)
    prefetch_off_s = float(np.min(pf_off_times))

    # join stage (q06 shape): correctness + timing vs pyarrow join,
    # interleaved for the same reason as above
    want_cnt, want_amt = run_join_baseline(sr_paths, dd_path)
    run_join_engine(sr_paths, dd_path)  # warm
    jcpu_times = []
    jtimes = []
    for _ in range(max(5, ITERS)):
        t0 = time.perf_counter()
        want_cnt, want_amt = run_join_baseline(sr_paths, dd_path)
        jcpu_times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        got_cnt, got_amt = run_join_engine(sr_paths, dd_path)
        jtimes.append(time.perf_counter() - t0)
        assert got_cnt == want_cnt, (got_cnt, want_cnt)
        assert abs(got_amt - want_amt) / max(abs(want_amt), 1) < 1e-9, \
            (got_amt, want_amt)
    join_cpu_s = float(np.min(jcpu_times))
    join_tpu_s = float(np.min(jtimes))

    # ---- SF10 leg: same pipeline at 10x rows, Spark-sized partitions ----
    # a failed phase fails the run: no *_error field, no exit 0
    sf10_fields = run_scaled_leg(10.0) if SF10 else {}

    # ---- device-resident compute loop -----------------------------------
    dev_fields = (device_compute_loop(sr_paths, dd_path)
                  if DEVICE_LOOP else {})

    from blaze_tpu.bridge.placement import placement_info
    pi = placement_info()
    bytes_per_s = input_bytes / tpu_s
    _persist_profile()
    placement, stage_lanes = _observed_placement(pi)
    hbm_peak = _hbm_peak_bytes_s()
    print(json.dumps({
        "metric": METRIC_NAME,
        "compute_placement": placement,
        "compute_placement_stages": stage_lanes,
        "session_device_kind": (pi.device_kind if pi else "unknown"),
        "dispatch_rtt_ms": (round(pi.rtt_ms, 1) if pi else None),
        "placement_policy": (pi.policy if pi else "unknown"),
        "value": round(n_rows / tpu_s),
        "unit": "rows/s",
        "vs_baseline": round(cpu_s / tpu_s, 3),
        "wall_s": round(tpu_s, 4),
        "baseline_wall_s": round(cpu_s, 4),
        "pushdown_baseline_wall_s": round(pushdown_cpu_s, 4),
        "steady_state_recompiles": steady_recompiles,
        "prefetch_on_wall_s": round(tpu_s, 4),
        "prefetch_off_wall_s": round(prefetch_off_s, 4),
        "prefetch_speedup": round(prefetch_off_s / tpu_s, 3),
        "input_bytes": input_bytes,
        "achieved_input_bytes_per_sec": round(bytes_per_s),
        "device_kind": _device_kind(),
        "hbm_peak_bytes_per_sec": hbm_peak,
        "roofline_frac": (round(bytes_per_s / hbm_peak, 6)
                          if hbm_peak else None),
        "groups": int(want_groups),
        "maps": N_MAPS, "reduces": N_REDUCES,
        "join_rows_per_sec": round(n_rows / join_tpu_s),
        "join_vs_baseline": round(join_cpu_s / join_tpu_s, 3),
        "join_wall_s": round(join_tpu_s, 4),
        "join_baseline_wall_s": round(join_cpu_s, 4),
        **sf10_fields,
        **dev_fields,
    }))
    sys.stdout.flush()


def run_scaled_leg(scale: float):
    """q01 pipeline at `scale`, engine vs baseline, Spark-sized
    partitioning (VERDICT r3 #1: record SF10, not just SF1)."""
    import shutil
    import tempfile

    import numpy as np
    sr_paths, dd_path = ensure_dataset(scale)
    n_maps, n_reduces = _spark_partitions(scale)
    want_groups, want_total = run_baseline(sr_paths, dd_path)
    warmdir = _scratch_dir("blaze_bench_sf_")
    try:
        run_engine(sr_paths, dd_path, warmdir, n_maps, n_reduces)
    finally:
        shutil.rmtree(warmdir, ignore_errors=True)
    ctimes = []
    times = []
    pd_times = []
    for _ in range(5):  # interleaved B,P,E triples (see child_main)
        t0 = time.perf_counter()
        want_groups, want_total = run_baseline(sr_paths, dd_path)
        ctimes.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_baseline(sr_paths, dd_path, pushdown=True)
        pd_times.append(time.perf_counter() - t0)
        tmpdir = _scratch_dir("blaze_bench_sf_")
        try:
            t0 = time.perf_counter()
            got_groups, got_total = run_engine(sr_paths, dd_path, tmpdir,
                                               n_maps, n_reduces)
            times.append(time.perf_counter() - t0)
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
        assert got_groups == want_groups, (got_groups, want_groups)
        assert abs(got_total - want_total) / max(abs(want_total), 1) \
            < 1e-9, (got_total, want_total)
    cpu_s = float(np.min(ctimes))
    eng_s = float(np.min(times))
    pushdown_cpu_s = float(np.min(pd_times))
    n_rows = sum(_parquet_rows(p) for p in sr_paths)
    # join leg at scale: the runtime-filter advantage grows with probe
    # size (join cost scales with rows probed; the filter caps it)
    want_cnt, want_amt = run_join_baseline(sr_paths, dd_path)
    run_join_engine(sr_paths, dd_path, n_maps)  # warm
    jc, je = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        want_cnt, want_amt = run_join_baseline(sr_paths, dd_path)
        jc.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        got_cnt, got_amt = run_join_engine(sr_paths, dd_path, n_maps)
        je.append(time.perf_counter() - t0)
        assert got_cnt == want_cnt, (got_cnt, want_cnt)
        assert abs(got_amt - want_amt) / max(abs(want_amt), 1) < 1e-9, \
            (got_amt, want_amt)
    jcpu_s = float(np.min(jc))
    jeng_s = float(np.min(je))
    return {
        "sf10_vs_baseline": round(cpu_s / eng_s, 3),
        "sf10_wall_s": round(eng_s, 4),
        "sf10_baseline_wall_s": round(cpu_s, 4),
        "sf10_pushdown_baseline_wall_s": round(pushdown_cpu_s, 4),
        "sf10_rows_per_sec": round(n_rows / eng_s),
        "sf10_maps": n_maps, "sf10_reduces": n_reduces,
        "sf10_join_vs_baseline": round(jcpu_s / jeng_s, 3),
        "sf10_join_wall_s": round(jeng_s, 4),
        "sf10_join_baseline_wall_s": round(jcpu_s, 4),
    }


def _diff_time(make_loop, fresh, *args, iters, read):
    """Differential timing: run the fold loop at `iters` and `4*iters`
    inside one program each; throughput comes from the EXTRA work over
    the EXTRA wall, so dispatch RTT, readback and per-call fixed costs
    cancel.  Each leg is min-of-3 after a warm run; `read` pulls a scalar
    back to the host, which also waits for the device.  Returns (wall_for_iters_equiv, last_output)."""
    walls = {}
    out = None
    for k in (iters, 4 * iters):
        loop = make_loop(k)
        o = loop(fresh(), *args)
        read(o)
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            o = loop(fresh(), *args)
            read(o)
            w = time.perf_counter() - t0
            best = w if best is None else min(best, w)
        walls[k] = best
        out = o
    extra = max(walls[4 * iters] - walls[iters], 1e-9)
    # `out` holds the 4*iters accumulation — callers decoding it must
    # divide by (4 * iters)
    return extra / 3.0, out


def device_compute_loop(sr_paths, dd_path, iters: int = 32):
    """Fused-stage compute RESIDENT on the accelerator, through the
    PRODUCTION fold (plan/fused.py): ship ONE ~1M-row window to the
    device and fold it `iters` times inside a single XLA program — one
    dispatch.  Measures what the chip does once data
    is in HBM (VERDICT r3 #3 / r4 #1).

    The workload is the q01 partial-agg shape grouped by
    (store_sk, returned_date_sk) — the compact rollup domain where the
    planner's stats pick the MXU strategy (kernels/mxu_agg.py: grouped
    agg as one-hot matmuls in the exact i32 limb tier).  Reported
    alongside: the production SCATTER strategy on the same plan, the
    open-addressing hash strategy on the sparse (cust, store) keys, and
    host-XLA twins of each — the same fold compiled for the host
    backend (the honest chip-vs-host comparison; the MXU fold's host
    twin runs the scatter reference formulation of identical
    semantics).  Result correctness is asserted against pyarrow every
    run."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu.exprs import BinaryExpr, col, lit
    from blaze_tpu.kernels import mxu_agg
    from blaze_tpu.ops import (AggExec, AggMode, FilterExec,
                               MemoryScanExec, make_agg)
    from blaze_tpu.plan import fused as F
    from blaze_tpu.parallel.stage import hash_agg_step, init_hash_carry

    dev = jax.devices()[0]  # the accelerator, regardless of placement
    lo, hi = date_sk_range(dd_path)
    t = pq.read_table(sr_paths,
                      columns=["sr_returned_date_sk", "sr_customer_sk",
                               "sr_store_sk", "sr_return_amt"])
    # tile the real table up to a >=1M-row window (VERDICT r4: 65K-row
    # dispatches amortize nothing; production folds windows this size).
    # At large SF the window SAMPLES uniformly across the table — dates
    # correlate with row position, so a head slice of SF100 data holds
    # zero d_year-2000 rows and the oracle degenerates to empty
    if t.num_rows >= (1 << 20):
        idx = np.linspace(0, t.num_rows - 1, 1 << 20).astype(np.int64)
        t = t.take(pa.array(idx))
    else:
        reps = max(1, -(-(1 << 20) // t.num_rows))
        if reps > 1:
            t = pa.concat_tables([t] * reps)
        t = t.slice(0, 1 << 20) if t.num_rows >= (1 << 20) else t
    n = t.num_rows

    rollup = pa.table({
        "date": t.column("sr_returned_date_sk"),
        "store": t.column("sr_store_sk"),
        "amt": t.column("sr_return_amt"),
    }).combine_chunks()  # one chunk -> ONE window batch (to_batches
    # never merges chunks, and the parquet row groups are 64K)

    def build_fused():
        scan = MemoryScanExec.from_arrow(rollup, batch_rows=n)
        flt = FilterExec(scan, [
            BinaryExpr(">=", col(0, "date"), lit(lo)),
            BinaryExpr("<=", col(0, "date"), lit(hi))])
        agg = AggExec(flt,
                      [(col(1, "store"), "store"), (col(0, "date"), "d")],
                      [(make_agg("sum", [col(2)]), AggMode.PARTIAL, "amt"),
                       (make_agg("count", [col(2)]), AggMode.PARTIAL,
                        "cnt")])
        node = F.fuse_plan(agg)
        assert isinstance(node, F.FusedPartialAggExec), "fusion regressed"
        return node

    node = build_fused()
    assert node._mxu_meta is not None, "rollup must be MXU-eligible"
    meta = node._mxu_meta
    ranges = tuple(node._ranges)
    kinds = tuple(rk for rk, _ok, _a in node._specs)
    num_slots = 1
    for rlo, rhi in ranges:
        num_slots *= (rhi - rlo + 2)

    window = next(F._batch_windows(node._source.execute(0), 1))
    cols_stacked, masks, _rows, _cnt = window
    # true per-iteration HBM operand traffic: column data + validity
    # bytes + the row mask (one-hot operands never leave VMEM)
    bpr = sum(c[0].dtype.itemsize + 1 for c in cols_stacked if c is not
              None) + 1

    # pyarrow oracle for the asserted result
    mask_pd = pa.compute.and_(
        pa.compute.greater_equal(rollup["date"], lo),
        pa.compute.less_equal(rollup["date"], hi))
    want = (rollup.filter(mask_pd).group_by(["store", "date"])
            .aggregate([("amt", "sum"), ("amt", "count")]))
    want_sum = pa.compute.sum(want["amt_sum"]).as_py() or 0.0
    want_cnt = pa.compute.sum(want["amt_count"]).as_py() or 0
    want_groups = want.num_rows

    def put_window(device):
        cs = tuple(None if c is None else
                   (jax.device_put(c[0], device),
                    jax.device_put(c[1], device))
                   for c in cols_stacked)
        return cs, jax.device_put(masks, device)

    def run_fold(device, use_pallas):
        """Production MXU fold, `iters` round trips over the resident
        window in ONE program; returns (wall_s, table)."""
        fold = F._mxu_fold_factory(node._prepare_key, node._prepare,
                                   ranges, meta, use_pallas)
        nb = meta.layout.n_blocks

        def fresh():
            return (jnp.zeros((meta.layout.sh, meta.layout.sl * nb),
                              jnp.int32), (), jnp.asarray(True))

        def make_loop(k):
            @jax.jit
            def loop(carry, cs, mk):
                def body(_i, c):
                    # carry-dependent always-true bit keeps every
                    # iteration live: without it XLA hoists the whole
                    # loop-invariant fold out of the fori_loop and the
                    # "throughput" becomes fiction (values >= 0 by
                    # construction, so the predicate never flips)
                    p = c[0].reshape(-1)[0] > jnp.int32(-(2**30))
                    return fold.raw(c, cs, mk & p)
                return jax.lax.fori_loop(0, k, body, carry)
            return loop

        with jax.default_device(device):
            cs, mk = put_window(device)
            wall, out = _diff_time(make_loop, fresh, cs, mk,
                                   iters=iters,
                                   read=lambda o: float(jnp.sum(
                                       o[0].astype(jnp.float32))))
            table, _mm, ok = jax.device_get(out)
            assert bool(ok), "fixed-point verify failed on bench data"
        return wall, table

    def run_scatter(device):
        """Production dense SCATTER fold on the same plan (the strategy
        the planner would pick past the MXU slot cap)."""
        fold = F._dense_fold_factory(node._prepare_key, node._prepare,
                                     ranges, kinds, num_slots)

        def make_loop(k):
            @jax.jit
            def loop(carry, cs, mk):
                def body(_i, c):
                    # same hoist-proofing as the MXU loop (counts >= 0)
                    p = c[0][1].reshape(-1)[0] > jnp.asarray(-(2**62))
                    return fold.raw(c, cs, mk & p)
                return jax.lax.fori_loop(0, k, body, carry)
            return loop

        with jax.default_device(device):
            cs, mk = put_window(device)
            wall, _out = _diff_time(
                make_loop,
                lambda: F._init_carry(kinds, node._acc_dtypes(),
                                      num_slots),
                cs, mk, iters=iters,
                read=lambda o: float(jnp.sum(o[0][0])))
        return wall

    def run_hash(device, hrows=1 << 16):
        """Open-addressing hash strategy on the sparse (cust, store)
        keys — the q01 shape whose domain outgrows dense tables.  Kept
        at its historical 64K-row shape: the probe-round kernel is the
        known-slow TPU path (the MXU strategy exists to avoid it) and
        larger resident folds of it fault the device."""
        th = t.slice(0, hrows)
        cust = np.ascontiguousarray(th.column("sr_customer_sk")
                                    .combine_chunks().fill_null(0)
                                    .to_numpy(zero_copy_only=False))
        store = np.ascontiguousarray(th.column("sr_store_sk")
                                     .combine_chunks().fill_null(0)
                                     .to_numpy(zero_copy_only=False))
        date = np.ascontiguousarray(th.column("sr_returned_date_sk")
                                    .combine_chunks().fill_null(0)
                                    .to_numpy(zero_copy_only=False))
        amt = np.ascontiguousarray(th.column("sr_return_amt")
                                   .combine_chunks().fill_null(0)
                                   .to_numpy(zero_copy_only=False))
        valid = (np.asarray(th.column("sr_returned_date_sk")
                            .combine_chunks().is_valid()) &
                 np.asarray(th.column("sr_customer_sk")
                            .combine_chunks().is_valid()) &
                 np.asarray(th.column("sr_store_sk")
                            .combine_chunks().is_valid()))
        aval = np.asarray(th.column("sr_return_amt")
                          .combine_chunks().is_valid())
        slots = 1 << 17

        def make_loop(k):
            @jax.jit
            def hash_fold(carry, date, cust, store, amt, valid, aval):
                def body(_i, c):
                    # hoist-proof: sum accs stay finite-and-bounded
                    p = c.accs[0].reshape(-1)[0] > -1e300
                    mask = valid & (date >= lo) & (date <= hi) & p
                    return hash_agg_step(
                        c, [(cust, valid), (store, valid)],
                        [("sum", amt, aval)], mask)[0]
                return jax.lax.fori_loop(0, k, body, carry)
            return hash_fold

        with jax.default_device(device):
            args = [jax.device_put(x, device) for x in
                    (date, cust, store, amt, valid, aval)]
            wall, _out = _diff_time(
                make_loop,
                lambda: init_hash_carry([jnp.int64, jnp.int64], ["sum"],
                                        (jnp.float64,), slots),
                *args, iters=iters,
                read=lambda o: float(jnp.sum(o.accs[0])))
        return wall

    use_pallas = dev.platform == "tpu"
    mxu_wall, table = run_fold(dev, use_pallas)

    # ---- correctness: decode the device table against pyarrow ----------
    presence, vals = mxu_agg.split_blocks(np.asarray(table), meta.layout)
    occ = np.nonzero(presence)[0]
    sp = meta.specs[0]
    vcnt = vals[sp.arr_valid][occ]
    cents = vals[sp.arr_cents][occ] + vcnt * sp.off
    got_sum = float(cents.sum()) / sp.scale / (4 * iters)
    got_cnt = int(vals[meta.specs[1].arr_valid][occ].sum()) // (4 * iters)
    assert got_cnt == want_cnt, (got_cnt, want_cnt)
    assert len(occ) == want_groups, (len(occ), want_groups)
    assert abs(got_sum - want_sum) / max(abs(want_sum), 1) < 1e-9, \
        (got_sum, want_sum)

    scatter_wall = run_scatter(dev)
    hrows = 1 << 16
    hash_wall = run_hash(dev, hrows)
    hash_fields = {"device_hash_rows_per_sec":
                   round(hrows * iters / hash_wall)}

    cpu = jax.local_devices(backend="cpu")[0]
    h_wall, _ht = run_fold(cpu, use_pallas=False)
    h_scatter = run_scatter(cpu)
    h_hash = run_hash(cpu, hrows)
    host_fields = {
        "host_xla_dense_rows_per_sec": round(n * iters / h_wall),
        "host_xla_scatter_rows_per_sec": round(n * iters / h_scatter),
        "host_xla_hash_rows_per_sec": round(hrows * iters / h_hash),
    }
    rows = n * iters
    hbm_peak = _hbm_peak_bytes_s()
    return {
        "device_rows_per_sec": round(rows / mxu_wall),
        "device_strategy": "mxu" if use_pallas else "mxu-ref",
        "device_scatter_rows_per_sec": round(rows / scatter_wall),
        **hash_fields,
        "device_loop_iters": iters,
        "device_loop_wall_s": round(mxu_wall, 4),
        "device_loop_batch_rows": n,
        "device_loop_groups": int(want_groups),
        "device_bytes_per_row": bpr,
        "device_hbm_frac": (round((rows * bpr / mxu_wall) / hbm_peak, 4)
                            if hbm_peak else None),
        "device_backend": dev.platform,
        **host_fields,
    }


def _parquet_rows(path):
    import pyarrow.parquet as pq
    return pq.ParquetFile(path).metadata.num_rows


# ===========================================================================
# --expr: eager-vs-fused expression microbenchmark (ISSUE 3)
# ===========================================================================

def expr_bench_main() -> int:
    """Standalone whole-stage-expression microbenchmark (`--expr`).

    One filter->project chain over a memory-resident table, run through
    the SAME FilterProjectExec operator both ways: fused = the chain
    compiled into one XLA program per batch (auron.tpu.expr.fuse=true),
    eager = per-op kernel dispatch through CachedExprsEvaluator.  Sides
    are warmed, then timed interleaved with min-of-samples (same noise
    discipline as the e2e bench).  Writes BENCH_EXPR.json next to this
    file and prints the record as one JSON line."""
    import numpy as np
    import pyarrow as pa

    from blaze_tpu import config
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.exprs import BinaryExpr, If, col, lit
    from blaze_tpu.exprs.program import (clear_program_cache,
                                         program_cache_info)
    from blaze_tpu.ops import FilterProjectExec, MemoryScanExec

    n = int(os.environ.get("BLAZE_BENCH_EXPR_ROWS", str(1 << 20)))
    iters = int(os.environ.get("BLAZE_BENCH_EXPR_ITERS", "10"))
    batch_rows = int(os.environ.get("BLAZE_BENCH_EXPR_BATCH", "65536"))
    rng = np.random.default_rng(0)
    tbl = pa.table({
        "a": pa.array(rng.integers(-100, 100, n)),
        "b": pa.array(rng.random(n) * 100),
        "c": pa.array(rng.integers(0, 1 << 16, n)),
    })
    filters = [BinaryExpr(">", col(0), lit(-50)),
               BinaryExpr("<", col(1), lit(90.0))]
    projs = [col(0),
             BinaryExpr("+", BinaryExpr("*", col(1), lit(2.0)), col(2)),
             If(BinaryExpr(">=", col(0), lit(0)), col(1),
                BinaryExpr("-", lit(0.0), col(1)))]
    names = ["a", "bc", "abs_b"]

    def run_once(fuse):
        with config.scoped(**{"auron.tpu.expr.fuse": fuse}):
            plan = FilterProjectExec(
                MemoryScanExec.from_arrow(tbl, batch_rows=batch_rows),
                filters, projs, names)
            return plan.execute_collect().num_rows

    clear_program_cache()
    rows_fused = run_once(True)   # warm: builds + compiles the program
    rows_eager = run_once(False)  # warm the eager kernels too
    assert rows_fused == rows_eager, (rows_fused, rows_eager)
    warm = xla_stats.snapshot()

    walls = {"fused": [], "eager": []}
    for _ in range(iters):
        t0 = time.perf_counter()
        run_once(True)
        walls["fused"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_once(False)
        walls["eager"].append(time.perf_counter() - t0)

    d = xla_stats.delta(warm)
    fused_s = float(np.min(walls["fused"]))
    eager_s = float(np.min(walls["eager"]))
    lookups = d["expr_programs_built"] + d["expr_program_cache_hits"]
    steady_hit_rate = (d["expr_program_cache_hits"] / lookups
                       if lookups else 0.0)
    rec = {
        "metric": "expr_fused_rows_per_sec",
        "value": round(n / fused_s),
        "unit": "rows/s",
        "vs_eager": round(eager_s / fused_s, 3),
        "rows": n,
        "batch_rows": batch_rows,
        "iters": iters,
        "selected_rows": int(rows_fused),
        "fused_wall_s": round(fused_s, 4),
        "eager_wall_s": round(eager_s, 4),
        "eager_rows_per_sec": round(n / eager_s),
        "steady_state_recompiles": int(d["total_compiles"]),
        "steady_programs_built": int(d["expr_programs_built"]),
        "steady_cache_hit_rate": round(steady_hit_rate, 3),
        "fused_batches": int(d["expr_fused_batches"]),
        "eager_batches": int(d["expr_eager_batches"]),
        "program_cache": program_cache_info(),
        "expr_stats": xla_stats.expr_stats(),
    }
    path = os.environ.get(
        "BLAZE_BENCH_EXPR_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_EXPR.json"))
    _write_bench(path, rec)
    print(json.dumps(rec))
    sys.stdout.flush()
    return 0


# ===========================================================================
# --chaos: fault-injection soak over the itest corpus (ISSUE 4)
# ===========================================================================

def chaos_bench_main() -> int:
    """Chaos soak (`--chaos`): run the itest queries through the staged
    DAG scheduler twice — once fault-free for the baseline, once with a
    seeded fault-injection script (task failures, fetch failures, frame
    corruption) — and assert ZERO divergence between the two result
    sets.  The point is the acceptance criterion of the fault-tolerance
    work: injected failures cost retries and recovery rounds, never
    wrong answers.  Writes BENCH_CHAOS.json and prints it as one JSON
    line."""
    import tempfile

    from blaze_tpu import config, faults
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.memory import MemManager
    from blaze_tpu.plan.stages import DagScheduler

    seed = int(os.environ.get("BLAZE_BENCH_CHAOS_SEED", "1234"))
    names = os.environ.get("BLAZE_BENCH_CHAOS_QUERIES",
                           "q01,q06,q95").split(",")
    scale = float(os.environ.get("BLAZE_BENCH_CHAOS_SCALE", "0.2"))
    rules = os.environ.get(
        "BLAZE_BENCH_CHAOS_RULES",
        "task-start=0.15,shuffle-read=0.08,"
        "shuffle-write=0.05:corrupt,ipc-decode=0.05,device-loop=0.5")

    MemManager.init(4 << 30)
    # force the staged wire path (a chaos run over the AQE local mode
    # would never touch shuffle files), keep retries fast, and give the
    # scripted failure rates enough budget to always converge
    knobs = {config.DAG_SINGLE_TASK_BYTES.key: 0,
             config.TASK_RETRY_BACKOFF_MS.key: 5,
             config.TASK_MAX_ATTEMPTS.key: 6,
             config.STAGE_MAX_RECOVERIES.key: 8,
             # stage loop forced on so the device-loop fault site is
             # live: an injected fault there must become a wholesale
             # staged fallback, never a divergent result
             config.STAGE_DEVICE_LOOP_ENABLE.key: "on"}
    for k, v in knobs.items():
        config.conf.set(k, v)

    def frame(tbl):
        import pandas as pd
        return tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
            {n: [] for n in tbl.schema.names})

    queries = []
    diverged = 0
    try:
        for qname in names:
            qname = qname.strip()
            builder, table_names = QUERIES[qname]
            tables = generate(table_names, scale=scale)
            with tempfile.TemporaryDirectory(prefix="chaos-") as d:
                paths = write_parquet_splits(tables, d, 2)
                plan_dict, _oracle = builder(paths, tables, 2)

                faults.clear()
                t0 = time.perf_counter()
                base = DagScheduler(work_dir=os.path.join(d, "dag0")) \
                    .run_collect(plan_dict)
                base_wall = time.perf_counter() - t0

                faults.configure(rules, seed=seed)
                before = xla_stats.snapshot()
                t0 = time.perf_counter()
                try:
                    got = DagScheduler(work_dir=os.path.join(d, "dag1")) \
                        .run_collect(plan_dict)
                finally:
                    inj_stats = faults.stats()
                    faults.clear()
                chaos_wall = time.perf_counter() - t0
                d_stats = xla_stats.delta(before)

                err = compare_frames(frame(got), frame(base))
                if err is not None:
                    diverged += 1
                queries.append({
                    "query": qname,
                    "base_wall_s": round(base_wall, 4),
                    "chaos_wall_s": round(chaos_wall, 4),
                    "divergence": err,
                    "faults_injected": int(d_stats["faults_injected"]),
                    "task_retries": int(d_stats["task_retries"]),
                    "fetch_failures": int(d_stats["fetch_failures"]),
                    "stage_recoveries": int(d_stats["stage_recoveries"]),
                    "recovered_map_tasks":
                        int(d_stats["recovered_map_tasks"]),
                    "stage_loop_tasks":
                        int(d_stats.get("stage_loop_tasks", 0)),
                    "stage_loop_fallbacks":
                        int(d_stats.get("stage_loop_fallbacks", 0)),
                    "site_stats": inj_stats,
                })
    finally:
        faults.clear()
        for k in knobs:
            config.conf.unset(k)

    rec = {
        "metric": "chaos_divergent_queries",
        "value": diverged,
        "unit": "queries",
        "seed": seed,
        "rules": rules,
        "scale": scale,
        "queries": queries,
        "total_faults_injected":
            sum(q["faults_injected"] for q in queries),
        "total_task_retries": sum(q["task_retries"] for q in queries),
        "total_stage_recoveries":
            sum(q["stage_recoveries"] for q in queries),
    }
    path = os.environ.get(
        "BLAZE_BENCH_CHAOS_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_CHAOS.json"))
    _write_bench(path, rec)
    print(json.dumps(rec))
    sys.stdout.flush()
    return 0 if diverged == 0 else 1


# ===========================================================================
# --workers: process-isolated worker-pool crash soak (ISSUE 11)
# ===========================================================================

def _pctl(vals, q: float):
    """Nearest-rank percentile of `vals` (q in [0, 1]); None when empty."""
    if not vals:
        return None
    import math
    s = sorted(vals)
    return s[max(0, min(len(s) - 1, int(math.ceil(q * len(s))) - 1))]


def _duration_mark():
    """Length markers into the xla_stats duration reservoirs, so a leg
    can slice out exactly its own task/wave samples afterwards."""
    from blaze_tpu.bridge import xla_stats
    d = xla_stats.duration_samples()
    return len(d["task_ns"]), len(d["wave_ns"])


def _durations_since(mark):
    from blaze_tpu.bridge import xla_stats
    d = xla_stats.duration_samples()
    return d["task_ns"][mark[0]:], d["wave_ns"][mark[1]:]


def _task_pctls_ms(task_ns) -> dict:
    return {"p50": round((_pctl(task_ns, 0.50) or 0) / 1e6, 3),
            "p99": round((_pctl(task_ns, 0.99) or 0) / 1e6, 3),
            "samples": len(task_ns)}


def workers_bench_main() -> int:
    """Worker-pool crash soak (`--workers`): route staged task execution
    through the process-isolated worker pool and kill it, repeatedly.
    Three legs, every result compared bit for bit against a fault-free
    in-process baseline:

      chaos      q01/q06/q95 with seeded SIGKILLs mid-map-task /
                 mid-shuffle-write (`worker-crash`), suppressed
                 heartbeats (`worker-hang`), and slow-but-alive workers
                 (`worker-slow`).  Crashes must cost retries on OTHER
                 workers and bounded recoveries — never wrong answers
                 or leaked spill files.
      blacklist  crash budget 0 plus one seeded kill: the crashed
                 worker must be observably blacklisted in pool health
                 while the query completes on the survivors.
      serve      concurrent QueryService run with one seeded worker
                 crash: the victim retries on another worker, every
                 admitted query completes correct, the service never
                 wedges.

    Writes BENCH_WORKERS.json and prints it as one JSON line."""
    import tempfile

    from blaze_tpu import config, faults
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.memory import MemManager
    from blaze_tpu.parallel import workers
    from blaze_tpu.plan.stages import DagScheduler
    from blaze_tpu.serving import QueryService

    seed = int(os.environ.get("BLAZE_BENCH_WORKERS_SEED", "1234"))
    names = os.environ.get("BLAZE_BENCH_WORKERS_QUERIES",
                           "q01,q06,q95").split(",")
    scale = float(os.environ.get("BLAZE_BENCH_WORKERS_SCALE", "0.2"))
    rules = os.environ.get(
        "BLAZE_BENCH_WORKERS_RULES",
        "worker-crash=0.25,worker-hang@3,worker-slow=0.2")

    MemManager.init(4 << 30)
    # staged wire path forced on (the pool only carries shuffle map
    # tasks), fast retries, and a liveness deadline short enough that a
    # seeded hang costs ~2s instead of the production default
    knobs = {config.DAG_SINGLE_TASK_BYTES.key: 0,
             config.TASK_RETRY_BACKOFF_MS.key: 5,
             config.TASK_MAX_ATTEMPTS.key: 6,
             config.STAGE_MAX_RECOVERIES.key: 8,
             config.WORKERS_COUNT.key: 2,
             config.WORKERS_HEARTBEAT_MS.key: 50,
             config.WORKERS_LIVENESS_MS.key: 1500,
             config.WORKERS_RESTART_BACKOFF_MS.key: 10,
             # the chaos leg kills workers far past the production
             # crash budget; it must keep recovering, not blacklist
             # the whole pool — blacklisting is leg 2's job
             config.WORKERS_CRASH_BUDGET.key: -1}
    for k, v in knobs.items():
        config.conf.set(k, v)

    def frame(tbl):
        import pandas as pd
        return tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
            {n: [] for n in tbl.schema.names})

    queries = []
    diverged = 0
    leaked = 0
    blacklist = {}
    serve = {}
    try:
        with tempfile.TemporaryDirectory(prefix="workers-") as d:
            # corpus + fault-free IN-PROCESS baselines: chaos legs must
            # match the thread path bit for bit, which also proves plain
            # cross-process determinism before any fault fires
            plans, bases, base_walls = [], [], []
            config.conf.set(config.WORKERS_ENABLE.key, "off")
            for qname in names:
                qname = qname.strip()
                builder, table_names = QUERIES[qname]
                tables = generate(table_names, scale=scale)
                paths = write_parquet_splits(
                    tables, os.path.join(d, qname), 2)
                plan_dict, _oracle = builder(paths, tables, 2)
                plans.append((qname, plan_dict))
                t0 = time.perf_counter()
                bases.append(frame(DagScheduler(
                    work_dir=os.path.join(d, qname, "base"))
                    .run_collect(plan_dict)))
                base_walls.append(time.perf_counter() - t0)
            config.conf.set(config.WORKERS_ENABLE.key, "on")

            # --- leg 1: per-query crash/hang/slow chaos through the pool
            for (qname, plan_dict), base, bwall in zip(plans, bases,
                                                       base_walls):
                faults.configure(rules, seed=seed)
                before = xla_stats.snapshot()
                dmark = _duration_mark()
                sched = DagScheduler(
                    work_dir=os.path.join(d, qname, "chaos"))
                t0 = time.perf_counter()
                try:
                    got = sched.run_collect(plan_dict)
                finally:
                    inj_stats = faults.stats()
                    faults.clear()
                wall = time.perf_counter() - t0
                ds = xla_stats.delta(before)
                leaks = sched.leak_report()
                n_leaked = sum(len(v) for v in leaks.values())
                leaked += n_leaked
                err = compare_frames(frame(got), base)
                if err is not None:
                    diverged += 1
                queries.append({
                    "query": qname,
                    "base_wall_s": round(bwall, 4),
                    "chaos_wall_s": round(wall, 4),
                    "divergence": err,
                    "worker_tasks": int(ds["worker_tasks"]),
                    "worker_crashes": int(ds["worker_crashes"]),
                    "worker_hangs": int(ds["worker_hangs"]),
                    "worker_restarts": int(ds["worker_restarts"]),
                    "worker_cancels": int(ds["worker_cancels"]),
                    "task_retries": int(ds["task_retries"]),
                    "fetch_failures": int(ds["fetch_failures"]),
                    "stage_recoveries": int(ds["stage_recoveries"]),
                    "recovered_map_tasks":
                        int(ds["recovered_map_tasks"]),
                    "task_duration_ms":
                        _task_pctls_ms(_durations_since(dmark)[0]),
                    "leaked": n_leaked,
                    "site_stats": inj_stats,
                })

            # --- leg 2: blacklist observability.  Budget 0 = first
            # crash blacklists; the retry must land on the survivor and
            # the dead slot must show up in pool health.
            workers.shutdown_pool(wait=False)
            config.conf.set(config.WORKERS_CRASH_BUDGET.key, 0)
            faults.configure("worker-crash@1", seed=seed)
            before = xla_stats.snapshot()
            dmark = _duration_mark()
            sched = DagScheduler(work_dir=os.path.join(d, "blacklist"))
            try:
                got = sched.run_collect(plans[0][1])
            finally:
                faults.clear()
            ds = xla_stats.delta(before)
            health = workers.pool_health()
            black = [s["worker"] for s in health.get("slots", [])
                     if s["state"] == "blacklisted"]
            err = compare_frames(frame(got), bases[0])
            if err is not None:
                diverged += 1
            leaks = sched.leak_report()
            leaked += sum(len(v) for v in leaks.values())
            blacklist = {
                "query": plans[0][0],
                "rules": "worker-crash@1",
                "crash_budget": 0,
                "divergence": err,
                "worker_crashes": int(ds["worker_crashes"]),
                "worker_blacklisted": int(ds["worker_blacklisted"]),
                "blacklisted_workers": black,
                "task_duration_ms":
                    _task_pctls_ms(_durations_since(dmark)[0]),
                "health": health,
            }
            config.conf.set(config.WORKERS_CRASH_BUDGET.key, -1)

            # --- leg 3: concurrent serve with one seeded worker crash;
            # the victim retries on another worker, nobody else notices
            workers.shutdown_pool(wait=False)
            n_conc = int(os.environ.get("BLAZE_BENCH_WORKERS_SERVE",
                                        "8"))
            faults.configure("worker-crash@2", seed=seed)
            before = xla_stats.snapshot()
            dmark = _duration_mark()
            svc = QueryService(max_concurrent=n_conc,
                               max_queue=4 * n_conc,
                               tenant_max_inflight=4 * n_conc)
            sdiv = sleaks = failed = done = 0
            try:
                handles = [(svc.submit(plans[i % len(plans)][1],
                                       tenant=f"t{i % 4}",
                                       deadline_ms=0.0),
                            i % len(plans))
                           for i in range(n_conc)]
                for h, j in handles:
                    h.exception(timeout=600)
                    if h.status == "done":
                        done += 1
                        if compare_frames(frame(h.result()),
                                          bases[j]) is not None:
                            sdiv += 1
                    else:
                        failed += 1
                    if h.leak_report is not None and any(
                            h.leak_report.values()):
                        sleaks += 1
            finally:
                faults.clear()
                svc.shutdown(wait=True, cancel_running=True)
            ds = xla_stats.delta(before)
            diverged += sdiv
            leaked += sleaks
            serve = {
                "concurrency": n_conc,
                "submitted": n_conc,
                "completed": done,
                "failed": failed,
                "divergent": sdiv,
                "leaked": sleaks,
                "worker_crashes": int(ds["worker_crashes"]),
                "worker_restarts": int(ds["worker_restarts"]),
                "task_retries": int(ds["task_retries"]),
                "task_duration_ms":
                    _task_pctls_ms(_durations_since(dmark)[0]),
            }
    finally:
        faults.clear()
        workers.shutdown_pool(wait=False)
        config.conf.unset(config.WORKERS_ENABLE.key)
        config.conf.unset(config.WORKERS_CRASH_BUDGET.key)
        for k in knobs:
            config.conf.unset(k)

    total_crashes = (sum(q["worker_crashes"] for q in queries)
                     + blacklist.get("worker_crashes", 0)
                     + serve.get("worker_crashes", 0))
    rec = {
        "metric": "workers_divergent_queries",
        "value": diverged,
        "unit": "queries",
        "seed": seed,
        "rules": rules,
        "scale": scale,
        "queries": queries,
        "blacklist": blacklist,
        "serve": serve,
        "leaked": leaked,
        "total_worker_crashes": total_crashes,
        "total_worker_tasks": sum(q["worker_tasks"] for q in queries),
        "total_task_retries": sum(q["task_retries"] for q in queries),
        "total_stage_recoveries":
            sum(q["stage_recoveries"] for q in queries),
    }
    path = os.environ.get(
        "BLAZE_BENCH_WORKERS_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_WORKERS.json"))
    _write_bench(path, rec)
    print(json.dumps(rec))
    sys.stdout.flush()
    ok = (diverged == 0 and leaked == 0 and total_crashes >= 1
          and len(blacklist.get("blacklisted_workers", [])) >= 1
          and serve.get("failed", 1) == 0
          and serve.get("completed", 0) == serve.get("submitted", -1))
    return 0 if ok else 1


# ===========================================================================
# --speculate: quantile-driven straggler hedging soak (ISSUE 12)
# ===========================================================================

def speculate_bench_main() -> int:
    """Speculation soak (`--speculate`): prove quantile-driven straggler
    hedging wins back tail latency without ever double-counting output.
    Legs, every result compared bit for bit against a fault-free
    in-process baseline:

      off   q01/q06/q95 through the worker pool under `worker-slow`
            chaos (a firing task stalls FAULTS_WORKER_SLOW_MS while
            alive), speculation DISABLED: stragglers run to completion
            and dominate the wave wall.
      on    identical seed/rules with speculation ENABLED: once the
            quantile share of a wave finishes, a straggler gets a
            duplicate attempt on a different worker; first commit wins.
            p99 wave wall must come in BELOW the off leg, with zero
            divergent queries and zero duplicate output blocks.
      race  `speculation-loser-commit-race=1.0` forces a winning
            attempt to SKIP cancelling its loser, so both race the
            commit on all three tiers — file (claim + one os.replace of
            the index), RSS with hardlinks, RSS claim-file fallback —
            and the late loser must be rejected on every one.

    Writes BENCH_SPECULATE.json and prints it as one JSON line."""
    import tempfile
    import threading

    from blaze_tpu import config, faults
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.memory import MemManager
    from blaze_tpu.parallel import workers
    from blaze_tpu.plan.stages import DagScheduler

    seed = int(os.environ.get("BLAZE_BENCH_SPECULATE_SEED", "1234"))
    names = os.environ.get("BLAZE_BENCH_SPECULATE_QUERIES",
                           "q01,q06,q95").split(",")
    scale = float(os.environ.get("BLAZE_BENCH_SPECULATE_SCALE", "0.2"))
    rules = os.environ.get("BLAZE_BENCH_SPECULATE_RULES",
                           "worker-slow=0.2")
    reps = int(os.environ.get("BLAZE_BENCH_SPECULATE_REPS", "3"))

    MemManager.init(4 << 30)
    # staged wire path on; CONCURRENT host dispatch (4 slot-waiter
    # threads) — with the default serial host dispatch a slow first
    # task blocks its siblings, the quantile trigger never arms, and
    # there is nothing to hedge.  4 pool workers leave spare capacity
    # for duplicates even when a sibling stage holds slots (q01 runs
    # two producer stages concurrently).  The slow fault's stall is
    # raised to 1500ms so a hedged duplicate has real wall time to win
    # back.  Quantile 0.25 arms the trigger off a wave's single fastest
    # task (waves are 4 wide; a wave with 3 stragglers must still arm),
    # while min runtime 400ms keeps the cutoff above the per-worker
    # per-stage XLA compile (~150-350ms a duplicate pays when it lands
    # on a worker that hasn't seen that stage's kernel) so only genuine
    # stalls hedge — a low cutoff duplicates healthy tasks and the
    # wasted dispatches eat the slots a real straggler's re-hedge needs.
    knobs = {config.DAG_SINGLE_TASK_BYTES.key: 0,
             config.TASK_RETRY_BACKOFF_MS.key: 5,
             config.TASK_MAX_ATTEMPTS.key: 6,
             config.STAGE_MAX_RECOVERIES.key: 8,
             config.HOST_TASK_PARALLELISM.key: 4,
             # executor sizing is cores-derived and collapses to 1 slot
             # on small CI hosts, which would serialize the stalls and
             # starve the trigger; pool tasks just wait on a child, so
             # 4 waiter threads are cheap regardless of cores
             config.TOKIO_WORKER_THREADS_PER_CPU.key: 8,
             # two more workers than the wave is wide: hedges need idle
             # slots at the exact moment the primaries are stalled
             config.WORKERS_COUNT.key: 6,
             config.WORKERS_HEARTBEAT_MS.key: 25,
             config.WORKERS_LIVENESS_MS.key: 2500,
             config.WORKERS_RESTART_BACKOFF_MS.key: 10,
             config.WORKERS_CRASH_BUDGET.key: -1,
             config.FAULTS_WORKER_SLOW_MS.key: 1500,
             config.SPECULATION_QUANTILE.key: 0.25,
             config.SPECULATION_MULTIPLIER.key: 2.0,
             config.SPECULATION_MIN_MS.key: 400}
    for k, v in knobs.items():
        config.conf.set(k, v)

    def frame(tbl):
        import pandas as pd
        return tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
            {n: [] for n in tbl.schema.names})

    diverged = 0
    leaked = 0
    legs: dict = {}
    race: dict = {}
    try:
        with tempfile.TemporaryDirectory(prefix="speculate-") as d:
            # corpus + fault-free in-process baselines
            plans, bases = [], []
            config.conf.set(config.WORKERS_ENABLE.key, "off")
            config.conf.set(config.SPECULATION_ENABLE.key, "off")
            for qname in names:
                qname = qname.strip()
                builder, table_names = QUERIES[qname]
                tables = generate(table_names, scale=scale)
                paths = write_parquet_splits(
                    tables, os.path.join(d, qname), 4)
                plan_dict, _oracle = builder(paths, tables, 4)
                plans.append((qname, plan_dict))
                bases.append(frame(DagScheduler(
                    work_dir=os.path.join(d, qname, "base"))
                    .run_collect(plan_dict)))

            # --- off/on legs: identical seeds and chaos, speculation
            # toggled — the wave-wall tail is the thing under test
            config.conf.set(config.WORKERS_ENABLE.key, "on")
            for leg in ("off", "on"):
                workers.shutdown_pool(wait=False)
                config.conf.set(config.SPECULATION_ENABLE.key, leg)
                # warm the fresh pool's workers fault-free first: the
                # first task in each child pays backend init + compile
                # (~seconds), and that cold-start wave would drown the
                # 400ms straggler signal the legs are comparing.  Two
                # concurrent rounds per query keep every pool slot busy
                # at once so ALL workers warm, not just the first four —
                # a hedge landing on a cold worker would pay the init
                # cost mid-measurement
                for (qname, plan_dict), base in zip(plans, bases):
                    rounds = []
                    for w in range(2):
                        sched = DagScheduler(work_dir=os.path.join(
                            d, qname, f"warm-{leg}-{w}"))
                        rounds.append(threading.Thread(
                            target=sched.run_collect, args=(plan_dict,)))
                    for t in rounds:
                        t.start()
                    for t in rounds:
                        t.join()
                before = xla_stats.snapshot()
                dmark = _duration_mark()
                wall_s = 0.0
                leg_div = 0
                for rep in range(reps):
                    for (qname, plan_dict), base in zip(plans, bases):
                        faults.configure(rules, seed=seed + rep)
                        sched = DagScheduler(work_dir=os.path.join(
                            d, qname, f"{leg}{rep}"))
                        t0 = time.perf_counter()
                        try:
                            got = sched.run_collect(plan_dict)
                        finally:
                            faults.clear()
                        wall_s += time.perf_counter() - t0
                        if compare_frames(frame(got), base) is not None:
                            leg_div += 1
                        leaks = sched.leak_report()
                        leaked += sum(len(v) for v in leaks.values())
                ds = xla_stats.delta(before)
                task_ns, wave_ns = _durations_since(dmark)
                diverged += leg_div
                legs[leg] = {
                    "queries": [q for q, _ in plans],
                    "reps": reps,
                    "wall_s": round(wall_s, 4),
                    "divergent": leg_div,
                    "wave_wall_ms": {
                        "p50": round((_pctl(wave_ns, 0.50) or 0) / 1e6, 3),
                        "p99": round((_pctl(wave_ns, 0.99) or 0) / 1e6, 3),
                        "samples": len(wave_ns)},
                    "task_duration_ms": _task_pctls_ms(task_ns),
                    "worker_tasks": int(ds["worker_tasks"]),
                    "task_retries": int(ds["task_retries"]),
                    "speculation_waves": int(ds["speculation_waves"]),
                    "speculation_attempts":
                        int(ds["speculation_attempts"]),
                    "speculation_wins": int(ds["speculation_wins"]),
                    "speculation_losers_cancelled":
                        int(ds["speculation_losers_cancelled"]),
                    "speculation_duplicate_commits":
                        int(ds["speculation_duplicate_commits"]),
                }

            # --- race leg: force the winner to skip cancelling its
            # loser, so BOTH attempts reach the commit point on every
            # tier; the commit arbitration must reject the late one
            workers.shutdown_pool(wait=False)
            config.conf.set(config.WORKERS_ENABLE.key, "off")
            config.conf.set(config.SPECULATION_ENABLE.key, "on")
            config.conf.set(config.SPECULATION_MULTIPLIER.key, 1.0)
            config.conf.set(config.SPECULATION_MIN_MS.key, 20)

            # (a) file tier, through the LIVE wave loop: the straggler's
            # primary attempt stalls long enough for the duplicate to
            # promote first, then promotes its own attempt-suffixed
            # output — and must lose the claim
            from blaze_tpu.bridge.tasks import run_tasks
            from blaze_tpu.shuffle.writer import promote_attempt_output, \
                resolve_attempt_data
            fbase = os.path.join(d, "race-file-0-0")
            outcomes: dict = {}
            olock = threading.Lock()

            def race_fn(i: int):
                if i != 3:
                    time.sleep(0.02)
                    return i
                with olock:
                    att = outcomes.setdefault("calls", 0)
                    outcomes["calls"] = att + 1
                if att == 0:
                    time.sleep(0.7)  # primary straggles past the dup
                data = f"{fbase}.a{att}.data"
                index = f"{fbase}.a{att}.index"
                with open(data, "wb") as f:
                    f.write(b"payload-a%d" % att)
                with open(index, "wb") as f:
                    f.write(b"index-a%d" % att)
                won = promote_attempt_output(data, index)
                with olock:
                    outcomes[att] = won
                return i

            before = xla_stats.snapshot()
            faults.configure("speculation-loser-commit-race=1.0",
                             seed=seed)
            try:
                run_tasks(race_fn, 4, 30.0, "speculate race leg",
                          max_workers=4)
                # the un-cancelled loser finishes on its own clock
                t_end = time.monotonic() + 10
                while 0 not in outcomes and time.monotonic() < t_end:
                    time.sleep(0.02)
            finally:
                faults.clear()
            ds_race = xla_stats.delta(before)
            _winner_data, winner_attempt = resolve_attempt_data(
                f"{fbase}.data")
            file_ok = (outcomes.get(1) is True
                       and outcomes.get(0) is False
                       and winner_attempt == 1
                       and not os.path.exists(f"{fbase}.a0.data")
                       and not os.path.exists(f"{fbase}.a0.index"))

            # (b)+(c) RSS tier: two attempts of the same map race
            # mapper_end; first commit wins on BOTH storage flavors
            from blaze_tpu.shuffle.rss import RssPushClient

            def rss_race(tag: str, use_hardlinks: bool) -> bool:
                client = RssPushClient(os.path.join(d, f"race-{tag}"),
                                       "race", 1, 1,
                                       use_hardlinks=use_hardlinks)
                try:
                    w0 = client.partition_writer(0, attempt=0)
                    w0(0, b"attempt0-frame")
                    w1 = client.partition_writer(0, attempt=1)
                    w1(0, b"attempt1-frame")
                    first = w0.commit()
                    second = w1.commit()
                    blocks = client.reader_blocks(0, timeout_s=2.0)
                    return (first is True and second is False
                            and blocks == [b"attempt0-frame"])
                finally:
                    client.cleanup()

            rss_link_ok = rss_race("hardlink", use_hardlinks=True)
            rss_claim_ok = rss_race("claim", use_hardlinks=False)
            race = {
                "rules": "speculation-loser-commit-race=1.0",
                "file_tier_loser_rejected": file_ok,
                "rss_hardlink_loser_rejected": rss_link_ok,
                "rss_claim_loser_rejected": rss_claim_ok,
                "commit_races_forced":
                    int(ds_race["speculation_commit_races"]),
                "loser_commits_rejected":
                    int(ds_race["speculation_loser_commits_rejected"]),
                "duplicate_commits":
                    int(ds_race["speculation_duplicate_commits"]),
            }
    finally:
        faults.clear()
        workers.shutdown_pool(wait=False)
        config.conf.unset(config.WORKERS_ENABLE.key)
        config.conf.unset(config.SPECULATION_ENABLE.key)
        for k in knobs:
            config.conf.unset(k)

    p99_off = legs.get("off", {}).get("wave_wall_ms", {}).get("p99") or 0
    p99_on = legs.get("on", {}).get("wave_wall_ms", {}).get("p99") or 0
    dup_blocks = (legs.get("on", {})
                  .get("speculation_duplicate_commits", 0)
                  + race.get("duplicate_commits", 0))
    reduction = (1.0 - p99_on / p99_off) if p99_off else 0.0
    rec = {
        "metric": "speculation_p99_wave_wall_reduction",
        "value": round(reduction, 4),
        "unit": "fraction",
        "seed": seed,
        "rules": rules,
        "scale": scale,
        "p99_wave_wall_ms_off": p99_off,
        "p99_wave_wall_ms_on": p99_on,
        "divergent_queries": diverged,
        "duplicate_output_blocks": dup_blocks,
        "leaked": leaked,
        "legs": legs,
        "race": race,
    }
    path = os.environ.get(
        "BLAZE_BENCH_SPECULATE_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_SPECULATE.json"))
    _write_bench(path, rec)
    print(json.dumps(rec))
    sys.stdout.flush()
    ok = (diverged == 0 and leaked == 0 and dup_blocks == 0
          and p99_off > 0 and p99_on < p99_off
          and legs.get("on", {}).get("speculation_wins", 0) >= 1
          and race.get("file_tier_loser_rejected") is True
          and race.get("rss_hardlink_loser_rejected") is True
          and race.get("rss_claim_loser_rejected") is True
          and race.get("commit_races_forced", 0) >= 1)
    return 0 if ok else 1


# ===========================================================================
# --deviceloop: device-resident stage loop vs staged per-batch (ISSUE 8)
# ===========================================================================

def deviceloop_bench_main() -> int:
    """Device-loop leg (`--deviceloop`): the same staged two-stage
    rollup (partial hash-agg -> hash exchange -> final agg) run twice —
    stage loop OFF (the per-batch staged executor) and ON (runtime/
    loop.py folds chunks of batches in ONE jit'd program per dispatch)
    — plus the itest q01/q06/q95 subset with the loop forced on vs off.

    Asserts and records:
      * bit-identical finals between the legs (the loop inherits the
        staged grow schedule exactly; q01/q06 are loop-INELIGIBLE —
        string keys / no group key — and must come back identical via
        the wholesale fallback);
      * the dispatch tax: total jit dispatches per map partition drop
        from O(batches x operators) to O(chunk boundaries);
      * loop wall vs staged wall on the synthetic rollup.

    The host-vectorized Arrow lane is disabled for BOTH legs so the
    staged twin uses the same jax hash lane the loop compiles — the
    bit-identity claim is then exact, not approximate.  Writes
    BENCH_DEVLOOP.json and prints it as one JSON line."""
    import shutil
    import tempfile

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu import config
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.memory import MemManager
    from blaze_tpu.plan.stages import DagScheduler

    MemManager.init(4 << 30)
    n_rows = int(os.environ.get("BLAZE_BENCH_DEVLOOP_ROWS", 400_000))
    n_groups = int(os.environ.get("BLAZE_BENCH_DEVLOOP_GROUPS", 4096))
    n_maps, n_reduces = 2, 3
    iters = int(os.environ.get("BLAZE_BENCH_DEVLOOP_ITERS", 3))
    knobs = {config.DAG_SINGLE_TASK_BYTES.key: 0,
             config.FUSED_HOST_VECTORIZED_ENABLE.key: False,
             # many batches per map task so the chunk fold has dispatch
             # tax to amortize
             config.BATCH_SIZE.key: 8192}
    for k, v in knobs.items():
        config.conf.set(k, v)

    root = tempfile.mkdtemp(prefix="devloop-")
    try:
        rng = np.random.default_rng(11)
        # wide int64 key domain: the dense lane declines (no compact
        # range), so the partial agg takes the hash lane the stage
        # compiler admits
        keys = (rng.integers(0, n_groups, n_rows) * 1000003 + 17
                ).astype(np.int64)
        vals = rng.integers(0, 10_000, n_rows).astype(np.int64)
        t = pa.table({"k": pa.array(keys), "v": pa.array(vals)})
        paths = []
        per = n_rows // n_maps
        for i in range(n_maps):
            p = os.path.join(root, f"in-{i}.parquet")
            pq.write_table(t.slice(i * per, per), p)
            paths.append(p)
        schema = {"fields": [
            {"name": "k", "type": {"id": "int64"}, "nullable": True},
            {"name": "v", "type": {"id": "int64"}, "nullable": True}]}
        plan = {
            "kind": "hash_agg",
            "groupings": [{"expr": {"kind": "column", "index": 0},
                           "name": "k"}],
            "aggs": [{"fn": "sum", "mode": "final", "name": "s",
                      "args": [{"kind": "column", "index": 1}]}],
            "input": {
                "kind": "local_exchange",
                "partitioning": {"kind": "hash",
                                 "exprs": [{"kind": "column",
                                            "index": 0}],
                                 "num_partitions": n_reduces},
                "input": {
                    "kind": "hash_agg",
                    "groupings": [{"expr": {"kind": "column",
                                            "name": "k"}, "name": "k"}],
                    "aggs": [{"fn": "sum", "mode": "partial",
                              "name": "s",
                              "args": [{"kind": "column",
                                        "name": "v"}]}],
                    "input": {"kind": "parquet_scan", "schema": schema,
                              "file_groups": [[p] for p in paths]}}}}

        def one_run(tag):
            d = os.path.join(root, tag)
            try:
                return DagScheduler(work_dir=d).run_collect(plan)
            finally:
                shutil.rmtree(d, ignore_errors=True)

        def leg(mode):
            config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key, mode)
            try:
                one_run(f"warm-{mode}")  # compile outside the clock
                walls = []
                before = xla_stats.snapshot()
                for it in range(iters):
                    t0 = time.perf_counter()
                    tbl = one_run(f"{mode}-{it}")
                    walls.append(time.perf_counter() - t0)
                d = xla_stats.delta(before)
                return tbl, float(np.min(walls)), d
            finally:
                config.conf.unset(config.STAGE_DEVICE_LOOP_ENABLE.key)

        staged_tbl, staged_wall, staged_d = leg("off")
        loop_tbl, loop_wall, loop_d = leg("on")

        def sorted_rows(tbl):
            df = tbl.to_pandas().sort_values("k").reset_index(drop=True)
            return list(map(tuple, df.itertuples(index=False)))

        # int64 sums: bit-identical is exact equality, no tolerance
        identical = sorted_rows(staged_tbl) == sorted_rows(loop_tbl)

        part_runs = iters * n_maps  # timed map-partition executions/leg
        staged_dispatches = int(staged_d["total_calls"])
        loop_dispatches = int(loop_d["total_calls"])
        rec = {
            "metric": "deviceloop_dispatches_per_partition",
            "value": round(loop_d["stage_loop_calls"]
                           / max(1, loop_d["stage_loop_tasks"]), 2),
            "unit": "program dispatches/partition",
            "rows": n_rows, "groups": n_groups,
            "maps": n_maps, "reduces": n_reduces,
            "bit_identical": identical,
            "staged_wall_s": round(staged_wall, 4),
            "loop_wall_s": round(loop_wall, 4),
            "loop_speedup": round(staged_wall / loop_wall, 3),
            # whole-leg jit dispatch counts (every metered kernel):
            # the tax the loop exists to kill
            "staged_total_dispatches": staged_dispatches,
            "loop_total_dispatches": loop_dispatches,
            "staged_dispatches_per_partition":
                round(staged_dispatches / part_runs, 1),
            "loop_dispatches_per_partition":
                round(loop_dispatches / part_runs, 1),
            "loop_tasks": int(loop_d["stage_loop_tasks"]),
            "loop_program_calls": int(loop_d["stage_loop_calls"]),
            "loop_batches_folded": int(loop_d["stage_loop_batches"]),
            "loop_dispatches_avoided":
                int(loop_d["stage_loop_staged_dispatches_avoided"]),
            "loop_fallbacks": int(loop_d["stage_loop_fallbacks"]),
            "loop_programs_built":
                int(loop_d["stage_loop_programs_built"]),
            "loop_program_cache_hits":
                int(loop_d["stage_loop_program_cache_hits"]),
        }

        # ---- itest subset: loop on vs off must be frame-identical ----
        names = os.environ.get("BLAZE_BENCH_DEVLOOP_QUERIES",
                               "q01,q06,q95").split(",")
        scale = float(os.environ.get("BLAZE_BENCH_DEVLOOP_SCALE", "0.2"))

        def frame(tbl):
            import pandas as pd
            return tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
                {n: [] for n in tbl.schema.names})

        divergent = 0
        qrecs = []
        for qname in names:
            qname = qname.strip()
            builder, table_names = QUERIES[qname]
            tables = generate(table_names, scale=scale)
            with tempfile.TemporaryDirectory(prefix="devloop-q-") as d:
                qpaths = write_parquet_splits(tables, d, 2)
                plan_dict, _oracle = builder(qpaths, tables, 2)
                config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key,
                                "off")
                base = DagScheduler(
                    work_dir=os.path.join(d, "dag0")).run_collect(
                        plan_dict)
                config.conf.set(config.STAGE_DEVICE_LOOP_ENABLE.key,
                                "on")
                before = xla_stats.snapshot()
                try:
                    got = DagScheduler(
                        work_dir=os.path.join(d, "dag1")).run_collect(
                            plan_dict)
                finally:
                    config.conf.unset(
                        config.STAGE_DEVICE_LOOP_ENABLE.key)
                d_stats = xla_stats.delta(before)
            err = compare_frames(frame(got), frame(base))
            if err is not None:
                divergent += 1
            qrecs.append({
                "query": qname, "divergence": err,
                "loop_tasks": int(d_stats.get("stage_loop_tasks", 0)),
                "loop_fallbacks":
                    int(d_stats.get("stage_loop_fallbacks", 0))})
        rec["queries"] = qrecs
        rec["divergent_queries"] = divergent
    finally:
        shutil.rmtree(root, ignore_errors=True)
        for k in knobs:
            config.conf.unset(k)

    path = os.environ.get(
        "BLAZE_BENCH_DEVLOOP_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_DEVLOOP.json"))
    _write_bench(path, rec)
    print(json.dumps(rec))
    sys.stdout.flush()
    ok = (rec["bit_identical"] and divergent == 0
          and rec["loop_tasks"] > 0)
    return 0 if ok else 1


# ===========================================================================
# --aggskip: adaptive partial-agg skipping microbenchmark (ISSUE 5)
# ===========================================================================

def aggskip_bench_main() -> int:
    """Partial-agg skipping microbenchmark (`--aggskip`).

    Two legs:

      1. High-NDV microbenchmark: a unique-ish int64 group key at two
         scales, partial stage timed with adaptive skipping ON (the
         ratio probe fires and the rest of the input streams through
         the pass-through lane) vs OFF (every batch lexsorted and
         compacted).  Values are INTEGERS so the skip/no-skip final
         results are byte-identical (float summation order differs
         between the two partial forms by design).

      2. Forced-skip itest leg: the chaos-bench query subset run
         through the staged DAG scheduler with ratio=0.0/minRows=1
         (every eligible partial agg switches immediately; pass-through
         batches interleave with the probe window's hashed batches on
         the shuffle wire) and compared frame-by-frame against the
         skip-disabled run.  divergent_queries MUST be 0.

    Writes BENCH_AGGSKIP.json and prints the record as one JSON line."""
    import tempfile

    import numpy as np
    import pandas as pd
    import pyarrow as pa

    from blaze_tpu import config
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.exprs import col
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.memory import MemManager
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.ops.agg import AggExec, AggMode, make_agg
    from blaze_tpu.plan.stages import DagScheduler

    MemManager.init(4 << 30)
    iters = int(os.environ.get("BLAZE_BENCH_AGGSKIP_ITERS", "5"))
    batch_rows = int(os.environ.get("BLAZE_BENCH_AGGSKIP_BATCH", "8192"))
    scales = [int(s) for s in os.environ.get(
        "BLAZE_BENCH_AGGSKIP_SCALES", "1,10").split(",")]
    base_rows = int(os.environ.get("BLAZE_BENCH_AGGSKIP_ROWS", "200000"))

    def make_table(n):
        rng = np.random.default_rng(42)
        # unique-ish key: drawn from a space 8x the row count, so the
        # probe window's reduction ratio is ~0.99 — far above the 0.9
        # default and representative of a mis-planned pre-aggregation
        return pa.table({
            "k": pa.array(rng.integers(0, n * 8, n)),
            "v": pa.array(rng.integers(-1000, 1000, n)),
        })

    def partial_stage(tbl, skip):
        scan = MemoryScanExec.from_arrow(tbl, batch_rows=batch_rows)
        plan = AggExec(scan, [(col(0, "k"), "k")],
                       [(make_agg("sum", [col(1, "v")]), AggMode.PARTIAL,
                         "s"),
                        (make_agg("count", [col(1, "v")]), AggMode.PARTIAL,
                         "c")])
        with config.scoped(**{
                config.PARTIAL_AGG_SKIPPING_ENABLE.key: skip}):
            t0 = time.perf_counter()
            out = plan.execute_collect().to_arrow()
            return time.perf_counter() - t0, out, plan

    def finalize(partial_tbl):
        scan = MemoryScanExec.from_arrow(partial_tbl)
        plan = AggExec(scan, [(col(0, "k"), "k")],
                       [(make_agg("sum", [col(1)]), AggMode.PARTIAL_MERGE,
                         "s"),
                        (make_agg("count", [col(2)]), AggMode.PARTIAL_MERGE,
                         "c")])
        out = plan.execute_collect().to_arrow()
        idx = pa.compute.sort_indices(out.column("k"))
        return out.take(idx)

    scale_recs = []
    for sf in scales:
        n = base_rows * sf
        tbl = make_table(n)
        # warm both paths (compiles the segmented-reduce and identity-gid
        # programs), then interleave timed runs, min-of-samples
        partial_stage(tbl, True)
        partial_stage(tbl, False)
        walls = {"skip": [], "noskip": []}
        last = {}
        for _ in range(iters):
            w, out_on, plan_on = partial_stage(tbl, True)
            walls["skip"].append(w)
            last["on"] = (out_on, plan_on)
            w, out_off, plan_off = partial_stage(tbl, False)
            walls["noskip"].append(w)
            last["off"] = (out_off, plan_off)
        out_on, plan_on = last["on"]
        out_off, plan_off = last["off"]
        fin_on = finalize(out_on)
        fin_off = finalize(out_off)
        identical = fin_on.equals(fin_off)  # byte-identical final merge
        skip_s = float(np.min(walls["skip"]))
        noskip_s = float(np.min(walls["noskip"]))
        scale_recs.append({
            "scale": sf,
            "rows": n,
            "groups": int(fin_on.num_rows),
            "skip_wall_s": round(skip_s, 4),
            "noskip_wall_s": round(noskip_s, 4),
            "speedup": round(noskip_s / skip_s, 3),
            "partial_skipped": int(plan_on.metrics.get("partial_skipped")),
            "passthrough_rows":
                int(plan_on.metrics.get("passthrough_rows")),
            "final_identical": bool(identical),
        })

    # --- forced-skip itest leg -------------------------------------------
    names = os.environ.get("BLAZE_BENCH_AGGSKIP_QUERIES",
                           "q01,q06,q95").split(",")
    itest_scale = float(os.environ.get("BLAZE_BENCH_AGGSKIP_SCALE", "0.2"))
    force = {config.PARTIAL_AGG_SKIPPING_ENABLE.key: True,
             config.PARTIAL_AGG_SKIPPING_RATIO.key: 0.0,
             config.PARTIAL_AGG_SKIPPING_MIN_ROWS.key: 1,
             config.DAG_SINGLE_TASK_BYTES.key: 0}

    def frame(tbl):
        return tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
            {c: [] for c in tbl.schema.names})

    queries = []
    diverged = 0
    for qname in names:
        qname = qname.strip()
        builder, table_names = QUERIES[qname]
        tables = generate(table_names, scale=itest_scale)
        with tempfile.TemporaryDirectory(prefix="aggskip-") as d:
            paths = write_parquet_splits(tables, d, 2)
            plan_dict, _oracle = builder(paths, tables, 2)
            config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
            try:
                config.conf.set(config.PARTIAL_AGG_SKIPPING_ENABLE.key,
                                False)
                t0 = time.perf_counter()
                base = DagScheduler(work_dir=os.path.join(d, "dag0")) \
                    .run_collect(plan_dict)
                base_wall = time.perf_counter() - t0
                for k, v in force.items():
                    config.conf.set(k, v)
                before = xla_stats.snapshot()
                t0 = time.perf_counter()
                got = DagScheduler(work_dir=os.path.join(d, "dag1")) \
                    .run_collect(plan_dict)
                skip_wall = time.perf_counter() - t0
                d_stats = xla_stats.delta(before)
            finally:
                for k in set(force) | {
                        config.PARTIAL_AGG_SKIPPING_ENABLE.key}:
                    config.conf.unset(k)
            err = compare_frames(frame(got), frame(base))
            if err is not None:
                diverged += 1
            queries.append({
                "query": qname,
                "base_wall_s": round(base_wall, 4),
                "forced_skip_wall_s": round(skip_wall, 4),
                "divergence": err,
                "skip_events": int(d_stats["partial_agg_skip_events"]),
                "skipped_rows": int(d_stats["partial_agg_skipped_rows"]),
            })

    rec = {
        "metric": "aggskip_divergent_queries",
        "value": diverged,
        "unit": "queries",
        "divergent_queries": diverged,
        "batch_rows": batch_rows,
        "iters": iters,
        "scales": scale_recs,
        "itest": {"scale": itest_scale, "queries": queries},
        "agg_stats": xla_stats.agg_stats(),
    }
    path = os.environ.get(
        "BLAZE_BENCH_AGGSKIP_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_AGGSKIP.json"))
    _write_bench(path, rec)
    print(json.dumps(rec))
    sys.stdout.flush()
    bad = (diverged or
           any(not s["final_identical"] or not s["partial_skipped"]
               for s in scale_recs))
    return 1 if bad else 0


# ===========================================================================
# --multichip: mesh-sharded map-stage scaling + device-shuffle legs (ISSUE 6)
# ===========================================================================

MULTICHIP_TIMEOUT_S = float(
    os.environ.get("BLAZE_BENCH_MULTICHIP_TIMEOUT", "900"))


def multichip_child_main() -> int:
    """One scaling leg (`--multichip-child N [--queries]`): build an
    N-device mesh and time the sharded map stage — partial agg +
    on-device hash partition + ICI all-to-all + final merge as ONE
    compiled XLA program (`distributed_grouped_agg`), the collective
    replacement for the host-file shuffle.  Total rows are FIXED across
    legs (strong scaling), so wall-clock should drop near-linearly with
    mesh size on a real multi-chip backend.

    With `--queries` (the widest leg) it also runs the itest trio
    q01/q06/q95 through the staged scheduler with the device shuffle on
    vs off (divergent_queries must be 0) and once more with a seeded
    shard-kill mid-collective (fallback to shuffle files, still 0
    divergence).  Prints ONE JSON line."""
    n_req = int(sys.argv[sys.argv.index("--multichip-child") + 1])
    # the supervisor states this child's platform in JAX_PLATFORMS
    # (multichip_bench_main); on cpu, n virtual host devices stand in
    # for the mesh and must be forced before jax import
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        import re
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d" % n_req
        ).strip()

    import jax
    import numpy as np
    import jax.numpy as jnp

    from blaze_tpu.parallel import distributed_grouped_agg, make_mesh
    from blaze_tpu.parallel.mesh import shard_rows

    n_use = min(n_req, len(jax.devices()))
    mesh = make_mesh(n_use)

    rows = int(os.environ.get("BLAZE_BENCH_MULTICHIP_ROWS", str(1 << 20)))
    rows -= rows % max(n_use, 1)  # NamedSharding needs even splits
    n_groups = 4096
    rng = np.random.default_rng(42)
    keys = rng.integers(0, n_groups, rows, dtype=np.int64)
    vals = rng.random(rows)
    ones = np.ones(rows, dtype=bool)

    step = distributed_grouped_agg(
        mesh, key_specs=1, agg_specs=["sum", "count"],
        num_slots=2 * n_groups, out_slots=2 * n_groups,
        merge_kinds=["sum", "count"])
    args = shard_rows(mesh, jnp.asarray(ones), jnp.asarray(keys),
                      jnp.asarray(ones), jnp.asarray(vals),
                      jnp.asarray(ones))

    out = step(*args)  # compile + warmup
    jax.block_until_ready(out.accs[0])
    assert int(np.asarray(out.slot_valid).sum()) == n_groups
    walls = []
    for _ in range(int(os.environ.get("BLAZE_BENCH_MULTICHIP_REPS", "20"))):
        t0 = time.perf_counter()
        out = step(*args)
        jax.block_until_ready(out.accs[0])
        walls.append(time.perf_counter() - t0)
    wall = min(walls)

    cores = os.cpu_count() or 1
    rec = {
        "n_devices_requested": n_req,
        "n_devices": n_use,
        "host_cpu_cores": cores,
        # virtual CPU devices past the physical core count timeshare one
        # host: scaling flattens for HARDWARE reasons, not engine ones —
        # flag the leg so the curve reader discounts it (refined below
        # from ACTUAL worker-process CPU accounting when the
        # process-per-device wave runs)
        "host_core_limited": (jax.default_backend() == "cpu"
                              and n_req > cores),
        # staged query execution in this leg runs through the
        # process-isolated worker pool (crash fault domains), not bare
        # threads; BLAZE_BENCH_MULTICHIP_WORKERS=0 opts out
        "worker_isolated": os.environ.get(
            "BLAZE_BENCH_MULTICHIP_WORKERS", "1") != "0",
        "platform": jax.default_backend(),
        "map_stage": {"rows": rows, "groups": n_groups,
                      "wall_s": round(wall, 6),
                      "rows_per_sec": int(rows / wall)},
    }
    if os.environ.get("BLAZE_BENCH_MULTICHIP_PROC", "1") != "0":
        # process-per-device harness: N pinned worker processes x 1
        # emulated device each, instead of N virtual devices
        # timesharing THIS process — the scaling curve free of
        # single-interpreter collective-sync overhead
        ps = _multichip_proc_stage(n_req)
        rec["proc_stage"] = ps
        if not ps.get("errors"):
            rec["host_core_limited"] = (
                jax.default_backend() == "cpu"
                and ps["cpu_parallelism"] < 0.75 * n_req)
    if os.environ.get("BLAZE_BENCH_MULTICHIP_LEDGER", "1") != "0":
        # per-leg device ledger: barrier_idle_s / dispatch_gap_s from a
        # traced device-shuffle run (bridge/history.device_ledger)
        rec["exchange_ledger"] = _multichip_exchange_probe(False)[0]
    if "--queries" in sys.argv:
        rec["itest"] = _multichip_queries(chaos=False)
        rec["chaos"] = _multichip_queries(chaos=True)
        rec["overlap"] = _multichip_overlap_probe()
    print(json.dumps(rec))
    sys.stdout.flush()
    return 0


def _multichip_proc_stage(n_req: int) -> dict:
    """Process-per-device scaling wave: a pinned WorkerPool
    (`auron.tpu.workers.pinDevices`) spawns `n_req` children, each
    seeing exactly ONE emulated device, and every child runs a
    fixed-size `_task_device_shard` workload concurrently (weak
    scaling: rows PER WORKER are constant, so the leg's aggregate
    throughput is the scaling signal — on real multi-device hardware
    it grows ~linearly, on a core-limited host it stays flat instead
    of regressing the way N virtual devices timesharing one
    interpreter did).  Wall is the min over timed waves (first wave
    warms jax import + compile per child); `cpu_parallelism` is the
    sum of child CPU seconds over wall — the honest host_core_limited
    signal (a 1-core host cannot exceed ~1.0 however many devices are
    requested)."""
    import threading as _threading

    from blaze_tpu import config
    from blaze_tpu.parallel.workers import WorkerPool

    rows = int(os.environ.get("BLAZE_BENCH_MULTICHIP_ROWS", str(1 << 20)))
    reps = int(os.environ.get("BLAZE_BENCH_MULTICHIP_REPS", "20"))
    waves = int(os.environ.get("BLAZE_BENCH_MULTICHIP_WAVES", "3"))
    shard = max(1, rows)  # per worker: weak scaling across legs
    config.conf.set(config.WORKERS_PIN_DEVICES.key, True)
    pool = None
    try:
        pool = WorkerPool(count=n_req, liveness_ms=60000).start()
        spec = "blaze_tpu.parallel.workers:_task_device_shard"
        results: list = [None] * n_req

        def wave():
            errs: list = []

            def one(i):
                try:
                    results[i] = pool.run(
                        {"fn": spec, "args": (shard, 4096, reps, 42 + i)},
                        timeout_s=MULTICHIP_TIMEOUT_S)
                except Exception as e:
                    errs.append(f"worker {i}: {e}")
            ts = [_threading.Thread(target=one, args=(i,))
                  for i in range(n_req)]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            return time.perf_counter() - t0, errs

        _warm, errs = wave()  # jax import + compile inside each child
        runs = []
        for _ in range(max(1, waves)):
            w, werrs = wave()
            errs += werrs
            runs.append((w, sum(float(r.get("cpu_s") or 0)
                                for r in results if r)))
        wall, cpu = min(runs)
        shards = [r for r in results if r]
        rec = {
            "workers": n_req, "rows": shard * n_req, "reps": reps,
            "wall_s": round(wall, 6),
            "rows_per_sec": int(shard * n_req * max(1, reps) / wall)
            if wall else 0,
            "cpu_s": round(cpu, 6),
            "cpu_parallelism": round(cpu / wall, 3) if wall else 0.0,
            "devices_per_worker": sorted({int(r.get("devices") or 0)
                                          for r in shards}),
            "pinned": [s.get("device_spec") for s in pool.health()],
        }
        if errs:
            rec["errors"] = errs[:3]
        return rec
    finally:
        if pool is not None:
            pool.shutdown(wait=False)
        config.conf.unset(config.WORKERS_PIN_DEVICES.key)


def _multichip_exchange_probe(overlap: bool, collect: bool = False):
    """One traced staged run with the device shuffle on: returns the
    device ledger's barrier/gap seconds plus the xla_stats
    shuffle_barrier_idle_ns / overlap-exchange deltas for this run (and
    the result Table when `collect`, for the sync-vs-overlap divergence
    check)."""
    import tempfile

    from blaze_tpu import config
    from blaze_tpu.bridge import tracing, xla_stats
    from blaze_tpu.bridge.history import device_ledger
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.memory import MemManager
    from blaze_tpu.plan.stages import DagScheduler

    qname = os.environ.get("BLAZE_BENCH_MULTICHIP_PROBE_QUERY", "q06")
    scale = float(os.environ.get("BLAZE_BENCH_MULTICHIP_PROBE_SCALE",
                                 "0.1"))
    MemManager.init(4 << 30)
    builder, table_names = QUERIES[qname]
    tables = generate(table_names, scale=scale)
    knobs = {config.DAG_SINGLE_TASK_BYTES.key: 0,
             config.SHUFFLE_DEVICE.key: "on",
             config.EXCHANGE_OVERLAP_ENABLE.key: overlap}
    with tempfile.TemporaryDirectory(prefix="mc-probe-") as d:
        paths = write_parquet_splits(tables, d, 2)
        plan_dict, _oracle = builder(paths, tables, 2)
        for k, v in knobs.items():
            config.conf.set(k, v)
        tracing.start_tracing()
        before = xla_stats.snapshot()
        try:
            t0 = time.perf_counter()
            got = DagScheduler(work_dir=os.path.join(d, "dag")) \
                .run_collect(plan_dict)
            wall = time.perf_counter() - t0
            ds = xla_stats.delta(before)
            spans = tracing.spans()
        finally:
            tracing.stop_tracing()
            for k in knobs:
                config.conf.unset(k)
        led = device_ledger(spans)
        rec = {"query": qname, "scale": scale, "overlap": bool(overlap),
               "wall_s": round(wall, 4),
               "barrier_idle_s": led["barrier_idle_s"],
               "dispatch_gap_s": led["dispatch_gap_s"],
               "device_busy_s": led["device_busy_s"],
               "barrier_idle_ns":
                   int(ds.get("shuffle_barrier_idle_ns", 0)),
               "overlap_exchanges":
                   int(ds.get("shuffle_device_overlap_exchanges", 0)),
               "device_exchanges":
                   int(ds.get("shuffle_device_exchanges", 0)),
               "fallbacks": int(ds.get("shuffle_device_fallbacks", 0))}
        return rec, (got if collect else None)


def _multichip_overlap_probe() -> dict:
    """Overlapped vs synchronous exchange on the SAME workload: the
    overlap leg must cut the barrier-idle counter (sync pays
    first-finisher-to-last-straggler wait before its one merged
    exchange; overlap pays only per-task dispatch-slot waits) by >= 30%
    and produce an identical result."""
    from blaze_tpu.itest.runner import compare_frames

    sync, base = _multichip_exchange_probe(False, collect=True)
    over, got = _multichip_exchange_probe(True, collect=True)
    err = compare_frames(got.to_pandas(), base.to_pandas())
    si, oi = sync["barrier_idle_ns"], over["barrier_idle_ns"]
    red = (1.0 - oi / si) if si else 0.0
    return {"sync": sync, "overlap": over, "divergence": err,
            "barrier_idle_reduction": round(red, 4)}


def _multichip_queries(chaos: bool) -> dict:
    """q01/q06/q95 through the staged DAG path: device shuffle ON vs
    the file-shuffle baseline, `compare_frames` as the divergence
    oracle.  chaos=True additionally kills one shard mid-collective
    (`device-collective@1`) so every eligible exchange exercises the
    file-shuffle fallback."""
    import tempfile

    from blaze_tpu import config, faults
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.memory import MemManager
    from blaze_tpu.plan.stages import DagScheduler

    names = os.environ.get("BLAZE_BENCH_MULTICHIP_QUERIES",
                           "q01,q06,q95").split(",")
    scale = float(os.environ.get("BLAZE_BENCH_MULTICHIP_SCALE", "0.2"))
    MemManager.init(4 << 30)
    knobs = {config.DAG_SINGLE_TASK_BYTES.key: 0,
             config.TASK_RETRY_BACKOFF_MS.key: 5}
    workers_on = os.environ.get(
        "BLAZE_BENCH_MULTICHIP_WORKERS", "1") != "0"
    if workers_on:
        # map tasks run process-isolated: a worker crash here must fall
        # back exactly like a shard-kill does (retry elsewhere), never
        # change the answer
        knobs.update({config.WORKERS_ENABLE.key: "on",
                      config.WORKERS_COUNT.key: 2,
                      config.WORKERS_RESTART_BACKOFF_MS.key: 10})
    for k, v in knobs.items():
        config.conf.set(k, v)

    def frame(tbl):
        import pandas as pd
        return tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
            {n: [] for n in tbl.schema.names})

    queries = []
    diverged = 0
    try:
        for qname in names:
            qname = qname.strip()
            builder, table_names = QUERIES[qname]
            tables = generate(table_names, scale=scale)
            with tempfile.TemporaryDirectory(prefix="multichip-") as d:
                paths = write_parquet_splits(tables, d, 2)
                plan_dict, _oracle = builder(paths, tables, 2)

                faults.clear()
                config.conf.set(config.SHUFFLE_DEVICE.key, "off")
                base = DagScheduler(work_dir=os.path.join(d, "dag0")) \
                    .run_collect(plan_dict)

                config.conf.set(config.SHUFFLE_DEVICE.key, "on")
                if chaos:
                    faults.configure("device-collective@1", seed=7)
                before = xla_stats.snapshot()
                try:
                    got = DagScheduler(work_dir=os.path.join(d, "dag1")) \
                        .run_collect(plan_dict)
                finally:
                    faults.clear()
                    config.conf.unset(config.SHUFFLE_DEVICE.key)
                ds = xla_stats.delta(before)

                err = compare_frames(frame(got), frame(base))
                if err is not None:
                    diverged += 1
                queries.append({
                    "query": qname,
                    "divergence": err,
                    "device_exchanges":
                        int(ds.get("shuffle_device_exchanges", 0)),
                    "device_rows": int(ds.get("shuffle_device_rows", 0)),
                    "fallbacks":
                        int(ds.get("shuffle_device_fallbacks", 0)),
                    "worker_tasks": int(ds.get("worker_tasks", 0)),
                })
    finally:
        faults.clear()
        config.conf.unset(config.SHUFFLE_DEVICE.key)
        for k in knobs:
            config.conf.unset(k)
        if workers_on:
            from blaze_tpu.parallel import workers as _workers
            _workers.shutdown_pool(wait=False)
    return {"queries": queries, "divergent_queries": diverged,
            "scale": scale, "worker_isolated": workers_on}


def multichip_bench_main() -> int:
    """Supervisor for `--multichip` (never imports jax): run one child
    per mesh width, merge the scaling curve + device-shuffle itest/chaos
    legs into BENCH_SF100.json, print the record as one JSON line."""
    legs_req = [int(x) for x in os.environ.get(
        "BLAZE_BENCH_MULTICHIP_DEVICES", "1,4,8").split(",")]
    widest = max(legs_req)
    legs = []
    errors = []
    # every child's platform is stated, never defaulted in the child:
    # JAX_PLATFORMS as given to this supervisor, which never opens a
    # chip itself, or the host platform when nothing was given
    platform = os.environ.get("JAX_PLATFORMS") or "cpu"
    print("multichip children run on JAX_PLATFORMS=%s" % platform,
          file=sys.stderr)
    for n in legs_req:
        args = [sys.executable, os.path.abspath(__file__),
                "--multichip-child", str(n)]
        if n == widest:
            args.append("--queries")
        rc, out, err, timed_out = _run_group(
            args, MULTICHIP_TIMEOUT_S,
            env={**os.environ, "JAX_PLATFORMS": platform})
        line = None
        for ln in reversed(out.splitlines()):
            if ln.startswith("{"):
                line = ln
                break
        if rc == 0 and line is not None:
            try:
                legs.append(json.loads(line))
                continue
            except json.JSONDecodeError:
                pass
        errors.append("leg n=%d: %s" % (
            n, "killed after %gs" % MULTICHIP_TIMEOUT_S if timed_out
            else (line or (err or out).strip()[-500:])))

    mc = {"metric": "multichip_map_stage_scaling", "unit": "x",
          "legs": []}
    base_wall = None
    base_proc = None
    for leg in legs:
        ms = leg["map_stage"]
        ps = leg.get("proc_stage") or {}
        if leg["n_devices"] == 1:
            base_wall = ms["wall_s"]
            if ps.get("rows_per_sec") and not ps.get("errors"):
                base_proc = ps["rows_per_sec"]
        entry = {"n_devices": leg["n_devices"],
                 "n_devices_requested": leg["n_devices_requested"],
                 "host_cpu_cores": leg.get("host_cpu_cores"),
                 "host_core_limited": leg.get("host_core_limited", False),
                 "worker_isolated": leg.get("worker_isolated", False),
                 "platform": leg["platform"], **ms}
        if ps:
            entry["proc_wall_s"] = ps.get("wall_s")
            entry["proc_rows_per_sec"] = ps.get("rows_per_sec")
            entry["cpu_parallelism"] = ps.get("cpu_parallelism")
            entry["proc_workers"] = ps.get("workers")
            if ps.get("errors"):
                entry["proc_errors"] = ps["errors"]
        if "exchange_ledger" in leg:
            # per-leg device ledger: the barrier the overlap work targets
            entry["barrier_idle_s"] = \
                leg["exchange_ledger"]["barrier_idle_s"]
            entry["dispatch_gap_s"] = \
                leg["exchange_ledger"]["dispatch_gap_s"]
            entry["barrier_idle_ns"] = \
                leg["exchange_ledger"]["barrier_idle_ns"]
        mc["legs"].append(entry)
        if "itest" in leg:
            mc["itest"] = leg["itest"]
        if "chaos" in leg:
            mc["chaos"] = leg["chaos"]
        if "overlap" in leg:
            mc["overlap"] = leg["overlap"]
    for entry in mc["legs"]:
        pr = entry.get("proc_rows_per_sec")
        if base_proc and pr and not entry.get("proc_errors"):
            # the process-per-device wave is the scaling curve: one
            # pinned child per device running a fixed per-device
            # workload, so speedup is the leg's aggregate throughput
            # over the 1-worker leg's — the 8-wide leg no longer pays
            # 8 virtual devices' collective sync inside ONE interpreter
            # (the old flat-to-regressing curve)
            entry["speedup_vs_1"] = round(pr / base_proc, 3)
            entry["speedup_basis"] = "process-per-device"
        else:
            entry["speedup_vs_1"] = (
                round(base_wall / entry["wall_s"], 3) if base_wall
                else None)
            entry["speedup_basis"] = "in-process-mesh"
    widest_entry = max(mc["legs"], key=lambda e: e["n_devices"],
                       default=None)
    mc["value"] = (widest_entry or {}).get("speedup_vs_1") or 0
    # monotone over the multi-device tail (8 >= 4): the 1-device leg is
    # 1.0 by construction and a 1-core host legitimately sits below it.
    # A small relative noise floor (same posture as the sentinel's
    # threshold) keeps wave jitter on a flat curve from flapping the ok
    # bit; a real regression like the old 0.777@8 is far outside it.
    tol = float(os.environ.get("BLAZE_BENCH_MULTICHIP_MONO_TOL", "0.03"))
    tail = sorted((e["n_devices"], e.get("speedup_vs_1") or 0)
                  for e in mc["legs"] if e["n_devices"] > 1)
    mc["monotone"] = all(b[1] >= a[1] * (1.0 - tol)
                         for a, b in zip(tail, tail[1:]))
    it = mc.get("itest", {}).get("divergent_queries")
    ch = mc.get("chaos", {}).get("divergent_queries")
    mc["divergent_queries"] = (
        it + ch if it is not None and ch is not None else -1)
    ov = mc.get("overlap")
    if ov is not None and ov.get("divergence") is not None:
        mc["divergent_queries"] = (mc["divergent_queries"] or 0) + 1
    if errors:
        mc["errors"] = errors

    path = os.environ.get(
        "BLAZE_BENCH_SF100_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_SF100.json"))
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, json.JSONDecodeError):
        rec = {}
    rec["multichip"] = mc
    if widest_entry:
        rec["n_devices"] = max(int(rec.get("n_devices", 1) or 1),
                               widest_entry["n_devices"])
    _write_bench(path, rec)
    print(json.dumps(mc))
    sys.stdout.flush()
    ov = mc.get("overlap")
    overlap_ok = (ov is None or
                  (ov.get("divergence") is None and
                   ov.get("barrier_idle_reduction", 0) >= 0.30))
    ok = (not errors and mc["divergent_queries"] == 0 and
          len(mc["legs"]) == len(legs_req) and mc["monotone"] and
          overlap_ok)
    return 0 if ok else 1


# ===========================================================================
# --serve: concurrent query-service soak + latency profile (ISSUE 7)
# ===========================================================================

def serve_bench_main() -> int:
    """Serving soak (`--serve`): replay the itest corpus through the
    admission-controlled QueryService at increasing concurrency
    (default 8..64), with seeded chaos (task faults, admission sheds,
    cancel races), a slice of tight deadlines, and a slice of explicit
    mid-flight cancels.  Acceptance: ZERO divergent surviving queries
    (every completed result bit-identical to its fault-free solo run)
    and ZERO leaks (scheduler leak reports empty, no registered
    MemConsumers, no service threads left).  Records p50/p99 wall
    latency plus shed/cancel counts per level into BENCH_SERVE.json.

    A second, chaos-free "dashboard" leg (ISSUE 15) replays a
    zipf-skewed repeat-heavy mix with the work-sharing rings on
    (result/subplan cache, single-flight, scan share) and records
    per-level hit/coalesce/share counters next to qps/p50/p99; every
    completed result must be Table.equals-identical to its solo run."""
    import tempfile
    import threading as _threading

    import numpy as _np

    from blaze_tpu import config, faults
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.memory import MemManager
    from blaze_tpu.plan.stages import DagScheduler
    from blaze_tpu.serving import QueryRejected, QueryService
    from blaze_tpu.serving.service import _percentile

    seed = int(os.environ.get("BLAZE_BENCH_SERVE_SEED", "1234"))
    names = os.environ.get("BLAZE_BENCH_SERVE_QUERIES",
                           "q01,q06,q95").split(",")
    scale = float(os.environ.get("BLAZE_BENCH_SERVE_SCALE", "0.2"))
    levels = [int(x) for x in os.environ.get(
        "BLAZE_BENCH_SERVE_LEVELS", "8,16,32,64").split(",")]
    rules = os.environ.get(
        "BLAZE_BENCH_SERVE_RULES",
        "task-start=0.05,shuffle-read=0.03,admit=0.03,cancel-race=0.5")

    MemManager.init(4 << 30)
    knobs = {config.DAG_SINGLE_TASK_BYTES.key: 0,
             config.TASK_RETRY_BACKOFF_MS.key: 5,
             config.TASK_MAX_ATTEMPTS.key: 6,
             config.STAGE_MAX_RECOVERIES.key: 8}
    for k, v in knobs.items():
        config.conf.set(k, v)

    def frame(tbl):
        import pandas as pd
        return tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
            {n: [] for n in tbl.schema.names})

    rec_levels = []
    divergent = 0
    leaks = 0
    dash_levels = []
    dash_divergent = dash_nonbit = dash_leaks = 0
    try:
        with tempfile.TemporaryDirectory(prefix="serve-") as d:
            # corpus + fault-free solo baselines, shared across levels
            plans, bases, arrow_bases = [], [], []
            for qname in names:
                qname = qname.strip()
                builder, table_names = QUERIES[qname]
                tables = generate(table_names, scale=scale)
                paths = write_parquet_splits(
                    tables, os.path.join(d, qname), 2)
                plan_dict, _oracle = builder(paths, tables, 2)
                plans.append((qname, plan_dict))
                base_tbl = DagScheduler().run_collect(plan_dict)
                arrow_bases.append(base_tbl)
                bases.append(frame(base_tbl))

            for conc in levels:
                n_queries = int(os.environ.get(
                    "BLAZE_BENCH_SERVE_PER_LEVEL", str(2 * conc)))
                rng = _np.random.default_rng(seed + conc)
                threads_before = {t.name
                                  for t in _threading.enumerate()}
                svc = QueryService(max_concurrent=conc,
                                   max_queue=n_queries,
                                   tenant_max_inflight=n_queries)
                faults.configure(rules, seed=seed + conc)
                submitted, timers, shed = [], [], 0
                t_level = time.perf_counter()
                try:
                    for i in range(n_queries):
                        j = i % len(plans)
                        deadline_ms = (float(rng.integers(5, 40))
                                       if i % 10 == 7 else 0.0)
                        try:
                            h = svc.submit(plans[j][1],
                                           tenant=f"t{i % 4}",
                                           deadline_ms=deadline_ms)
                        except QueryRejected:
                            shed += 1
                            continue
                        if i % 9 == 4:
                            tm = _threading.Timer(
                                float(rng.uniform(0.0, 0.1)),
                                svc.cancel, args=(h.query_id,))
                            tm.start()
                            timers.append(tm)
                        submitted.append((h, j))

                    outcome = {"done": 0, "cancelled": 0, "failed": 0}
                    walls = []
                    for h, j in submitted:
                        err = h.exception(timeout=600)
                        outcome[h.status] += 1
                        if h.status == "done":
                            walls.append(h.wall_s or 0.0)
                            if compare_frames(frame(h.result()),
                                              bases[j]) is not None:
                                divergent += 1
                        elif h.status == "failed" and not isinstance(
                                err, (faults.InjectedFault,
                                      faults.FetchFailedError)):
                            divergent += 1  # non-chaos failure: count it
                        if h.leak_report is not None and any(
                                h.leak_report.values()):
                            leaks += 1
                finally:
                    for tm in timers:
                        tm.cancel()
                    faults.clear()
                    svc.shutdown(wait=True, cancel_running=True)
                wall_level = time.perf_counter() - t_level
                if MemManager.get()._consumers:
                    leaks += 1
                for _ in range(50):
                    lingering = [
                        t.name for t in _threading.enumerate()
                        if t.name.startswith("blaze-serve")
                        and t.name not in threads_before]
                    if not lingering:
                        break
                    time.sleep(0.1)
                leaks += len(lingering)
                walls.sort()
                cnt = svc.stats()["counters"]
                rec_levels.append({
                    "concurrency": conc,
                    "submitted": len(submitted),
                    "shed_at_submit": shed,
                    "completed": outcome["done"],
                    "cancelled": cnt["cancelled"],
                    "deadline": cnt["deadline"],
                    "failed": outcome["failed"],
                    "p50_ms": round(_percentile(walls, 0.50) * 1e3, 2),
                    "p99_ms": round(_percentile(walls, 0.99) * 1e3, 2),
                    "wall_s": round(wall_level, 3),
                    "qps": round(len(submitted) / wall_level, 2)
                    if wall_level > 0 else None,
                })

            # ---- dashboard leg (ISSUE 15): repeat-heavy zipf replay
            # with the work-sharing rings ON and chaos OFF.  Sharing
            # must be BIT-identical, not merely equivalent: every
            # completed result is compared with Table.equals against
            # its fault-free solo run.  The cache is process-wide and
            # deliberately NOT reset between levels — the first level
            # pays the cold cost, later levels ride the warm rings,
            # which is exactly the repeat-heavy dashboard shape.
            from blaze_tpu.bridge import xla_stats as _xs
            from blaze_tpu.cache import reset_cache
            faults.clear()
            pool = [(qname, p, t)
                    for (qname, p), t in zip(plans, arrow_bases)]
            # limit-wrapped variants share every producer subtree with
            # their base plan but differ at the result fingerprint, so
            # they exercise the subplan ring when the result ring misses
            for qname, plan_dict in plans:
                variant = {"kind": "limit", "limit": 10 ** 9,
                           "input": plan_dict}
                pool.append((qname + "+limit", variant,
                             DagScheduler().run_collect(variant)))
            weights = _np.array([1.0 / (r + 1) ** 1.1
                                 for r in range(len(pool))])
            weights /= weights.sum()
            cache_knobs = {config.CACHE_ENABLE.key: True,
                           config.SERVING_SINGLE_FLIGHT.key: True,
                           config.CACHE_SCAN_SHARE.key: True}
            for k, v in cache_knobs.items():
                config.conf.set(k, v)
            reset_cache()
            try:
                for conc in levels:
                    n_sub = 4 * conc
                    rng = _np.random.default_rng(seed * 7 + conc)
                    picks = rng.choice(len(pool), size=n_sub,
                                       p=weights)
                    threads_before = {t.name
                                      for t in _threading.enumerate()}
                    svc = QueryService(max_concurrent=conc,
                                       max_queue=n_sub,
                                       tenant_max_inflight=n_sub)
                    before = _xs.cache_stats()
                    t_level = time.perf_counter()
                    handles = []
                    walls = []
                    completed = 0
                    try:
                        for i, j in enumerate(picks):
                            try:
                                h = svc.submit(pool[j][1],
                                               tenant=f"t{i % 4}")
                            except QueryRejected:
                                continue
                            handles.append((h, int(j)))
                        for h, j in handles:
                            h.exception(timeout=600)
                            if h.status == "done":
                                completed += 1
                                walls.append(h.wall_s or 0.0)
                                if not h.result().equals(pool[j][2]):
                                    dash_nonbit += 1
                            else:
                                # clean leg: every query must land
                                dash_divergent += 1
                            if h.leak_report is not None and any(
                                    h.leak_report.values()):
                                dash_leaks += 1
                    finally:
                        svc.shutdown(wait=True, cancel_running=True)
                    wall_level = time.perf_counter() - t_level
                    # the result cache itself stays registered between
                    # levels by design; anything else is a leak
                    if any(getattr(c, "name", "") != "result_cache"
                           for c in MemManager.get()._consumers):
                        dash_leaks += 1
                    for _ in range(50):
                        lingering = [
                            t.name for t in _threading.enumerate()
                            if t.name.startswith("blaze-serve")
                            and t.name not in threads_before]
                        if not lingering:
                            break
                        time.sleep(0.1)
                    dash_leaks += len(lingering)
                    walls.sort()
                    cs = _xs.cache_stats()
                    dd = {k2: cs[k2] - before.get(k2, 0) for k2 in cs}
                    rh = dd.get("result_cache_hits", 0)
                    rm = dd.get("result_cache_misses", 0)
                    sph = dd.get("subplan_cache_hits", 0)
                    spm = dd.get("subplan_cache_misses", 0)
                    ssh = dd.get("scan_share_hits", 0)
                    ssm = dd.get("scan_share_misses", 0)
                    dash_levels.append({
                        "concurrency": conc,
                        "submitted": len(handles),
                        "completed": completed,
                        "p50_ms": round(
                            _percentile(walls, 0.50) * 1e3, 2),
                        "p99_ms": round(
                            _percentile(walls, 0.99) * 1e3, 2),
                        "wall_s": round(wall_level, 3),
                        "qps": round(len(handles) / wall_level, 2)
                        if wall_level > 0 else None,
                        "result_cache_hits": rh,
                        "result_cache_misses": rm,
                        "result_cache_hit_rate": round(
                            rh / (rh + rm), 4) if rh + rm else None,
                        "subplan_cache_hits": sph,
                        "subplan_cache_misses": spm,
                        "coalesced": dd.get(
                            "single_flight_coalesces", 0),
                        "promoted": dd.get(
                            "single_flight_promotions", 0),
                        "scan_share_hits": ssh,
                        "scan_share_misses": ssm,
                        "scan_share_ratio": round(
                            ssh / (ssh + ssm), 4)
                        if ssh + ssm else None,
                        "scan_share_bytes_saved": dd.get(
                            "scan_share_bytes_saved", 0),
                        "cache_used_bytes": cs.get(
                            "cache_used_bytes_last", 0),
                    })
            finally:
                for k in cache_knobs:
                    config.conf.unset(k)
                reset_cache()
            if MemManager.get()._consumers:
                dash_leaks += 1
    finally:
        faults.clear()
        for k in knobs:
            config.conf.unset(k)

    rec = {
        "metric": "serve_divergent_queries",
        "value": divergent,
        "unit": "queries",
        "seed": seed,
        "rules": rules,
        "scale": scale,
        "queries": [q.strip() for q in names],
        "levels": rec_levels,
        "leaks": leaks,
        "dashboard": {
            "levels": dash_levels,
            "qps_growth_low_to_high": round(
                dash_levels[-1]["qps"] / dash_levels[0]["qps"], 2)
            if len(dash_levels) > 1 and dash_levels[0]["qps"]
            else None,
            "divergent_queries": dash_divergent,
            "non_bit_identical": dash_nonbit,
            "leaks": dash_leaks,
        },
    }
    path = os.environ.get(
        "BLAZE_BENCH_SERVE_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_SERVE.json"))
    _write_bench(path, rec)
    print(json.dumps(rec))
    sys.stdout.flush()
    return 0 if (divergent == 0 and leaks == 0 and dash_divergent == 0
                 and dash_nonbit == 0 and dash_leaks == 0) else 1


# ===========================================================================
# --stream: streaming soak — epochs, mid-soak chaos, exactly-once gate
# ===========================================================================

def stream_bench_main() -> int:
    """Streaming soak (`--stream`): a Kafka -> tumbling event-time
    window -> sink query runs as ONE continuous query through the
    serving layer and the staged DagScheduler for >= 20 micro-batch
    epochs, with a seeded `stream-epoch` fault killing an epoch
    mid-soak and a `checkpoint-commit` fault crashing a commit.
    Recovery must replay from the last committed checkpoint manifest,
    and the final sink output must be BIT-IDENTICAL to an offline batch
    recompute over the same records — zero lost, zero duplicated rows.
    Persists sustained rows/s, p50/p99 epoch wall and recovery time to
    BENCH_STREAM.json; exit 1 on any divergence."""
    import tempfile

    import pyarrow as pa
    import pyarrow.compute as pc

    from blaze_tpu import config, faults
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.memory import MemManager
    from blaze_tpu.ops.kafka import KafkaRecord
    from blaze_tpu.ops.window import EventTimeWindowSpec
    from blaze_tpu.serving.service import QueryService
    from blaze_tpu.streaming import (MemoryStreamSource, StreamExecutor,
                                     StreamWindowConfig,
                                     streaming_service_executor)

    MemManager.init(4 << 30)
    parts_n = int(os.environ.get("BLAZE_BENCH_STREAM_PARTITIONS", "4"))
    per_part = int(os.environ.get("BLAZE_BENCH_STREAM_RECORDS", "2000"))
    poll = int(os.environ.get("BLAZE_BENCH_STREAM_POLL", "100"))
    seed = int(os.environ.get("BLAZE_BENCH_STREAM_SEED", "77"))
    window_ms = 5_000
    min_epochs = 20

    import random as _random
    rng = _random.Random(seed)
    partitions = []
    for p in range(parts_n):
        recs, ts = [], 0
        for i in range(per_part):
            ts += rng.randint(0, 50)  # monotone per partition: no lates
            row = {"k": f"k{rng.randint(0, 7)}", "v": rng.randint(0, 999)}
            recs.append(KafkaRecord(
                value=json.dumps(row).encode("utf-8"),
                offset=i, partition=p, timestamp_ms=ts))
        partitions.append(recs)

    plan = {"kind": "kafka_scan", "topic": "bench", "format": "json",
            "operator_id": "stream-bench", "num_partitions": parts_n,
            "schema": {"fields": [
                {"name": "k", "type": {"id": "utf8"}, "nullable": True},
                {"name": "v", "type": {"id": "int64"}, "nullable": True}]}}
    win = StreamWindowConfig(
        spec=EventTimeWindowSpec(size_ms=window_ms), keys=["k"],
        aggs=[("sum", "v"), ("count", None)])
    sink_dir = tempfile.mkdtemp(prefix="blaze-stream-sink-")
    ckpt_dir = tempfile.mkdtemp(prefix="blaze-stream-ckpt-")

    holder = {}

    def build(plan_ir, ctx):
        ex = StreamExecutor(
            plan_ir, MemoryStreamSource(partitions), win,
            sink_dir=sink_dir, checkpoint_dir=ckpt_dir, ctx=ctx,
            max_records_per_poll=poll)
        holder["ex"] = ex
        return ex

    # mid-soak chaos: kill one epoch outright and one manifest commit
    mid = max(2, (per_part // poll) // 2)
    xla_stats.reset()
    service = QueryService(max_concurrent=1,
                           executor=streaming_service_executor(build))
    t0 = time.perf_counter()
    with faults.scoped(("stream-epoch", dict(at=(mid,))),
                       ("checkpoint-commit", dict(at=(mid + 3,))),
                       seed=seed):
        handle = service.submit(plan, tenant="stream-bench")
        summary = handle.result(timeout=600)
        injected = sum(st["fires"] for st in faults.stats().values())
    wall_s = time.perf_counter() - t0
    service.shutdown()
    ex = holder["ex"]

    # offline batch oracle: independent recompute with pyarrow group_by
    rows_k, rows_v, rows_ts = [], [], []
    for recs in partitions:
        for r in recs:
            row = json.loads(r.value)
            rows_k.append(row["k"])
            rows_v.append(row["v"])
            rows_ts.append(r.timestamp_ms)
    flat = pa.table({"k": pa.array(rows_k, pa.string()),
                     "v": pa.array(rows_v, pa.int64()),
                     "ts": pa.array(rows_ts, pa.int64())})
    ws = pc.multiply(pc.divide(flat["ts"], window_ms), window_ms)
    flat = flat.append_column("window_start", ws.cast(pa.int64()))
    oracle = flat.group_by(["k", "window_start"]).aggregate(
        [("v", "sum"), ("v", "count")])
    oracle = oracle.append_column(
        "window_end", pc.add(oracle["window_start"], window_ms)
        .cast(pa.int64()))
    oracle = oracle.select(["k", "window_start", "window_end",
                            "v_sum", "v_count"]) \
        .rename_columns(["k", "window_start", "window_end",
                         "sum_v", "count"])
    oracle = oracle.cast(pa.schema([
        ("k", pa.string()), ("window_start", pa.int64()),
        ("window_end", pa.int64()), ("sum_v", pa.int64()),
        ("count", pa.int64())]))

    got = ex.sink.committed_table()
    order = [("window_start", "ascending"), ("k", "ascending")]
    got_s = got.sort_by(order)
    oracle_s = oracle.sort_by(order)
    identical = got_s.equals(oracle_s)
    lost = max(0, oracle_s.num_rows - got_s.num_rows)
    duplicated = max(0, got_s.num_rows - oracle_s.num_rows)

    walls_ms = sorted(w / 1e6 for w in ex.epoch_walls_ns)

    def pct(q):
        if not walls_ms:
            return 0.0
        return walls_ms[min(len(walls_ms) - 1,
                            int(q * (len(walls_ms) - 1) + 0.5))]

    stats = xla_stats.stream_stats()
    rec = {
        "metric": "stream_soak_rows_per_sec",
        "value": round(summary["records_consumed"] / wall_s, 1),
        "unit": "rows/s",
        "epochs": summary["epochs"],
        "records": summary["records_consumed"],
        "rows_emitted": summary["rows_emitted"],
        "epoch_wall_ms_p50": round(pct(0.50), 3),
        "epoch_wall_ms_p99": round(pct(0.99), 3),
        "recoveries": summary["recoveries"],
        "recovery_ms": [round(w / 1e6, 3)
                        for w in ex.recovery_walls_ns],
        "faults_injected": injected,
        "checkpoints": stats["stream_checkpoints"],
        "sink_commits": stats["stream_sink_commits"],
        "sink_dup_skips": stats["stream_sink_dup_skips"],
        "lost_rows": lost,
        "duplicated_rows": duplicated,
        "bit_identical_vs_offline": identical,
        "min_epochs_gate": summary["epochs"] >= min_epochs,
        "seed": seed,
        "partitions": parts_n,
        "records_per_partition": per_part,
    }
    path = os.environ.get(
        "BLAZE_BENCH_STREAM_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_STREAM.json"))
    _write_bench(path, rec)
    print(json.dumps(rec, default=str))
    sys.stdout.flush()
    ok = (identical and lost == 0 and duplicated == 0
          and summary["epochs"] >= min_epochs
          and summary["recoveries"] >= 1)
    return 0 if ok else 1


# ===========================================================================
# --obs: tracing overhead gate + stitched-trace soak (ISSUE 13)
# ===========================================================================

def obs_bench_main() -> int:
    """Observability overhead gate (`--obs`): run q01/q06/q95 through
    the process-isolated worker pool with tracing OFF then ON
    (`auron.tpu.trace.enable`) and assert the traced wall stays within
    the overhead budget (default 2%, aggregate across queries,
    min-of-iters per leg to damp scheduler noise).  The traced legs
    must also really trace: per-query span counts and child spans
    stitched in over the worker wire (`obs_spans_ingested`) are
    recorded and must be non-zero, and traced results must match the
    untraced runs bit for bit.

    A second section exercises the statistics feedback plane
    (`auron.tpu.stats.enable`): the same queries run with the statstore
    OFF then ON, the stats legs must stay within the same overhead
    budget and match bit for bit, and the per-fingerprint priors must
    really merge (run_count grows across runs).  ETA accuracy is
    recorded cold (prior from one run) vs warm (prior from all earlier
    runs) against the actual walls — recorded, not gated.

    Writes BENCH_OBS.json and prints it as one JSON line."""
    import tempfile

    from blaze_tpu import config
    from blaze_tpu.bridge import tracing, xla_stats
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.memory import MemManager
    from blaze_tpu.parallel import workers
    from blaze_tpu.plan import statstore
    from blaze_tpu.plan.stages import DagScheduler

    names = os.environ.get("BLAZE_BENCH_OBS_QUERIES",
                           "q01,q06,q95").split(",")
    scale = float(os.environ.get("BLAZE_BENCH_OBS_SCALE", "0.2"))
    iters = int(os.environ.get("BLAZE_BENCH_OBS_ITERS", "3"))
    budget = float(os.environ.get("BLAZE_BENCH_OBS_BUDGET", "0.02"))

    MemManager.init(4 << 30)
    # staged wire path through the pool: the traced leg must pay the
    # full cross-process span shipping cost, not a thread shortcut
    knobs = {config.DAG_SINGLE_TASK_BYTES.key: 0,
             config.WORKERS_ENABLE.key: "on",
             config.WORKERS_COUNT.key: 2,
             config.WORKERS_HEARTBEAT_MS.key: 50}
    for k, v in knobs.items():
        config.conf.set(k, v)

    def frame(tbl):
        import pandas as pd
        return tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
            {n: [] for n in tbl.schema.names})

    queries = []
    diverged = 0
    stats_queries = []
    stats_diverged = 0
    try:
        with tempfile.TemporaryDirectory(prefix="obs-") as d:
            plans = []
            for qname in names:
                qname = qname.strip()
                builder, table_names = QUERIES[qname]
                tables = generate(table_names, scale=scale)
                paths = write_parquet_splits(
                    tables, os.path.join(d, qname), 2)
                plan_dict, _oracle = builder(paths, tables, 2)
                plans.append((qname, plan_dict))

            def run(qname, plan_dict, tag, runs):
                walls, got = [], None
                for it in range(runs):
                    sched = DagScheduler(work_dir=os.path.join(
                        d, qname, f"{tag}{it}"))
                    t0 = time.perf_counter()
                    got = sched.run_collect(plan_dict)
                    walls.append(time.perf_counter() - t0)
                return min(walls), got

            # warmup: XLA compile caches and pool spawn are paid once,
            # OUTSIDE both timed legs
            workers.get_pool()
            for qname, plan_dict in plans:
                run(qname, plan_dict, "warm", 1)

            for qname, plan_dict in plans:
                tracing.stop_tracing()
                tracing.reset_conf_probe()
                config.conf.unset(config.TRACE_ENABLE.key)
                base_wall, base = run(qname, plan_dict, "off", iters)

                config.conf.set(config.TRACE_ENABLE.key, "on")
                tracing.reset_conf_probe()
                before = xla_stats.snapshot()
                span0 = len(tracing.spans())
                traced_wall, got = run(qname, plan_dict, "on", iters)
                ds = xla_stats.delta(before)
                spans = len(tracing.spans()) - span0
                config.conf.unset(config.TRACE_ENABLE.key)
                tracing.reset_conf_probe()

                err = compare_frames(frame(got), frame(base))
                if err is not None:
                    diverged += 1
                queries.append({
                    "query": qname,
                    "base_wall_s": round(base_wall, 4),
                    "traced_wall_s": round(traced_wall, 4),
                    "overhead_pct": round(
                        (traced_wall / base_wall - 1.0) * 100, 2),
                    "spans": spans,
                    "spans_ingested":
                        int(ds.get("obs_spans_ingested", 0)),
                    "divergence": err,
                })

            # --- statstore feedback plane: overhead + ETA accuracy ---
            stats_dir = os.path.join(d, "statstore")
            for qname, plan_dict in plans:
                config.conf.unset(config.STATS_ENABLE.key)
                statstore.reset_conf_probe()
                off_wall, off_res = run(qname, plan_dict, "soff", iters)

                config.conf.set(config.STATS_ENABLE.key, "on")
                config.conf.set(config.STATS_DIR.key, stats_dir)
                statstore.reset_conf_probe()
                walls, preds, fp, got = [], [], None, None
                for it in range(iters):
                    prior = statstore.prior(fp) if fp else None
                    preds.append((prior or {}).get(
                        "derived", {}).get("wall_p50_s"))
                    sched = DagScheduler(work_dir=os.path.join(
                        d, qname, f"son{it}"))
                    t0 = time.perf_counter()
                    got = sched.run_collect(plan_dict)
                    walls.append(time.perf_counter() - t0)
                    fp = sched.stats_fingerprint or fp
                prior = statstore.prior(fp) if fp else None
                config.conf.unset(config.STATS_ENABLE.key)
                config.conf.unset(config.STATS_DIR.key)
                statstore.reset_conf_probe()

                err = compare_frames(frame(got), frame(off_res))
                if err is not None:
                    stats_diverged += 1

                def eta_err(i):
                    # |prior p50 - actual wall| as a % of the actual;
                    # None when no prior existed yet for that run
                    if not (1 <= i < len(walls)) or preds[i] is None \
                            or walls[i] <= 0:
                        return None
                    return round(abs(preds[i] - walls[i])
                                 / walls[i] * 100, 2)

                stats_queries.append({
                    "query": qname,
                    "base_wall_s": round(off_wall, 4),
                    "stats_wall_s": round(min(walls), 4),
                    "overhead_pct": round(
                        (min(walls) / off_wall - 1.0) * 100, 2),
                    "runs_merged": int((prior or {}).get(
                        "run_count", 0)),
                    "eta_cold_error_pct": eta_err(1),
                    "eta_warm_error_pct": eta_err(len(walls) - 1),
                    "divergence": err,
                })
    finally:
        workers.shutdown_pool(wait=False)
        for k in knobs:
            config.conf.unset(k)
        config.conf.unset(config.TRACE_ENABLE.key)
        config.conf.unset(config.STATS_ENABLE.key)
        config.conf.unset(config.STATS_DIR.key)
        tracing.stop_tracing()
        tracing.reset_conf_probe()
        statstore.reset_conf_probe()

    total_base = sum(q["base_wall_s"] for q in queries)
    total_traced = sum(q["traced_wall_s"] for q in queries)
    overhead = (total_traced / total_base - 1.0) if total_base else 0.0
    s_base = sum(q["base_wall_s"] for q in stats_queries)
    s_on = sum(q["stats_wall_s"] for q in stats_queries)
    stats_overhead = (s_on / s_base - 1.0) if s_base else 0.0
    rec = {
        "metric": "tracing_overhead_pct",
        "value": round(overhead * 100, 2),
        "unit": "percent",
        "budget_pct": budget * 100,
        "scale": scale,
        "iters": iters,
        "queries": queries,
        "total_spans": sum(q["spans"] for q in queries),
        "total_spans_ingested":
            sum(q["spans_ingested"] for q in queries),
        "divergent_queries": diverged,
        "statstore": {
            "overhead_pct": round(stats_overhead * 100, 2),
            "budget_pct": budget * 100,
            "divergent_queries": stats_diverged,
            "runs_merged": sum(q["runs_merged"] for q in stats_queries),
            "queries": stats_queries,
        },
    }
    path = os.environ.get(
        "BLAZE_BENCH_OBS_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_OBS.json"))
    _write_bench(path, rec)
    print(json.dumps(rec, default=str))
    sys.stdout.flush()
    ok = (diverged == 0 and overhead <= budget
          and all(q["spans"] > 0 for q in queries)
          and sum(q["spans_ingested"] for q in queries) > 0
          and stats_diverged == 0 and stats_overhead <= budget
          and all(q["runs_merged"] >= 2 for q in stats_queries))
    return 0 if ok else 1


def aqe_bench_main() -> int:
    """Adaptive-query-execution gate (`--aqe`): run synthetic join/agg
    workloads static-vs-adaptive and assert the three runtime rules pay
    for themselves with bit-identical results.

    Legs (each compares the adaptive result against the static run via
    compare_frames; any divergence fails the gate):

    * ``broadcast``  small-dim shuffle join: the runtime switch must
      elide the probe exchange (walls recorded, not gated);
    * ``skew``       skewed fact join at high static partition count:
      the composed skew-split + coalesce rewrite must beat the static
      wall by >2x (per-task dispatch tax is the win);
    * ``coalesce``   tiny-partition agg: the standalone coalesce rule;
    * ``history``    statstore-warmed planning: the second (cache-miss)
      run plans straight to the adaptive shape at BIND time and must
      beat the first run's wall.

    ``--fast`` is the CI smoke: 1 rep, skew leg only, same >2x and
    zero-divergence gates.  Writes BENCH_AQE.json (env override
    BLAZE_BENCH_AQE_PATH) and prints it as one JSON line."""
    import copy
    import tempfile

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu import config
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.memory import MemManager
    from blaze_tpu.plan import adaptive, statstore
    from blaze_tpu.plan.stages import DagScheduler

    fast = "--fast" in sys.argv
    iters = int(os.environ.get("BLAZE_BENCH_AQE_ITERS",
                               "1" if fast else "3"))

    MemManager.init(4 << 30)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)

    schema2 = lambda a, b: {"fields": [  # noqa: E731
        {"name": a, "type": {"id": "int64"}, "nullable": True},
        {"name": b, "type": {"id": "float64"}, "nullable": True}]}

    def write_splits(d, name, t, nsplit):
        paths = []
        step = -(-t.num_rows // nsplit)
        for i in range(nsplit):
            p = os.path.join(d, f"{name}-{i}.parquet")
            pq.write_table(t.slice(i * step, step), p)
            paths.append([p])
        return paths

    def exchange(inp, nparts):
        return {"kind": "local_exchange",
                "partitioning": {
                    "kind": "hash",
                    "exprs": [{"kind": "column", "index": 0}],
                    "num_partitions": nparts},
                "input": inp}

    def join_plan(d, tag, nparts, n, hot_frac, nfact):
        rng = np.random.default_rng(17)
        if hot_frac > 0:
            keys = np.where(rng.random(n) < hot_frac, 0,
                            rng.integers(1, 200, n)).astype(np.int64)
        else:
            keys = rng.integers(0, 200, n).astype(np.int64)
        fact = pa.table({"k": pa.array(keys),
                         "v": pa.array(rng.random(n))})
        dim = pa.table({"k": pa.array(np.arange(200, dtype=np.int64)),
                        "w": pa.array(rng.random(200))})
        return {"kind": "hash_join", "join_type": "inner",
                "left": exchange(
                    {"kind": "parquet_scan", "schema": schema2("k", "w"),
                     "file_groups": write_splits(d, f"dim-{tag}", dim,
                                                 2)}, nparts),
                "right": exchange(
                    {"kind": "parquet_scan", "schema": schema2("k", "v"),
                     "file_groups": write_splits(d, f"fact-{tag}", fact,
                                                 nfact)}, nparts),
                "left_keys": [{"kind": "column", "index": 0}],
                "right_keys": [{"kind": "column", "index": 0}],
                "build_side": "left"}

    def agg_plan(d, nparts):
        rng = np.random.default_rng(23)
        n = 40_000
        t = pa.table({"k": pa.array(rng.integers(0, 500, n),
                                    type=pa.int64()),
                      "v": pa.array(rng.random(n))})
        return {"kind": "hash_agg",
                "groupings": [{"expr": {"kind": "column", "index": 0},
                               "name": "k"}],
                "aggs": [{"fn": "sum", "mode": "final", "name": "s",
                          "args": [{"kind": "column", "index": 1}]}],
                "input": exchange({
                    "kind": "hash_agg",
                    "groupings": [{"expr": {"kind": "column",
                                            "name": "k"}, "name": "k"}],
                    "aggs": [{"fn": "sum", "mode": "partial",
                              "name": "s",
                              "args": [{"kind": "column",
                                        "name": "v"}]}],
                    "input": {"kind": "parquet_scan",
                              "schema": schema2("k", "v"),
                              "file_groups": write_splits(d, "agg", t,
                                                          2)}}, nparts)}

    def frame(tbl):
        import pandas as pd
        df = (tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
            {n: [] for n in tbl.schema.names}))
        return df.set_axis(range(df.shape[1]), axis=1)

    def run(plan, d, tag, conf, reps):
        """min-wall over `reps` runs of `plan` under `conf`; returns
        (wall, table, aqe counter delta)."""
        for k, v in conf.items():
            config.conf.set(k, v)
        adaptive.reset_conf_probe()
        before = xla_stats.aqe_stats()
        walls, got = [], None
        try:
            for it in range(reps):
                sched = DagScheduler(
                    work_dir=os.path.join(d, f"{tag}{it}"))
                t0 = time.perf_counter()
                got = sched.run_collect(copy.deepcopy(plan))
                walls.append(time.perf_counter() - t0)
        finally:
            for k in conf:
                config.conf.unset(k)
            adaptive.reset_conf_probe()
        after = xla_stats.aqe_stats()
        delta = {k: after[k] - before[k]
                 for k in after if after[k] != before[k]}
        return min(walls), got, delta

    aqe_on = {config.AQE_ENABLE.key: True}
    legs = {}
    rules = {"broadcast": 0, "skew_split": 0, "coalesce": 0,
             "history_seeds": 0}
    diverged = 0

    def leg(name, plan, d, conf, gate_rule=None):
        nonlocal diverged
        # warm XLA/compile caches outside both timed runs
        run(plan, d, f"{name}-warm", {}, 1)
        s_wall, s_got, _ = run(plan, d, f"{name}-s", {}, iters)
        a_wall, a_got, delta = run(plan, d, f"{name}-a", conf, iters)
        err = compare_frames(frame(a_got), frame(s_got))
        if err is not None:
            diverged += 1
        rules["broadcast"] += delta.get("aqe_broadcast_switches", 0)
        rules["skew_split"] += delta.get("aqe_skew_splits", 0)
        rules["coalesce"] += delta.get("aqe_partitions_coalesced", 0)
        legs[name] = {
            "static_wall_s": round(s_wall, 4),
            "aqe_wall_s": round(a_wall, 4),
            "speedup": round(s_wall / max(a_wall, 1e-9), 3),
            "counters": delta,
            "divergence": err,
        }
        return legs[name]

    try:
        with tempfile.TemporaryDirectory(prefix="aqe-") as d:
            skew_conf = dict(aqe_on)
            skew_conf[config.AQE_BROADCAST_THRESHOLD.key] = 0
            skew_conf[config.AQE_SKEW_FACTOR.key] = 2.0
            skew = leg("skew",
                       join_plan(d, "skew", nparts=160, n=50_000,
                                 hot_frac=0.75, nfact=8),
                       d, skew_conf)

            if not fast:
                leg("broadcast",
                    join_plan(d, "bc", nparts=32, n=40_000,
                              hot_frac=0.0, nfact=4),
                    d, aqe_on)
                leg("coalesce", agg_plan(d, nparts=32), d, aqe_on)

                # history leg: cold run observes and records, warm run
                # plans straight to the adaptive shape from the prior.
                # coalesceTarget=1 disables the runtime coalesce rule
                # and partition seeding, isolating the seeded broadcast.
                hplan = join_plan(d, "hist", nparts=48, n=40_000,
                                  hot_frac=0.0, nfact=4)
                run(hplan, d, "hist-warmup", {}, 1)
                hconf = dict(aqe_on)
                hconf[config.AQE_HISTORY_SEED.key] = True
                hconf[config.AQE_COALESCE_TARGET.key] = 1
                hconf[config.STATS_ENABLE.key] = True
                hconf[config.STATS_DIR.key] = os.path.join(d, "stats")
                statstore.reset_conf_probe()
                try:
                    cold_wall, cold_got, cold_delta = run(
                        hplan, d, "hist-cold", hconf, 1)
                    warm_wall, warm_got, warm_delta = run(
                        hplan, d, "hist-warm", hconf, iters)
                finally:
                    statstore.reset_conf_probe()
                err = compare_frames(frame(warm_got), frame(cold_got))
                if err is not None:
                    diverged += 1
                rules["history_seeds"] += warm_delta.get(
                    "aqe_history_seeds", 0)
                legs["history"] = {
                    "cold_wall_s": round(cold_wall, 4),
                    "warm_wall_s": round(warm_wall, 4),
                    "speedup": round(cold_wall / max(warm_wall, 1e-9),
                                     3),
                    "cold_counters": cold_delta,
                    "warm_counters": warm_delta,
                    "divergence": err,
                }
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)

    rec = {
        "metric": "aqe_skew_join_speedup",
        "value": skew["speedup"],
        "unit": "x",
        "iters": iters,
        "fast": fast,
        "divergent_queries": diverged,
        "rules_fired": rules,
        "legs": legs,
    }
    path = os.environ.get(
        "BLAZE_BENCH_AQE_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_AQE.json"))
    _write_bench(path, rec)
    print(json.dumps(rec, default=str))
    sys.stdout.flush()
    ok = (diverged == 0 and skew["speedup"] > 2.0
          and rules["skew_split"] > 0 and rules["coalesce"] > 0)
    if not fast:
        ok = (ok and rules["broadcast"] > 0
              and rules["history_seeds"] > 0
              and legs["history"]["warm_wall_s"]
              < legs["history"]["cold_wall_s"])
    return 0 if ok else 1


# ===========================================================================
# --encodings: strings/decimals on the device lanes (ISSUE 20)
# ===========================================================================

def encodings_bench_main() -> int:
    """Encoding-lane gate (`--encodings`): the two workloads the old
    type gates evicted to the host — a string-keyed group-by and a
    decimal aggregation — run with the ISSUE 20 encoding lanes OFF
    (seed behaviour: utf8 keys reject the stage loop, decimal columns
    reject the device exchange) and ON (dictionary codes fold on the
    int lanes, decimals ride the mesh as their unscaled int64s).

    Asserts and records per leg:
      * bit-identical frames between the legs (the encodings are
        representational, never semantic);
      * placement flips from host to device-loop / device-exchange
        (`stage_loop_tasks` / `shuffle_device_exchanges` engagement
        with zero fallbacks);
      * the host-lane eviction fraction before/after — the per-column
        `host_evictions_*` counters over total placement decisions.

    ``--fast`` is the CI smoke: smaller corpus, 1 iteration, same
    gates.  Writes BENCH_ENCODINGS.json (env override
    BLAZE_BENCH_ENCODINGS_PATH) and prints it as one JSON line."""
    import shutil
    import tempfile
    from decimal import Decimal

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu import config
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.memory import MemManager
    from blaze_tpu.plan.stages import DagScheduler

    fast = "--fast" in sys.argv
    n_rows = int(os.environ.get("BLAZE_BENCH_ENCODINGS_ROWS",
                                "20000" if fast else "120000"))
    iters = int(os.environ.get("BLAZE_BENCH_ENCODINGS_ITERS",
                               "1" if fast else "3"))
    n_maps, n_reduces = 2, 3

    MemManager.init(4 << 30)
    knobs = {config.DAG_SINGLE_TASK_BYTES.key: 0,
             config.STAGE_DEVICE_LOOP_ENABLE.key: "on",
             config.SHUFFLE_DEVICE.key: "on"}
    for k, v in knobs.items():
        config.conf.set(k, v)

    enc_on = {config.ENCODING_DICT_ENABLE.key: True,
              config.ENCODING_DECIMAL_ENABLE.key: True}

    def write_splits(root, name, t):
        paths = []
        per = -(-t.num_rows // n_maps)
        for i in range(n_maps):
            p = os.path.join(root, f"{name}-{i}.parquet")
            pq.write_table(t.slice(i * per, per), p)
            paths.append([p])
        return paths

    def two_stage(groups, schema, fn="sum"):
        return {
            "kind": "hash_agg",
            "groupings": [{"expr": {"kind": "column", "index": 0},
                           "name": "k"}],
            "aggs": [{"fn": fn, "mode": "final", "name": "s",
                      "args": [{"kind": "column", "index": 1}]}],
            "input": {
                "kind": "local_exchange",
                "partitioning": {"kind": "hash",
                                 "exprs": [{"kind": "column",
                                            "index": 0}],
                                 "num_partitions": n_reduces},
                "input": {
                    "kind": "hash_agg",
                    "groupings": [{"expr": {"kind": "column",
                                            "name": "k"}, "name": "k"}],
                    "aggs": [{"fn": fn, "mode": "partial", "name": "s",
                              "args": [{"kind": "column",
                                        "name": "v"}]}],
                    "input": groups}}}

    def string_plan(root):
        rng = np.random.default_rng(29)
        # multi-byte utf8 + empty string + NULLs in the key domain
        domain = ([f"sku-{i:04d}" for i in range(200)]
                  + ["", "véhicule", "北京市", "zäh-🚀"])
        idx = rng.integers(0, len(domain), n_rows)
        keys = [domain[i] if rng.random() > 0.05 else None
                for i in idx]
        t = pa.table({"k": pa.array(keys, type=pa.string()),
                      "v": pa.array(rng.random(n_rows))})
        schema = {"fields": [
            {"name": "k", "type": {"id": "utf8"}, "nullable": True},
            {"name": "v", "type": {"id": "float64"},
             "nullable": True}]}
        scan = {"kind": "parquet_scan", "schema": schema,
                "file_groups": write_splits(root, "str", t)}
        return two_stage(scan, schema)

    def decimal_plan(root):
        rng = np.random.default_rng(31)
        keys = rng.integers(0, 300, n_rows)
        vals = [Decimal(int(rng.integers(-10**7, 10**7))).scaleb(-2)
                if rng.random() > 0.08 else None
                for _ in range(n_rows)]
        t = pa.table({"k": pa.array(keys, type=pa.int64()),
                      "v": pa.array(vals, type=pa.decimal128(12, 2))})
        schema = {"fields": [
            {"name": "k", "type": {"id": "int64"}, "nullable": True},
            {"name": "v", "type": {"id": "decimal", "precision": 12,
                                   "scale": 2}, "nullable": True}]}
        scan = {"kind": "parquet_scan", "schema": schema,
                "file_groups": write_splits(root, "dec", t)}
        return two_stage(scan, schema)

    def frame(tbl):
        import pandas as pd
        df = (tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
            {n: [] for n in tbl.schema.names}))
        if len(df):
            df = df.sort_values(df.columns[0], na_position="first")
        return df.reset_index(drop=True)

    def eviction_fraction(d):
        """Host-lane evictions over total placement decisions in the
        counter delta: what fraction of device-lane opportunities the
        type gates turned away."""
        ev = (int(d.get("host_evictions_string", 0))
              + int(d.get("host_evictions_decimal", 0))
              + int(d.get("host_evictions_other", 0)))
        kept = (int(d.get("stage_loop_tasks", 0))
                + int(d.get("shuffle_device_exchanges", 0)))
        total = ev + kept
        return round(ev / total, 4) if total else None

    def run_leg(root, tag, plan, conf):
        for k, v in conf.items():
            config.conf.set(k, v)
        try:
            # warm outside the clock (compiles, parquet page cache)
            DagScheduler(work_dir=os.path.join(
                root, f"{tag}-warm")).run_collect(plan)
            xla_stats.reset()
            walls, tbl = [], None
            before = xla_stats.snapshot()
            for it in range(iters):
                t0 = time.perf_counter()
                tbl = DagScheduler(work_dir=os.path.join(
                    root, f"{tag}-{it}")).run_collect(plan)
                walls.append(time.perf_counter() - t0)
            d = xla_stats.delta(before)
        finally:
            for k in conf:
                config.conf.unset(k)
        loop_tasks = int(d.get("stage_loop_tasks", 0))
        exchanges = int(d.get("shuffle_device_exchanges", 0))
        fallbacks = (int(d.get("stage_loop_fallbacks", 0))
                     + int(d.get("shuffle_device_fallbacks", 0)))
        if loop_tasks and exchanges:
            placement = "device-loop"
        elif loop_tasks or exchanges:
            placement = "mixed"
        else:
            placement = "host"
        return tbl, {
            "wall_s": round(float(np.min(walls)), 4),
            "placement": placement,
            "stage_loop_tasks": loop_tasks,
            "device_exchanges": exchanges,
            "fallbacks": fallbacks,
            "eviction_fraction": eviction_fraction(d),
            "counters": {k: int(d[k]) for k in (
                "dict_encoded_columns", "dict_exchange_remaps",
                "decimal_scaled_int32_dispatches",
                "decimal_scaled_int64_dispatches",
                "decimal_limb_dispatches", "host_evictions_string",
                "host_evictions_decimal", "host_evictions_other")
                if d.get(k)},
        }

    legs = {}
    diverged = 0
    root = tempfile.mkdtemp(prefix="encodings-")
    try:
        for name, plan in (("string_group_by", string_plan(root)),
                           ("decimal_agg", decimal_plan(root))):
            base_tbl, off = run_leg(root, f"{name}-off", plan, {})
            got_tbl, on = run_leg(root, f"{name}-on", plan, enc_on)
            err = compare_frames(frame(got_tbl), frame(base_tbl))
            if err is not None:
                diverged += 1
            legs[name] = {
                "off": off, "on": on, "divergence": err,
                "speedup": round(off["wall_s"]
                                 / max(on["wall_s"], 1e-9), 3),
            }
    finally:
        shutil.rmtree(root, ignore_errors=True)
        for k in knobs:
            config.conf.unset(k)

    s, dml = legs["string_group_by"], legs["decimal_agg"]
    rec = {
        "metric": "encodings_device_placement_legs",
        "value": sum(1 for leg in legs.values()
                     if leg["on"]["placement"] != "host"
                     and leg["on"]["fallbacks"] == 0),
        "unit": "legs device-resident (of 2)",
        "rows": n_rows, "iters": iters, "fast": fast,
        "divergent_queries": diverged,
        "eviction_fraction_before": {
            n: legs[n]["off"]["eviction_fraction"] for n in legs},
        "eviction_fraction_after": {
            n: legs[n]["on"]["eviction_fraction"] for n in legs},
        "legs": legs,
    }
    path = os.environ.get(
        "BLAZE_BENCH_ENCODINGS_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_ENCODINGS.json"))
    _write_bench(path, rec)
    print(json.dumps(rec, default=str))
    sys.stdout.flush()

    def _frac_drops(name):
        b = legs[name]["off"]["eviction_fraction"]
        a = legs[name]["on"]["eviction_fraction"]
        return b is not None and (a is None or a < b)

    ok = (diverged == 0 and rec["value"] == 2
          and s["on"]["stage_loop_tasks"] > 0
          and s["off"]["stage_loop_tasks"] == 0
          and dml["on"]["device_exchanges"] > 0
          and dml["off"]["device_exchanges"] == 0
          and _frac_drops("string_group_by")
          and _frac_drops("decimal_agg"))
    return 0 if ok else 1


# ===========================================================================
# --fleet: replicated-serving kill-replica soak (ISSUE 19)
# ===========================================================================

def fleet_bench_main() -> int:
    """Fleet soak (`--fleet`): an N-replica loopback serving fleet —
    real replica PROCESSES behind the fingerprint-affine router, a
    shared socket RSS shuffle service, and a shared history dir — runs
    the q01/q06/q95 mix; mid-run one replica is SIGKILLed while holding
    queries.  Invariants, each compared against fault-free in-process
    baselines:

      * 0 lost queries — every submitted query returns a result;
      * 0 divergent results — re-routed/retried queries match the
        baseline bit for bit;
      * 0 duplicate committed blocks — first-wins commit held on the
        shared RSS tier despite the crossfire of retried map attempts;
      * affinity preserved — 100% hit-rate before the kill, and the
        surviving replicas keep their own fingerprints after it;
      * per-replica history rollups account for every completed query.

    Writes BENCH_FLEET.json and prints it as one JSON line."""
    import glob
    import tempfile

    from blaze_tpu import config
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.bridge.history import HistoryStore
    from blaze_tpu.fleet import FleetQueryLost, FleetRouter, spawn_replica
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.memory import MemManager
    from blaze_tpu.plan.stages import DagScheduler
    from blaze_tpu.shuffle.rss import RssSocketServer

    fast = "--fast" in sys.argv
    n_replicas = int(os.environ.get(
        "BLAZE_BENCH_FLEET_REPLICAS", "2" if fast else "3"))
    names = os.environ.get(
        "BLAZE_BENCH_FLEET_QUERIES",
        "q01,q06" if fast else "q01,q06,q95").split(",")
    scale = float(os.environ.get(
        "BLAZE_BENCH_FLEET_SCALE", "0.02" if fast else "0.05"))
    rounds = int(os.environ.get(
        "BLAZE_BENCH_FLEET_ROUNDS", "2" if fast else "4"))

    MemManager.init(4 << 30)
    # router supervision at bench cadence: a SIGKILLed replica must be
    # classified down in ~1s, not the production 2s default
    for k, v in ((config.FLEET_HEARTBEAT_MS.key, 100),
                 (config.FLEET_LIVENESS_MS.key, 1000),
                 (config.FLEET_PROBE_BACKOFF_MS.key, 100),
                 (config.FLEET_RETRIES.key, 3)):
        config.conf.set(k, v)

    def frame(tbl):
        import pandas as pd
        return tbl.to_pandas() if tbl.num_rows else pd.DataFrame(
            {n: [] for n in tbl.schema.names})

    lost = 0
    divergent = 0
    duplicates = 0
    successes = 0
    procs = {}
    rss_srv = None
    router = None
    per_query = []
    try:
        with tempfile.TemporaryDirectory(prefix="fleet-") as d:
            # corpus + fault-free in-process baselines
            plans, bases = [], []
            for qname in names:
                qname = qname.strip()
                builder, table_names = QUERIES[qname]
                tables = generate(table_names, scale=scale)
                paths = write_parquet_splits(
                    tables, os.path.join(d, qname), 2)
                plan_dict, _oracle = builder(paths, tables, 2)
                plans.append((qname, plan_dict))
                bases.append(frame(DagScheduler(
                    work_dir=os.path.join(d, qname, "base"))
                    .run_collect(plan_dict)))

            rss_root = os.path.join(d, "rss-store")
            os.makedirs(rss_root)
            rss_srv = RssSocketServer(rss_root).start()
            hist_dir = os.path.join(d, "hist")
            replica_conf = {
                config.HISTORY_ENABLE.key: "true",
                config.HISTORY_DIR.key: hist_dir,
                # staged wire path so exchanges actually traverse the
                # shared RSS service (single-task fusion would bypass it)
                config.DAG_SINGLE_TASK_BYTES.key: 0,
                config.SHUFFLE_SERVICE.key: rss_srv.url,
                config.TASK_RETRY_BACKOFF_MS.key: 5,
            }
            endpoints = []
            for i in range(n_replicas):
                rid = f"replica-{i}"
                proc, addr = spawn_replica(rid, conf=replica_conf)
                procs[rid] = proc
                endpoints.append((rid, addr))
            router = FleetRouter(endpoints)

            def run_one(qname, plan_dict, base, tag):
                nonlocal lost, divergent, successes
                t0 = time.perf_counter()
                try:
                    got = router.execute(plan_dict, timeout_s=300.0)
                except FleetQueryLost as e:
                    lost += 1
                    per_query.append({"query": qname, "leg": tag,
                                      "lost": True, "error": str(e)})
                    return
                wall = time.perf_counter() - t0
                successes += 1
                err = compare_frames(frame(got), base)
                if err is not None:
                    divergent += 1
                per_query.append({"query": qname, "leg": tag,
                                  "wall_s": round(wall, 4),
                                  "divergent": err})

            # warm-up: establish affinity (and each replica's caches)
            for (qname, plan_dict), base in zip(plans, bases):
                run_one(qname, plan_dict, base, "warmup")
            pre_kill = router.health()
            affinity_pre = pre_kill["affinity_hit_rate"]

            kill_round = max(0, rounds // 2)
            killed = None
            for rnd in range(rounds):
                if rnd == kill_round:
                    # SIGKILL the busiest replica WHILE it holds the
                    # round's queries: submit async, then pull the rug
                    victim = max(
                        (r for r in router.health()["replicas"]
                         if r["state"] == "up"),
                        key=lambda r: r["queries_routed"])["replica"]
                    futs = [(qname, router.submit(
                                plan_dict, timeout_s=300.0), base)
                            for (qname, plan_dict), base
                            in zip(plans, bases)]
                    time.sleep(0.05)
                    procs[victim].kill()  # SIGKILL, no drain
                    killed = victim
                    for qname, fut, base in futs:
                        try:
                            got = fut.result(timeout=600.0)
                        except FleetQueryLost as e:
                            lost += 1
                            per_query.append(
                                {"query": qname, "leg": "kill",
                                 "lost": True, "error": str(e)})
                            continue
                        successes += 1
                        err = compare_frames(frame(got), base)
                        if err is not None:
                            divergent += 1
                        per_query.append({"query": qname, "leg": "kill",
                                          "divergent": err})
                else:
                    for (qname, plan_dict), base in zip(plans, bases):
                        run_one(qname, plan_dict, base, f"round-{rnd}")

            health = router.health()
            fleet_counters = xla_stats.fleet_stats()

            # first-wins held on the shared RSS tier: exactly one
            # committed manifest per (shuffle, map) — and with the
            # O_EXCL/hardlink arbitration a second one cannot exist,
            # so any extra commit file IS a duplicate committed block
            seen = set()
            for manifest in glob.glob(os.path.join(
                    rss_root, "rss-*", "commit-m*")):
                if manifest.endswith(".owner"):
                    continue
                key = (os.path.basename(os.path.dirname(manifest)),
                       os.path.basename(manifest))
                if key in seen:
                    duplicates += 1
                seen.add(key)

            # per-replica history rollup over the SHARED dir: completed
            # counts must account for every query the fleet answered
            rollup = HistoryStore(hist_dir).rollup()
            replica_counts = {k: v["completed"]
                              for k, v in rollup["replicas"].items()}
            rollup_total = sum(replica_counts.values())

            # graceful teardown: drain survivors via SIGTERM
            router.drain_all()
            for rid, proc in procs.items():
                if proc.poll() is None:
                    proc.terminate()
            for proc in procs.values():
                try:
                    proc.wait(timeout=30)
                except Exception:
                    proc.kill()
    finally:
        if router is not None:
            router.close()
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
        if rss_srv is not None:
            rss_srv.stop()
        for k in (config.FLEET_HEARTBEAT_MS.key,
                  config.FLEET_LIVENESS_MS.key,
                  config.FLEET_PROBE_BACKOFF_MS.key,
                  config.FLEET_RETRIES.key):
            config.conf.unset(k)

    submitted = len(per_query)
    affinity_post = health["affinity_hit_rate"]
    rec = {
        "metric": "fleet_soak_lost_queries",
        "value": lost,
        "unit": "queries",
        "fast": fast,
        "replicas": n_replicas,
        "rounds": rounds,
        "scale": scale,
        "submitted": submitted,
        "completed": successes,
        "lost_queries": lost,
        "divergent_results": divergent,
        "duplicate_committed_blocks": duplicates,
        "killed_replica": killed,
        "affinity_hit_rate_pre_kill": affinity_pre,
        "affinity_hit_rate_final": affinity_post,
        "replicas_up_final": health["replicas_up"],
        "fleet_reroutes": fleet_counters["fleet_reroutes"],
        "fleet_replica_down_events":
            fleet_counters["fleet_replica_down_events"],
        "history_completed_by_replica": replica_counts,
        "history_completed_total": rollup_total,
        "queries": per_query,
    }
    path = os.environ.get(
        "BLAZE_BENCH_FLEET_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_FLEET.json"))
    _write_bench(path, rec)
    print(json.dumps(rec, default=str))
    sys.stdout.flush()
    ok = (lost == 0 and divergent == 0 and duplicates == 0
          and killed is not None
          and successes == submitted
          # every query the fleet completed is attributed to exactly
          # one replica in the shared history rollup
          and rollup_total == successes
          # affinity: perfect while the fleet was whole, and the kill
          # only moves the victim's fingerprints
          and affinity_pre == 1.0
          and (affinity_post or 0) >= 0.5
          and health["replicas_up"] == n_replicas - 1)
    return 0 if ok else 1


def sentinel_bench_main() -> int:
    """--sentinel: self-check of the regression sentinel CI contract.

    Writes a baseline artifact through the unified writer, then runs the
    sentinel twice: identical candidate must exit 0, and a candidate
    with one metric regressed past threshold must exit 2 naming it.
    """
    import tempfile
    from blaze_tpu.tools import sentinel
    from blaze_tpu.tools.bench_schema import write_bench_artifact

    threshold = float(os.environ.get("BLAZE_BENCH_SENTINEL_THRESHOLD",
                                     "0.10"))
    base_rec = {
        "metric": "sentinel_selfcheck",
        "q01_wall_s": 1.25,
        "q01_rows_per_sec": 48_000.0,
        "shuffle": {"device_bytes": 1 << 20, "spill_bytes": 0},
        "expr_cache_hit_rate": 0.92,
    }
    checks = []
    with tempfile.TemporaryDirectory(prefix="blaze_sentinel_") as td:
        base_path = os.path.join(td, "BENCH_BASE.json")
        same_path = os.path.join(td, "BENCH_SAME.json")
        regr_path = os.path.join(td, "BENCH_REGR.json")
        write_bench_artifact(base_path, base_rec)
        write_bench_artifact(same_path, dict(base_rec))
        regressed = dict(base_rec)
        regressed["q01_wall_s"] = base_rec["q01_wall_s"] * 1.5
        write_bench_artifact(regr_path, regressed)

        rc_same = sentinel.main(["--baseline", base_path,
                                 "--candidate", same_path,
                                 "--threshold", str(threshold), "--ci"])
        checks.append({"name": "identical_exits_zero",
                       "exit_code": rc_same, "ok": rc_same == 0})

        rc_regr = sentinel.main(["--baseline", base_path,
                                 "--candidate", regr_path,
                                 "--threshold", str(threshold), "--ci"])
        findings = sentinel.compare(
            sentinel.load(base_path), sentinel.load(regr_path),
            threshold=threshold, ci=True)
        named = [f["metric"] for f in findings
                 if f["kind"] == "regression"]
        checks.append({"name": "regression_exits_two_and_names_metric",
                       "exit_code": rc_regr,
                       "regressions_named": named,
                       "ok": rc_regr == 2 and named == ["q01_wall_s"]})

    ok = all(c["ok"] for c in checks)
    rec = {
        "metric": "sentinel_selfcheck_pass",
        "value": int(ok),
        "unit": "bool",
        "threshold": threshold,
        "checks": checks,
    }
    path = os.environ.get(
        "BLAZE_BENCH_SENTINEL_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_SENTINEL.json"))
    _write_bench(path, rec)
    print(json.dumps(rec, default=str))
    sys.stdout.flush()
    return 0 if ok else 1


def main():
    if "--expr" in sys.argv:
        sys.exit(expr_bench_main())
    if "--chaos" in sys.argv:
        sys.exit(chaos_bench_main())
    if "--workers" in sys.argv:
        sys.exit(workers_bench_main())
    if "--speculate" in sys.argv:
        sys.exit(speculate_bench_main())
    if "--serve" in sys.argv:
        sys.exit(serve_bench_main())
    if "--aggskip" in sys.argv:
        sys.exit(aggskip_bench_main())
    if "--deviceloop" in sys.argv:
        sys.exit(deviceloop_bench_main())
    if "--stream" in sys.argv:
        sys.exit(stream_bench_main())
    if "--obs" in sys.argv:
        sys.exit(obs_bench_main())
    if "--aqe" in sys.argv:
        sys.exit(aqe_bench_main())
    if "--encodings" in sys.argv:
        sys.exit(encodings_bench_main())
    if "--fleet" in sys.argv:
        sys.exit(fleet_bench_main())
    if "--sentinel" in sys.argv:
        sys.exit(sentinel_bench_main())
    if "--multichip-child" in sys.argv:
        sys.exit(multichip_child_main())
    if "--multichip" in sys.argv:
        sys.exit(multichip_bench_main())
    if "--child" in sys.argv:
        try:
            child_main()
        except BaseException:
            import traceback
            _error_line(traceback.format_exc())
            try:
                _shutdown_pool()
            except Exception:
                pass
            os._exit(2)  # bypass stuck non-daemon threads
        try:
            _shutdown_pool()
        except Exception:
            pass
        os._exit(0)
    sys.exit(supervise())


if __name__ == "__main__":
    main()

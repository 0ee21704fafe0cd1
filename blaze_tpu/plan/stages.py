"""Stage DAG scheduler: execute a whole multi-stage plan over the wire.

Parity role: what Spark's driver + AuronShuffleManager do around the
reference engine.  Auron never schedules stages itself — Spark splits the
physical plan at exchange boundaries, runs map tasks that end in
ShuffleWriterExec (.data/.index files, AuronShuffleWriterBase.scala:39),
tracks map outputs, and starts reduce stages whose plans begin with
IpcReaderExec over the fetched blocks (AuronBlockStoreShuffleReaderBase
.scala:29-66).  This module is that driver: it takes ONE engine-IR plan
containing `local_exchange` nodes (what convert/spark.py emits for
ShuffleExchangeExec), cuts it into stages, and runs every task of every
stage as protobuf TaskDefinition bytes through NativeExecutionRuntime —
the full production wire path, no in-process shortcuts.

Cutting rules:
  * `local_exchange` -> the child becomes a producer stage whose per-task
    plan is wrapped in `shuffle_writer` (hash/round-robin/single
    partitioning, per-map .data/.index files); the consumer side reads an
    `ipc_reader` bound to the producer's registered block map (the
    MapOutputTracker analog).
  * scans carry ONE file group per task on the wire (FileScanExecConf),
    so each task's plan keeps only its own group — except under a
    broadcast build side, where the scan collapses to ALL files (a
    broadcast is a full copy; BroadcastJoinExec pulls every partition of
    its build child).
"""

from __future__ import annotations

import functools
import logging
import os
import tempfile
import threading
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import pyarrow as pa

from blaze_tpu.bridge.context import current_task
from blaze_tpu.bridge.metrics import MetricNode
from blaze_tpu.bridge.resource import put_resource, remove_resource
from blaze_tpu.faults import FetchFailedError, InjectedFault

log = logging.getLogger("blaze_tpu.stages")

_SCAN_KINDS = ("parquet_scan", "orc_scan")


def _broadcast_reader_rids(d: Any, in_broadcast: bool = False) -> set:
    """Resource ids of ipc_readers sitting under a broadcast build side
    anywhere in `d` (those exchanges stay on the file shuffle)."""
    rids: set = set()
    if not isinstance(d, dict) or "kind" not in d:
        return rids
    k = d.get("kind")
    if k == "ipc_reader" and in_broadcast:
        rids.add(d.get("resource_id"))
    if k in ("broadcast_join", "broadcast_nested_loop_join"):
        build = d.get("build_side", "right")
        for side in ("left", "right"):
            rids |= _broadcast_reader_rids(d.get(side),
                                           in_broadcast or side == build)
        return rids
    if k == "broadcast_join_build_hash_map":
        return rids | _broadcast_reader_rids(d.get("input"), True)
    for key, val in d.items():
        if isinstance(val, dict) and "kind" in val:
            rids |= _broadcast_reader_rids(val, in_broadcast)
        elif key == "inputs" and isinstance(val, list):
            for v in val:
                rids |= _broadcast_reader_rids(v, in_broadcast)
    return rids


def _batches_to_columns(batches: List[pa.RecordBatch], schema):
    """Concatenate record batches into per-column (data, validity) numpy
    arrays — the flat layout DeviceExchange shards over the mesh."""
    import numpy as np

    from blaze_tpu.batch import _arrow_fixed_values, _unpack_validity
    ncols = len(schema.fields)
    datas: List[list] = [[] for _ in range(ncols)]
    valids: List[list] = [[] for _ in range(ncols)]
    for rb in batches:
        for i, f in enumerate(schema.fields):
            arr = rb.column(i)
            if isinstance(arr, pa.ChunkedArray):
                arr = arr.combine_chunks()
            datas[i].append(np.ascontiguousarray(
                _arrow_fixed_values(arr, f.data_type)))
            valids[i].append(_unpack_validity(arr))
    return ([np.concatenate(d) for d in datas],
            [np.concatenate(v) for v in valids])


def _columns_to_batch(datas, valids, arrow_schema: pa.Schema
                      ) -> pa.RecordBatch:
    """Inverse of _batches_to_columns for one reduce partition.  date32
    and timestamps travelled the mesh as their integer storage; the
    cast back to the logical arrow type is lossless."""
    import numpy as np
    arrays = []
    for data, valid, f in zip(datas, valids, arrow_schema):
        valid = np.asarray(valid, dtype=bool)
        mask = None if bool(valid.all()) else ~valid
        t = f.type
        if pa.types.is_date32(t) or pa.types.is_timestamp(t):
            arrays.append(pa.array(data, mask=mask).cast(t))
        elif pa.types.is_boolean(t):
            arrays.append(pa.array(np.asarray(data, dtype=bool), mask=mask))
        elif pa.types.is_decimal(t):
            # the mesh carried the unscaled ints; a pa.array(..., type=t)
            # would read them as whole decimal values and rescale
            from blaze_tpu.batch import decimal_from_unscaled
            arrays.append(decimal_from_unscaled(
                np.asarray(data, dtype=np.int64), valid, t))
        else:
            arrays.append(pa.array(data, type=t, mask=mask))
    return pa.RecordBatch.from_arrays(arrays, schema=arrow_schema)


def _shuffle_scratch_base() -> Optional[str]:
    """Shuffle files are transient: prefer the RAM disk (the standard
    spark.local.dir-on-tmpfs deployment) when it has real headroom —
    ext4 journaling is pure critical-path overhead for data read back
    milliseconds later.  None -> tempfile's default."""
    try:
        sv = os.statvfs("/dev/shm")
        if sv.f_bavail * sv.f_frsize >= (2 << 30):
            return "/dev/shm"
    except OSError:
        pass
    return None


@dataclass
class Stage:
    sid: int
    plan: Dict[str, Any]          # stage-root IR (no shuffle_writer yet)
    partitioning: Optional[Dict[str, Any]]  # None for the result stage
    resource_id: Optional[str]
    num_tasks: int = 1            # producer-side task count
    deps: List[int] = field(default_factory=list)
    out_schema: Optional[Dict[str, Any]] = None
    # planner verdict for the device-resident exchange (plan/planner.py
    # exchange_device_spec); None = this boundary stays on file shuffle
    device_spec: Optional[Dict[str, Any]] = None
    # adaptive execution (plan/adaptive.py): set when a runtime rule
    # rewrote this stage — carries the rule name and the DERIVED
    # fingerprint that replaces the static subtree identity everywhere
    # downstream (statstore, subplan cache)
    aqe: Optional[Dict[str, Any]] = None


class DagScheduler:
    """Split at exchanges, then run stages bottom-up over the proto wire."""

    def __init__(self, work_dir: Optional[str] = None,
                 max_task_parallelism: Optional[int] = None,
                 task_timeout_s: float = 600.0,
                 query_ctx=None):
        self._owns_dir = work_dir is None
        self._dir = work_dir or tempfile.mkdtemp(
            prefix="blaze-dag-", dir=_shuffle_scratch_base())
        os.makedirs(self._dir, exist_ok=True)
        self._files: List[str] = []
        # owning serving.QueryContext: threaded to every task slot so
        # cancellation/deadline interrupts retries, pool waits and batch
        # loops (None = standalone single-query use, unchanged)
        from blaze_tpu.bridge.context import current_query
        self._query = query_ctx if query_ctx is not None else current_query()
        # elastic-shuffle clients (auron.tpu.shuffle.service), torn down
        # with the rest of the scratch state
        self._rss_clients: List[Any] = []
        self._cleanup_lock = threading.Lock()
        if max_task_parallelism is None:
            # executor sizing knob (ref rt.rs:108-112 tokio worker threads
            # = TOKIO_WORKER_THREADS_PER_CPU x task cpus)
            from blaze_tpu import config
            per_cpu = max(1, config.TOKIO_WORKER_THREADS_PER_CPU.get())
            max_task_parallelism = min(16, per_cpu *
                                       max(1, (os.cpu_count() or 4) // 2))
        self._par = max_task_parallelism
        self._timeout = task_timeout_s
        self._run_id = uuid.uuid4().hex[:10]
        self.stages: List[Stage] = []
        self._resources: List[str] = []
        self.exec_mode: Optional[str] = None  # "local" | "staged"
        # sid -> {map_id -> (data_file, offsets)}: the MapOutputTracker
        # analog.  blocks_for closures read THIS dict at call time, so a
        # recovered map task's fresh output is what the retried reduce
        # task fetches — never a stale snapshot of the poisoned one.
        # On the resident tier an entry is the `ResidentMapOutput` a map
        # task committed in place of files (shuffle/writer.py).
        self._stage_outputs: Dict[int, Dict[int, Any]] = {}
        # every resident output a map task of this run committed, to be
        # let go with the run whatever became of its table entry
        self._resident_outputs: List[Any] = []
        # resource ids of the readers under a broadcast build side
        # (split() finds them): those exchanges stay on files
        self._broadcast_rids: set = set()
        # (sid, map_id) -> pool worker id that produced the committed
        # output (None on the in-process path).  A worker crash
        # re-validates exactly these entries; validation failure marks
        # the table entry None, which blocks_for converts into the
        # FetchFailedError the lineage recovery already handles.
        self._map_worker: Dict[tuple, Optional[int]] = {}
        # (sid, map_id) -> times the task body ran; lineage-recovery
        # tests assert exactly ONE map task re-ran after a poisoned block
        self.task_runs: Dict[tuple, int] = {}
        # (sid, partition) -> id of the chip the task last ran on (0
        # where nothing is pinned); tasks run by pool workers are absent
        self.task_chips: Dict[tuple, int] = {}
        # speculation: monotone per-(sid, map) attempt-id allocator (each
        # retry OR speculative duplicate gets a fresh id), and the table
        # of WINNING attempt ids — lineage recovery and crash
        # invalidation only ever deal with the committed winner
        self._attempt_seq: Dict[tuple, int] = {}
        self._map_attempt: Dict[tuple, int] = {}
        self._attempt_lock = threading.Lock()
        # per-stage operator-metric trees, merged across that stage's
        # tasks at finalize time (the MetricsUpdater analog)
        self.stage_metrics: Dict[int, MetricNode] = {}
        self._metrics_lock = threading.Lock()
        # sid -> {"compute": "device-loop"|"staged"|"mixed",
        #         "exchange": "device"|"rss"|"file"|"result"} — the
        # OBSERVED per-stage placement (explain and history read this
        # instead of the session-level default, which reported "cpu"
        # even when device lanes ran)
        self.stage_placement: Dict[int, Dict[str, str]] = {}
        # work-sharing (auron.tpu.cache.subplan): sid -> (fp, snapshot)
        # of stages served FROM the cross-query cache this run, and of
        # stages whose fresh output should be stored after the map wave
        self._cached_stages: Dict[int, tuple] = {}
        self._pending_subplan: Dict[int, tuple] = {}
        # statistics feedback plane (plan/statstore.py; armed per run by
        # _stats_begin only when auron.tpu.stats.enable): the run's plan
        # fingerprint, per-shuffle-boundary observations captured at
        # producer completion (the map-output table is gone by cleanup),
        # and the counter/reservoir baselines the final ingest deltas
        self.stats_fingerprint: Optional[str] = None
        self.stage_boundaries: Dict[int, Dict[str, Any]] = {}
        # adaptive execution (plan/adaptive.py): the run's rewrite/seed
        # event log, copied onto the serving QueryHandle at finish
        self.aqe_events: List[Dict[str, Any]] = []
        self._stats_base: Optional[dict] = None
        self._stats_dur0: Dict[str, int] = {}
        self._stats_t0: float = 0.0

    def _record_task_metrics(self, sid: int, tree: MetricNode,
                             task=None) -> None:
        """`task`: the TaskContext of a task that ran in this process."""
        from blaze_tpu.bridge import profiling
        with self._metrics_lock:
            merged = self.stage_metrics.setdefault(
                sid, MetricNode(name=tree.name))
            merged.merge_from(tree)
            if task is not None:
                self.task_chips[(sid, task.partition_id)] = task.device_id
        profiling.record_metrics(tree.to_dict())
        from blaze_tpu.plan import statstore
        if statstore.enabled():
            qid = getattr(self._query, "query_id", None)
            if qid is not None:
                from blaze_tpu.serving import progress
                values = tree.values or {}
                progress.note_rows(
                    qid, sid,
                    rows=int(values.get("output_rows", 0) or 0),
                    bytes_=int(values.get("io_bytes", 0) or 0))

    def collect_metrics(self) -> Optional[MetricNode]:
        """Merged metric tree of the result stage (the operator tree the
        caller's rows actually flowed through), or None before any run."""
        if not self.stage_metrics:
            return None
        return self.stage_metrics[max(self.stage_metrics)]

    # -- splitting ---------------------------------------------------------

    def split(self, plan: Dict[str, Any]) -> List[Stage]:
        """Returns stages in dependency order; the last one is the result
        stage (its output streams back to the caller, the collect path)."""
        self.stages = []  # a scheduler instance may be reused per query
        root, deps = self._split_node(plan)
        n_tasks, schema = self._plan_info(root)
        result = Stage(sid=len(self.stages), plan=root, partitioning=None,
                       resource_id=None, deps=deps, num_tasks=n_tasks,
                       out_schema=schema)
        self.stages.append(result)
        self._mark_device_exchanges()
        return self.stages

    def _mark_device_exchanges(self) -> None:
        """Planner pass: mark each exchange device-resident when BOTH
        sides of the boundary are mesh-shardable.  The producer side is
        decided by exchange_device_spec (hash keys as direct column
        refs, all-fixed-width row schema); the consumer side declines
        readers under broadcast builds — a broadcast replays EVERY
        partition once per task, which the file path streams through
        the page cache while in-memory device blocks would pin the full
        copy per replay."""
        from blaze_tpu.plan.planner import exchange_device_spec
        demoted: set = set()
        for st in self.stages:
            demoted |= _broadcast_reader_rids(st.plan)
        self._broadcast_rids = demoted
        for st in self.stages:
            if st.partitioning is None or st.resource_id in demoted:
                continue
            st.device_spec = exchange_device_spec(st.partitioning,
                                                  st.out_schema)

    def _split_node(self, d: Dict[str, Any]):
        """Rewrite one node; returns (new_dict, dep_stage_ids)."""
        if not isinstance(d, dict) or "kind" not in d:
            return d, []
        if d["kind"] == "local_exchange":
            child, deps = self._split_node(d["input"])
            part = dict(d["partitioning"])
            n_out = 1 if part["kind"] == "single" \
                else int(part.get("num_partitions", 1))
            sid = len(self.stages)
            rid = f"stage://{self._run_id}/{sid}"
            n_tasks, schema = self._plan_info(child)
            stage = Stage(sid=sid, plan=child, partitioning=part,
                          resource_id=rid, deps=deps, num_tasks=n_tasks,
                          out_schema=schema)
            self.stages.append(stage)
            reader = {"kind": "ipc_reader", "resource_id": rid,
                      "schema": schema,
                      "num_partitions": n_out}
            return reader, [sid]
        out = dict(d)
        deps: List[int] = []
        for key, val in d.items():
            if isinstance(val, dict) and "kind" in val:
                out[key], sub = self._split_node(val)
                deps.extend(sub)
            elif key == "inputs" and isinstance(val, list):  # union
                subs = []
                for v in val:
                    nv, sub = self._split_node(v)
                    subs.append(nv)
                    deps.extend(sub)
                out[key] = subs
        return out, deps

    @staticmethod
    def _plan_info(d: Dict[str, Any]):
        """ONE planning pass per stage: (task count, output schema dict)."""
        from blaze_tpu.plan import create_plan
        from blaze_tpu.plan.types import schema_to_dict
        plan = create_plan(d)
        return max(1, plan.num_partitions), schema_to_dict(plan.schema)

    # -- per-task plan rewrite --------------------------------------------

    def _per_task(self, d, task: int, n_tasks: int,
                  in_broadcast: bool = False):
        if not isinstance(d, dict) or "kind" not in d:
            return d
        k = d["kind"]
        out = dict(d)
        if k in _SCAN_KINDS:
            groups = d.get("file_groups", [])
            if in_broadcast:
                # a broadcast is a full copy: every task sees every file
                all_files = [f for g in groups for f in g]
                new_groups: List[List[str]] = [[] for _ in range(n_tasks)]
                new_groups[task] = all_files
            else:
                if len(groups) > n_tasks:
                    raise ValueError(
                        f"scan has {len(groups)} file groups but the stage "
                        f"runs {n_tasks} tasks; repartition the input")
                # in-process semantics: partition p of a scan with fewer
                # groups than the stage yields nothing (ops emit only for
                # partition < child.num_partitions)
                new_groups = [[] for _ in range(n_tasks)]
                if task < len(groups):
                    new_groups[task] = list(groups[task])
            out["file_groups"] = new_groups
            return out
        # build sides of broadcast joins are full copies for every task
        if k in ("broadcast_join", "broadcast_nested_loop_join"):
            build = d.get("build_side", "right")
            for side in ("left", "right"):
                out[side] = self._per_task(d[side], task, n_tasks,
                                           in_broadcast or side == build)
            if "join_filter" in out and out["join_filter"] is None:
                del out["join_filter"]
            return out
        if k == "broadcast_join_build_hash_map":
            out["input"] = self._per_task(d["input"], task, n_tasks, True)
            return out
        for key, val in d.items():
            if isinstance(val, dict) and "kind" in val:
                out[key] = self._per_task(val, task, n_tasks, in_broadcast)
            elif key == "inputs" and isinstance(val, list):
                out[key] = [self._per_task(v, task, n_tasks, in_broadcast)
                            for v in val]
        return out

    # -- execution ---------------------------------------------------------

    def _run_tasks(self, fn, n: int, what: str, remote=None,
                   sid: Optional[int] = None) -> List[Any]:
        from blaze_tpu.bridge.tasks import default_task_parallelism, run_tasks
        # host placement caps slots harder than the executor-size knob:
        # serial tasks around intra-op-parallel C++ kernels beat
        # GIL-contended task concurrency (see default_task_parallelism)
        workers = min(self._par, default_task_parallelism(n))
        if sid is not None:
            from blaze_tpu.plan import statstore
            if statstore.enabled():
                qid = getattr(self._query, "query_id", None)
                if qid is not None:
                    from blaze_tpu.serving import progress
                    progress.note_stage_start(qid, sid, n)
                    inner = fn

                    def fn(i, _inner=inner, _qid=qid, _sid=sid):
                        out = _inner(i)
                        progress.note_task_done(_qid, _sid)
                        return out
        return run_tasks(fn, n, self._timeout, what, max_workers=workers,
                         query=self._query, remote=remote)

    def _note_placement(self, sid: int, exchange: str,
                        loop_before: int) -> None:
        """Record the OBSERVED placement of one stage.  On the rss/file
        tiers the device loop engages inside the fused operator itself,
        so the evidence is the xla_stats stage_loop_tasks delta across
        the stage's map tasks (best-effort under concurrent queries)."""
        from blaze_tpu.bridge import xla_stats
        after = xla_stats.stage_loop_stats()["stage_loop_tasks"]
        self.stage_placement[sid] = {
            "compute": "device-loop" if after > loop_before else "staged",
            "exchange": exchange}
        self._note_history_stage(sid)

    def _note_history_stage(self, sid: int) -> None:
        """Persist one stage_complete event: observed placement plus the
        merged metric summary of the stage's tasks (bridge/history.py;
        no-op unless auron.tpu.history.enable and a serving query owns
        the run)."""
        from blaze_tpu.bridge import history
        if not history.enabled():
            return
        qid = getattr(self._query, "query_id", None)
        if qid is None:
            return
        placement = self.stage_placement.get(sid, {})
        with self._metrics_lock:
            node = self.stage_metrics.get(sid)
            values = dict(node.values) if node is not None else {}
        metrics = {k: int(values[k]) for k in
                   ("output_rows", "output_batches", "elapsed_compute_ns",
                    "spilled_bytes", "io_bytes") if k in values}
        tasks = next((s.num_tasks for s in self.stages if s.sid == sid),
                     None)
        history.note_stage(qid, sid=sid,
                           exchange=placement.get("exchange", "unknown"),
                           compute=placement.get("compute", "unknown"),
                           tasks=tasks, metrics=metrics)

    @staticmethod
    def _part_of(stage: Stage) -> Dict[str, Any]:
        part = dict(stage.partitioning)
        if part["kind"] == "single":
            part = {"kind": "single", "num_partitions": 1}
        return part

    def _map_data_path(self, sid: int, m: int) -> str:
        return os.path.join(self._dir, f"s{self._run_id}-{sid}-{m}.data")

    def _next_attempt(self, sid: int, m: int) -> int:
        with self._attempt_lock:
            a = self._attempt_seq.get((sid, m), 0)
            self._attempt_seq[(sid, m)] = a + 1
            return a

    def _map_task_def(self, stage: Stage, part: Dict[str, Any],
                      m: int) -> Dict[str, Any]:
        """The self-contained shuffle-writer TaskDefinition for one map
        task — everything a worker PROCESS needs (absolute file paths,
        the per-task plan slice), no scheduler state.

        With speculation enabled every invocation (first run, retry,
        speculative duplicate, recovery re-run) writes under a FRESH
        attempt-suffixed .data/.index pair; the writer's first-wins
        promotion (shuffle.writer.promote_attempt_output) decides which
        attempt owns the final unsuffixed index — ONE os.replace is the
        commit, and the loser's files are discarded unread."""
        from blaze_tpu import config
        data = self._map_data_path(stage.sid, m)
        index = data[:-5] + ".index"
        attempt = 0
        if config.SPECULATION_ENABLE.get():
            attempt = self._next_attempt(stage.sid, m)
            base = data[:-5]
            data = f"{base}.a{attempt}.data"
            index = f"{base}.a{attempt}.index"
        plan = {"kind": "shuffle_writer", "partitioning": part,
                "data_file": data,
                "index_file": index,
                "input": self._per_task(stage.plan, m, stage.num_tasks)}
        return {"stage_id": stage.sid, "partition_id": m,
                "num_partitions": stage.num_tasks,
                "task_attempt_id": attempt, "plan": plan}

    def _run_map_task(self, stage: Stage, part: Dict[str, Any],
                      m: int) -> None:
        """One producer map task: stage plan -> shuffle_writer ->
        .data/.index (the writer commits via tmp + os.replace, so a
        recovery re-run atomically replaces the poisoned output)."""
        from blaze_tpu.bridge.runtime import NativeExecutionRuntime
        from blaze_tpu.plan.proto_serde import task_definition_to_bytes
        td = task_definition_to_bytes(self._map_task_def(stage, part, m))
        rt = NativeExecutionRuntime(td).start()
        try:
            for _ in rt.batches():
                pass
        finally:
            self._record_task_metrics(stage.sid, rt.finalize(),
                                      rt.task)
        with self._metrics_lock:
            self.task_runs[(stage.sid, m)] = \
                self.task_runs.get((stage.sid, m), 0) + 1
            self._map_worker[(stage.sid, m)] = None

    @staticmethod
    def _reader_rids(d) -> set:
        """Every stage:// shuffle resource an ipc_reader in this plan
        slice will resolve at execute time."""
        rids: set = set()
        if isinstance(d, dict):
            rid = d.get("resource_id")
            if d.get("kind") == "ipc_reader" and isinstance(rid, str) \
                    and rid.startswith("stage://"):
                rids.add(rid)
            for v in d.values():
                if isinstance(v, dict):
                    rids |= DagScheduler._reader_rids(v)
                elif isinstance(v, list):
                    for x in v:
                        rids |= DagScheduler._reader_rids(x)
        return rids

    def _shuffle_inputs(self, plan) -> Optional[Dict[str, list]]:
        """MapOutputTracker analog: resolve every stage:// reader in a
        per-task plan to its on-disk segment list, so a worker PROCESS
        can read upstream shuffle output without the parent's resource
        map.  {rid: [per-reduce-partition [(data, off, len, sid, mid)]]}.
        None = some input is not file-backed (device or RSS shuffle
        tier) and the task must stay in-process.  An invalidated map
        output raises FetchFailedError here, at dispatch, exactly as
        blocks_for would at read time."""
        inputs: Dict[str, list] = {}
        for rid in self._reader_rids(plan):
            try:
                up_sid = int(rid.rsplit("/", 1)[1])
            except ValueError:
                return None
            outputs = dict(self._stage_outputs.get(up_sid) or {})
            if not outputs or not all(
                    e is None or isinstance(e, tuple)
                    for e in outputs.values()):
                # device/RSS tier, or an output resident on the chip:
                # blocks live in-process
                return None
            n_out = None
            for entry in outputs.values():
                if entry is not None:
                    n_out = len(entry[1]) - 1
                    break
            if n_out is None:
                return None
            parts = []
            for p in range(n_out):
                segs = []
                for map_id in sorted(outputs):
                    entry = outputs[map_id]
                    if entry is None:
                        raise FetchFailedError(
                            up_sid, map_id,
                            "map output invalidated after worker crash")
                    data, offsets = entry
                    length = offsets[p + 1] - offsets[p]
                    if length:
                        segs.append((data, int(offsets[p]), int(length),
                                     up_sid, map_id))
                parts.append(segs)
            inputs[rid] = parts
        return inputs

    def _map_remote(self, stage: Stage, part: Dict[str, Any]):
        """Worker-pool spec factory for this stage's map tasks, or None
        when the pool is disabled (the in-process path stays the
        default).  spec(m) is re-evaluated per ATTEMPT, so shuffle-input
        locations are re-resolved after a lineage recovery round; it
        returns None for a task whose inputs aren't shippable, which
        falls that one task back in-process."""
        from blaze_tpu import config
        if not config.WORKERS_ENABLE.get():
            # serving-mode queries may opt map tasks onto the pool even
            # when the global switch is off, so N admitted queries get
            # process parallelism instead of time-slicing one interpreter
            if self._query is None or not config.SERVING_USE_WORKERS.get():
                return None

        def spec(m: int) -> Optional[Dict[str, Any]]:
            td = self._map_task_def(stage, part, m)
            si = self._shuffle_inputs(td["plan"]["input"])
            if si is None:
                return None
            if si:
                td["shuffle_inputs"] = si
            return {"fn": "blaze_tpu.parallel.workers:run_shuffle_map_task",
                    "args": (td,)}
        return spec

    def _absorb_remote_results(self, stage: Stage, results,
                               map_ids=None) -> None:
        """Fold worker-process map-task results into scheduler state:
        the metric tree rode the result frame home, and the producing
        worker's id is remembered so a later crash of that worker can
        re-validate exactly these outputs."""
        if map_ids is None:
            map_ids = range(len(results))
        for m, res in zip(map_ids, results):
            if not isinstance(res, dict):
                continue  # in-process fallback already recorded itself
            tree = res.get("metrics")
            if tree:
                self._record_task_metrics(stage.sid,
                                          MetricNode.from_dict(tree))
            with self._metrics_lock:
                self.task_runs[(stage.sid, m)] = \
                    self.task_runs.get((stage.sid, m), 0) + 1
                self._map_worker[(stage.sid, m)] = res.get("_worker_id")

    def _read_map_output(self, stage: Stage, m: int, n_out: int) -> tuple:
        """Validated (data_file, offsets) for one map output; a bad index
        is re-raised carrying the producer's (stage, map) identity so the
        recovery loop knows exactly which task to re-run.

        Under speculation the unsuffixed index is the COMMITTED winner's
        (one os.replace promoted it) and the claim file names which
        attempt's .data file backs it — resolve_attempt_data maps the
        base path to the winner; without a claim (speculation off) the
        base path IS the data file, byte-identical to the old behavior."""
        from blaze_tpu.shuffle.exchange import read_index_file
        from blaze_tpu.shuffle.writer import resolve_attempt_data
        base = self._map_data_path(stage.sid, m)
        data, attempt = resolve_attempt_data(base)
        try:
            offsets = read_index_file(base[:-5] + ".index",
                                      expected_partitions=n_out,
                                      data_file=data)
        except FetchFailedError as e:
            raise FetchFailedError(stage.sid, m, e.reason) from e
        with self._attempt_lock:
            self._map_attempt[(stage.sid, m)] = attempt
        return data, offsets

    def _register_stage_files(self, sid: int) -> None:
        """Sweep the scratch dir for this stage's files (attempt-suffixed
        outputs, claim files, promoted indexes) into the cleanup list —
        a losing speculative attempt's leftovers must not outlive the
        scheduler even when the loser already unlinked its own pair."""
        prefix = f"s{self._run_id}-{sid}-"
        try:
            names = os.listdir(self._dir)
        except OSError:
            return
        for name in names:
            if not name.startswith(prefix):
                continue
            p = os.path.join(self._dir, name)
            if p not in self._files:
                self._files.append(p)

    def _clear_map_commit(self, sid: int, m: int) -> None:
        """Un-commit one map output before a lineage-recovery re-run:
        the committed winner's index is the poisoned block being
        recovered, so the claim AND the promoted index must go — a
        fresh attempt can then win the first-wins race cleanly.  A
        no-op when no claim exists (speculation off: the recovery
        re-run os.replaces the unsuffixed index in place, as always)."""
        base = self._map_data_path(sid, m)
        owner = base[:-5] + ".index.owner"
        if not os.path.exists(owner):
            return
        for p in (owner, base[:-5] + ".index"):
            try:
                os.unlink(p)
            except OSError:
                pass

    @staticmethod
    def _is_cancellation(e: BaseException) -> bool:
        """Cancellation/deadline/kill must never be swallowed into a
        shuffle-tier fallback: the query is being torn down, not
        recovering."""
        from blaze_tpu.serving.context import is_cancellation
        return is_cancellation(e)

    # -- cross-query subplan cache (auron.tpu.cache.subplan) ---------------

    def _subplan_cache_key(self, stage: Stage):
        """(fingerprint, snapshot) when this producer stage is shareable
        across queries, else None.  Only LEAF stages qualify: a stage
        reading upstream exchanges carries run-scoped stage:// resource
        ids, so its identity can never match another run's anyway."""
        from blaze_tpu import config
        if not (config.CACHE_ENABLE.get() and config.CACHE_SUBPLAN.get()):
            return None
        if stage.partitioning is None or self._reader_rids(stage.plan):
            return None
        if stage.aqe is not None:
            # an AQE-rewritten stage carries run-scoped derived
            # resources; its static fingerprint no longer describes its
            # shape (belt and braces: rewritten stages always hold
            # readers, which the check above already declines)
            return None
        from blaze_tpu.plan import fingerprint as fp_mod
        snap = fp_mod.source_snapshot(stage.plan)
        if snap is None:
            return None
        part = self._part_of(stage)
        fp = fp_mod.subplan_fingerprint(stage.plan, part, stage.num_tasks)
        return fp, snap

    def _try_cached_producer(self, stage: Stage) -> bool:
        """Serve one map stage from the cross-query cache: publish the
        cached partition blocks under the stage's resource id (the raw-
        bytes block shape the device tier already publishes) and skip
        the whole map wave.  Misses remember the key so the fresh output
        is stored after the file-tier wave commits."""
        key = self._subplan_cache_key(stage)
        if key is None:
            return False
        from blaze_tpu.cache import results as result_cache
        cache = result_cache.get_cache()
        if cache is None:
            return False
        fp, snap = key
        blocks = cache.get_subplan(fp, snap)
        if blocks is None:
            self._pending_subplan[stage.sid] = key
            return False
        sid = stage.sid
        self._cached_stages[sid] = key
        # empty map-output table: _shuffle_inputs finds no file-backed
        # entries, so consumer tasks stay in-process (same contract as
        # the device tier)
        self._stage_outputs[sid] = {}

        def blocks_for(reduce_id: int, _blocks=blocks):
            for blk in _blocks.get(reduce_id, ()):
                yield blk

        put_resource(stage.resource_id, blocks_for)
        if stage.resource_id not in self._resources:
            self._resources.append(stage.resource_id)
        self.stage_placement[sid] = {"compute": "cached",
                                     "exchange": "cached"}
        self._note_history_stage(sid)
        from blaze_tpu.bridge import tracing
        tracing.instant("subplan_cache_hit", stage=sid, fingerprint=fp)
        return True

    def _maybe_store_subplan(self, stage: Stage) -> None:
        """After a file-tier map wave commits, store the per-reduce
        partition bytes (the exact committed .data segments, still in
        their on-disk IPC frame form) so a later query with the same
        producing subtree replays them instead of re-running the wave."""
        key = self._pending_subplan.pop(stage.sid, None)
        if key is None:
            return
        from blaze_tpu.cache import results as result_cache
        cache = result_cache.get_cache()
        if cache is None:
            return
        outputs = self._stage_outputs.get(stage.sid) or {}
        n_out = int(self._part_of(stage).get("num_partitions", 1))
        blocks: Dict[int, list] = {}
        try:
            for map_id in sorted(outputs):
                entry = outputs[map_id]
                if entry is None:
                    return  # invalidated mid-wave: nothing safe to store
                data, offsets = entry
                with open(data, "rb") as f:
                    for r in range(n_out):
                        length = int(offsets[r + 1] - offsets[r])
                        if not length:
                            continue
                        f.seek(int(offsets[r]))
                        blocks.setdefault(r, []).append(f.read(length))
        except OSError:
            return  # torn output: cache nothing, the files stay truth
        cache.put_subplan(key[0], key[1], blocks)

    def _invalidate_cached_stage(self, sid: int) -> None:
        """A cached stage's replay went bad: drop the entry and re-run
        the producer with the cache bypassed — fresh execution is the
        recovery path, never a second replay of suspect bytes."""
        key = self._cached_stages.pop(sid, None)
        if key is None:
            return
        from blaze_tpu.cache import results as result_cache
        cache = result_cache.get_cache()
        if cache is not None:
            cache.invalidate(key[0])

    def _run_producer(self, stage: Stage) -> None:
        """One exchange boundary: device-resident collective when the
        planner marked it eligible; else the resident tier when writer
        and reader share one chip and one process (`_resident_tier`: the
        map output stays on the chip); else the elastic shuffle service
        (auron.tpu.shuffle.service) when configured, so concurrent
        queries don't contend on local disk; host shuffle files
        otherwise — and the file path is ALSO the fallback for any
        device-, resident- or service-tier failure.  The higher tiers
        are optimizations, never a new failure mode."""
        if self._try_cached_producer(stage):
            return
        if stage.device_spec is not None:
            try:
                self._run_producer_device(stage)
                return
            except (KeyboardInterrupt, SystemExit):
                raise
            except FetchFailedError:
                # an UPSTREAM block was poisoned: the lineage identity
                # must reach the recovery loop, not trigger a fallback
                raise
            except Exception as e:
                if self._is_cancellation(e):
                    raise
                from blaze_tpu.bridge import tracing, xla_stats
                self._note_undeclared_fallback("device_shuffle", e,
                                               stage=stage.sid)
                xla_stats.note_device_shuffle_fallback()
                tracing.instant("device_shuffle_fallback",
                                stage=stage.sid, error=type(e).__name__)
        rss_root = self._rss_root()
        if self._resident_tier(stage):
            try:
                self._run_producer_file(stage, resident=True)
                return
            except (KeyboardInterrupt, SystemExit):
                raise
            except FetchFailedError:
                raise
            except Exception as e:
                if self._is_cancellation(e):
                    raise
                self._note_undeclared_fallback("resident_shuffle", e,
                                               stage=stage.sid)
        if rss_root is not None:
            try:
                self._run_producer_rss(stage, rss_root)
                return
            except (KeyboardInterrupt, SystemExit):
                raise
            except FetchFailedError:
                raise
            except Exception as e:
                if self._is_cancellation(e):
                    raise
                from blaze_tpu.bridge import tracing
                tracing.instant("rss_shuffle_fallback", stage=stage.sid,
                                error=type(e).__name__)
        self._run_producer_file(stage)
        self._maybe_store_subplan(stage)

    def _resident_tier(self, stage: Stage) -> bool:
        """Whether this exchange's map output may stay on the chip
        (shuffle/writer.py's resident lane), from what the scheduler can
        see; no key selects it.  It is the tier of the case the mesh tier
        declines: compute placed on the device and ONE device in the mesh,
        so writer and reader share a chip.  They have to share a process
        too (no worker pool, no shuffle service root), and whoever else
        needs the output as files keeps it on
        files: speculation (attempt-suffixed files are its commit
        protocol), the subplan cache about to store this stage's segments,
        adaptive re-planning (it splits a skewed partition by file
        segment), a reader under a broadcast build (it replays every
        partition once a task, which files stream through the page cache).
        A single-partition exchange keeps the streaming Arrow writer: its
        rows are few and its consumers read Arrow.  What a map task's
        batches hold decides the rest, task by task, in the writer."""
        from blaze_tpu import config
        from blaze_tpu.bridge.placement import host_resident
        from blaze_tpu.parallel.mesh import current_mesh
        from blaze_tpu.plan import adaptive
        part = self._part_of(stage)
        return (int(part.get("num_partitions", 1)) > 1
                and not host_resident()
                and current_mesh().devices.size == 1
                and self._map_remote(stage, part) is None
                and self._rss_root() is None
                and not config.SPECULATION_ENABLE.get()
                and stage.sid not in self._pending_subplan
                and not adaptive.enabled()
                and stage.resource_id not in self._broadcast_rids)

    def _commit_resident(self, outputs: Dict[int, Any], m: int,
                         output) -> bool:
        """The sink a map task of a resident wave commits to: the first
        output committed for map task `m` is the one readers see, as the
        first file commit is."""
        with self._metrics_lock:
            if outputs.setdefault(m, output) is not output:
                return False
            self._resident_outputs.append(output)
        return True

    @staticmethod
    def _note_undeclared_fallback(site: str, e: Exception,
                                  **where) -> None:
        """The device tiers fall back on ANY failure, but only the
        engine's own declared degradations (typed ineligibility,
        capacity overflow, scripted chaos) may do so quietly.  Anything
        else — a lowering or compile error above all — is logged at
        ERROR with its traceback and kept in xla_stats, so it cannot
        pass unseen with tracing off while the reference path returns
        identical results."""
        from blaze_tpu.parallel.stage import DeviceExchangeError
        from blaze_tpu.plan.stage_compiler import StageLoopIneligible
        from blaze_tpu.runtime.loop import StageLoopFallback
        if isinstance(e, (DeviceExchangeError, StageLoopFallback,
                          StageLoopIneligible, InjectedFault)):
            return
        from blaze_tpu.bridge import xla_stats
        log.error("%s fell back on an undeclared error (%s)", site,
                  ", ".join(f"{k}={v}" for k, v in where.items()),
                  exc_info=e)
        xla_stats.note_unexpected_fallback(site, e, **where)

    @staticmethod
    def _rss_root() -> Optional[str]:
        """Shared-storage root of the elastic shuffle tier, or None for
        local files (the default)."""
        from blaze_tpu import config
        root = config.SHUFFLE_SERVICE.get().strip()
        return root or None

    def _run_map_task_collect(self, stage: Stage,
                              m: int) -> List[pa.RecordBatch]:
        """One producer map task WITHOUT the shuffle_writer wrapper: the
        stage plan's batches come back over the wire for the device
        exchange to repartition.  Same TaskDefinition path, metrics and
        task_runs accounting as the file-shuffle map task."""
        from blaze_tpu.bridge.runtime import NativeExecutionRuntime
        from blaze_tpu.plan.proto_serde import task_definition_to_bytes
        td = task_definition_to_bytes(
            {"stage_id": stage.sid, "partition_id": m,
             "num_partitions": stage.num_tasks,
             "plan": self._per_task(stage.plan, m, stage.num_tasks)})
        rt = NativeExecutionRuntime(td).start()
        try:
            out = list(rt.batches())
        finally:
            self._record_task_metrics(stage.sid, rt.finalize(),
                                      rt.task)
        with self._metrics_lock:
            self.task_runs[(stage.sid, m)] = \
                self.task_runs.get((stage.sid, m), 0) + 1
        return out

    def _run_map_task_loop(self, stage: Stage, m: int):
        """One producer map task through the device-resident stage loop
        (runtime/loop.py): ONE program dispatch per chunk of batches,
        then a device-side drain so the map output reaches
        DeviceExchange without a host round trip.  Returns (datas,
        valids, n) device column arrays, or None — disabled, stage
        ineligible, or wholesale fallback — in which case the caller
        runs the staged per-batch collect.  Cancellation and lineage
        (FetchFailed) always propagate."""
        from blaze_tpu.bridge.runtime import NativeExecutionRuntime
        from blaze_tpu.plan import stage_compiler
        from blaze_tpu.plan.proto_serde import task_definition_to_bytes
        if not stage_compiler.stage_loop_active():
            return None
        td = task_definition_to_bytes(
            {"stage_id": stage.sid, "partition_id": m,
             "num_partitions": stage.num_tasks,
             "plan": self._per_task(stage.plan, m, stage.num_tasks)})
        rt = NativeExecutionRuntime(td)  # plan pipeline only: not started
        prog = stage_compiler.compile_task_plan(rt.plan)
        if prog is None:
            return None
        from blaze_tpu.bridge import tracing, xla_stats
        from blaze_tpu.bridge.context import task_scope
        from blaze_tpu.runtime import loop as device_loop
        xla_stats.note_task_placed(rt.task.device_id)
        try:
            with task_scope(rt.task), \
                    tracing.execution_context(stage=stage.sid,
                                              partition=m), \
                    tracing.span("task", mode="loop",
                                 device=rt.task.device_id), \
                    device_loop.charged_table(prog) as table:
                # the table stays charged to the task's chip until its
                # groups are drained into the exchange's columns
                carry = device_loop.run_partition(
                    prog, m, ctx=str(stage.sid), table=table)
                with tracing.span("agg_drain", table="loop"):
                    out = device_loop.drain_device(prog, carry)
        except (KeyboardInterrupt, SystemExit, FetchFailedError):
            raise
        except Exception as e:
            if self._is_cancellation(e):
                raise
            self._note_undeclared_fallback("stage_loop", e,
                                           stage=stage.sid, task=m)
            xla_stats.note_stage_loop_fallback(str(e))
            tracing.instant("stage_loop_fallback", stage=stage.sid,
                            task=m, reason=str(e))
            return None
        finally:
            self._record_task_metrics(stage.sid, rt.finalize(),
                                      rt.task)
        with self._metrics_lock:
            self.task_runs[(stage.sid, m)] = \
                self.task_runs.get((stage.sid, m), 0) + 1
        return out

    @staticmethod
    def _merge_map_outputs(batches: List[pa.RecordBatch], col_tasks,
                           schema):
        """Per-task map outputs -> one host (cols, valids) column set
        for the exchange: the staged batches, then the loop tasks'
        device columns read back.  (A wave of loop tasks only never
        comes here: its columns stay where they lie,
        `DeviceExchange.dispatch_placed`.)"""
        import numpy as np
        from blaze_tpu.xputil import asnp
        cols, valids = _batches_to_columns(batches, schema)
        for datas, vls, _n in col_tasks:
            for i, (d, v) in enumerate(zip(datas, vls)):
                cols[i] = np.concatenate(
                    [cols[i], asnp(d).astype(cols[i].dtype)])
                valids[i] = np.concatenate(
                    [valids[i], asnp(v).astype(bool)])
        return cols, valids

    def _exchange_sync(self, stage: Stage, spec, n_out: int, schema):
        """Synchronous device exchange: run the whole map wave, merge
        every task's columns into one set, then ONE exchange + encode.
        The `device_exchange` span covers merge+exchange+encode only —
        NOT the map wave — so the device ledger's barrier_idle category
        sees the real fold-end -> exchange-start gap this path pays.
        Two child spans on this thread cut it: `exchange_stage` from its
        opening to the collective's dispatch (the host concat of a
        staged wave, padding, the cut over the mesh, or the per-chip
        assembly of a placed one) and `exchange_unstage` from the
        overflow scalar's arrival to the last IPC block (the readback
        of the receive buffers, the host split, the encode); what lies
        between them is the wait for the collective.
        shuffle_barrier_idle_ns counts the FIRST-finisher's wait: the
        earliest-completed task's output sits at the barrier until the
        last straggler lands and the merged exchange can start — the
        exact idle the overlapped path dispatches away."""
        import time as _time

        from blaze_tpu import config
        from blaze_tpu.bridge import tracing, xla_stats
        from blaze_tpu.parallel.stage import (DeviceExchange,
                                              DeviceExchangeError)
        from blaze_tpu.shuffle.ipc import write_batches_to_bytes

        done_ns: List[int] = []

        def one_map(m: int):
            out = self._run_map_task_loop(stage, m)
            if out is not None:
                done_ns.append(_time.perf_counter_ns())
                return ("cols", out)
            res = ("batches", self._run_map_task_collect(stage, m))
            done_ns.append(_time.perf_counter_ns())
            return res

        per_task = self._run_tasks(
            one_map, stage.num_tasks,
            f"stage {stage.sid} (device shuffle)", sid=stage.sid)
        batches = [b for kind, out in per_task if kind == "batches"
                   for b in out if b.num_rows]
        col_tasks = [out for kind, out in per_task
                     if kind == "cols" and out[2] > 0]
        loop_tasks = sum(1 for kind, _o in per_task if kind == "cols")
        blocks: Dict[int, bytes] = {}
        if batches or col_tasks:
            exchange = DeviceExchange()
            device = current_task().device_id
            # a wave of loop tasks only: the exchange starts from the
            # chips their columns lie on; any staged batch forces the
            # host concat
            placed = bool(col_tasks) and not batches
            with tracing.span("device_exchange", stage=stage.sid,
                              tasks=stage.num_tasks, partitions=n_out,
                              device=device, chips=exchange.mesh.size,
                              staged=not placed) as whole:
                with tracing.span(
                        "exchange_stage", stage=stage.sid,
                        tasks=stage.num_tasks, device=device,
                        staged_tasks=stage.num_tasks - loop_tasks) as st:
                    if placed:
                        est = sum(int(c.nbytes) for t in col_tasks
                                  for c in t[0])
                    else:
                        cols, valids = self._merge_map_outputs(
                            batches, col_tasks, schema)
                        est = sum(int(c.nbytes) for c in cols)
                    if est > config.SHUFFLE_DEVICE_MAX_BYTES.get():
                        raise DeviceExchangeError(
                            f"map output {est}B exceeds "
                            f"auron.tpu.shuffle.device.maxBytes")
                    if done_ns:
                        xla_stats.note_barrier_idle(max(
                            0, _time.perf_counter_ns() - min(done_ns)))
                    ticket = (exchange.dispatch_placed(
                        col_tasks, spec["key_indices"], n_out,
                        ctx=str(stage.sid)) if placed
                        else exchange.dispatch(
                            cols, valids, spec["key_indices"], n_out,
                            ctx=str(stage.sid)))
                    st.update(rows=ticket.n, bytes=est)
                    whole["rows"] = ticket.n
                exchange.settle(ticket)   # the wait for the collective
                with tracing.span("exchange_unstage", rows=ticket.n,
                                  partitions=n_out) as un:
                    parts = exchange.drain(ticket)
                    un["bytes_read"] = ticket.read_bytes or 0
                    arrow_schema = schema.to_arrow()
                    for r, (datas, vls) in enumerate(parts):
                        if datas and len(datas[0]):
                            rb = _columns_to_batch(datas, vls,
                                                   arrow_schema)
                            blocks[r] = write_batches_to_bytes([rb])
        return blocks, loop_tasks

    def _exchange_overlapped(self, stage: Stage, spec, n_out: int,
                             schema):
        """Overlap scheduler (auron.tpu.exchange.overlap.enable): each
        map task's columns are DISPATCHED into the mesh collective the
        moment its fold finishes (parallel/stage.py ExchangeTicket) and
        DRAINED on one background thread, so task k's all-to-all and
        partition split run while task k+1 is still folding.  Contracts
        kept vs the synchronous path:

          * dispatch/drain failures — injected `device-collective`
            faults included — are recorded and re-raised only AFTER the
            wave, so task-retry machinery never sees them and the
            wholesale file fallback stays the one failure path;
          * overlap is fenced at hash-table regrow boundaries
            (runtime/loop.py exchange_fence) to keep the atomic
            overflow/rehash contract;
          * cancellation propagates from the wave within one chunk, and
            the drainer thread is always joined (leak_report clean);
          * assembly concatenates per-partition rows in the synchronous
            merge order (staged-batch tasks by task index, then
            device-col tasks; a wave of device-col tasks only by chip,
            then task) and encodes ONE RecordBatch per partition, so
            published blocks are byte-identical.
        """
        import queue as _queue
        import time as _time

        import numpy as np

        from blaze_tpu import config
        from blaze_tpu.bridge import tracing, xla_stats
        from blaze_tpu.parallel.stage import (DeviceExchange,
                                              DeviceExchangeError)
        from blaze_tpu.runtime import loop as device_loop
        from blaze_tpu.shuffle.ipc import write_batches_to_bytes

        exchange = DeviceExchange()
        depth = max(1, int(config.EXCHANGE_OVERLAP_DEPTH.get()))
        max_bytes = config.SHUFFLE_DEVICE_MAX_BYTES.get()
        slots = threading.Semaphore(depth)
        lock = threading.Lock()
        idle = threading.Condition(lock)
        state = {"inflight": 0, "est": 0, "first_dispatch": None}
        errors: List[BaseException] = []
        parts_by_task: Dict[Tuple[int, int], list] = {}
        q: "_queue.Queue" = _queue.Queue()

        def drainer():
            while True:
                item = q.get()
                if item is None:
                    return
                key, ticket = item
                try:
                    # host columns already: drain() reads the received
                    # rows back through xputil.to_host (one `d2h` span)
                    parts = exchange.drain(ticket)
                    tracing.emit_span(
                        "device_exchange",
                        _time.perf_counter_ns() - ticket.dispatch_ns,
                        stage=stage.sid, task=key[-1], partitions=n_out,
                        overlapped=True, device=key[-2], rows=ticket.n,
                        staged=key[0] == 0)
                    xla_stats.note_exchange_overlap()
                    with lock:
                        parts_by_task[key] = parts
                except BaseException as e:  # re-raised after the wave
                    with lock:
                        errors.append(e)
                finally:
                    with idle:
                        state["inflight"] -= 1
                        idle.notify_all()
                    slots.release()

        def fence():
            # regrow boundary: drain every in-flight ticket before the
            # carry doubles (runtime/loop.py calls this pre-rehash)
            with idle:
                while state["inflight"]:
                    idle.wait(0.05)

        def one_map(m: int):
            out = self._run_map_task_loop(stage, m)
            if out is not None:
                kind, rank = "cols", 1
                cols, valids, nrows = out
            else:
                kind, rank = "batches", 0
                bs = [b for b in self._run_map_task_collect(stage, m)
                      if b.num_rows]
                cols, valids = _batches_to_columns(bs, schema)
                nrows = len(cols[0]) if cols else 0
            with lock:
                doomed = bool(errors)
            if doomed or nrows == 0:
                return (kind, None)
            fold_end = _time.perf_counter_ns()
            slots.acquire()  # backpressure: at most `depth` in flight
            try:
                with lock:
                    state["est"] += sum(int(c.nbytes) for c in cols)
                    est = state["est"]
                if est > max_bytes:
                    raise DeviceExchangeError(
                        f"map output {est}B exceeds "
                        f"auron.tpu.shuffle.device.maxBytes")
                ticket = (exchange.dispatch_placed(
                    [(cols, valids, nrows)], spec["key_indices"], n_out,
                    ctx=str(stage.sid)) if kind == "cols"
                    else exchange.dispatch(
                        cols, valids, spec["key_indices"], n_out,
                        ctx=str(stage.sid)))
                with idle:
                    if state["first_dispatch"] is None:
                        state["first_dispatch"] = ticket.dispatch_ns
                    state["inflight"] += 1
                # barrier idle here is only the backpressure wait for a
                # dispatch slot — vs the sync path's first-finisher wait
                # for the LAST straggler before its one merged exchange
                xla_stats.note_barrier_idle(
                    max(0, ticket.dispatch_ns - fold_end))
                chip = exchange.chip_of(cols) if kind == "cols" else 0
                q.put(((rank, chip, m), ticket))
            except BaseException as e:
                slots.release()
                with lock:
                    errors.append(e)
            return (kind, True)

        drain_thread = threading.Thread(
            target=drainer, name=f"exchange-drain-{stage.sid}",
            daemon=True)
        drain_thread.start()
        try:
            with device_loop.exchange_fence(fence):
                per_task = self._run_tasks(
                    one_map, stage.num_tasks,
                    f"stage {stage.sid} (device shuffle)",
                    sid=stage.sid)
        finally:
            q.put(None)
            drain_thread.join()
        if errors:
            raise errors[0]
        loop_tasks = sum(1 for kind, _o in per_task if kind == "cols")

        blocks: Dict[int, bytes] = {}
        # sync merge order: staged tasks by task index, then loop tasks;
        # a wave of loop tasks only by (chip, task), as the exchange
        # that starts from the chips (dispatch_placed) leaves them
        keys = sorted(parts_by_task)
        if any(rank == 0 for rank, _chip, _m in keys):
            keys.sort(key=lambda k: (k[0], k[2]))
        if keys:
            arrow_schema = schema.to_arrow()
            base = parts_by_task[keys[0]]
            for r in range(n_out):
                part_list = [parts_by_task[k][r] for k in keys]
                ncols = len(base[r][0])
                datas = [np.concatenate(
                    [np.asarray(p[0][i]).astype(base[r][0][i].dtype)
                     for p in part_list]) for i in range(ncols)]
                vls = [np.concatenate(
                    [np.asarray(p[1][i]).astype(bool)
                     for p in part_list]) for i in range(ncols)]
                if datas and len(datas[0]):
                    rb = _columns_to_batch(datas, vls, arrow_schema)
                    blocks[r] = write_batches_to_bytes([rb])
        return blocks, loop_tasks

    def _run_producer_device(self, stage: Stage) -> None:
        """Tentpole path: run the producer's map tasks — through the
        device-resident stage loop when the stage compiles, the staged
        per-batch executor otherwise — repartition their output through
        the mesh collective (parallel/stage.py DeviceExchange) and
        publish per-reduce-partition rows as in-memory IPC bytes blocks
        (shuffle/reader.py read_block consumes raw bytes directly).
        With auron.tpu.exchange.overlap.enable the exchange is
        dispatched per map task and drained in the background
        (_exchange_overlapped); otherwise one synchronous exchange runs
        after the wave (_exchange_sync) — both publish byte-identical
        blocks.  Any failure raises out to _run_producer, which falls
        back to the file path."""
        from blaze_tpu import config
        from blaze_tpu.plan.types import schema_from_dict

        spec = stage.device_spec
        n_out = int(spec["num_partitions"])
        schema = schema_from_dict(stage.out_schema)

        if config.EXCHANGE_OVERLAP_ENABLE.get():
            blocks, loop_tasks = self._exchange_overlapped(
                stage, spec, n_out, schema)
        else:
            blocks, loop_tasks = self._exchange_sync(
                stage, spec, n_out, schema)
        self.stage_placement[stage.sid] = {
            "compute": ("device-loop" if loop_tasks == stage.num_tasks
                        else "mixed" if loop_tasks else "staged"),
            "exchange": "device"}
        self._note_history_stage(stage.sid)
        from blaze_tpu.plan import adaptive, statstore
        if statstore.enabled() or adaptive.enabled():
            self._note_boundary(stage, [len(blocks.get(r, b""))
                                        for r in range(n_out)], "device")

        sid = stage.sid
        self._stage_outputs[sid] = {}

        def blocks_for(reduce_id: int):
            blk = blocks.get(reduce_id)
            if blk is not None:
                yield blk

        put_resource(stage.resource_id, blocks_for)
        if stage.resource_id not in self._resources:
            self._resources.append(stage.resource_id)

    def _run_producer_rss(self, stage: Stage, root: str) -> None:
        """Elastic shuffle tier: map tasks PUSH partition frames to the
        shared-storage shuffle service (shuffle/rss.py, the Celeborn
        analog) instead of writing local .data/.index files.  Each task
        retry pushes under a FRESH attempt id — commits are first-wins,
        so readers see exactly one complete attempt per map regardless
        of mid-push failures."""
        from blaze_tpu.bridge import tracing
        from blaze_tpu.bridge.runtime import NativeExecutionRuntime
        from blaze_tpu.plan.proto_serde import task_definition_to_bytes
        from blaze_tpu.shuffle.rss import rss_client_for

        part = self._part_of(stage)
        n_out = int(part.get("num_partitions", 1))
        client = rss_client_for(root, f"{self._run_id}-{stage.sid}",
                                stage.num_tasks, n_out)
        self._rss_clients.append(client)
        attempts: Dict[int, int] = {}
        attempts_lock = threading.Lock()

        def run_map(m: int) -> None:
            with attempts_lock:
                attempt = attempts.get(m, 0)
                attempts[m] = attempt + 1
            writer = client.partition_writer(m, attempt)
            rid = f"rss://{self._run_id}/{stage.sid}/{m}/a{attempt}"
            put_resource(rid, writer)
            try:
                plan = {"kind": "rss_shuffle_writer", "partitioning": part,
                        "rss_resource_id": rid,
                        "input": self._per_task(stage.plan, m,
                                                stage.num_tasks)}
                td = task_definition_to_bytes(
                    {"stage_id": stage.sid, "partition_id": m,
                     "num_partitions": stage.num_tasks, "plan": plan})
                rt = NativeExecutionRuntime(td).start()
                try:
                    for _ in rt.batches():
                        pass
                finally:
                    self._record_task_metrics(stage.sid, rt.finalize(),
                                              rt.task)
                if not writer.commit():
                    # a sibling attempt already committed: this output
                    # is dead (reject-late arbitration); the task still
                    # succeeds — the winner's frames are what readers see
                    from blaze_tpu.bridge import xla_stats as _xs
                    _xs.note_speculation(loser_commits_rejected=1)
            finally:
                remove_resource(rid)
            with self._metrics_lock:
                self.task_runs[(stage.sid, m)] = \
                    self.task_runs.get((stage.sid, m), 0) + 1

        from blaze_tpu.bridge import xla_stats
        loop_before = xla_stats.stage_loop_stats()["stage_loop_tasks"]
        with tracing.span("rss_exchange", stage=stage.sid,
                          tasks=stage.num_tasks, partitions=n_out):
            self._run_tasks(run_map, stage.num_tasks,
                            f"stage {stage.sid} (rss push)",
                            sid=stage.sid)
        self._note_placement(stage.sid, "rss", loop_before)

        self._stage_outputs[stage.sid] = {}
        timeout = self._timeout

        def blocks_for(reduce_id: int):
            for blk in client.reader_blocks(reduce_id, timeout_s=timeout):
                yield blk

        put_resource(stage.resource_id, blocks_for)
        if stage.resource_id not in self._resources:
            self._resources.append(stage.resource_id)

    def _run_producer_file(self, stage: Stage,
                           resident: bool = False) -> None:
        """The map wave of one exchange, each task's output committed as
        a `.data` / `.index` pair.  `resident` (`_resident_tier`): each
        task finds a sink in the resource map, under its own `.data` path,
        and commits its output there as it lies on the chip where its
        batches allow (shuffle/writer.py); a task that wrote files all the
        same, a spilled output and a recovered one are file segments among
        the resident blocks."""
        from blaze_tpu.shuffle.reader import FileSegmentBlock
        from blaze_tpu.shuffle.writer import RESIDENT_SINK, ResidentMapOutput

        os.makedirs(self._dir, exist_ok=True)
        part = self._part_of(stage)
        n_out = int(part.get("num_partitions", 1))

        for m in range(stage.num_tasks):
            data = self._map_data_path(stage.sid, m)
            for p in (data, data[:-5] + ".index"):
                if p not in self._files:
                    self._files.append(p)

        committed: Dict[int, Any] = {}   # resident commits, by map task

        def run_map(m: int) -> None:
            if not resident:
                return self._run_map_task(stage, part, m)
            # handed through the resource map, as the shuffle service's
            # partition writer is: the TaskDefinition still crosses the
            # plan-serde boundary with nothing but its paths
            rid = RESIDENT_SINK + self._map_data_path(stage.sid, m)
            put_resource(rid, functools.partial(self._commit_resident,
                                                committed, m))
            try:
                return self._run_map_task(stage, part, m)
            finally:
                remove_resource(rid)

        from blaze_tpu.bridge import tracing, xla_stats
        loop_before = xla_stats.stage_loop_stats()["stage_loop_tasks"]
        with tracing.span("shuffle_exchange", stage=stage.sid,
                          tasks=stage.num_tasks,
                          partitioning=part["kind"]) as attrs:
            try:
                results = self._run_tasks(
                    run_map,
                    stage.num_tasks, f"stage {stage.sid} (shuffle write)",
                    remote=self._map_remote(stage, part),
                    sid=stage.sid)
                outputs = {m: committed.get(m)
                           or self._read_map_output(stage, m, n_out)
                           for m in range(stage.num_tasks)}
            except BaseException:
                for out in committed.values():
                    out.release()
                raise
            finally:
                # attempt-suffixed outputs, claim files and a late
                # loser's leftovers all join the cleanup list even when
                # the wave itself failed
                self._register_stage_files(stage.sid)
            attrs["tier"] = ("file" if not committed else "resident"
                             if len(committed) == stage.num_tasks
                             else "mixed")
        self._absorb_remote_results(stage, results)
        self._note_placement(stage.sid, attrs["tier"], loop_before)

        self._stage_outputs[stage.sid] = outputs
        files = [e[1] for e in outputs.values() if isinstance(e, tuple)]
        # bytes that reached files alone: a resident output counts when
        # (and if) it spills
        xla_stats.note_host_exchange(sum(int(off[-1]) for off in files))
        from blaze_tpu.plan import adaptive, statstore
        if statstore.enabled() or adaptive.enabled():
            self._note_boundary(stage, [
                sum(int(off[r + 1] - off[r]) for off in files)
                + sum(int(out.partition_rows[r]) * out.row_bytes
                      for out in committed.values())
                for r in range(n_out)], attrs["tier"])

        sid = stage.sid

        def blocks_for(reduce_id: int):
            # live read of the output map, in map-id order: recovered
            # outputs are picked up, and reduce input order stays
            # deterministic across recovery rounds
            outputs = self._stage_outputs[sid]
            for map_id in sorted(outputs):
                entry = outputs[map_id]
                if entry is None:
                    # invalidated after a worker crash: the producer
                    # must re-run before any reduce reads this slot
                    raise FetchFailedError(
                        sid, map_id,
                        "map output invalidated after worker crash")
                if isinstance(entry, ResidentMapOutput):
                    block = entry.block(reduce_id, sid, map_id)
                    if block is not None:
                        yield block
                    continue
                data, offsets = entry
                length = offsets[reduce_id + 1] - offsets[reduce_id]
                if length:
                    yield FileSegmentBlock(data, offsets[reduce_id],
                                           length, stage_id=sid,
                                           map_id=map_id)

        put_resource(stage.resource_id, blocks_for)
        if stage.resource_id not in self._resources:
            self._resources.append(stage.resource_id)

    # -- lineage recovery --------------------------------------------------

    def _recover_map_output(self, ff: FetchFailedError,
                            stages_by_id: Dict[int, Stage]) -> None:
        """Re-run exactly the map task that produced a poisoned block and
        republish its output (Spark's stage-resubmission narrowed to one
        task: in-process there is no executor loss, so only the named
        output can be bad)."""
        stage = stages_by_id.get(ff.stage_id)
        if stage is None or stage.partitioning is None \
                or not 0 <= ff.map_id < stage.num_tasks:
            raise ff  # no lineage to recover from
        if ff.stage_id in self._cached_stages:
            # the poisoned blocks were a cross-query cache replay:
            # invalidate the entry and re-produce the stage for real
            # (cache bypassed — the run owns fresh files from here on)
            self._invalidate_cached_stage(ff.stage_id)
            self._run_producer_file(stage)
            return
        from blaze_tpu.bridge import tracing, xla_stats
        part = self._part_of(stage)
        with tracing.span("stage_recovery", stage=ff.stage_id,
                          map_task=ff.map_id):
            # the poisoned block IS the committed winner: clear its
            # commit claim first so the recovery re-run's fresh attempt
            # can win the first-wins arbitration (also heals a torn
            # claim-without-index crash window)
            self._clear_map_commit(stage.sid, ff.map_id)
            # through the task pool: the re-run gets the same bounded
            # retry/backoff as any task (transient faults may still
            # fire), and under the worker pool it is process-isolated
            # like any other map task
            remote = self._map_remote(stage, part)
            try:
                results = self._run_tasks(
                    lambda _i: self._run_map_task(stage, part, ff.map_id),
                    1,
                    f"stage {ff.stage_id} recovery (map {ff.map_id})",
                    remote=(lambda _i: remote(ff.map_id))
                    if remote else None)
            finally:
                self._register_stage_files(stage.sid)
            self._absorb_remote_results(stage, results,
                                        map_ids=[ff.map_id])
            self._stage_outputs[stage.sid][ff.map_id] = \
                self._read_map_output(stage, ff.map_id,
                                      int(part.get("num_partitions", 1)))
        xla_stats.note_stage_recovery(1)
        from blaze_tpu.bridge import history
        if history.enabled():
            history.note_stage_recovery(
                getattr(self._query, "query_id", None),
                sid=ff.stage_id, map_task=ff.map_id)

    def invalidate_worker_outputs(self, worker_id) -> None:
        """WorkerPool crash listener: re-validate every committed map
        output the dead worker produced.  Committed outputs are FILES
        (tmp + os.replace), so unlike an executor's in-memory block
        store they normally survive the process — but a crash wedged
        between the .data and .index commits (or mid-rename) leaves a
        torn pair.  Anything that fails validation is marked None in
        the map-output table; blocks_for converts that into the
        FetchFailedError the lineage recovery loop already handles, so
        ONLY the poisoned producers re-run."""
        if worker_id is None:
            return
        with self._metrics_lock:
            owned = [key for key, w in self._map_worker.items()
                     if w == worker_id]
        if not owned:
            return
        stages_by_id = {st.sid: st for st in self.stages}
        for sid, m in owned:
            stage = stages_by_id.get(sid)
            outputs = self._stage_outputs.get(sid)
            if stage is None or outputs is None or m not in outputs \
                    or outputs[m] is None:
                continue
            n_out = int(self._part_of(stage).get("num_partitions", 1))
            try:
                outputs[m] = self._read_map_output(stage, m, n_out)
            except FetchFailedError:
                outputs[m] = None
                log.warning("stage %d map %d output invalidated after "
                            "worker %s crash", sid, m, worker_id)

    # -- AQE small-query fast path -----------------------------------------

    @staticmethod
    def _scan_input_bytes(plan: Dict[str, Any]) -> int:
        """Total bytes behind every file scan in the plan; local files
        only — any non-stat-able input (remote FS, mem tables count 0)
        disables the estimate with a sentinel."""
        total = 0
        stack = [plan]
        while stack:
            d = stack.pop()
            if not isinstance(d, dict):
                continue
            if d.get("kind") in _SCAN_KINDS:
                for group in d.get("file_groups", []):
                    for p in group:
                        try:
                            total += os.path.getsize(p)
                        except (OSError, TypeError):
                            return 1 << 62
            for v in d.values():
                if isinstance(v, dict):
                    stack.append(v)
                elif isinstance(v, list):
                    stack.extend(x for x in v if isinstance(x, dict))
        return total

    def _run_single_task(self, plan: Dict[str, Any]) -> pa.Table:
        """Local execution mode: the whole query runs in-process with
        exchanges as LocalShuffleExchange — the analog of Spark AQE's
        local shuffle reader / coalesce-to-one-partition on small
        queries, where per-stage fixed costs (task spin-up, plan
        round-trips, shuffle files) dominate the actual work several
        times over.  Exchanges never leave the process, so nothing
        needs a wire encoding."""
        from blaze_tpu.plan import create_plan
        from blaze_tpu.plan.column_pruning import prune_columns
        from blaze_tpu.plan.fused import fuse_plan
        from blaze_tpu.plan.planner import collapse_filter_project

        node = fuse_plan(prune_columns(
            collapse_filter_project(create_plan(plan))))
        out = node.execute_collect().to_arrow()
        self._record_task_metrics(0, node.collect_metrics())
        if isinstance(out, pa.RecordBatch):
            return pa.Table.from_batches([out])
        return out

    def _note_boundary(self, stage: Stage, part_bytes: List[int],
                       exchange: str) -> None:
        """Capture one shuffle boundary's per-partition bytes for the
        statistics store, keyed by the producer's subtree fingerprint.
        Must run at producer completion — cleanup() clears the
        map-output table before run_collect returns.  (The rss tier
        holds no local sizes; its boundaries are not captured.)"""
        try:
            from blaze_tpu.plan import fingerprint as fp_mod
            part = (self._part_of(stage) if stage.partitioning is not None
                    else None)
            # an AQE-rewritten stage records under its DERIVED
            # fingerprint: its plan embeds run-scoped derived resource
            # ids, and the static identity must never accrete stats
            # from a rewritten shape
            fp = (stage.aqe or {}).get("fingerprint") or \
                fp_mod.subplan_fingerprint(stage.plan, part,
                                           stage.num_tasks)
            with self._metrics_lock:
                node = self.stage_metrics.get(stage.sid)
                rows = (int(node.values.get("output_rows", 0) or 0)
                        if node is not None else 0)
            self.stage_boundaries[stage.sid] = {
                "fingerprint": fp, "sid": stage.sid,
                "tasks": stage.num_tasks,
                "partitions": len(part_bytes),
                "partition_bytes": [int(b) for b in part_bytes],
                "exchange": exchange, "output_rows": rows}
        except Exception:
            pass

    def _stats_begin(self, plan: Dict[str, Any]) -> None:
        """Arm the statistics feedback plane for this run: fingerprint
        the plan, baseline the counter plane + duration reservoirs, and
        register live progress.  No-op (one boolean) when
        auron.tpu.stats.enable is off."""
        from blaze_tpu.plan import statstore
        self.stats_fingerprint = None
        self.stage_boundaries = {}
        self._stats_base = None
        if not statstore.enabled():
            return
        try:
            import time
            from blaze_tpu.bridge import xla_stats
            from blaze_tpu.plan import fingerprint as fp_mod
            self.stats_fingerprint = fp_mod.plan_fingerprint(plan)
            self._stats_base = xla_stats.snapshot()
            self._stats_dur0 = {k: len(v) for k, v in
                                xla_stats.duration_samples().items()}
            self._stats_t0 = time.perf_counter()
            qid = getattr(self._query, "query_id", None)
            if qid is not None:
                prior = statstore.prior(self.stats_fingerprint)
                prior_wall = None
                if prior is not None:
                    prior_wall = (prior.get("derived") or {}).get(
                        "wall_p50_s")
                    if prior_wall:
                        xla_stats.note_stats(eta_seeded=1)
                from blaze_tpu.serving import progress
                progress.note_query_start(qid, self.stats_fingerprint,
                                          prior_wall)
        except Exception:
            self.stats_fingerprint = None
            self._stats_base = None

    def _stats_end(self, ok: bool) -> None:
        """Close the feedback loop: settle live progress and, on
        success, ingest this run's observation into the statstore
        (failed runs would poison the priors).  Never raises."""
        base, self._stats_base = self._stats_base, None
        if base is None:
            return
        try:
            import time
            from blaze_tpu.bridge import xla_stats
            from blaze_tpu.plan import statstore
            wall_s = time.perf_counter() - self._stats_t0
            qid = getattr(self._query, "query_id", None)
            if qid is not None:
                from blaze_tpu.serving import progress
                progress.note_query_done(
                    qid, "finished" if ok else "failed", wall_s=wall_s)
            if not ok:
                return
            delta = xla_stats.delta(base)
            samples = xla_stats.duration_samples()
            task_ns = samples.get("task_ns", [])[
                self._stats_dur0.get("task_ns", 0):]
            # host-lane eviction evidence, as counter deltas (per-query
            # slice of the process plane; approximate under concurrency,
            # same caveat as the history attribution)
            reasons = {}
            for key, reason in (("stage_loop_fallbacks", "stage_loop"),
                                ("expr_eager_batches", "expr_eager"),
                                # per-column causes (ISSUE 20): WHY the
                                # stage left the device lane, not just
                                # that it did
                                ("host_evictions_string", "string_column"),
                                ("host_evictions_decimal",
                                 "decimal_column"),
                                ("host_evictions_other", "other_column")):
                n = int(delta.get(key, 0))
                if n > 0:
                    reasons[reason] = n
            statstore.ingest({
                "fingerprint": self.stats_fingerprint,
                "wall_s": wall_s,
                "task_ns": task_ns,
                "counters": {k: int(delta.get(k, 0))
                             for k in statstore.INGEST_COUNTERS},
                "fallback_reasons": reasons,
                "stages": sorted(self.stage_boundaries.values(),
                                 key=lambda b: b["sid"]),
            })
        except Exception:
            pass

    def run_collect(self, plan: Dict[str, Any]) -> pa.Table:
        """Execute the whole DAG; returns the result stage's output."""
        from blaze_tpu.bridge import tracing
        self._stats_begin(plan)
        ok = False
        # every span the scheduler (and anything below it) emits carries
        # the owning query id, so one query stitches into one trace
        with tracing.execution_context(
                query=getattr(self._query, "query_id", None)):
            try:
                out = self._run_collect(plan)
                ok = True
                return out
            finally:
                self._stats_end(ok)

    def _run_collect(self, plan: Dict[str, Any]) -> pa.Table:
        from blaze_tpu.bridge.runtime import NativeExecutionRuntime
        from blaze_tpu.plan.proto_serde import task_definition_to_bytes
        from blaze_tpu.plan.types import schema_from_dict

        from blaze_tpu import config
        if self._query is not None:
            self._query.check()  # shed before any work if already overdue
        self.stage_metrics = {}  # instance may be reused per query
        self.task_runs = {}
        self.task_chips = {}
        threshold = config.DAG_SINGLE_TASK_BYTES.get()
        if threshold > 0 and self._scan_input_bytes(plan) <= threshold:
            self.exec_mode = "local"
            try:
                return self._run_single_task(plan)
            finally:
                self.cleanup()  # the owned scratch dir lives on tmpfs

        self.exec_mode = "staged"
        # re-arm the scratch dir: a streaming executor reuses one
        # scheduler across micro-batch epochs and cleanup() removed it
        # at the end of the previous epoch
        os.makedirs(self._dir, exist_ok=True)
        # history-driven planning (plan/adaptive.py): seed broadcast
        # choices, partition counts and the agg strategy from statstore
        # priors BEFORE the split — _stats_begin already fingerprinted
        # the ORIGINAL plan, so priors stay keyed consistently across
        # cold and warm runs.  Returns the plan unchanged when off.
        from blaze_tpu.plan import adaptive
        self.aqe_events = []
        plan = adaptive.seed_plan(plan, self)
        stages = self.split(plan)
        stages_by_id = {st.sid: st for st in stages}
        max_recoveries = max(0, config.STAGE_MAX_RECOVERIES.get())
        # under the worker pool, a crashed worker's committed outputs
        # are re-validated immediately (invalidate_worker_outputs) so a
        # torn commit surfaces as lineage recovery, not a bad read
        crash_pool = None
        if config.WORKERS_ENABLE.get() or (
                self._query is not None
                and config.SERVING_USE_WORKERS.get()):
            from blaze_tpu.parallel import workers as _workers
            crash_pool = _workers.get_pool()
            if crash_pool is not None:
                crash_pool.add_crash_listener(
                    self.invalidate_worker_outputs)
        try:
            result = stages[-1]
            out_schema = schema_from_dict(result.out_schema).to_arrow()

            def run_result(p: int) -> List[pa.RecordBatch]:
                td = task_definition_to_bytes(
                    {"stage_id": result.sid, "partition_id": p,
                     "num_partitions": result.num_tasks,
                     "plan": self._per_task(result.plan, p,
                                            result.num_tasks)})
                rt = NativeExecutionRuntime(td).start()
                try:
                    return list(rt.batches())
                finally:
                    self._record_task_metrics(result.sid, rt.finalize(),
                                              rt.task)

            # bounded lineage recovery: a FetchFailedError anywhere in
            # the DAG names the producer map task whose output is
            # poisoned; re-run just that task, then resume from the
            # first stage that never completed (auron.tpu.stage
            # .maxRecoveries caps the rounds so persistent corruption
            # still terminates)
            completed: set = set()
            recoveries = 0
            # adaptive re-planning hook (plan/adaptive.py): fires
            # between a producer's map-output commit and the next
            # dispatch; None when auron.tpu.aqe.enable is off
            aqe_rt = adaptive.runtime_for(self)
            while True:
                try:
                    for st in stages[:-1]:
                        if st.sid not in completed:
                            self._run_producer(st)
                            completed.add(st.sid)
                            if aqe_rt is not None:
                                aqe_rt.on_producer_commit(
                                    st, completed, stages_by_id)
                    from blaze_tpu.bridge import xla_stats
                    loop_before = xla_stats.stage_loop_stats()[
                        "stage_loop_tasks"]
                    parts = self._run_tasks(
                        run_result, result.num_tasks,
                        f"stage {result.sid} (result)", sid=result.sid)
                    self._note_placement(result.sid, "result",
                                         loop_before)
                    break
                except FetchFailedError as ff:
                    recoveries += 1
                    if recoveries > max_recoveries:
                        raise FetchFailedError(
                            ff.stage_id, ff.map_id,
                            f"{ff.reason} (gave up after "
                            f"{max_recoveries} recovery rounds)") from ff
                    self._recover_map_output(ff, stages_by_id)
            batches = [b for bl in parts for b in bl if b.num_rows]
            if not batches:
                return out_schema.empty_table()
            return pa.Table.from_batches(batches)
        finally:
            if crash_pool is not None:
                crash_pool.remove_crash_listener(
                    self.invalidate_worker_outputs)
            self.cleanup()

    def cleanup(self) -> None:
        """Idempotent AND safe under concurrent callers: run_collect's
        finally, a cancelling service thread, context-manager exit and
        __del__ may all race here.  State lists are swapped out under a
        lock, so every resource/file is released exactly once."""
        # __del__ can run during interpreter shutdown after the lock (or
        # the module globals) are torn down — degrade to best-effort
        lock = getattr(self, "_cleanup_lock", None)
        if lock is None:
            return
        with lock:
            resources, self._resources = self._resources, []
            files, self._files = self._files, []
            rss_clients, self._rss_clients = self._rss_clients, []
            resident, self._resident_outputs = self._resident_outputs, []
            self._stage_outputs = {}
            self._map_worker = {}
            self._map_attempt = {}
            self._attempt_seq = {}
        for rid in resources:
            try:
                remove_resource(rid)
            except Exception:
                pass
        for path in files:
            try:
                os.unlink(path)
            except OSError:
                pass
        for client in rss_clients:
            try:
                client.cleanup()
            except Exception:
                pass
        for output in resident:
            # the rows on the chip and their memory-manager charge
            output.release()
        if self._owns_dir:
            import shutil
            # recreated lazily by the next _run_producer if reused
            shutil.rmtree(self._dir, ignore_errors=True)

    def leak_report(self) -> Dict[str, List[str]]:
        """What this scheduler still holds: shuffle temp files on disk,
        resource-map entries, RSS shuffle roots, map outputs resident on
        the chip (or their memory-manager charge), and the owned scratch
        dir.  Empty lists everywhere == nothing leaked; tests assert
        exactly that after failed/cancelled queries."""
        from blaze_tpu.bridge.resource import get_resource
        report: Dict[str, List[str]] = {
            "files": [], "resources": [], "rss_roots": [], "dirs": [],
            "resident": []}
        with self._cleanup_lock:
            files = list(self._files)
            resources = list(self._resources)
            rss_clients = list(self._rss_clients)
            resident = list(self._resident_outputs)
        report["resident"] = [
            f"{out.name}: {out.rows} rows, {out.mem_used} B charged"
            for out in resident if out.on_chip or out.mem_used]
        for path in files:
            if os.path.exists(path):
                report["files"].append(path)
        for rid in resources:
            if get_resource(rid) is not None:
                report["resources"].append(rid)
        for client in rss_clients:
            if os.path.isdir(client.root):
                report["rss_roots"].append(client.root)
        if self._owns_dir and os.path.isdir(self._dir):
            leftovers = [os.path.join(self._dir, f)
                         for f in os.listdir(self._dir)]
            if leftovers:
                report["dirs"].append(self._dir)
                report["files"].extend(leftovers)
        # not a leak: the flight recorder's post-mortem artifact for this
        # query, referenced here so failure triage starts from the leak
        # report.  Key present only when a dump exists.
        qid = getattr(self._query, "query_id", None)
        if qid is not None:
            from blaze_tpu.bridge import context as _bctx
            dump = _bctx.flight_dump(qid)
            if dump is not None and dump.get("path"):
                report["flight_dump"] = [dump["path"]]
        return report

    def __enter__(self) -> "DagScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.cleanup()

    def __del__(self) -> None:
        # last-resort backstop for callers that drop the scheduler
        # without run_collect ever reaching its finally (put_resource
        # entries would otherwise leak process-wide); interpreter
        # shutdown may have torn down globals, so never let this raise
        try:
            self.cleanup()
        except Exception:
            pass

    # -- observability -----------------------------------------------------

    def describe(self) -> str:
        lines = []
        for st in self.stages:
            kind = "result" if st.partitioning is None else \
                st.partitioning["kind"]
            lines.append(f"stage {st.sid}: tasks={st.num_tasks} "
                         f"out={kind} deps={st.deps}")
        return "\n".join(lines)


def execute_spark_plan_json(plan_json, num_partitions: int = 2,
                            work_dir: Optional[str] = None) -> pa.Table:
    """Front door: Spark `toJSON` physical plan -> converter -> stage DAG
    -> protobuf tasks -> engine.  The full L6->wire->L3 production path in
    one call (ref: what AuronConverters + Spark's scheduler do together)."""
    import time as _time

    from blaze_tpu.bridge import ui
    from blaze_tpu.convert.spark import convert_spark_plan
    res = convert_spark_plan(plan_json, num_partitions=num_partitions)
    t0 = _time.perf_counter()
    out = DagScheduler(work_dir=work_dir).run_collect(res.plan)
    ui.record_completion(res.query_id, _time.perf_counter() - t0)
    return out

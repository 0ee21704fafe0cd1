"""Per-task execution runtime: the NativeExecutionRuntime analog.

Parity: native-engine/auron/src/rt.rs (`:64` NativeExecutionRuntime, `:76`
start — decode TaskDefinition, create plan, spawn producer; `:142` the
sync_channel(1) producer/consumer handoff; `:175-192` the hot batch loop;
`:253` next_batch; `:287` finalize) and exec.rs:42 callNative / :122
nextBatch / :133 finalizeNative / :144 onExit.

The producer thread pulls batches from the operator tree and pushes Arrow
batches into a bounded queue — device work is enqueued ahead of the host
consumer (XLA async dispatch is the tokio analog), and the queue depth is
the `auron.input.batch.prefetch` double-buffering knob.
"""

from __future__ import annotations

import logging
import queue
import threading
import traceback
from typing import Any, Callable, Dict, Iterator, List, Optional

import pyarrow as pa

from blaze_tpu import config
from blaze_tpu.bridge.context import TaskContext, task_scope
from blaze_tpu.bridge.metrics import MetricNode
from blaze_tpu.ops.base import CoalesceStream, ExecutionPlan

log = logging.getLogger("blaze_tpu.runtime")

_SENTINEL = object()


class NativeExecutionRuntime:
    """One runtime per task attempt (ref rt.rs:64)."""

    def __init__(self, task_definition: Dict[str, Any],
                 plan: Optional[ExecutionPlan] = None):
        from blaze_tpu.bridge.placement import ensure_placement
        from blaze_tpu.plan import create_plan, decode_task_definition
        from blaze_tpu.plan.fused import fuse_plan
        ensure_placement()  # once per process; may pin compute to host XLA
        td = decode_task_definition(task_definition)
        from blaze_tpu.bridge.context import current_query
        from blaze_tpu.parallel.mesh import task_device
        self.task = TaskContext(
            stage_id=td.get("stage_id", 0),
            partition_id=td.get("partition_id", 0),
            num_partitions=td.get("num_partitions", 1),
            task_attempt_id=td.get("task_attempt_id", 0),
            # a pure function of the partition id and the visible
            # devices: a retry or a speculative attempt of this task
            # lands on the same chip, and a warm-up pass compiles
            # exactly what the window runs
            device=task_device(td.get("partition_id", 0)),
            # the constructor runs on the task-pool thread inside the
            # service's query_scope: the query rides the TaskContext into
            # the producer/prefetch threads that re-enter via task_scope
            query=current_query())
        from blaze_tpu.bridge.context import current_attempt_token
        tok = current_attempt_token()
        if tok is not None:
            # speculative-attempt cancel token: when the sibling attempt
            # commits first, check_running() turns into TaskKilledError
            # at the next batch boundary and this attempt's output is
            # discarded before it can reach a commit point
            self.task.is_running = lambda: not tok.is_set()
        from blaze_tpu.plan.column_pruning import prune_columns
        from blaze_tpu.plan.planner import collapse_filter_project
        self.plan = fuse_plan(prune_columns(collapse_filter_project(
            plan if plan is not None else create_plan(td["plan"]))))
        depth = max(1, config.INPUT_BATCH_PREFETCH.get())
        self._queue: "queue.Queue" = queue.Queue(maxsize=depth)
        self._error: Optional[BaseException] = None
        self._finalized = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # host-pinned compute has no async device work to overlap with the
        # consumer: the producer thread + queue handoff would only add GIL
        # contention and context switches, so pull batches synchronously
        # (the reference's tokio runtime is the analog of the THREADED
        # path, rt.rs:114-140; host mode ~ its current_thread runtime)
        from blaze_tpu.bridge.placement import host_resident
        self._sync = host_resident()
        self._sync_iter = None

    # -- lifecycle (ref rt.rs:76 start) ------------------------------------
    def start(self) -> "NativeExecutionRuntime":
        if self._sync:
            return self
        self._thread = threading.Thread(target=self._produce, daemon=True,
                                        name=f"blaze-task-"
                                             f"{self.task.stage_id}."
                                             f"{self.task.partition_id}")
        self._thread.start()
        return self

    def _sync_batches(self) -> Iterator[pa.RecordBatch]:
        # arrow_batches: plans whose output is already Arrow-resident
        # (fused host agg, scans) skip the ColumnBatch round trip; the
        # base implementation is exactly the old compact().to_arrow()
        from blaze_tpu.bridge import tracing, xla_stats
        xla_stats.note_task_placed(self.task.device_id)
        with task_scope(self.task), \
                tracing.execution_context(stage=self.task.stage_id,
                                          partition=self.task.partition_id), \
                tracing.span("task", mode="sync",
                             device=self.task.device_id):
            stream = self.plan.arrow_batches(self.task.partition_id)
            stats = config.INPUT_BATCH_STATISTICS.get()
            for rb in stream:
                if self._finalized.is_set():
                    return
                if rb.num_rows == 0:
                    continue
                if stats:
                    m = self.plan.metrics
                    m.add("output_batches_total", 1)
                    m.add("output_rows_total", rb.num_rows)
                    m.add("output_bytes_total", rb.nbytes)
                yield rb

    def _produce(self) -> None:
        from blaze_tpu.bridge import tracing, xla_stats
        xla_stats.note_task_placed(self.task.device_id)
        try:
            with task_scope(self.task), \
                    tracing.execution_context(
                        stage=self.task.stage_id,
                        partition=self.task.partition_id), \
                    tracing.span("task", mode="producer",
                                 device=self.task.device_id):
                stream = self.plan.arrow_batches(self.task.partition_id)
                stats = config.INPUT_BATCH_STATISTICS.get()
                for rb in stream:  # HOT LOOP (ref rt.rs:175-192)
                    if self._finalized.is_set():
                        return
                    if rb.num_rows == 0:
                        continue
                    if stats:
                        m = self.plan.metrics
                        m.add("output_batches_total", 1)
                        m.add("output_rows_total", rb.num_rows)
                        m.add("output_bytes_total", rb.nbytes)
                    self._put(rb)
        except BaseException as e:  # surfaced like setError
            log.error("[stage %d partition %d] native execution failed:\n%s",
                      self.task.stage_id, self.task.partition_id,
                      traceback.format_exc())
            self._error = e
        finally:
            self._put(_SENTINEL)

    def _put(self, item) -> None:
        while not self._finalized.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    # -- consumer side (ref rt.rs:253 next_batch) --------------------------
    def next_batch(self, timeout: Optional[float] = None
                   ) -> Optional[pa.RecordBatch]:
        """Next output batch, or None at end-of-stream.  Raises the
        producer's error if it failed."""
        if self._sync:
            if self._sync_iter is None:
                self._sync_iter = self._sync_batches()
            return next(self._sync_iter, None)
        if self._error is not None:
            raise self._error
        item = self._queue.get(timeout=timeout)
        if item is _SENTINEL:
            if self._error is not None:
                raise self._error
            return None
        return item

    def batches(self) -> Iterator[pa.RecordBatch]:
        while True:
            rb = self.next_batch()
            if rb is None:
                return
            yield rb

    # -- teardown (ref rt.rs:287 finalize) ---------------------------------
    def finalize(self) -> MetricNode:
        self._finalized.set()
        self.task.is_running = lambda: False
        if self._sync:
            self._sync_iter = None
            return self.plan.collect_metrics()
        # drain so a blocked producer can observe the flag and exit
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        if self._thread is not None:
            self._thread.join(timeout=10)
        return self.plan.collect_metrics()


def execute_plan(plan_or_td, partition: Optional[int] = None
                 ) -> List[pa.RecordBatch]:
    """Convenience driver: run one task to completion (test helper —
    the NativeHelper.executeNativePlan analog)."""
    if isinstance(plan_or_td, ExecutionPlan):
        parts = ([partition] if partition is not None
                 else range(plan_or_td.num_partitions))
        out: List[pa.RecordBatch] = []
        for p in parts:
            rt = NativeExecutionRuntime(
                {"stage_id": 0, "partition_id": p,
                 "num_partitions": plan_or_td.num_partitions},
                plan=plan_or_td).start()
            try:
                out.extend(rt.batches())
            finally:
                rt.finalize()
        return out
    rt = NativeExecutionRuntime(plan_or_td).start()
    try:
        return list(rt.batches())
    finally:
        rt.finalize()

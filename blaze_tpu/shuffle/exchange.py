"""Local shuffle exchange: stage boundary without a cluster.

The reference relies on Spark's BlockManager for transport; in spark-local
mode the full native write/read path is still exercised through real files
(SURVEY.md §4 'multi-node without a cluster').  LocalShuffleExchange is that
analog: map partitions write .data/.index via ShuffleWriterExec, reduce
partitions read their file segments via IpcReaderExec — same files, same
frames, same index contract as the distributed deployment.
"""

from __future__ import annotations

import os
import tempfile
import uuid
from typing import List, Optional

import numpy as np

from blaze_tpu.bridge.context import (TaskContext, current_task,
                                      task_scope)
from blaze_tpu.bridge.resource import put_resource, remove_resource
from blaze_tpu.faults import FetchFailedError
from blaze_tpu.ops.base import ExecutionPlan
from blaze_tpu.schema import Schema
from blaze_tpu.shuffle.partitioning import Partitioning
from blaze_tpu.shuffle.reader import FileSegmentBlock, IpcReaderExec
from blaze_tpu.shuffle.writer import ShuffleWriterExec


def read_index_file(path: str, expected_partitions: Optional[int] = None,
                    data_file: Optional[str] = None) -> List[int]:
    """Cumulative offsets (ref AuronShuffleWriterBase.scala:68-78).

    A shuffle index is the map task's MapStatus: if it is truncated or
    inconsistent, every slice computed from it is garbage.  Validate the
    shape up front — length a multiple of 8, `expected_partitions`+1
    entries when the reducer count is known, monotone offsets starting
    at 0, last offset within the `.data` file — and raise a clear
    FetchFailedError (callers attach the producer's stage/map identity)
    instead of silently slicing garbage."""

    def bad(why: str) -> FetchFailedError:
        from blaze_tpu.bridge import xla_stats
        xla_stats.note_fetch_failure()
        return FetchFailedError(reason=f"bad shuffle index {path}: {why}")

    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError as e:
        raise bad(str(e)) from e
    if len(data) == 0 or len(data) % 8:
        raise bad(f"{len(data)} bytes is not a whole number of "
                  f"int64 offsets")
    offsets = np.frombuffer(data, dtype="<i8")
    if expected_partitions is not None \
            and len(offsets) != expected_partitions + 1:
        raise bad(f"{len(offsets)} offsets, want "
                  f"{expected_partitions + 1} for {expected_partitions} "
                  f"reduce partitions (truncated index?)")
    if offsets[0] != 0:
        raise bad(f"first offset {offsets[0]} != 0")
    if len(offsets) > 1 and bool(np.any(np.diff(offsets) < 0)):
        raise bad("offsets are not monotone non-decreasing")
    if data_file is not None:
        try:
            size = os.path.getsize(data_file)
        except OSError as e:
            raise bad(f"data file missing: {e}") from e
        if int(offsets[-1]) > size:
            raise bad(f"last offset {int(offsets[-1])} exceeds data "
                      f"file size {size}")
    return offsets.tolist()


class LocalShuffleExchange(ExecutionPlan):
    """Materializing exchange: runs all map tasks on first reduce pull."""

    def __init__(self, child: ExecutionPlan, partitioning: Partitioning,
                 work_dir: Optional[str] = None, stage_id: int = 0):
        super().__init__([child])
        self.partitioning = partitioning
        self.stage_id = stage_id
        self._dir = work_dir or tempfile.mkdtemp(prefix="blaze-exchange-")
        self._shuffle_id = uuid.uuid4().hex[:12]
        self._materialized = False
        self._map_outputs: List[tuple] = []  # (data_file, offsets)
        self.reader = IpcReaderExec(
            f"shuffle://{self._shuffle_id}", child.schema,
            partitioning.num_partitions)
        self.reader._children = []  # standalone reader node

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    @property
    def num_partitions(self) -> int:
        return self.partitioning.num_partitions

    def _materialize(self) -> None:
        if self._materialized:
            return
        child = self.children[0]
        for map_id in range(child.num_partitions):
            data = os.path.join(self._dir,
                                f"shuffle-{self._shuffle_id}-{map_id}.data")
            index = data.replace(".data", ".index")
            writer = ShuffleWriterExec(child, self.partitioning, data, index)
            writer.metrics = self.metrics  # surface write metrics here
            # a map task inside the reducing task: on the reducer's chip
            with task_scope(TaskContext(stage_id=self.stage_id,
                                        partition_id=map_id,
                                        num_partitions=child.num_partitions,
                                        device=current_task().device)):
                list(writer.execute(map_id))
            self._map_outputs.append((data, read_index_file(
                index,
                expected_partitions=self.partitioning.num_partitions,
                data_file=data)))

        def blocks_for(reduce_id: int):
            for map_id, (data, offsets) in enumerate(self._map_outputs):
                length = offsets[reduce_id + 1] - offsets[reduce_id]
                if length:
                    yield FileSegmentBlock(data, offsets[reduce_id], length,
                                           stage_id=self.stage_id,
                                           map_id=map_id)
        put_resource(f"shuffle://{self._shuffle_id}", blocks_for)
        self._materialized = True

    def execute(self, partition: int):
        self._materialize()
        return self.reader.execute(partition)

    def cleanup(self) -> None:
        remove_resource(f"shuffle://{self._shuffle_id}")
        for data, _ in self._map_outputs:
            for p in (data, data.replace(".data", ".index")):
                try:
                    os.unlink(p)
                except OSError:
                    pass
        self._map_outputs = []
        self._materialized = False

"""Entry point `runtime_pair`: a map stage and a reduce stage as
TaskDefinition bytes straight into `NativeExecutionRuntime`, tasks of a
stage on the program's task pool (spark local[N]), the reduce side fed by
a block map registered as a resource (the MapOutputTracker analog).

Copied from `bench.py` run_engine; no scheduler, no joins, no sort.
"""

from __future__ import annotations

import os
import shutil
import tempfile

import pyarrow as pa

STAGE_TIMEOUT_S = 300.0


class Entry:
    def __init__(self, query, paths, tables, cfg, work_dir: str):
        self.stages = query.plan(paths, tables, cfg["partitions"])
        self.work_dir = work_dir
        self.shuffle_dir = None

    def begin(self) -> None:
        self.shuffle_dir = tempfile.mkdtemp(prefix="shuffle-",
                                            dir=self.work_dir)

    def end(self) -> None:
        shutil.rmtree(self.shuffle_dir, ignore_errors=True)
        self.shuffle_dir = None

    def problem(self):
        return None

    def run(self) -> pa.Table:
        from blaze_tpu.bridge import profiling
        from blaze_tpu.bridge.resource import put_resource
        from blaze_tpu.bridge.runtime import NativeExecutionRuntime
        from blaze_tpu.bridge.tasks import run_tasks
        from blaze_tpu.plan.proto_serde import task_definition_to_bytes
        from blaze_tpu.shuffle.exchange import read_index_file
        from blaze_tpu.shuffle.reader import FileSegmentBlock
        st, d = self.stages, self.shuffle_dir
        n_maps, n_reduces = st["n_maps"], st["n_reduces"]

        def run_map(m):
            td = task_definition_to_bytes(st["map"](m, d))
            rt = NativeExecutionRuntime(td).start()
            try:
                for _ in rt.batches():
                    pass
            finally:
                profiling.record_metrics(rt.finalize().to_dict())

        run_tasks(run_map, n_maps, STAGE_TIMEOUT_S, "pair map stage")
        offsets = [read_index_file(os.path.join(d, f"shuffle_{m}.index"))
                   for m in range(n_maps)]

        def blocks_for(partition):
            out = []
            for m in range(n_maps):
                off = offsets[m]
                length = off[partition + 1] - off[partition]
                if length > 0:
                    out.append(FileSegmentBlock(
                        os.path.join(d, f"shuffle_{m}.data"),
                        off[partition], length))
            return out

        put_resource(st["resource_id"], blocks_for)

        def run_reduce(r):
            td = task_definition_to_bytes(st["reduce"](r))
            rt = NativeExecutionRuntime(td).start()
            try:
                return list(rt.batches())
            finally:
                profiling.record_metrics(rt.finalize().to_dict())

        parts = run_tasks(run_reduce, n_reduces, STAGE_TIMEOUT_S,
                          "pair reduce stage")
        batches = [rb for part in parts for rb in part]
        return pa.Table.from_batches(batches)

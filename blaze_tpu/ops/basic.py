"""Filter, Project, Limit, Union, RenameColumns, Expand, Empty, Debug.

Parity: filter_exec.rs / project_exec.rs (both through the shared
CachedExprsEvaluator, ref common/cached_exprs_evaluator.rs:522),
limit_exec.rs:305, union_exec.rs (per-input partition routing, proto
auron.proto:552-562), rename_columns_exec.rs, expand_exec.rs:506
(grouping-sets fan-out), empty_partitions_exec.rs, debug_exec.rs.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

from blaze_tpu.batch import ColumnBatch
from blaze_tpu.exprs import (CachedExprsEvaluator, FusedExprsEvaluator,
                             PhysicalExpr)
from blaze_tpu.ops.base import BatchIterator, CoalesceStream, ExecutionPlan
from blaze_tpu.schema import Field, Schema


class FilterExec(ExecutionPlan):
    """Selection-mask filter; no compaction until density drops
    (ref filter_exec.rs; compaction by CoalesceStream)."""

    accepts_prune = True

    def __init__(self, child: ExecutionPlan, predicates: Sequence[PhysicalExpr]):
        super().__init__([child])
        self._predicates = list(predicates)

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int, extra_prune=None) -> BatchIterator:
        """`extra_prune`: a condition of the consumer's over this filter's
        output, which is its child's: handed on with the filter's own
        conjuncts to a child that prunes by statistics
        (`execute_pruned`)."""
        # per-partition instance, but the compiled program behind it is
        # resolved from the process-wide fingerprint cache (exprs/program)
        ev = FusedExprsEvaluator(filters=self._predicates,
                                 in_schema=self.schema)
        conjuncts = self._predicates + (
            [extra_prune] if extra_prune is not None else [])
        def gen():
            for batch in self.children[0].execute_pruned(partition,
                                                         conjuncts):
                yield ev.filter(batch)
        return iter(CoalesceStream(gen(), metrics=self.metrics))


class ProjectExec(ExecutionPlan):
    def __init__(self, child: ExecutionPlan,
                 exprs: Sequence[PhysicalExpr], names: Sequence[str]):
        super().__init__([child])
        self._exprs = list(exprs)
        self._names = list(names)
        self._out_schema: Optional[Schema] = None

    @property
    def schema(self) -> Schema:
        if self._out_schema is None:
            in_schema = self.children[0].schema
            self._out_schema = Schema([
                Field(n, e.data_type(in_schema)) for n, e in
                zip(self._names, self._exprs)])
        return self._out_schema

    def execute(self, partition: int) -> BatchIterator:
        ev = FusedExprsEvaluator(projections=self._exprs,
                                 in_schema=self.children[0].schema)
        out_schema = self.schema
        for batch in self.children[0].execute(partition):
            yield ev.project(batch, out_schema)


class FilterProjectExec(ExecutionPlan):
    """Fused filter+project sharing one evaluator (the reference fuses these
    through the shared CachedExprsEvaluator when adjacent)."""

    def __init__(self, child: ExecutionPlan, predicates: Sequence[PhysicalExpr],
                 exprs: Sequence[PhysicalExpr], names: Sequence[str]):
        super().__init__([child])
        self._predicates = list(predicates)
        self._exprs = list(exprs)
        self._names = list(names)
        self._out_schema: Optional[Schema] = None

    @property
    def schema(self) -> Schema:
        if self._out_schema is None:
            in_schema = self.children[0].schema
            self._out_schema = Schema([
                Field(n, e.data_type(in_schema)) for n, e in
                zip(self._names, self._exprs)])
        return self._out_schema

    def execute(self, partition: int) -> BatchIterator:
        ev = FusedExprsEvaluator(filters=self._predicates,
                                 projections=self._exprs,
                                 in_schema=self.children[0].schema)
        out_schema = self.schema
        def gen():
            for batch in self.children[0].execute(partition):
                yield ev.filter_project(batch, out_schema)
        return iter(CoalesceStream(gen(), metrics=self.metrics))


class LimitExec(ExecutionPlan):
    """LocalLimit (per partition) / GlobalLimit on partition 0, with
    offset-skip (ref limit_exec.rs:305, LimitExecNode offset field)."""

    def __init__(self, child: ExecutionPlan, limit: int, offset: int = 0):
        super().__init__([child])
        self._limit = limit
        self._offset = offset

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int) -> BatchIterator:
        to_skip = self._offset
        remaining = self._limit
        for batch in self.children[0].execute(partition):
            if remaining <= 0:
                break
            n = batch.selected_count()
            if to_skip:
                if n <= to_skip:
                    to_skip -= n
                    continue
                batch = batch.compact().take(list(range(to_skip, n)))
                n -= to_skip
                to_skip = 0
            if n <= remaining:
                remaining -= n
                yield batch
            else:
                packed = batch.compact().take(list(range(remaining)))
                remaining = 0
                yield packed
                break


class UnionExec(ExecutionPlan):
    """Concatenates children partition-wise (ref union_exec.rs; proto
    union inputs carry num_partitions/cur_partition, auron.proto:552-562)."""

    def __init__(self, children: Sequence[ExecutionPlan]):
        super().__init__(children)

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    @property
    def num_partitions(self) -> int:
        return max(c.num_partitions for c in self.children)

    def execute(self, partition: int) -> BatchIterator:
        for child in self.children:
            if partition < child.num_partitions:
                yield from child.execute(partition)


class RenameColumnsExec(ExecutionPlan):
    """Schema aliasing between stages (ref rename_columns_exec.rs)."""

    def __init__(self, child: ExecutionPlan, names: Sequence[str]):
        super().__init__([child])
        self._names = list(names)

    @property
    def schema(self) -> Schema:
        child_schema = self.children[0].schema
        return Schema([Field(n, f.data_type, f.nullable)
                       for n, f in zip(self._names, child_schema)])

    def execute(self, partition: int) -> BatchIterator:
        out_schema = self.schema
        for batch in self.children[0].execute(partition):
            yield ColumnBatch(out_schema, batch.columns, batch.num_rows,
                              batch.selection)


class ExpandExec(ExecutionPlan):
    """Grouping-sets fan-out: each input row is projected through K
    projection lists (ref expand_exec.rs:506)."""

    def __init__(self, child: ExecutionPlan,
                 projections: Sequence[Sequence[PhysicalExpr]],
                 names: Sequence[str]):
        super().__init__([child])
        self._projections = [list(p) for p in projections]
        self._names = list(names)
        self._out_schema: Optional[Schema] = None

    @property
    def schema(self) -> Schema:
        if self._out_schema is None:
            in_schema = self.children[0].schema
            self._out_schema = Schema([
                Field(n, e.data_type(in_schema)) for n, e in
                zip(self._names, self._projections[0])])
        return self._out_schema

    def execute(self, partition: int) -> BatchIterator:
        out_schema = self.schema
        evs = [CachedExprsEvaluator(projections=p) for p in self._projections]
        def gen():
            for batch in self.children[0].execute(partition):
                for ev in evs:
                    yield ev.project(batch, out_schema)
        return iter(CoalesceStream(gen(), metrics=self.metrics))


class EmptyPartitionsExec(ExecutionPlan):
    """N empty partitions (ref empty_partitions_exec.rs)."""

    def __init__(self, schema: Schema, num_partitions: int = 1):
        super().__init__()
        self._schema = schema
        self._n = num_partitions

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def num_partitions(self) -> int:
        return self._n

    def execute(self, partition: int) -> BatchIterator:
        return iter(())


class DebugExec(ExecutionPlan):
    """Pass-through that logs batches (ref debug_exec.rs)."""

    def __init__(self, child: ExecutionPlan, tag: str = "debug"):
        super().__init__([child])
        self._tag = tag

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int) -> BatchIterator:
        import logging
        log = logging.getLogger("blaze_tpu.debug")
        for i, batch in enumerate(self.children[0].execute(partition)):
            log.info("[%s] partition=%d batch=%d rows=%d", self._tag,
                     partition, i, batch.selected_count())
            yield batch

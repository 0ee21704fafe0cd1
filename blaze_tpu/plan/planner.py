"""Physical planner: plan IR dicts -> operator trees.

Parity: PhysicalPlanner::create_plan (ref auron-planner/src/planner.rs:
122-922) pattern-matching the PhysicalPlanNode oneof (28 operators,
auron.proto:27-56), parse_protobuf_partitioning (planner.rs:1201) and
TaskDefinition decoding (auron.proto:814, rt.rs:79-90).

Node kinds: parquet_scan, memory_scan, filter, project, filter_project,
sort, limit, union, rename_columns, expand, empty_partitions, debug,
hash_agg, sort_agg, sort_merge_join, hash_join, broadcast_join, window,
generate, shuffle_writer, rss_shuffle_writer, ipc_reader, ipc_writer,
ffi_reader, coalesce_batches, parquet_sink.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

from blaze_tpu.ops import (AggExec, DebugExec, EmptyPartitionsExec,
                           ExpandExec, FilterExec, FilterProjectExec,
                           GenerateExec, LimitExec, MemoryScanExec,
                           ParquetScanExec, ProjectExec, RenameColumnsExec,
                           SortExec, UnionExec, WindowExec)
from blaze_tpu.ops.agg import AggExecMode, AggMode, make_agg
from blaze_tpu.ops.agg.exec import AggExec as _AggExec
from blaze_tpu.ops.base import CoalesceStream, ExecutionPlan
from blaze_tpu.ops.generate import (ExplodeGenerator, JsonTupleGenerator,
                                    UDTFGenerator)
from blaze_tpu.ops.joins import (BroadcastJoinExec, JoinType,
                                 ShuffledHashJoinExec, SortMergeJoinExec)
from blaze_tpu.ops.window import (LeadLagFunc, NthValueFunc, RankFunc,
                                  WindowAggFunc, WindowRankType)
from blaze_tpu.plan.exprs import expr_from_dict, sort_spec_from_dict
from blaze_tpu.plan.types import schema_from_dict
from blaze_tpu.schema import Schema
from blaze_tpu.shuffle import (FFIReaderExec, HashPartitioning, IpcReaderExec,
                               IpcWriterExec, LocalShuffleExchange,
                               Partitioning, RangePartitioning,
                               RoundRobinPartitioning, RssShuffleWriterExec,
                               ShuffleWriterExec, SinglePartitioning)


class CoalesceBatchesExec(ExecutionPlan):
    """Explicit re-batching node (ref CoalesceStream auto-wrap,
    rt.rs:160-166; also a plan-addressable node for parity)."""

    def __init__(self, child: ExecutionPlan, batch_size: Optional[int] = None):
        super().__init__([child])
        self._batch_size = batch_size

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int):
        return iter(CoalesceStream(self.children[0].execute(partition),
                                   self._batch_size, metrics=self.metrics))


def create_plan(d: Dict[str, Any]) -> ExecutionPlan:
    """Decode one plan node (and recursively its children)."""
    k = d["kind"]

    if k == "parquet_scan":
        schema = schema_from_dict(d["schema"])
        pred = (expr_from_dict(d["predicate"], schema)
                if d.get("predicate") else None)
        pschema = (schema_from_dict(d["partition_schema"])
                   if d.get("partition_schema") else None)
        return ParquetScanExec(schema, d["file_groups"],
                               projection=d.get("projection"),
                               predicate=pred,
                               partition_schema=pschema,
                               partition_values=d.get("partition_values"))
    if k == "memory_scan":
        import pyarrow as pa
        schema = schema_from_dict(d["schema"])
        from blaze_tpu.bridge.resource import get_resource
        table = get_resource(d["resource_id"])
        if table is None:
            raise KeyError(f"memory_scan resource {d['resource_id']!r}")
        return MemoryScanExec.from_arrow(table,
                                         d.get("num_partitions", 1))
    if k == "ipc_reader":
        return IpcReaderExec(d["resource_id"], schema_from_dict(d["schema"]),
                             d.get("num_partitions", 1))
    if k == "ffi_reader":
        return FFIReaderExec(d["resource_id"], schema_from_dict(d["schema"]),
                             d.get("num_partitions", 1))
    if k == "empty_partitions":
        return EmptyPartitionsExec(schema_from_dict(d["schema"]),
                                   d.get("num_partitions", 1))
    if k == "orc_scan":
        from blaze_tpu.ops.orc import OrcScanExec
        opschema = (schema_from_dict(d["partition_schema"])
                    if d.get("partition_schema") else None)
        return OrcScanExec(schema_from_dict(d["schema"]), d["file_groups"],
                           projection=d.get("projection"),
                           partition_schema=opschema,
                           partition_values=d.get("partition_values"))
    if k == "kafka_scan":
        return _create_kafka_scan(d)

    child = create_plan(d["input"]) if "input" in d else None
    in_schema = child.schema if child is not None else None

    if k == "filter":
        preds = [expr_from_dict(p, in_schema) for p in d["predicates"]]
        return FilterExec(child, preds)
    if k == "project":
        exprs = [expr_from_dict(e, in_schema) for e in d["exprs"]]
        return ProjectExec(child, exprs, d["names"])
    if k == "filter_project":
        preds = [expr_from_dict(p, in_schema) for p in d["predicates"]]
        exprs = [expr_from_dict(e, in_schema) for e in d["exprs"]]
        return FilterProjectExec(child, preds, exprs, d["names"])
    if k == "sort":
        specs = [sort_spec_from_dict(s, in_schema) for s in d["specs"]]
        return SortExec(child, specs, fetch=d.get("fetch"))
    if k == "limit":
        return LimitExec(child, d["limit"], offset=d.get("offset", 0))
    if k == "union":
        children = [create_plan(c) for c in d["inputs"]]
        return UnionExec(children)
    if k == "rename_columns":
        return RenameColumnsExec(child, d["names"])
    if k == "expand":
        projections = [[expr_from_dict(e, in_schema) for e in proj]
                       for proj in d["projections"]]
        return ExpandExec(child, projections, d["names"])
    if k == "debug":
        return DebugExec(child, d.get("tag", "debug"))
    if k == "coalesce_batches":
        return CoalesceBatchesExec(child, d.get("batch_size"))

    if k in ("hash_agg", "sort_agg"):
        groups = [(expr_from_dict(g["expr"], in_schema), g["name"])
                  for g in d.get("groupings", [])]
        aggs = []
        for a in d.get("aggs", []):
            children = [expr_from_dict(c, in_schema)
                        for c in a.get("args", [])]
            fn = make_agg(a["fn"], children, **a.get("options", {}))
            aggs.append((fn, AggMode(a.get("mode", "partial")), a["name"]))
        mode = (AggExecMode.HASH_AGG if k == "hash_agg"
                else AggExecMode.SORT_AGG)
        return AggExec(child, groups, aggs, mode,
                       skip_partial_hint=bool(
                           d.get("supports_partial_skipping")))

    if k == "broadcast_nested_loop_join":
        from blaze_tpu.ops.joins.bnlj import BroadcastNestedLoopJoinExec
        left = create_plan(d["left"])
        right = create_plan(d["right"])
        flt = (expr_from_dict(d["join_filter"])
               if d.get("join_filter") else None)
        return BroadcastNestedLoopJoinExec(
            left, right, JoinType(d.get("join_type", "inner")),
            build_side=d.get("build_side", "right"), join_filter=flt,
            broadcast_id=d.get("broadcast_id"))

    if k == "broadcast_join_build_hash_map":
        from blaze_tpu.ops.joins.exec import BuildHashMapExec
        keys = [expr_from_dict(e, in_schema) for e in d["keys"]]
        return BuildHashMapExec(child, keys)

    if k in ("sort_merge_join", "hash_join", "broadcast_join"):
        left = create_plan(d["left"])
        right = create_plan(d["right"])
        lkeys = [expr_from_dict(e, left.schema) for e in d["left_keys"]]
        rkeys = [expr_from_dict(e, right.schema) for e in d["right_keys"]]
        jt = JoinType(d.get("join_type", "inner"))
        flt = None
        if d.get("join_filter"):
            flt = expr_from_dict(d["join_filter"])  # bound on joined schema
        cls = {"sort_merge_join": SortMergeJoinExec,
               "hash_join": ShuffledHashJoinExec,
               "broadcast_join": BroadcastJoinExec}[k]
        kw = dict(build_side=d.get("build_side", "right"), join_filter=flt,
                  null_aware_anti=d.get("null_aware_anti", False))
        if k == "broadcast_join" and d.get("broadcast_id"):
            kw["broadcast_id"] = d["broadcast_id"]
            # a build-map stage on the broadcast side shares its map with
            # this join through the cache id (ref cached_build_hash_map_id,
            # broadcast_join_build_hash_map_exec.rs)
            from blaze_tpu.ops.joins.exec import BuildHashMapExec
            build = right if d.get("build_side", "right") == "right" else left
            if isinstance(build, BuildHashMapExec):
                build.cache_id = d["broadcast_id"]
        return cls(left, right, lkeys, rkeys, jt, **kw)

    if k == "window":
        funcs = []
        for w in d["functions"]:
            wk = w["kind"]
            if wk in [t.value for t in WindowRankType]:
                funcs.append(RankFunc(w["name"], WindowRankType(wk)))
            elif wk in ("lead", "lag"):
                off = w.get("offset", 1)
                funcs.append(LeadLagFunc(
                    w["name"], expr_from_dict(w["expr"], in_schema),
                    off if wk == "lead" else -off, w.get("default")))
            elif wk == "nth_value":
                funcs.append(NthValueFunc(
                    w["name"], expr_from_dict(w["expr"], in_schema),
                    w.get("n", 1),
                    ignore_nulls=w.get("ignore_nulls", False)))
            elif wk == "agg":
                children = [expr_from_dict(c, in_schema)
                            for c in w.get("args", [])]
                funcs.append(WindowAggFunc(
                    w["name"], make_agg(w["fn"], children),
                    running=w.get("running", True)))
            else:
                raise ValueError(f"unknown window function kind {wk!r}")
        part = [expr_from_dict(e, in_schema)
                for e in d.get("partition_by", [])]
        order = [sort_spec_from_dict(s, in_schema)
                 for s in d.get("order_by", [])]
        return WindowExec(child, funcs, part, order,
                          group_limit=d.get("group_limit"))

    if k == "generate":
        g = d["generator"]
        gk = g["kind"]
        if gk in ("explode", "posexplode"):
            gen = ExplodeGenerator(expr_from_dict(g["child"], in_schema),
                                   position=(gk == "posexplode"),
                                   outer=g.get("outer", False))
        elif gk == "json_tuple":
            gen = JsonTupleGenerator(expr_from_dict(g["child"], in_schema),
                                     g["fields"])
        elif gk == "udtf":
            from blaze_tpu.bridge.resource import get_resource
            from blaze_tpu.plan.types import field_from_dict
            fn = get_resource(f"udtf://{g['name']}")
            gen = UDTFGenerator(
                args=[expr_from_dict(a, in_schema)
                      for a in g.get("args", [])],
                fn=fn, fields=[field_from_dict(f) for f in g["fields"]])
        else:
            raise ValueError(f"unknown generator kind {gk!r}")
        required = d.get("required_cols")
        if required is None and d.get("required_child_output") is not None:
            required = [in_schema.index_of(nm)
                        for nm in d["required_child_output"]]
        return GenerateExec(child, gen, required,
                            outer=g.get("outer", False))

    if k == "shuffle_writer":
        part = partitioning_from_dict(d["partitioning"], in_schema)
        return ShuffleWriterExec(child, part, d["data_file"], d["index_file"])
    if k == "rss_shuffle_writer":
        from blaze_tpu.bridge.resource import get_resource
        part = partitioning_from_dict(d["partitioning"], in_schema)
        writer = get_resource(d["rss_resource_id"])
        return RssShuffleWriterExec(child, part, writer)
    if k == "local_exchange":
        part = partitioning_from_dict(d["partitioning"], in_schema)
        return LocalShuffleExchange(child, part,
                                    stage_id=d.get("stage_id", 0))
    if k == "ipc_writer":
        from blaze_tpu.bridge.resource import get_resource
        sink = get_resource(d["sink_resource_id"])
        return IpcWriterExec(child, sink)
    if k == "parquet_sink":
        from blaze_tpu.ops.sink import ParquetSinkExec
        return ParquetSinkExec(child, _sink_path(d),
                               partition_cols=d.get("partition_cols"))
    if k == "orc_sink":
        from blaze_tpu.ops.sink import OrcSinkExec
        return OrcSinkExec(child, _sink_path(d))

    raise ValueError(f"unknown plan node kind {k!r}")


def collapse_filter_project(node: ExecutionPlan) -> ExecutionPlan:
    """Planner rewrite: merge adjacent Filter->Project chains into one
    `FilterProjectExec`, and Project->Project into a single Project by
    substituting the inner projections into the outer's bound references
    — so the whole-stage expression compiler (exprs/program.py) traces
    the full chain as ONE XLA program instead of one per operator.

    Runs before prune_columns/fuse_plan in the runtime rewrite chains
    (both passes already understand FilterProjectExec).  Stateful inner
    expressions are never substituted (duplication would re-evaluate
    them); collapse simply stops at those nodes."""
    from blaze_tpu import config
    if not config.COLLAPSE_FILTER_PROJECT.get():
        return node
    return _collapse(node)


def _collapse(node: ExecutionPlan) -> ExecutionPlan:
    kids = node.children
    for i, c in enumerate(kids):
        kids[i] = _collapse(c)
    if isinstance(node, ProjectExec):
        child = node.children[0]
        if isinstance(child, FilterExec):
            return FilterProjectExec(child.children[0], child._predicates,
                                     node._exprs, node._names)
        if isinstance(child, ProjectExec):
            merged = _substitute_all(node._exprs, child._exprs)
            if merged is not None:
                return ProjectExec(child.children[0], merged, node._names)
    return node


#: Pure expression classes safe to duplicate/re-evaluate when an inner
#: projection substitutes into several outer references.  Stateful or
#: context-reading exprs (Rand, RowNum, UDFs, subqueries, scalar
#: functions...) are deliberately absent: substitution bails.
def _pure(e) -> bool:
    from blaze_tpu.exprs import (BinaryExpr, BoundReference, CaseWhen, Cast,
                                 Coalesce, If, InList, IsNotNull, IsNull,
                                 Like, Literal, Not, RLike, StringPredicate)
    ok = (BoundReference, Literal, BinaryExpr, Not, IsNull, IsNotNull, If,
          CaseWhen, Coalesce, InList, Cast, Like, RLike, StringPredicate)
    return isinstance(e, ok) and all(_pure(c) for c in e.children())


def _substitute_all(outer, inner):
    """outer exprs rewritten over inner's input, or None to bail."""
    if not all(_pure(e) for e in inner):
        return None
    from blaze_tpu.exprs.fold import map_exprs
    from blaze_tpu.exprs import BoundReference

    def subst(e):
        if isinstance(e, BoundReference):
            return inner[e.index]
        return map_exprs(e, subst)

    try:
        return [subst(e) for e in outer]
    except (TypeError, IndexError):
        return None


def _sink_path(d: Dict[str, Any]) -> str:
    """Sinks address their output through either a direct path or a
    host-registered FS resource (ref NativeParquetSinkUtils via the JVM
    resource map, jni_bridge.rs:452-453)."""
    if d.get("path"):
        return d["path"]
    rid = d.get("fs_resource_id", "")
    from blaze_tpu.bridge.resource import get_resource
    resolved = get_resource(rid)
    return resolved if resolved is not None else rid


def _create_kafka_scan(d: Dict[str, Any]) -> ExecutionPlan:
    """(ref flink/kafka_scan_exec.rs:81 + kafka_mock_scan_exec.rs)"""
    import json as _json
    from blaze_tpu.ops.kafka import (JsonDeserializer, KafkaRecord,
                                     KafkaScanExec, MockKafkaScanExec,
                                     PbDeserializer)
    schema = schema_from_dict(d["schema"])
    fmt = d.get("format", "json")
    if fmt == "json":
        deser = JsonDeserializer(schema)
    elif fmt == "protobuf":
        cfg = _json.loads(d.get("format_config_json") or "{}")
        deser = PbDeserializer(schema, cfg)
    else:
        raise ValueError(f"unknown kafka format {fmt!r}")
    ts_field = d.get("event_time_field")
    mock = d.get("mock_data_json_array")
    if mock:
        rows = _json.loads(mock)
        recs = [KafkaRecord(value=_json.dumps(r).encode("utf-8"), offset=i)
                for i, r in enumerate(rows)]
        return MockKafkaScanExec(schema, deser, [recs],
                                 event_time_field=ts_field)
    source = d.get("operator_id") or d.get("topic")
    return KafkaScanExec(schema, deser, f"kafka://{source}",
                         d.get("num_partitions", 1),
                         event_time_field=ts_field)


def partitioning_from_dict(d: Dict[str, Any],
                           schema: Optional[Schema]) -> Partitioning:
    """(ref parse_protobuf_partitioning, planner.rs:1201)"""
    k = d["kind"]
    if k == "hash":
        exprs = [expr_from_dict(e, schema) for e in d["exprs"]]
        return HashPartitioning(exprs, d["num_partitions"])
    if k == "round_robin":
        return RoundRobinPartitioning(d["num_partitions"])
    if k == "single":
        return SinglePartitioning()
    if k == "range":
        import base64
        import io
        import pyarrow as pa
        specs = [sort_spec_from_dict(s, schema) for s in d["specs"]]
        with pa.ipc.open_stream(io.BytesIO(
                base64.b64decode(d["bounds_ipc"]))) as r:
            bounds = next(iter(r))
        return RangePartitioning(specs, d["num_partitions"], bounds)
    raise ValueError(f"unknown partitioning kind {k!r}")


# fixed-width row schemas the mesh exchange can carry: each column
# travels as one jnp array + one bool validity lane.  date32 rides as
# int32 and timestamp_us as int64 — the murmur3 pid of the underlying
# integer is identical either way (partitioning.py hashes them through
# the same mode), so re-tagging at the arrow boundary is lossless.
_DEVICE_EXCHANGE_TIDS = frozenset((
    "bool", "int8", "int16", "int32", "int64", "float32", "float64",
    "date32", "timestamp_us"))


def _note_exchange_type_eviction(tid) -> None:
    """An exchange boundary just stayed on the host file shuffle because
    of a column TYPE (not mode/keys): account the reason so the advisor
    shows what actually evicted it."""
    from blaze_tpu.bridge import xla_stats
    if tid in ("utf8", "binary"):
        xla_stats.note_encoding(host_evictions_string=1)
    elif tid == "decimal":
        xla_stats.note_encoding(host_evictions_decimal=1)
    else:
        xla_stats.note_encoding(host_evictions_other=1)


def exchange_device_spec(partitioning: Optional[Dict[str, Any]],
                         out_schema: Optional[Dict[str, Any]]
                         ) -> Optional[Dict[str, Any]]:
    """Tentpole planner pass: decide whether one exchange boundary can
    go device-resident, i.e. ride the mesh collective instead of the
    host file shuffle.  Returns {'key_indices', 'num_partitions'} when
    BOTH sides of the boundary are mesh-shardable:

      map side    every output column fixed-width (no strings/decimals/
                  nested — those still need the host row format) so the
                  whole row set shards as flat device arrays;
      reduce side the hash keys are direct column references, so the
                  Spark-compatible pid is computable on device with the
                  ONE shared hash definition (H.spark_partition_ids).

    `auron.tpu.shuffle.device`: off -> never; on -> whenever eligible;
    auto (default) -> eligible AND compute is device-resident (bridge/
    placement) AND more than one device in the mesh — or the stage
    loop is forced on (auron.tpu.stage.deviceLoop.enable=on), whose
    device-resident map output should stay D2D.  Host-pinned
    placement (CPU tests, auron.tpu.placement=host) keeps the file
    path: there the collective is emulation-only overhead, and a
    1-device collective never beats the local fast path.
    """
    from blaze_tpu import config

    mode = (config.SHUFFLE_DEVICE.get() or "auto").strip().lower()
    if mode not in ("on", "auto"):
        return None
    if not partitioning or partitioning.get("kind") != "hash":
        return None
    n_out = int(partitioning.get("num_partitions", 1))
    if n_out < 1:
        return None
    fields = (out_schema or {}).get("fields", [])
    if not fields:
        return None
    for f in fields:
        t = f.get("type", {})
        tid = t.get("id")
        if tid in _DEVICE_EXCHANGE_TIDS:
            continue
        if (tid == "decimal" and int(t.get("precision", 99)) <= 18
                and config.ENCODING_DECIMAL_ENABLE.get()):
            # p<=18 decimals already travel as unscaled int64 on device
            # (batch._arrow_fixed_values), hash as longs (kernels/
            # hashing "decimal" tid), and rebuild losslessly on the
            # reduce side (batch.decimal_from_unscaled) — mesh-shardable
            continue
        _note_exchange_type_eviction(tid)
        return None
    names = [f.get("name") for f in fields]
    key_indices = []
    for e in partitioning.get("exprs", []):
        if not isinstance(e, dict) or e.get("kind") != "column":
            return None  # computed keys still go through the host path
        idx = e.get("index")
        if idx is None:
            name = e.get("name")
            idx = names.index(name) if name in names else None
        if idx is None or not (0 <= int(idx) < len(fields)):
            return None
        key_indices.append(int(idx))
    if not key_indices:
        return None
    if mode == "auto":
        import jax

        from blaze_tpu.bridge.placement import host_resident
        if config.STAGE_DEVICE_LOOP_ENABLE.get().strip().lower() == "on":
            # a forced stage loop produces device-resident map output
            # (runtime/loop.py drain_device); keeping the exchange on
            # device avoids a pointless D2H just to re-upload
            pass
        elif host_resident() or len(jax.devices()) < 2:
            return None
    return {"key_indices": key_indices, "num_partitions": n_out}


# ---------------------------------------------------------------------------
# TaskDefinition (ref auron.proto:814, rt.rs:79-90)
# ---------------------------------------------------------------------------

def decode_task_definition(data) -> Dict[str, Any]:
    """Accepts a dict (already decoded), a JSON string/bytes, or raw
    protobuf `TaskDefinition` bytes (the preserved wire contract,
    ref auron.proto:814 / rt.rs:79-90)."""
    if isinstance(data, (bytes, bytearray)):
        data = bytes(data)
        head = data.lstrip()[:1]
        if head in (b"{", b"["):  # JSON IR
            data = data.decode("utf-8")
        else:
            from blaze_tpu.plan.proto_serde import task_definition_from_bytes
            return task_definition_from_bytes(data)
    if isinstance(data, str):
        data = json.loads(data)
    return data


def plan_to_json(d: Dict[str, Any]) -> str:
    return json.dumps(d, separators=(",", ":"))


def plan_from_json(s) -> Dict[str, Any]:
    return decode_task_definition(s)

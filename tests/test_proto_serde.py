"""Protobuf plan-serde boundary tests.

The wire contract is the vendored auron.proto (TaskDefinition /
PhysicalPlanNode / PhysicalExprNode).  These tests check (a) IR dicts
round-trip through proto bytes, (b) decoded proto plans build the same
operator trees the JSON path builds, and (c) NativeExecutionRuntime accepts
raw TaskDefinition bytes end-to-end.
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.plan import create_plan
from blaze_tpu.plan.proto import auron_pb2 as pb
from blaze_tpu.plan.proto_serde import (expr_from_proto, expr_to_proto,
                                        partitioning_from_proto,
                                        partitioning_to_proto,
                                        plan_from_proto, plan_to_proto,
                                        scalar_from_proto, scalar_to_proto,
                                        schema_from_proto, schema_to_proto,
                                        task_definition_from_bytes,
                                        task_definition_to_bytes,
                                        type_from_proto, type_to_proto)


def _roundtrip_expr(d):
    return expr_from_proto(expr_to_proto(d))


def _roundtrip_plan(d):
    node = plan_to_proto(d)
    blob = node.SerializeToString()
    parsed = pb.PhysicalPlanNode()
    parsed.ParseFromString(blob)
    return plan_from_proto(parsed)


SCHEMA_D = {"fields": [
    {"name": "k", "type": {"id": "int64"}, "nullable": True},
    {"name": "v", "type": {"id": "float64"}, "nullable": True},
    {"name": "s", "type": {"id": "utf8"}, "nullable": True},
]}


class TestTypesAndScalars:
    @pytest.mark.parametrize("t", [
        {"id": "bool"}, {"id": "int8"}, {"id": "int16"}, {"id": "int32"},
        {"id": "int64"}, {"id": "float32"}, {"id": "float64"},
        {"id": "utf8"}, {"id": "binary"}, {"id": "date32"},
        {"id": "timestamp_us"}, {"id": "null"},
        {"id": "decimal", "precision": 12, "scale": 2},
    ])
    def test_type_roundtrip(self, t):
        assert type_from_proto(type_to_proto(t)) == t

    def test_nested_types(self):
        t = {"id": "list", "children": [
            {"name": "item", "type": {"id": "int64"}, "nullable": True}]}
        assert type_from_proto(type_to_proto(t)) == t
        t = {"id": "struct", "children": [
            {"name": "a", "type": {"id": "utf8"}, "nullable": True},
            {"name": "b", "type": {"id": "float64"}, "nullable": False}]}
        assert type_from_proto(type_to_proto(t)) == t

    def test_schema_roundtrip(self):
        assert schema_from_proto(schema_to_proto(SCHEMA_D)) == SCHEMA_D

    @pytest.mark.parametrize("value,t", [
        (42, {"id": "int64"}), (1.5, {"id": "float64"}),
        ("abc", {"id": "utf8"}), (True, {"id": "bool"}),
        (None, {"id": "int64"}), (b"\x00\x01", {"id": "binary"}),
    ])
    def test_scalar_roundtrip(self, value, t):
        got, got_t = scalar_from_proto(scalar_to_proto(value, t))
        assert got == value
        assert got_t == t

    def test_scalar_matches_reference_encoding(self):
        # the reference decodes ScalarValue as: Arrow IPC stream, batch 0,
        # column 0, row 0 (auron-planner/src/lib.rs:451-459)
        sv = scalar_to_proto(7, {"id": "int64"})
        import io
        with pa.ipc.open_stream(io.BytesIO(sv.ipc_bytes)) as r:
            rb = next(iter(r))
        assert rb.column(0)[0].as_py() == 7


class TestExprs:
    @pytest.mark.parametrize("d", [
        {"kind": "column", "name": "k"},
        {"kind": "column", "index": 3},
        {"kind": "literal", "value": 10, "type": {"id": "int64"}},
        {"kind": "binary", "op": ">",
         "l": {"kind": "column", "index": 0},
         "r": {"kind": "literal", "value": 5, "type": {"id": "int64"}}},
        {"kind": "is_null", "child": {"kind": "column", "index": 1}},
        {"kind": "is_not_null", "child": {"kind": "column", "index": 1}},
        {"kind": "not", "child": {"kind": "column", "index": 0}},
        {"kind": "in_list", "child": {"kind": "column", "index": 0},
         "values": [1, 2, 3], "negated": True},
        {"kind": "cast", "child": {"kind": "column", "index": 0},
         "type": {"id": "float64"}},
        {"kind": "try_cast", "child": {"kind": "column", "index": 2},
         "type": {"id": "int32"}},
        {"kind": "like", "child": {"kind": "column", "index": 2},
         "pattern": "a%", "negated": False, "case_insensitive": False},
        {"kind": "string_starts_with",
         "child": {"kind": "column", "index": 2}, "pattern": "pre"},
        {"kind": "string_ends_with",
         "child": {"kind": "column", "index": 2}, "pattern": "suf"},
        {"kind": "string_contains",
         "child": {"kind": "column", "index": 2}, "pattern": "mid"},
        {"kind": "scalar_function", "name": "upper",
         "args": [{"kind": "column", "index": 2}]},
        {"kind": "scalar_function", "name": "substring_index",
         "args": [{"kind": "column", "index": 2}]},  # ext-function path
        {"kind": "row_num"}, {"kind": "spark_partition_id"},
        {"kind": "monotonically_increasing_id"},
        {"kind": "randn", "seed": 7},
        {"kind": "bloom_filter_might_contain", "uuid": "bf-1",
         "value": {"kind": "column", "index": 0}},
        {"kind": "scalar_subquery", "uuid": "sq-9",
         "type": {"id": "int64"}},
        {"kind": "get_indexed_field",
         "child": {"kind": "column", "index": 0}, "index": 2},
        {"kind": "get_map_value",
         "child": {"kind": "column", "index": 0}, "key": "k1"},
        {"kind": "rlike", "child": {"kind": "column", "index": 2},
         "pattern": "^a.*", "case_insensitive": False},
    ])
    def test_expr_roundtrip(self, d):
        assert _roundtrip_expr(d) == d

    def test_case_roundtrip(self):
        d = {"kind": "case",
             "branches": [[{"kind": "binary", "op": "==",
                            "l": {"kind": "column", "index": 0},
                            "r": {"kind": "literal", "value": 1,
                                  "type": {"id": "int64"}}},
                           {"kind": "literal", "value": "one",
                            "type": {"id": "utf8"}}]],
             "else": {"kind": "literal", "value": "other",
                      "type": {"id": "utf8"}}}
        assert _roundtrip_expr(d) == d

    def test_case_with_operand_decodes_to_equality(self):
        e = pb.PhysicalExprNode()
        e.case_.expr.CopyFrom(expr_to_proto({"kind": "column", "index": 0}))
        wt = e.case_.when_then_expr.add()
        wt.when_expr.CopyFrom(expr_to_proto(
            {"kind": "literal", "value": 1, "type": {"id": "int64"}}))
        wt.then_expr.CopyFrom(expr_to_proto(
            {"kind": "literal", "value": 10, "type": {"id": "int64"}}))
        d = expr_from_proto(e)
        assert d["branches"][0][0]["op"] == "=="

    def test_coalesce_rides_the_scalar_function_enum(self):
        d = {"kind": "coalesce", "args": [{"kind": "column", "index": 0},
                                          {"kind": "column", "index": 1}]}
        assert _roundtrip_expr(d) == d

    def test_sc_and_decodes_to_binary(self):
        e = pb.PhysicalExprNode()
        e.sc_and_expr.left.CopyFrom(expr_to_proto({"kind": "column",
                                                   "index": 0}))
        e.sc_and_expr.right.CopyFrom(expr_to_proto({"kind": "column",
                                                    "index": 1}))
        assert expr_from_proto(e)["op"] == "and"

    def test_udf_wrapper_roundtrip(self):
        d = {"kind": "udf", "name": "my_fn",
             "args": [{"kind": "column", "index": 0}],
             "type": {"id": "int64"}}
        assert _roundtrip_expr(d) == d


class TestPartitioning:
    def test_hash(self):
        d = {"kind": "hash", "exprs": [{"kind": "column", "index": 0}],
             "num_partitions": 8}
        assert partitioning_from_proto(partitioning_to_proto(d)) == d

    def test_single_round_robin(self):
        assert partitioning_from_proto(
            partitioning_to_proto({"kind": "single"})) == {"kind": "single"}
        d = {"kind": "round_robin", "num_partitions": 4}
        assert partitioning_from_proto(partitioning_to_proto(d)) == d

    def test_range_bounds_survive(self):
        import base64
        import io
        rb = pa.record_batch([pa.array([10, 20, 30])], names=["b0"])
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, rb.schema) as w:
            w.write_batch(rb)
        d = {"kind": "range",
             "specs": [{"expr": {"kind": "column", "index": 0},
                        "descending": False, "nulls_first": True}],
             "num_partitions": 4,
             "bounds_ipc": base64.b64encode(sink.getvalue()).decode()}
        got = partitioning_from_proto(partitioning_to_proto(d))
        with pa.ipc.open_stream(io.BytesIO(
                base64.b64decode(got["bounds_ipc"]))) as r:
            got_rb = next(iter(r))
        assert got_rb.column(0).to_pylist() == [10, 20, 30]
        assert got["specs"] == d["specs"]


def _q01ish_plan_dict(path):
    scan = {"kind": "parquet_scan", "schema": SCHEMA_D,
            "file_groups": [[path]]}
    flt = {"kind": "filter", "input": scan,
           "predicates": [{"kind": "binary", "op": ">",
                           "l": {"kind": "column", "name": "k"},
                           "r": {"kind": "literal", "value": 2,
                                 "type": {"id": "int64"}}}]}
    agg = {"kind": "hash_agg", "input": flt,
           "groupings": [{"expr": {"kind": "column", "name": "s"},
                          "name": "s"}],
           "aggs": [{"fn": "sum", "mode": "partial", "name": "v_sum",
                     "args": [{"kind": "column", "name": "v"}]}]}
    return agg


class TestPlans:
    def test_scan_filter_agg_roundtrip(self):
        d = _q01ish_plan_dict("/tmp/x.parquet")
        got = _roundtrip_plan(d)
        assert got["kind"] == "hash_agg"
        assert got["groupings"][0]["name"] == "s"
        assert got["aggs"][0] == d["aggs"][0]
        flt = got["input"]
        assert flt["predicates"] == d["input"]["predicates"]
        scan = flt["input"]
        assert scan["schema"] == SCHEMA_D
        assert scan["file_groups"] == [["/tmp/x.parquet"]]

    def test_merge_mode_rebinds_acc_columns_positionally(self):
        # partial output layout: [s, v_sum] -> final agg's acc col is idx 1
        d = {"kind": "hash_agg",
             "input": {"kind": "ipc_reader", "resource_id": "r1",
                       "schema": {"fields": [
                           {"name": "s", "type": {"id": "utf8"},
                            "nullable": True},
                           {"name": "v_sum", "type": {"id": "float64"},
                            "nullable": True}]},
                       "num_partitions": 1},
             "groupings": [{"expr": {"kind": "column", "index": 0},
                            "name": "s"}],
             "aggs": [{"fn": "sum", "mode": "final", "name": "v_sum",
                       "args": [{"kind": "column", "index": 1}]}]}
        got = _roundtrip_plan(d)
        assert got["aggs"][0]["args"] == [{"kind": "column", "index": 1}]

    def test_avg_merge_claims_two_acc_columns(self):
        d = {"kind": "hash_agg",
             "input": {"kind": "ipc_reader", "resource_id": "r1",
                       "schema": SCHEMA_D, "num_partitions": 1},
             "groupings": [{"expr": {"kind": "column", "index": 0},
                            "name": "k"}],
             "aggs": [{"fn": "avg", "mode": "final", "name": "a",
                       "args": [{"kind": "column", "index": 1},
                                {"kind": "column", "index": 2}]},
                      {"fn": "count", "mode": "final", "name": "c",
                       "args": [{"kind": "column", "index": 3}]}]}
        got = _roundtrip_plan(d)
        assert got["aggs"][0]["args"] == [{"kind": "column", "index": 1},
                                          {"kind": "column", "index": 2}]
        assert got["aggs"][1]["args"] == [{"kind": "column", "index": 3}]

    def test_joins_roundtrip(self):
        reader = {"kind": "ipc_reader", "resource_id": "r", "schema":
                  SCHEMA_D, "num_partitions": 2}
        for kind in ("hash_join", "broadcast_join", "sort_merge_join"):
            d = {"kind": kind, "left": reader, "right": reader,
                 "left_keys": [{"kind": "column", "index": 0}],
                 "right_keys": [{"kind": "column", "index": 0}],
                 "join_type": "left_semi"}
            if kind != "sort_merge_join":
                d["build_side"] = "right"
            if kind == "broadcast_join":
                d["broadcast_id"] = "b-1"
            got = _roundtrip_plan(d)
            assert got["kind"] == kind
            assert got["join_type"] == "left_semi"
            assert got["left_keys"] == d["left_keys"]

    def test_window_roundtrip(self):
        d = {"kind": "window",
             "input": {"kind": "ipc_reader", "resource_id": "r",
                       "schema": SCHEMA_D, "num_partitions": 1},
             "functions": [
                 {"kind": "row_number", "name": "rn"},
                 {"kind": "rank", "name": "rk"},
                 {"kind": "lead", "name": "ld", "offset": 2,
                  "expr": {"kind": "column", "index": 1}},
                 {"kind": "lag", "name": "lg", "offset": 1,
                  "expr": {"kind": "column", "index": 1}},
                 {"kind": "nth_value", "name": "nv", "n": 3,
                  "expr": {"kind": "column", "index": 1}},
                 {"kind": "agg", "fn": "sum", "name": "ws",
                  "args": [{"kind": "column", "index": 1}]}],
             "partition_by": [{"kind": "column", "index": 0}],
             "order_by": [{"expr": {"kind": "column", "index": 1},
                           "descending": True, "nulls_first": False}],
             "group_limit": 5}
        got = _roundtrip_plan(d)
        assert [f["kind"] for f in got["functions"]] == \
            [f["kind"] for f in d["functions"]]
        assert got["functions"][2]["offset"] == 2
        assert got["functions"][3]["offset"] == 1
        assert got["functions"][4]["n"] == 3
        assert got["group_limit"] == 5
        assert got["order_by"] == d["order_by"]

    def test_generate_sort_limit_union_roundtrip(self):
        reader = {"kind": "ipc_reader", "resource_id": "r",
                  "schema": SCHEMA_D, "num_partitions": 1}
        gen = {"kind": "generate", "input": reader,
               "generator": {"kind": "explode",
                             "child": {"kind": "column", "index": 0},
                             "outer": True},
               "required_child_output": ["k", "v"]}
        got = _roundtrip_plan(gen)
        assert got["generator"]["kind"] == "explode"
        assert got["generator"]["outer"] is True
        assert got["required_child_output"] == ["k", "v"]

        srt = {"kind": "sort", "input": reader,
               "specs": [{"expr": {"kind": "column", "index": 0},
                          "descending": False, "nulls_first": True}],
               "fetch": 10}
        got = _roundtrip_plan(srt)
        assert got["fetch"] == 10 and got["specs"] == srt["specs"]

        lim = {"kind": "limit", "input": reader, "limit": 7, "offset": 2}
        got = _roundtrip_plan(lim)
        assert got["limit"] == 7 and got["offset"] == 2

        un = {"kind": "union", "inputs": [reader, reader]}
        got = _roundtrip_plan(un)
        assert len(got["inputs"]) == 2

    def test_shuffle_writer_roundtrip(self):
        d = {"kind": "shuffle_writer",
             "input": {"kind": "ipc_reader", "resource_id": "r",
                       "schema": SCHEMA_D, "num_partitions": 1},
             "partitioning": {"kind": "hash",
                              "exprs": [{"kind": "column", "index": 0}],
                              "num_partitions": 4},
             "data_file": "/tmp/s.data", "index_file": "/tmp/s.index"}
        got = _roundtrip_plan(d)
        assert got == d

    def test_expand_roundtrip(self):
        d = {"kind": "expand",
             "input": {"kind": "ipc_reader", "resource_id": "r",
                       "schema": SCHEMA_D, "num_partitions": 1},
             "projections": [
                 [{"kind": "column", "index": 0},
                  {"kind": "literal", "value": None, "type": {"id": "null"}}],
                 [{"kind": "column", "index": 0},
                  {"kind": "column", "index": 1}]],
             "names": ["k", "g"]}
        got = _roundtrip_plan(d)
        assert got["projections"] == d["projections"]
        assert got["names"] == d["names"]


class TestTaskDefinition:
    def test_bytes_roundtrip(self):
        td = {"stage_id": 3, "partition_id": 1, "task_attempt_id": 99,
              "plan": _q01ish_plan_dict("/tmp/x.parquet")}
        blob = task_definition_to_bytes(td)
        got = task_definition_from_bytes(blob)
        assert got["stage_id"] == 3
        assert got["partition_id"] == 1
        assert got["task_attempt_id"] == 99
        assert got["plan"]["kind"] == "hash_agg"

    def test_runtime_accepts_raw_proto_bytes(self, tmp_path):
        from blaze_tpu.bridge.runtime import NativeExecutionRuntime
        t = pa.table({"k": pa.array([1, 2, 3, 4, 5], type=pa.int64()),
                      "v": pa.array([1.0, 2.0, 3.0, 4.0, 5.0]),
                      "s": pa.array(["a", "b", "a", "b", "a"])})
        path = str(tmp_path / "t.parquet")
        pq.write_table(t, path)
        td = {"stage_id": 0, "partition_id": 0,
              "plan": _q01ish_plan_dict(path)}
        blob = task_definition_to_bytes(td)
        rt = NativeExecutionRuntime(blob).start()
        try:
            batches = list(rt.batches())
        finally:
            rt.finalize()
        out = pa.Table.from_batches(batches).to_pydict()
        # rows with k > 2: (3.0, a), (4.0, b), (5.0, a)
        got = dict(zip(out["s"], out["v_sum.sum"]))
        assert got == {"a": 8.0, "b": 4.0}

    def test_decoded_plan_builds_same_operator_tree_as_json(self, tmp_path):
        t = pa.table({"k": pa.array([1, 5, 9], type=pa.int64()),
                      "v": pa.array([1.0, 2.0, 3.0]),
                      "s": pa.array(["x", "y", "x"])})
        path = str(tmp_path / "t.parquet")
        pq.write_table(t, path)
        d = _q01ish_plan_dict(path)
        via_json = create_plan(d)
        via_proto = create_plan(_roundtrip_plan(d))
        assert type(via_json) is type(via_proto)
        assert via_json.schema.names == via_proto.schema.names
        j = [b.to_arrow() for b in via_json.execute(0)]
        p = [b.to_arrow() for b in via_proto.execute(0)]
        assert pa.Table.from_batches(j).equals(pa.Table.from_batches(p))


class TestReviewRegressions:
    def test_right_sided_semi_anti_refuse_to_encode(self):
        reader = {"kind": "ipc_reader", "resource_id": "r",
                  "schema": SCHEMA_D, "num_partitions": 1}
        d = {"kind": "hash_join", "left": reader, "right": reader,
             "left_keys": [{"kind": "column", "index": 0}],
             "right_keys": [{"kind": "column", "index": 0}],
             "join_type": "right_semi", "build_side": "left"}
        with pytest.raises(ValueError, match="no wire encoding"):
            plan_to_proto(d)

    def test_nth_value_ignore_nulls_roundtrip(self):
        d = {"kind": "window",
             "input": {"kind": "ipc_reader", "resource_id": "r",
                       "schema": SCHEMA_D, "num_partitions": 1},
             "functions": [{"kind": "nth_value", "name": "nv", "n": 2,
                            "ignore_nulls": True,
                            "expr": {"kind": "column", "index": 1}}],
             "partition_by": [], "order_by": []}
        got = _roundtrip_plan(d)
        assert got["functions"][0]["ignore_nulls"] is True
        assert got["functions"][0]["n"] == 2


class TestNullAwareAnti:
    def _run(self, left_rows, right_rows):
        from blaze_tpu.ops import MemoryScanExec
        from blaze_tpu.ops.joins import JoinType
        from blaze_tpu.ops.joins.exec import BroadcastJoinExec
        from blaze_tpu.exprs import col
        lt = pa.table({"x": pa.array(left_rows, type=pa.int64())})
        rt_ = pa.table({"y": pa.array(right_rows, type=pa.int64())})
        j = BroadcastJoinExec(
            MemoryScanExec.from_arrow(lt), MemoryScanExec.from_arrow(rt_),
            [col(0)], [col(0)], JoinType.LEFT_ANTI, build_side="right",
            null_aware_anti=True)
        out = [b.compact().to_arrow() for b in j.execute(0)]
        if not out:
            return []
        return pa.Table.from_batches(out)["x"].to_pylist()

    def test_null_in_build_rejects_everything(self):
        assert self._run([1, 2, None], [2, None]) == []

    def test_null_probe_keys_never_pass(self):
        assert self._run([1, 2, None], [2, 3]) == [1]

    def test_empty_build_keeps_all_rows_even_null(self):
        # x NOT IN (empty set) is TRUE for every x, including NULL
        assert self._run([1, None], []) == [1, None]


class TestNthValueIgnoreNulls:
    def test_nth_non_null_per_partition(self):
        from blaze_tpu.ops import MemoryScanExec, WindowExec
        from blaze_tpu.ops.window import NthValueFunc
        from blaze_tpu.exprs import col
        t = pa.table({"p": pa.array([1, 1, 1, 2, 2], type=pa.int64()),
                      "v": pa.array([None, 10, 20, None, 30],
                                    type=pa.int64())})
        w = WindowExec(
            MemoryScanExec.from_arrow(t),
            [NthValueFunc("nv", col(1), 2, ignore_nulls=True)],
            [col(0)], [])
        out = pa.Table.from_batches(
            [b.compact().to_arrow() for b in w.execute(0)])
        # partition 1: 2nd non-null = 20; partition 2: only one non-null
        assert out["nv"].to_pylist() == [20, 20, 20, None, None]


class TestReviewRegressions2:
    def test_regex_imatch_decodes_case_insensitive(self):
        e = pb.PhysicalExprNode()
        e.binary_expr.op = "RegexIMatch"
        e.binary_expr.l.CopyFrom(expr_to_proto({"kind": "column",
                                                "index": 0}))
        e.binary_expr.r.literal.CopyFrom(
            scalar_to_proto("^ab", {"id": "utf8"}))
        d = expr_from_proto(e)
        assert d["case_insensitive"] is True
        from blaze_tpu.plan.exprs import expr_from_dict
        rl = expr_from_dict(d)
        assert rl.case_insensitive is True

    def test_string_concat_decodes_to_concat_fn(self):
        e = pb.PhysicalExprNode()
        e.binary_expr.op = "StringConcat"
        e.binary_expr.l.CopyFrom(expr_to_proto({"kind": "column",
                                                "index": 0}))
        e.binary_expr.r.CopyFrom(expr_to_proto({"kind": "column",
                                                "index": 1}))
        d = expr_from_proto(e)
        assert d == {"kind": "scalar_function", "name": "concat",
                     "args": [{"kind": "column", "index": 0},
                              {"kind": "column", "index": 1}]}

    def test_multi_group_scan_refuses_to_encode(self):
        d = {"kind": "parquet_scan", "schema": SCHEMA_D,
             "file_groups": [["a.parquet"], ["b.parquet"]]}
        with pytest.raises(ValueError, match="ONE file group"):
            plan_to_proto(d)

    def test_broadcast_build_map_gets_cache_id(self):
        from blaze_tpu.ops.joins.exec import BuildHashMapExec
        reader = {"kind": "ipc_reader", "resource_id": "r",
                  "schema": SCHEMA_D, "num_partitions": 1}
        d = {"kind": "broadcast_join", "left": reader,
             "right": {"kind": "broadcast_join_build_hash_map",
                       "input": reader,
                       "keys": [{"kind": "column", "index": 0}]},
             "left_keys": [{"kind": "column", "index": 0}],
             "right_keys": [{"kind": "column", "index": 0}],
             "join_type": "inner", "build_side": "right",
             "broadcast_id": "bc-7"}
        plan = create_plan(d)
        build = plan.children[1]
        assert isinstance(build, BuildHashMapExec)
        assert build.cache_id == "bc-7"


def test_bnlj_rides_the_wire_as_keyless_broadcast_join():
    """broadcast_nested_loop_join has no dedicated proto node (matching
    the reference's PhysicalPlanType oneof); it encodes as a keyless
    broadcast_join and decodes back (review/report-caught: the wire tier
    crashed on q24's BNLJ scalar-threshold stage)."""
    import pytest
    from blaze_tpu.plan.proto_serde import plan_from_proto, plan_to_proto
    mem = {"kind": "empty_partitions", "num_partitions": 1,
           "schema": {"fields": [
               {"name": "a", "type": {"id": "int64"}, "nullable": True}]}}
    d = {"kind": "broadcast_nested_loop_join", "left": mem, "right": mem,
         "left_keys": [], "right_keys": [], "join_type": "inner",
         "build_side": "right"}
    back = plan_from_proto(plan_to_proto(d))
    assert back["kind"] == "broadcast_nested_loop_join"
    assert back["join_type"] == "inner"
    # an INNER residual condition lifts into a filter over the cross
    # product (wire-equivalent); outer variants are rejected
    filt = {"kind": "binary", "op": ">",
            "l": {"kind": "column", "index": 0},
            "r": {"kind": "literal", "value": 0, "type": {"id": "int64"}}}
    lifted = plan_from_proto(plan_to_proto(dict(d, join_filter=filt)))
    assert lifted["kind"] == "filter"
    assert lifted["input"]["kind"] == "broadcast_nested_loop_join"
    with pytest.raises(ValueError, match="no wire encoding"):
        plan_to_proto(dict(d, join_type="left", join_filter=filt))


def test_generate_required_cols_survive_the_wire(tmp_path):
    """generate's `required_cols` (index form) must translate to the
    wire's name-based required_child_output — an empty list decodes as
    'keep no child columns' and silently narrows the output schema
    (wire-report-caught on gq1)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu.plan import create_plan
    from blaze_tpu.plan.proto_serde import plan_from_proto, plan_to_proto
    t = pa.table({"sk": pa.array([1, 2]),
                  "items": pa.array([[1, 2], [3]],
                                    type=pa.list_(pa.int64()))})
    p = str(tmp_path / "g.parquet")
    pq.write_table(t, p)
    ir = {"kind": "generate",
          "generator": {"kind": "posexplode",
                        "child": {"kind": "column", "name": "items"},
                        "outer": False},
          "required_cols": [0],
          "input": {"kind": "parquet_scan", "schema": {"fields": [
              {"name": "sk", "type": {"id": "int64"}, "nullable": True},
              {"name": "items", "type": {"id": "list", "children": [
                  {"name": "item", "type": {"id": "int64"},
                   "nullable": True}]}, "nullable": True}]},
              "file_groups": [[p]]}}
    direct = create_plan(ir)
    wired = create_plan(plan_from_proto(plan_to_proto(ir)))
    assert [f.name for f in wired.schema] == \
        [f.name for f in direct.schema]
    assert len(wired.schema) == 3  # sk + pos + exploded element


@pytest.mark.parametrize("value,type_dict", [
    (0.0, {"id": "float64"}), (-0.0, {"id": "float64"}),
    (1, {"id": "int64"}), (1, {"id": "int32"}), (True, {"id": "bool"}),
    (None, {"id": "utf8"}), (None, {"id": "int32"}), ("", {"id": "utf8"}),
    (float("nan"), {"id": "float64"})])
def test_a_literal_is_written_once_and_reads_as_itself(value, type_dict):
    """A plan's literals go through Arrow IPC once a process: what tells
    two of them apart is the value's repr, its Python type and the type
    it is written as (0.0 == -0.0 and True == 1, so the value alone would
    not), and a caller may change the dict it is handed."""
    import math
    first = scalar_to_proto(value, type_dict)
    again = scalar_to_proto(value, dict(type_dict))
    assert first.ipc_bytes == again.ipc_bytes
    for _ in range(2):  # read, then read from what was kept
        got, got_type = scalar_from_proto(first)
        assert got_type == type_dict and type(got) is type(value)
        if isinstance(value, float):
            assert math.isnan(got) if math.isnan(value) \
                else math.copysign(1.0, got) == math.copysign(1.0, value)
        else:
            assert got == value
        got_type["id"] = "changed"

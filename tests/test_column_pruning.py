"""Column-pruning pass (plan/column_pruning.py — Catalyst ColumnPruning
analog): scans narrow to referenced columns, BoundReferences remap, and
results are identical with the pass on or off."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu import config
from blaze_tpu.exprs import BinaryExpr, col, lit
from blaze_tpu.memory import MemManager
from blaze_tpu.ops import AggExec, AggMode, FilterExec, ProjectExec
from blaze_tpu.ops.agg.functions import make_agg
from blaze_tpu.ops.joins import JoinType
from blaze_tpu.ops.joins.exec import BroadcastJoinExec
from blaze_tpu.ops.scan import ParquetScanExec
from blaze_tpu.plan.column_pruning import prune_columns
from blaze_tpu.schema import Schema


@pytest.fixture(autouse=True)
def budget():
    MemManager.init(4 << 30)


def _wide_file(tmp_path, n=5000, name="wide.parquet"):
    rng = np.random.default_rng(0)
    t = pa.table({f"c{i}": pa.array(rng.integers(0, 50, n))
                  for i in range(10)})
    p = str(tmp_path / name)
    pq.write_table(t, p)
    return p, t


def _collect(plan):
    out = []
    for p in range(plan.num_partitions):
        out.extend(b.compact().to_arrow() for b in plan.execute(p))
    return pa.Table.from_batches([b for b in out if b.num_rows])


def test_agg_over_filter_prunes_scan(tmp_path):
    p, t = _wide_file(tmp_path)
    def build():
        scan = ParquetScanExec(Schema.from_arrow(t.schema), [[p]])
        flt = FilterExec(scan, [BinaryExpr(">", col(3, "c3"), lit(10))])
        return AggExec(flt, [(col(7, "c7"), "k")],
                       [(make_agg("sum", [col(5)]), AggMode.COMPLETE,
                         "s")])
    pruned = prune_columns(build())
    # the scan under the pass reads only c3, c5, c7
    node = pruned
    while node.children:
        node = node.children[0]
    assert isinstance(node, ParquetScanExec)
    assert [f.name for f in node.schema] == ["c3", "c5", "c7"]
    got = _collect(pruned).to_pandas().sort_values("k").reset_index(
        drop=True)
    config.conf.set(config.COLUMN_PRUNING_ENABLE.key, False)
    try:
        want = _collect(build()).to_pandas().sort_values("k") \
            .reset_index(drop=True)
    finally:
        config.conf.unset(config.COLUMN_PRUNING_ENABLE.key)
    pd.testing.assert_frame_equal(got, want)


def test_join_prunes_both_sides(tmp_path):
    p1, t1 = _wide_file(tmp_path, name="l.parquet")
    p2, t2 = _wide_file(tmp_path, n=300, name="r.parquet")
    def build():
        l = ParquetScanExec(Schema.from_arrow(t1.schema), [[p1]])
        r = ParquetScanExec(Schema.from_arrow(t2.schema), [[p2]])
        j = BroadcastJoinExec(l, r, [col(2)], [col(4)], JoinType.INNER)
        # references l.c2, l.c6, r.c4 (=idx 14), r.c9 (=idx 19)
        return ProjectExec(j, [col(2), col(6), col(14), col(19)],
                           ["a", "b", "c", "d"])
    pruned = prune_columns(build())
    scans = []
    def walk(n):
        if isinstance(n, ParquetScanExec):
            scans.append([f.name for f in n.schema])
        for c in n.children:
            walk(c)
    walk(pruned)
    assert scans == [["c2", "c6"], ["c4", "c9"]]
    got = _collect(pruned).to_pandas().sort_values(
        ["a", "b", "c", "d"]).reset_index(drop=True)
    config.conf.set(config.COLUMN_PRUNING_ENABLE.key, False)
    try:
        want = _collect(build()).to_pandas().sort_values(
            ["a", "b", "c", "d"]).reset_index(drop=True)
    finally:
        config.conf.unset(config.COLUMN_PRUNING_ENABLE.key)
    pd.testing.assert_frame_equal(got, want)


def test_semi_join_is_a_barrier_but_descends(tmp_path):
    p1, t1 = _wide_file(tmp_path, name="l2.parquet")
    p2, t2 = _wide_file(tmp_path, n=300, name="r2.parquet")
    l = ParquetScanExec(Schema.from_arrow(t1.schema), [[p1]])
    r_scan = ParquetScanExec(Schema.from_arrow(t2.schema), [[p2]])
    r = AggExec(r_scan, [(col(4, "c4"), "k")],
                [(make_agg("count", [col(4)]), AggMode.COMPLETE, "n")])
    j = BroadcastJoinExec(l, r, [col(2)], [col(0)], JoinType.LEFT_SEMI)
    pruned = prune_columns(j)
    # left side untouched (semi barrier); right side pruned under agg
    assert len(pruned.children[0].schema) == 10
    inner = pruned.children[1].children[0]
    assert [f.name for f in inner.schema] == ["c4"]


def test_shared_broadcast_id_with_different_pruning(tmp_path):
    """Two plans sharing one broadcast_id but pruned to different build
    columns must not serve each other's cached join map (the cache key
    folds the build schema; reproduced wrong results before the fix)."""
    p1, t1 = _wide_file(tmp_path, name="probe.parquet")
    p2, t2 = _wide_file(tmp_path, n=300, name="build.parquet")

    def build(keep_idx, name):
        l = ParquetScanExec(Schema.from_arrow(t1.schema), [[p1]])
        r = ParquetScanExec(Schema.from_arrow(t2.schema), [[p2]])
        j = BroadcastJoinExec(l, r, [col(2)], [col(4)], JoinType.INNER,
                              broadcast_id="shared-bhj")
        return prune_columns(
            ProjectExec(j, [col(2), col(keep_idx)], ["k", name]))

    a = _collect(build(10 + 6, "v6")).to_pandas()   # right c6
    b = _collect(build(10 + 9, "v9")).to_pandas()   # right c9
    probe = t1.to_pandas()
    bld = t2.to_pandas()
    for out, cname, vname in ((a, "c6", "v6"), (b, "c9", "v9")):
        want = probe.merge(bld, left_on="c2", right_on="c4",
                           suffixes=("", "_r"))
        want_vals = sorted(want[cname + "_r"].tolist())
        assert sorted(out[vname].tolist()) == want_vals


@pytest.mark.parametrize("build_side", ["right", "left"])
def test_broadcast_build_side_under_an_aggregation_is_projected(
        tmp_path, build_side):
    """The pass narrows scans; a broadcast build side whose scan lies under
    an aggregation keeps every column the aggregation makes.  The join
    then reads it through a projection of what it and its parents use, so
    a build side collected whole holds those columns alone (and one of
    fixed-width columns alone can stay on the device)."""
    p1, t1 = _wide_file(tmp_path, name="p.parquet")
    p2, t2 = _wide_file(tmp_path, n=300, name="b.parquet")

    def build():
        probe = ParquetScanExec(Schema.from_arrow(t1.schema), [[p1]])
        b_scan = ParquetScanExec(Schema.from_arrow(t2.schema), [[p2]])
        agg = AggExec(b_scan, [(col(4, "c4"), "k"), (col(5, "c5"), "k2")],
                      [(make_agg("count", [col(4)]), AggMode.COMPLETE, "n"),
                       (make_agg("max", [col(7)]), AggMode.COMPLETE, "m")])
        if build_side == "right":
            j = BroadcastJoinExec(probe, agg, [col(2)], [col(0)],
                                  JoinType.INNER)
            return ProjectExec(j, [col(2), col(10 + 3)], ["k", "m"])
        j = BroadcastJoinExec(agg, probe, [col(0)], [col(2)],
                              JoinType.INNER, build_side="left")
        return ProjectExec(j, [col(4 + 2), col(3)], ["k", "m"])

    pruned = prune_columns(build())
    join = pruned.children[0]
    side = join.children[1 if build_side == "right" else 0]
    assert isinstance(side, ProjectExec)
    assert [f.name for f in side.schema] == ["k", "m"]
    assert [f.name for f in side.children[0].schema] == ["k", "k2", "n", "m"]
    # the probe side is narrowed at its scan, as ever, not projected
    other = join.children[0 if build_side == "right" else 1]
    assert isinstance(other, ParquetScanExec)
    assert [f.name for f in other.schema] == ["c2"]
    got = _collect(pruned).to_pandas().sort_values(["k", "m"]) \
        .reset_index(drop=True)
    config.conf.set(config.COLUMN_PRUNING_ENABLE.key, False)
    try:
        want = _collect(build()).to_pandas().sort_values(["k", "m"]) \
            .reset_index(drop=True)
    finally:
        config.conf.unset(config.COLUMN_PRUNING_ENABLE.key)
    assert len(want) > 0
    pd.testing.assert_frame_equal(got, want)

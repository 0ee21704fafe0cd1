"""Fused-stage compiler tests: plan rewriting + result parity with the
eager AggExec path (plan/fused.py)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu import config
from blaze_tpu.exprs import BinaryExpr, col, lit
from blaze_tpu.ops import (AggExec, AggMode, FilterExec, MemoryScanExec,
                           make_agg)
from blaze_tpu.plan import create_plan
from blaze_tpu.plan.fused import FusedPartialAggExec, fuse_plan
from blaze_tpu.shuffle import HashPartitioning, LocalShuffleExchange


def _table(n=5000, seed=0, nulls=False):
    rng = np.random.default_rng(seed)
    cust = rng.integers(1, 200, n).astype(float)
    if nulls:
        mask = rng.random(n) < 0.05
        cust[mask] = np.nan
        cust_arr = pa.array(np.where(mask, None, cust).tolist(),
                            type=pa.int64())
    else:
        cust_arr = pa.array(cust.astype(np.int64))
    return pa.table({
        "date": pa.array(rng.integers(100, 200, n)),
        "cust": cust_arr,
        "store": pa.array(rng.integers(1, 13, n)),
        "amt": pa.array(np.round(rng.random(n) * 100, 2)),
    })


def _partial_agg_plan(scan):
    flt = FilterExec(scan, [BinaryExpr(">", col(0, "date"), lit(150))])
    return AggExec(flt,
                   [(col(1, "cust"), "cust"), (col(2, "store"), "store")],
                   [(make_agg("sum", [col(3)]), AggMode.PARTIAL, "amt_sum"),
                    (make_agg("count", [col(3)]), AggMode.PARTIAL, "cnt"),
                    (make_agg("min", [col(3)]), AggMode.PARTIAL, "amt_min"),
                    (make_agg("max", [col(3)]), AggMode.PARTIAL, "amt_max")])


def _collect(plan):
    out = [b.compact().to_arrow() for b in plan.execute(0)]
    out = [b for b in out if b.num_rows]
    t = pa.Table.from_batches(out, schema=plan.schema.to_arrow())
    df = t.to_pandas().sort_values(["cust", "store"]).reset_index(drop=True)
    return df


class TestDense:
    def test_memory_scan_fuses_dense_and_matches_eager(self):
        t = _table(nulls=True)
        eager = _partial_agg_plan(MemoryScanExec.from_arrow(t))
        fused = fuse_plan(_partial_agg_plan(MemoryScanExec.from_arrow(t)))
        assert isinstance(fused, FusedPartialAggExec)
        assert fused.fused_mode == "dense"
        a, b = _collect(eager), _collect(fused)
        assert len(a) == len(b)
        for c in a.columns:
            np.testing.assert_allclose(
                a[c].to_numpy(dtype=float), b[c].to_numpy(dtype=float),
                rtol=1e-9, err_msg=c)

    def test_parquet_stats_bounds(self, tmp_path):
        t = _table()
        path = str(tmp_path / "t.parquet")
        pq.write_table(t, path, row_group_size=1000)
        schema_d = {"fields": [
            {"name": "date", "type": {"id": "int64"}, "nullable": True},
            {"name": "cust", "type": {"id": "int64"}, "nullable": True},
            {"name": "store", "type": {"id": "int64"}, "nullable": True},
            {"name": "amt", "type": {"id": "float64"}, "nullable": True}]}
        d = {"kind": "hash_agg",
             "input": {"kind": "filter",
                       "input": {"kind": "parquet_scan", "schema": schema_d,
                                 "file_groups": [[path]]},
                       "predicates": [{"kind": "binary", "op": ">",
                                       "l": {"kind": "column",
                                             "name": "date"},
                                       "r": {"kind": "literal", "value": 150,
                                             "type": {"id": "int64"}}}]},
             "groupings": [{"expr": {"kind": "column", "name": "cust"},
                            "name": "cust"},
                           {"expr": {"kind": "column", "name": "store"},
                            "name": "store"}],
             "aggs": [{"fn": "sum", "mode": "partial", "name": "amt_sum",
                       "args": [{"kind": "column", "name": "amt"}]}]}
        eager = create_plan(d)
        fused = fuse_plan(create_plan(d))
        assert isinstance(fused, FusedPartialAggExec)
        assert fused.fused_mode == "dense"
        a, b = _collect(eager), _collect(fused)
        np.testing.assert_allclose(a["amt_sum.sum"].to_numpy(),
                                   b["amt_sum.sum"].to_numpy(), rtol=1e-9)

    def test_complete_mode_fuses(self):
        t = _table()
        scan = MemoryScanExec.from_arrow(t)
        agg = AggExec(scan, [(col(2, "store"), "store")],
                      [(make_agg("sum", [col(3)]), AggMode.COMPLETE, "s"),
                       (make_agg("count", [col(3)]), AggMode.COMPLETE, "c")])
        fused = fuse_plan(agg)
        assert isinstance(fused, FusedPartialAggExec)
        df = pa.Table.from_batches(
            [b.compact().to_arrow() for b in fused.execute(0)]).to_pandas()
        want = t.to_pandas().groupby("store").agg(
            s=("amt", "sum"), c=("amt", "count")).reset_index()
        got = df.sort_values("store").reset_index(drop=True)
        np.testing.assert_allclose(got["s"].to_numpy(),
                                   want["s"].to_numpy(), rtol=1e-9)
        assert (got["c"].to_numpy() == want["c"].to_numpy()).all()


class TestSorted:
    def _plan_with_computed_key(self, t):
        # group key is an arithmetic expr -> no traceable bounds -> sorted
        scan = MemoryScanExec.from_arrow(t)
        return AggExec(scan,
                       [(BinaryExpr("%", col(1, "cust"), lit(50)), "kmod")],
                       [(make_agg("sum", [col(3)]), AggMode.PARTIAL, "s")])

    def test_sorted_path_matches_eager(self):
        t = _table()
        eager = self._plan_with_computed_key(t)
        fused = fuse_plan(self._plan_with_computed_key(t))
        assert isinstance(fused, FusedPartialAggExec)
        assert fused.fused_mode == "sorted"
        a = pa.Table.from_batches([b.compact().to_arrow()
                                   for b in eager.execute(0)]).to_pandas()
        b = pa.Table.from_batches([b.compact().to_arrow()
                                   for b in fused.execute(0)]).to_pandas()
        a = a.sort_values("kmod").reset_index(drop=True)
        b = b.sort_values("kmod").reset_index(drop=True)
        np.testing.assert_allclose(a["s.sum"].to_numpy(),
                                   b["s.sum"].to_numpy(), rtol=1e-9)

    def test_overflow_degrades_to_passthrough_and_final_agg_fixes_it(self):
        t = _table(n=4000)
        config.conf.set(config.ON_DEVICE_AGG_CAPACITY.key, 16)
        # device hash-table mechanics under test: bypass the Arrow path
        config.conf.set(config.FUSED_HOST_VECTORIZED_ENABLE.key, False)
        try:
            partial = fuse_plan(self._plan_with_computed_key(t))
            assert partial.fused_mode == "sorted"
            ex = LocalShuffleExchange(partial,
                                      HashPartitioning([col(0)], 1))
            final = AggExec(ex, [(col(0, "kmod"), "kmod")],
                            [(make_agg("sum", [col(1)]),
                              AggMode.PARTIAL_MERGE, "s")])
            out = pa.Table.from_batches(
                [b.compact().to_arrow() for b in final.execute(0)]
            ).to_pandas().sort_values("kmod").reset_index(drop=True)
            assert int(partial.metrics.get("partial_skipped")) >= 1
        finally:
            config.conf.unset(config.ON_DEVICE_AGG_CAPACITY.key)
            config.conf.unset(config.FUSED_HOST_VECTORIZED_ENABLE.key)
        df = t.to_pandas()
        df["kmod"] = df.cust % 50
        want = df.groupby("kmod").amt.sum().reset_index() \
            .sort_values("kmod").reset_index(drop=True)
        np.testing.assert_allclose(out["s.sum"].to_numpy(),
                                   want["amt"].to_numpy(), rtol=1e-9)


class TestEligibility:
    def test_string_keys_fuse_onto_host_path(self):
        """utf8 group keys ride the host-vectorized fused path (Arrow's
        hash agg handles strings natively); the eager lexsort fallback
        dominated string-keyed queries.  Device strategies still require
        fixed-width keys (the fuse gate re-checks placement)."""
        t = pa.table({"s": pa.array(["a", "b", "a", None]),
                      "v": pa.array([1.0, 2.0, 3.0, 4.0])})
        agg = AggExec(MemoryScanExec.from_arrow(t),
                      [(col(0, "s"), "s")],
                      [(make_agg("sum", [col(1)]), AggMode.PARTIAL, "v")])
        fused = fuse_plan(agg)
        assert isinstance(fused, FusedPartialAggExec)
        out = fused.execute_collect().to_arrow()
        got = dict(zip(out.column(0).to_pylist(),
                       out.column(1).to_pylist()))
        assert got == {"a": 4.0, "b": 2.0, None: 4.0}

    def test_avg_not_fused(self):
        t = _table(n=100)
        agg = AggExec(MemoryScanExec.from_arrow(t),
                      [(col(2, "store"), "store")],
                      [(make_agg("avg", [col(3)]), AggMode.PARTIAL, "a")])
        assert not isinstance(fuse_plan(agg), FusedPartialAggExec)

    def test_mixed_modes_not_fused(self):
        t = _table(n=100)
        agg = AggExec(MemoryScanExec.from_arrow(t),
                      [(col(2, "store"), "store")],
                      [(make_agg("sum", [col(3)]), AggMode.PARTIAL, "s"),
                       (make_agg("count", [col(3)]), AggMode.FINAL, "c")])
        assert not isinstance(fuse_plan(agg), FusedPartialAggExec)


class TestMergeModeFusion:
    def _two_stage(self, t, partitions=2):
        partial = AggExec(MemoryScanExec.from_arrow(t),
                          [(col(1, "cust"), "cust")],
                          [(make_agg("sum", [col(3)]), AggMode.PARTIAL,
                            "s"),
                           (make_agg("count", [col(3)]), AggMode.PARTIAL,
                            "c")])
        ex = LocalShuffleExchange(partial,
                                  HashPartitioning([col(0)], partitions))
        final = AggExec(ex, [(col(0, "cust"), "cust")],
                        [(make_agg("sum", [col(1)]), AggMode.FINAL, "s"),
                         (make_agg("count", [col(2)]), AggMode.FINAL,
                          "c")])
        return final

    def test_final_mode_fuses_and_matches_pandas(self):
        t = _table(n=6000)
        plan = fuse_plan(self._two_stage(t))
        assert isinstance(plan, FusedPartialAggExec)
        assert plan.fused_mode == "sorted"
        out = []
        for p in range(plan.num_partitions):
            out.extend(b.compact().to_arrow() for b in plan.execute(p))
        got = pa.Table.from_batches([b for b in out if b.num_rows]) \
            .to_pandas().sort_values("cust").reset_index(drop=True)
        want = t.to_pandas().groupby("cust", as_index=False).agg(
            s=("amt", "sum"), c=("amt", "count")) \
            .sort_values("cust").reset_index(drop=True)
        assert len(got) == len(want)
        np.testing.assert_allclose(got.s.to_numpy(), want.s.to_numpy(),
                                   rtol=1e-9)
        assert (got.c.to_numpy() == want.c.to_numpy()).all()

    @pytest.mark.parametrize("compact", [False, True],
                             ids=["old_slots", "compacted"])
    def test_final_mode_grows_instead_of_skipping(self, compact,
                                                  monkeypatch):
        """`compacted`: the tables of this test are smaller than the
        least width a rehash compacts to, so the floor is taken out of
        the width; the staged path then hands each rehash the carry's
        own group count and the answer stays what it was."""
        import blaze_tpu.plan.fused as fused
        widths, real = [], fused.rehash_width

        def width(groups, old_slots):
            lanes = min(old_slots, fused._pow2(groups))
            widths.append((groups, old_slots, lanes))
            return lanes if compact else real(groups, old_slots)
        monkeypatch.setattr(fused, "rehash_width", width)
        t = _table(n=6000)  # ~200 distinct cust per partition
        config.conf.set(config.ON_DEVICE_AGG_CAPACITY.key, 16)
        # this test exercises the DEVICE hash-table growth mechanics; the
        # host-vectorized Arrow path (default under host placement) never
        # builds that table
        config.conf.set(config.FUSED_HOST_VECTORIZED_ENABLE.key, False)
        try:
            plan = fuse_plan(self._two_stage(t, partitions=1))
            assert isinstance(plan, FusedPartialAggExec)
            out = [b.compact().to_arrow() for b in plan.execute(0)]
            got = pa.Table.from_batches([b for b in out if b.num_rows]) \
                .to_pandas().sort_values("cust").reset_index(drop=True)
            assert plan.metrics.get("table_grown") >= 1
            assert plan.metrics.get("partial_skipped") == 0
        finally:
            config.conf.unset(config.ON_DEVICE_AGG_CAPACITY.key)
            config.conf.unset(config.FUSED_HOST_VECTORIZED_ENABLE.key)
        want = t.to_pandas().groupby("cust", as_index=False).agg(
            s=("amt", "sum")).sort_values("cust").reset_index(drop=True)
        assert len(got) == len(want)
        np.testing.assert_allclose(got.s.to_numpy(), want.s.to_numpy(),
                                   rtol=1e-9)
        # one width a rehash, from the groups the carry held: never more
        # than its slots, and some of them fewer lanes than slots
        assert len(widths) == plan.metrics.get("table_grown")
        assert all(0 <= g <= lanes <= slots for g, slots, lanes in widths)
        assert any(lanes < slots for _g, slots, lanes in widths)

    def test_config_gate(self):
        t = _table(n=100)
        config.conf.set(config.FUSED_STAGE_ENABLE.key, False)
        try:
            agg = _partial_agg_plan(MemoryScanExec.from_arrow(t))
            assert not isinstance(fuse_plan(agg), FusedPartialAggExec)
        finally:
            config.conf.unset(config.FUSED_STAGE_ENABLE.key)

    def test_inner_agg_rewritten_in_place(self):
        # the fused node must also be found under other operators
        t = _table(n=500)
        partial = _partial_agg_plan(MemoryScanExec.from_arrow(t))
        ex = LocalShuffleExchange(partial,
                                  HashPartitioning([col(0), col(1)], 2))
        final = AggExec(ex,
                        [(col(0, "cust"), "cust"), (col(1, "store"),
                                                    "store")],
                        [(make_agg("sum", [col(2)]), AggMode.PARTIAL_MERGE,
                          "amt_sum")])
        top = fuse_plan(final)
        # both stages fuse now: the top-level PARTIAL_MERGE and the inner
        # PARTIAL under the exchange
        assert isinstance(top, FusedPartialAggExec)
        assert isinstance(ex.children[0], FusedPartialAggExec)


class TestHostVectorized:
    """The Arrow C++ hash-agg path taken under host placement
    (plan/fused.py _execute_host_vectorized) must be bit-compatible with
    the device hash-table path across null keys, all-null sums, count
    modes and the merge threshold."""

    def _run(self, plan):
        out = []
        for p in range(plan.num_partitions):
            out.extend(b.compact().to_arrow() for b in plan.execute(p))
        return pa.Table.from_batches([b for b in out if b.num_rows])

    def test_matches_device_path_with_null_keys(self):
        t = _table(n=8000, nulls=True)
        def build():
            scan = MemoryScanExec.from_arrow(t)
            flt = FilterExec(scan, [BinaryExpr(">", col(0, "date"),
                                               lit(150))])
            return fuse_plan(AggExec(
                flt, [(col(1, "cust"), "cust")],
                [(make_agg("sum", [col(3)]), AggMode.PARTIAL, "s"),
                 (make_agg("count", [col(3)]), AggMode.PARTIAL, "c"),
                 (make_agg("min", [col(0)]), AggMode.PARTIAL, "mn"),
                 (make_agg("max", [col(0)]), AggMode.PARTIAL, "mx")]))
        host = self._run(build()).to_pandas().sort_values(
            "cust", na_position="first").reset_index(drop=True)
        config.conf.set(config.FUSED_HOST_VECTORIZED_ENABLE.key, False)
        try:
            dev = self._run(build()).to_pandas().sort_values(
                "cust", na_position="first").reset_index(drop=True)
        finally:
            config.conf.unset(config.FUSED_HOST_VECTORIZED_ENABLE.key)
        assert len(host) == len(dev)
        np.testing.assert_allclose(host["s.sum"].to_numpy(float),
                                   dev["s.sum"].to_numpy(float), rtol=1e-9)
        assert (host["c.count"].to_numpy() ==
                dev["c.count"].to_numpy()).all()
        assert (host["mn.min"].to_numpy(float) ==
                dev["mn.min"].to_numpy(float)).all()

    def test_merge_threshold_re_merges(self):
        # force the incremental acc-table merge by shrinking the buffer
        t = _table(n=5000)
        scan = MemoryScanExec.from_arrow(t, batch_rows=256)
        plan = fuse_plan(AggExec(
            scan, [(col(1, "cust"), "cust")],
            [(make_agg("sum", [col(3)]), AggMode.PARTIAL, "s")]))
        assert isinstance(plan, FusedPartialAggExec)
        config.conf.set(config.FUSED_HOST_COLLECT_ROWS.key, 512)
        try:
            got = self._run(plan).to_pandas()
        finally:
            config.conf.unset(config.FUSED_HOST_COLLECT_ROWS.key)
        got = got.groupby("cust", as_index=False)["s.sum"].sum() \
            .sort_values("cust").reset_index(drop=True)
        want = t.to_pandas().groupby("cust", as_index=False).amt.sum() \
            .sort_values("cust").reset_index(drop=True)
        np.testing.assert_allclose(got["s.sum"].to_numpy(),
                                   want["amt"].to_numpy(), rtol=1e-9)

    def test_float_keys_stay_on_device_path(self):
        t = pa.table({"k": pa.array([1.0, float("nan"), float("nan")]),
                      "v": pa.array([1.0, 2.0, 3.0])})
        plan = fuse_plan(AggExec(
            MemoryScanExec.from_arrow(t), [(col(0, "k"), "k")],
            [(make_agg("sum", [col(1)]), AggMode.PARTIAL, "s")]))
        assert isinstance(plan, FusedPartialAggExec)
        assert not plan._host_vectorized_eligible()
        # NaN keys group together (Spark NormalizeFloatingNumbers)
        out = self._run(plan)
        assert out.num_rows == 2


class TestHostPartialSkipping:
    def test_high_cardinality_partial_skips_and_final_fixes_it(self):
        """Host-vectorized PARTIAL agg over near-unique keys must degrade
        to pass-through (AGG_TRIGGER_PARTIAL_SKIPPING analog) while the
        FINAL stage still produces exact results."""
        import numpy as np
        n = 4000
        rng = np.random.default_rng(3)
        t = pa.table({"k": pa.array(np.arange(n)),  # all-distinct keys
                      "v": pa.array(rng.random(n))})
        config.conf.set(config.FUSED_HOST_COLLECT_ROWS.key, 512)
        config.conf.set(config.PARTIAL_AGG_SKIPPING_MIN_ROWS.key, 256)
        try:
            partial = fuse_plan(AggExec(
                MemoryScanExec.from_arrow(t, batch_rows=256),
                [(col(0, "k"), "k")],
                [(make_agg("sum", [col(1)]), AggMode.PARTIAL, "s"),
                 (make_agg("count", [col(1)]), AggMode.PARTIAL, "c")]))
            assert isinstance(partial, FusedPartialAggExec)
            ex = LocalShuffleExchange(partial,
                                      HashPartitioning([col(0)], 2))
            final = AggExec(ex, [(col(0, "k"), "k")],
                            [(make_agg("sum", [col(1)]), AggMode.FINAL,
                              "s"),
                             (make_agg("count", [col(2)]), AggMode.FINAL,
                              "c")])
            out = []
            for p in range(2):
                out.extend(b.compact().to_arrow()
                           for b in final.execute(p))
            got = pa.Table.from_batches(
                [b for b in out if b.num_rows]).to_pandas() \
                .sort_values("k").reset_index(drop=True)
            assert int(partial.metrics.get("partial_skipped") or 0) >= 1
        finally:
            config.conf.unset(config.FUSED_HOST_COLLECT_ROWS.key)
            config.conf.unset(config.PARTIAL_AGG_SKIPPING_MIN_ROWS.key)
        want = t.to_pandas().groupby("k", as_index=False).agg(
            s=("v", "sum"), c=("v", "count")).sort_values("k") \
            .reset_index(drop=True)
        assert len(got) == len(want)
        np.testing.assert_allclose(got.s.to_numpy(), want.s.to_numpy(),
                                   rtol=1e-9)
        assert (got.c.to_numpy() == want.c.to_numpy()).all()

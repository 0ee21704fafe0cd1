"""Streaming sort-merge join tests: join-type matrix vs the hash-join
result (and pandas), sorted-children passthrough, SHJ->SMJ fallback
(ref joins/test.rs matrix, sort_merge_join_exec.rs:397)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from blaze_tpu import config
from blaze_tpu.exprs import BinaryExpr, col, lit
from blaze_tpu.ops import MemoryScanExec, SortExec
from blaze_tpu.ops.joins import JoinType
from blaze_tpu.ops.joins.exec import (ShuffledHashJoinExec,
                                      SortMergeJoinExec)


def _tables(seed=0, n_left=4000, n_right=3000, nulls=True):
    rng = np.random.default_rng(seed)
    lk = rng.integers(0, 500, n_left).astype(float)
    rk = rng.integers(0, 500, n_right).astype(float)
    if nulls:
        lk[rng.random(n_left) < 0.03] = np.nan
        rk[rng.random(n_right) < 0.03] = np.nan
    left = pa.table({
        "lk": pa.array([None if np.isnan(x) else int(x) for x in lk],
                       type=pa.int64()),
        "lv": pa.array(np.round(rng.random(n_left) * 10, 3))})
    right = pa.table({
        "rk": pa.array([None if np.isnan(x) else int(x) for x in rk],
                       type=pa.int64()),
        "rv": pa.array(np.round(rng.random(n_right) * 10, 3))})
    return left, right


def _run(plan):
    out = [b.compact().to_arrow() for b in plan.execute(0)]
    out = [b for b in out if b.num_rows]
    if not out:
        return pd.DataFrame()
    return pa.Table.from_batches(out).to_pandas()


def _sorted_frames(df):
    if df.empty:
        return df
    return df.sort_values(list(df.columns)).reset_index(drop=True)


@pytest.fixture(params=["acero", "streaming"])
def smj_path(request):
    """Both SMJ host paths stay covered: the Acero materialized join
    and the streaming run-cursor merge it falls back to when a side passes
    the collect budget (here: at its first row)."""
    key = config.FUSED_HOST_COLLECT_ROWS.key
    old = config.FUSED_HOST_COLLECT_ROWS.get()
    if request.param == "streaming":
        config.conf.set(key, 0)
    yield request.param
    config.conf.set(key, old)


@pytest.mark.parametrize("jt", list(JoinType))
def test_smj_matches_hash_join(jt, smj_path):
    left, right = _tables()
    smj = SortMergeJoinExec(
        MemoryScanExec.from_arrow(left, batch_rows=512),
        MemoryScanExec.from_arrow(right, batch_rows=512),
        [col(0)], [col(0)], jt)
    shj = ShuffledHashJoinExec(
        MemoryScanExec.from_arrow(left, batch_rows=512),
        MemoryScanExec.from_arrow(right, batch_rows=512),
        [col(0)], [col(0)], jt)
    a = _sorted_frames(_run(smj))
    b = _sorted_frames(_run(shj))
    assert len(a) == len(b), (jt, len(a), len(b))
    if len(a):
        pd.testing.assert_frame_equal(a, b, check_dtype=False,
                                      check_exact=False, atol=1e-9)


def test_smj_with_join_filter(smj_path):
    left, right = _tables(seed=3, n_left=1000, n_right=800)
    flt = BinaryExpr(">", col(1), col(3))  # lv > rv on joined schema
    smj = SortMergeJoinExec(
        MemoryScanExec.from_arrow(left), MemoryScanExec.from_arrow(right),
        [col(0)], [col(0)], JoinType.INNER, join_filter=flt)
    shj = ShuffledHashJoinExec(
        MemoryScanExec.from_arrow(left), MemoryScanExec.from_arrow(right),
        [col(0)], [col(0)], JoinType.INNER, join_filter=flt)
    a = _sorted_frames(_run(smj))
    b = _sorted_frames(_run(shj))
    assert len(a) == len(b)
    if len(a):
        pd.testing.assert_frame_equal(a, b, check_dtype=False,
                                      check_exact=False, atol=1e-9)


def test_smj_multi_key(smj_path):
    rng = np.random.default_rng(5)
    left = pa.table({"a": pa.array(rng.integers(0, 20, 2000)),
                     "b": pa.array(rng.integers(0, 10, 2000)),
                     "v": pa.array(rng.random(2000))})
    right = pa.table({"a": pa.array(rng.integers(0, 20, 1500)),
                      "b": pa.array(rng.integers(0, 10, 1500)),
                      "w": pa.array(rng.random(1500))})
    smj = SortMergeJoinExec(
        MemoryScanExec.from_arrow(left, batch_rows=256),
        MemoryScanExec.from_arrow(right, batch_rows=256),
        [col(0), col(1)], [col(0), col(1)], JoinType.INNER)
    got = _run(smj)
    want = left.to_pandas().merge(right.to_pandas(), on=["a", "b"])
    assert len(got) == len(want)


def test_smj_exploits_presorted_children():
    """A SortExec child on the join keys must stream through unwrapped."""
    left, right = _tables(seed=7, n_left=500, n_right=400)
    ls = SortExec(MemoryScanExec.from_arrow(left), [(col(0), False, True)])
    rs = SortExec(MemoryScanExec.from_arrow(right), [(col(0), False, True)])
    smj = SortMergeJoinExec(ls, rs, [col(0)], [col(0)], JoinType.INNER)
    assert smj._sorted_child(0) is ls
    assert smj._sorted_child(1) is rs
    got = _run(smj)
    want = left.to_pandas().dropna(subset=["lk"]).merge(
        right.to_pandas().dropna(subset=["rk"]),
        left_on="lk", right_on="rk")
    assert len(got) == len(want)


def test_smj_string_keys(smj_path):
    left = pa.table({"k": pa.array(["a", "b", "b", None, "c"]),
                     "v": pa.array([1, 2, 3, 4, 5], type=pa.int64())})
    right = pa.table({"k": pa.array(["b", "c", "c", None]),
                      "w": pa.array([10, 20, 30, 40], type=pa.int64())})
    smj = SortMergeJoinExec(
        MemoryScanExec.from_arrow(left), MemoryScanExec.from_arrow(right),
        [col(0)], [col(0)], JoinType.FULL)
    got = _run(smj)
    # inner pairs: 2 left 'b' rows x 1 right 'b' + 1 left 'c' x 2 right 'c';
    # unmatched left: 'a' and the NULL key; unmatched right: the NULL key
    assert len(got) == 2 + 2 + 2 + 1
    assert got.w.isna().sum() == 2   # unmatched left rows
    assert got.v.isna().sum() == 1   # unmatched right row


def test_shj_falls_back_to_smj_on_large_build():
    left, right = _tables(seed=11, n_left=3000, n_right=2500)
    config.conf.set(config.SMJ_FALLBACK_ENABLE.key, True)
    config.conf.set(config.SMJ_FALLBACK_ROWS_THRESHOLD.key, 100)
    try:
        shj = ShuffledHashJoinExec(
            MemoryScanExec.from_arrow(left),
            MemoryScanExec.from_arrow(right),
            [col(0)], [col(0)], JoinType.INNER)
        got = _sorted_frames(_run(shj))
        assert shj.metrics.get("smj_fallback") >= 1
    finally:
        config.conf.unset(config.SMJ_FALLBACK_ENABLE.key)
        config.conf.unset(config.SMJ_FALLBACK_ROWS_THRESHOLD.key)
    want = left.to_pandas().dropna(subset=["lk"]).merge(
        right.to_pandas().dropna(subset=["rk"]),
        left_on="lk", right_on="rk")
    assert len(got) == len(want)


def test_smj_nan_float_keys_match_like_spark(smj_path):
    """Spark treats NaN as a NORMAL value in join keys (NaN semantics
    doc; NormalizeFloatingNumbers applies to join keys): NaN joins NaN.
    NULL keys still never match.  SMJ, the vectorized hash probe, and
    the Acero host path must all agree."""
    left = pa.table({"lk": pa.array([1.0, 2.0, float("nan"), None]),
                     "lv": pa.array([10, 20, 30, 40], type=pa.int64())})
    right = pa.table({"rk": pa.array([2.0, 3.0, float("nan"), None]),
                      "rv": pa.array([200, 300, 400, 500],
                                     type=pa.int64())})
    smj = SortMergeJoinExec(
        MemoryScanExec.from_arrow(left), MemoryScanExec.from_arrow(right),
        [col(0)], [col(0)], JoinType.INNER)
    shj = ShuffledHashJoinExec(
        MemoryScanExec.from_arrow(left), MemoryScanExec.from_arrow(right),
        [col(0)], [col(0)], JoinType.INNER)
    a, b = _run(smj), _run(shj)
    assert len(a) == len(b) == 2  # 2.0 match + NaN match; nulls drop
    a = a.sort_values("lv")
    assert a.iloc[0].lk == 2.0 and a.iloc[0].rv == 200
    assert a.iloc[1].rv == 400  # NaN joined NaN


def test_smj_acero_overflow_resumes_streaming():
    """Collect-budget overflow mid-Acero-collection hands the consumed
    chunks to the streaming merge (sorted children) or re-executes
    (unsorted children) — results identical either way."""
    left, right = _tables(seed=3)
    key = config.FUSED_HOST_COLLECT_ROWS.key
    old = config.FUSED_HOST_COLLECT_ROWS.get()
    try:
        for presort in (True, False):
            l_scan = MemoryScanExec.from_arrow(left, batch_rows=256)
            r_scan = MemoryScanExec.from_arrow(right, batch_rows=256)
            lk, rk = [col(0, "lk")], [col(0, "rk")]
            if presort:
                l_in = SortExec(l_scan, [(lk[0], False, True)])
                r_in = SortExec(r_scan, [(rk[0], False, True)])
            else:
                l_in, r_in = l_scan, r_scan
            config.conf.set(key, old)
            want = _run(SortMergeJoinExec(l_in, r_in, lk, rk,
                                          JoinType.INNER))
            config.conf.set(key, 500)  # forces overflow on both sides
            got = _run(SortMergeJoinExec(l_in, r_in, lk, rk,
                                         JoinType.INNER))
            assert len(got) == len(want), (presort, len(got), len(want))
            gs = got.sort_values(list(got.columns)).reset_index(drop=True)
            ws = want.sort_values(list(want.columns)).reset_index(drop=True)
            pd.testing.assert_frame_equal(gs, ws)
    finally:
        config.conf.set(key, old)

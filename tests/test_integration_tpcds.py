"""TPC-DS integration tests (the dev/auron-it tier, SURVEY.md §4 tier 4).

Every query runs the full production path: synthetic tables written to
parquet file splits -> JSON-IR plan dict -> create_plan -> fuse_plan ->
execute, compared cell-wise against a pandas oracle, with plan-stability
goldens snapshotted from the DECODED (and fused) plan.

Scale: BLAZE_TPCDS_SCALE env (default 0.2; BASELINE configs call for 1.0 —
run `BLAZE_TPCDS_SCALE=1.0 pytest tests/test_integration_tpcds.py` for
the full SF1 tier).
"""

import os

import pytest

pytestmark = pytest.mark.slow  # deselect with -m 'not slow'

from blaze_tpu.itest import check_plan_stability, generate, run_query
from blaze_tpu.itest.queries import QUERIES
from blaze_tpu.itest.tpcds_data import write_parquet_splits
from blaze_tpu.memory import MemManager
from blaze_tpu.plan import create_plan
from blaze_tpu.plan.fused import fuse_plan

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "goldens")
SCALE = float(os.environ.get("BLAZE_TPCDS_SCALE", "0.2"))


@pytest.fixture(autouse=True)
def budget():
    MemManager.init(4 << 30)


def _build(qname, tmp_path, scale=SCALE, partitions=2):
    builder, table_names = QUERIES[qname]
    tables = generate(table_names, scale=scale)
    paths = write_parquet_splits(tables, str(tmp_path), partitions)
    plan_dict, oracle = builder(paths, tables, partitions)
    return plan_dict, oracle


@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_tpcds_query(qname, tmp_path):
    plan_dict, oracle = _build(qname, tmp_path)
    plan = fuse_plan(create_plan(plan_dict))
    res = run_query(qname, plan, oracle)
    assert res.passed, f"{qname}: {res.detail}"
    diff = check_plan_stability(
        plan, os.path.join(GOLDEN_DIR, f"{qname}.plan.txt"),
        update=os.environ.get("BLAZE_UPDATE_GOLDENS") == "1")
    assert diff is None, f"plan changed for {qname}:\n{diff}"


def _spill_counts(metrics) -> int:
    total = metrics.get("spill_count") or 0
    for child in getattr(metrics, "children", []):
        total += _spill_counts(child)
    return int(total)


def test_q01_spills_under_pressure(tmp_path):
    """End-to-end spill: a tiny memory budget must drive the shuffle /
    agg consumers to disk without changing the result (VERDICT r1 #4).
    The plan runs un-fused (create_plan only, no fuse_plan), so the eager
    MemConsumer aggregation path carries the load."""
    plan_dict, oracle = _build("q01", tmp_path, scale=0.2)
    MemManager.init(256 << 10)  # 256 KiB budget
    try:
        plan = create_plan(plan_dict)
        res = run_query("q01-spill", plan, oracle)
        assert res.passed, res.detail
        spills = _spill_counts(plan.collect_metrics())
        assert spills > 0, \
            "expected at least one spill under a 256KiB budget"
    finally:
        MemManager.init(4 << 30)


@pytest.mark.slow
def test_wire_query_on_real_accelerator():
    """Device-placement wire path on REAL accelerator hardware: q52
    through DagScheduler with auron.tpu.placement=device.  conftest.py
    pins pytest to the CPU platform, so this skips everywhere pytest
    runs; the chip's proof of the same path is a benchmark cell's run
    (`sf10_q01_x1` and `sf1_q06_x1` go through DagScheduler and compare
    every answer; `chip_smoke.py` proves the kernels alone)."""
    import jax

    from blaze_tpu import config
    if jax.default_backend() == "cpu":
        pytest.skip("no accelerator backend in this environment")
    import tempfile

    import pandas as pd

    from blaze_tpu.bridge import placement as P
    from blaze_tpu.itest import generate
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.plan.stages import DagScheduler
    config.conf.set(config.PLACEMENT.key, "device")
    P._info = None  # re-decide placement under the forced policy
    try:
        builder, tn = QUERIES["q52"]
        tables = generate(tn, scale=0.05)
        with tempfile.TemporaryDirectory() as tmp:
            paths = write_parquet_splits(tables, tmp, 2)
            plan_dict, oracle = builder(paths, tables, 2)
            got = DagScheduler(work_dir=tmp + "/dag").run_collect(
                plan_dict)
            g = got.to_pandas() if got.num_rows else pd.DataFrame(
                {n: [] for n in got.schema.names})
            assert compare_frames(g, oracle()) is None
    finally:
        config.conf.unset(config.PLACEMENT.key)
        P._info = None

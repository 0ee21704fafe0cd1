"""Per-fingerprint statistics store (plan/statstore.py) and the
advisor built on it (plan/advisor.py): sketch determinism, merged
priors across runs, deterministic replay, retention, disabled-path
hygiene, and the findings catalog.
"""

import json
import os
import subprocess
import sys

import pytest

from blaze_tpu import config
from blaze_tpu.plan import advisor, statstore


@pytest.fixture(autouse=True)
def clean_probe():
    statstore.reset_conf_probe()
    try:
        yield
    finally:
        for opt in (config.STATS_ENABLE, config.STATS_DIR,
                    config.STATS_MAX_FINGERPRINTS,
                    config.STATS_SKETCH_CENTROIDS):
            config.conf.unset(opt.key)
        statstore.reset_conf_probe()


@pytest.fixture
def stats_on(tmp_path):
    d = str(tmp_path / "stats")
    config.conf.set(config.STATS_ENABLE.key, "on")
    config.conf.set(config.STATS_DIR.key, d)
    statstore.reset_conf_probe()
    return d


def _obs(fp="fp-a", wall=1.0, **over):
    obs = {
        "fingerprint": fp,
        "wall_s": wall,
        "task_ns": [1_000_000, 2_000_000, 4_000_000],
        "counters": {"partial_agg_probe_rows": 100,
                     "partial_agg_probe_groups": 40,
                     "expr_programs_built": 2,
                     "expr_program_cache_hits": 6},
        "fallback_reasons": {},
        "stages": [{"fingerprint": "st-0", "sid": 0, "tasks": 2,
                    "partitions": 4,
                    "partition_bytes": [100, 110, 90, 105],
                    "exchange": "file", "output_rows": 50}],
    }
    obs.update(over)
    return obs


# -- quantile sketch ---------------------------------------------------------

def test_sketch_quantiles_and_extremes():
    sk = statstore.sketch_new()
    statstore.sketch_add(sk, [float(i) for i in range(1, 101)], budget=32)
    assert sk["count"] == 100
    assert statstore.sketch_quantile(sk, 0.0) == 1.0  # exact min
    assert statstore.sketch_quantile(sk, 1.0) == 100.0  # exact max
    p50 = statstore.sketch_quantile(sk, 0.5)
    assert 45.0 <= p50 <= 56.0  # bounded error under compression
    assert statstore.sketch_spread(sk) == pytest.approx(80.0, abs=8.0)


def test_sketch_compression_is_deterministic():
    vals = [float((i * 37) % 101) for i in range(200)]
    a, b = statstore.sketch_new(), statstore.sketch_new()
    statstore.sketch_add(a, vals, budget=16)
    statstore.sketch_add(b, vals, budget=16)
    assert a == b  # same input -> byte-identical sketch


def test_sketch_merge_preserves_count_and_extremes():
    a, b = statstore.sketch_new(), statstore.sketch_new()
    statstore.sketch_add(a, [1.0, 2.0, 3.0], budget=8)
    statstore.sketch_add(b, [100.0], budget=8)
    m = statstore.sketch_merge(a, b, budget=8)
    assert m["count"] == 4
    assert m["min"] == 1.0 and m["max"] == 100.0
    assert statstore.sketch_quantile(m, 1.0) == 100.0


def test_empty_sketch_quantile_is_none():
    assert statstore.sketch_quantile(statstore.sketch_new(), 0.5) is None
    assert statstore.sketch_spread(statstore.sketch_new()) is None


# -- disabled path -----------------------------------------------------------

def test_disabled_by_default_writes_nothing(tmp_path):
    d = str(tmp_path / "stats")
    config.conf.set(config.STATS_DIR.key, d)  # dir set, enable NOT set
    statstore.reset_conf_probe()
    assert statstore.enabled() is False
    assert statstore.ingest(_obs()) is None
    assert statstore.prior("fp-a") is None
    assert not os.path.exists(d)  # not even the directory


# -- merge across runs -------------------------------------------------------

def test_two_runs_merge_into_one_record(stats_on):
    statstore.ingest(_obs(wall=1.0))
    rec = statstore.ingest(_obs(wall=1.2))
    assert rec["run_count"] == 2
    assert rec["wall_s"]["count"] == 2
    # counters accumulate; ratios are recomputed from the tallies
    assert rec["counters"]["partial_agg_probe_rows"] == 200
    assert rec["derived"]["agg_probe_ratio"] == pytest.approx(0.4)
    assert rec["derived"]["expr_cache_hit_rate"] == pytest.approx(0.75)
    assert rec["derived"]["wall_p50_s"] == pytest.approx(1.1)
    # the stage merged under its subplan fingerprint
    st = rec["stages"]["st-0"]
    assert st["run_count"] == 2
    assert st["partition_bytes"]["count"] == 8
    assert st["last_partition_bytes"] == [100, 110, 90, 105]


def test_more_runs_tighten_the_wall_spread(stats_on):
    statstore.ingest(_obs(wall=1.0))
    statstore.ingest(_obs(wall=5.0))
    wide = statstore.prior("fp-a")["derived"]["wall_spread_s"]
    for _ in range(20):
        statstore.ingest(_obs(wall=3.0))
    tight = statstore.prior("fp-a")["derived"]["wall_spread_s"]
    assert tight < wide  # p90-p10 narrows as mass concentrates


def test_fresh_process_replay_is_bit_stable(stats_on):
    statstore.ingest(_obs(wall=1.0))
    rec = statstore.ingest(_obs(wall=1.5))
    in_proc = statstore._dumps(rec)
    out = subprocess.run(
        [sys.executable, "-c",
         "import json,sys\n"
         "from blaze_tpu.plan import statstore\n"
         "r = statstore.StatStore(sys.argv[1]).record('fp-a')\n"
         "sys.stdout.write(statstore._dumps(r))", stats_on],
        capture_output=True, text=True, check=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.stdout == in_proc


def test_torn_trailing_line_is_skipped(stats_on):
    statstore.ingest(_obs(wall=1.0))
    path = statstore._fp_path(stats_on, "fp-a")
    with open(path, "a") as f:
        f.write('{"v": 1, "run_cou')  # crash mid-append
    rec = statstore.StatStore(stats_on).record("fp-a")
    assert rec is not None and rec["run_count"] == 1
    # the next ingest merges onto the last VALID line
    rec = statstore.ingest(_obs(wall=2.0))
    assert rec["run_count"] == 2


def test_record_with_a_retired_counter_still_replays(stats_on):
    """A store written by an older build may carry a counter that has
    since left INGEST_COUNTERS (the kernel lanes' decline count did, with
    its eviction reason): the record is read as it stands, the old key
    rides along untouched and later runs merge."""
    statstore.ingest(_obs(wall=1.0))
    path = statstore._fp_path(stats_on, "fp-a")
    with open(path) as f:
        old = json.loads(f.read().splitlines()[-1])
    old["counters"]["retired_lane_declines"] = 3
    old["fallback_reasons"]["retired_lane"] = 3
    with open(path, "w") as f:
        f.write(statstore._dumps(old) + "\n")
    assert "retired_lane_declines" not in statstore.INGEST_COUNTERS
    rec = statstore.ingest(_obs(wall=2.0))
    assert rec["run_count"] == 2
    assert rec["counters"]["partial_agg_probe_rows"] == 200
    assert rec["counters"]["retired_lane_declines"] == 3
    assert statstore.StatStore(stats_on).record("fp-a") == rec
    assert {f["kind"] for f in advisor.findings(rec)} >= {"host_eviction"}


def test_compaction_bounds_file_growth(stats_on):
    for i in range(statstore._MAX_LINES + 3):
        statstore.ingest(_obs(wall=1.0 + i * 0.01))
    path = statstore._fp_path(stats_on, "fp-a")
    with open(path) as f:
        lines = f.read().splitlines()
    assert len(lines) <= statstore._MAX_LINES
    rec = statstore.StatStore(stats_on).record("fp-a")
    assert rec["run_count"] == statstore._MAX_LINES + 3  # nothing lost


def test_retention_prunes_oldest_fingerprints(stats_on):
    config.conf.set(config.STATS_MAX_FINGERPRINTS.key, 3)
    for i in range(6):
        path = statstore._fp_path(stats_on, f"fp-{i}")
        statstore.ingest(_obs(fp=f"fp-{i}"))
        os.utime(path, (1_000_000 + i, 1_000_000 + i))
    statstore.ingest(_obs(fp="fp-9"))
    fps = statstore.StatStore(stats_on).fingerprints()
    assert len(fps) <= 3
    assert "fp-9" in fps  # newest survives


def test_store_summary_shape(stats_on):
    statstore.ingest(_obs())
    (s,) = statstore.StatStore(stats_on).summary()
    assert s["fingerprint"] == "fp-a"
    assert s["run_count"] == 1
    assert s["stages"] == 1
    assert s["wall_p50_s"] == pytest.approx(1.0)


def test_ingest_counters_exist_in_xla_stats():
    from blaze_tpu.bridge import xla_stats
    snap = xla_stats.snapshot()
    missing = [k for k in statstore.INGEST_COUNTERS if k not in snap]
    assert not missing, f"statstore names unknown counters: {missing}"


# -- advisor -----------------------------------------------------------------

def _record(**runs):
    rec = statstore._new_record("fp-adv")
    for obs in runs.get("observations", [_obs(fp="fp-adv")]):
        statstore.merge_observation(rec, obs)
    return rec


def test_advisor_broadcast_candidate():
    rec = _record()
    kinds = {f["kind"] for f in advisor.findings(rec)}
    assert "broadcast_candidate" in kinds  # ~400B shuffle


def test_advisor_skew_partition_names_the_partition():
    obs = _obs(fp="fp-adv")
    obs["stages"][0]["partition_bytes"] = [100, 100, 100, 5000]
    rec = _record(observations=[obs])
    (f,) = [f for f in advisor.findings(rec)
            if f["kind"] == "skew_partition"]
    assert f["evidence"]["partition"] == 3
    assert f["evidence"]["ratio"] == pytest.approx(50.0)


def test_advisor_host_eviction_and_high_cardinality():
    obs = _obs(fp="fp-adv")
    obs["counters"]["partial_agg_probe_groups"] = 95
    obs["fallback_reasons"] = {"stage_loop": 3}
    rec = _record(observations=[obs])
    kinds = {f["kind"] for f in advisor.findings(rec)}
    assert "high_cardinality_agg" in kinds  # ratio 0.95 >= 0.8
    assert "host_eviction" in kinds


def test_advisor_low_cache_hit_rate():
    obs = _obs(fp="fp-adv")
    obs["counters"]["expr_programs_built"] = 20
    obs["counters"]["expr_program_cache_hits"] = 2
    rec = _record(observations=[obs])
    assert any(f["kind"] == "low_cache_hit_rate"
               for f in advisor.findings(rec))


def test_advisor_dominant_bottleneck_uses_report():
    rec = statstore._new_record("fp-adv")
    bn = {"dominant": "exchange_wire", "dominant_fraction": 0.7,
          "wall_s": 2.0, "categories": {"exchange_wire": 1.4}}
    (f,) = [f for f in advisor.findings(rec, bn)
            if f["kind"] == "dominant_bottleneck"]
    assert "exchange_wire" in f["summary"]


def test_advisor_findings_are_deterministically_ordered():
    rec = _record()
    a = advisor.findings(rec)
    b = advisor.findings(rec)
    assert a == b
    assert a == sorted(a, key=lambda f: (
        f["kind"], -1 if f["stage"] is None else f["stage"],
        f["summary"]))


def test_advisor_empty_record_is_quiet():
    assert advisor.findings(None) == []
    assert advisor.findings(statstore._new_record("fp-x")) == []


# -- end-to-end: scheduler ingest -------------------------------------------

def test_scheduler_ingests_boundaries_and_merges_priors(
        stats_on, tmp_path):
    from blaze_tpu.memory import MemManager
    from blaze_tpu.plan.stages import DagScheduler
    from tests.test_serving import _two_stage_plan

    MemManager.init(4 << 30)
    plan = _two_stage_plan(tmp_path, n=2_000)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    try:
        fp = None
        for i in range(2):
            sched = DagScheduler(work_dir=str(tmp_path / f"run{i}"))
            sched.run_collect(plan)
            assert sched.stats_fingerprint
            assert fp in (None, sched.stats_fingerprint)  # stable fp
            fp = sched.stats_fingerprint
        rec = statstore.prior(fp)
        assert rec["run_count"] == 2
        assert rec["wall_s"]["count"] == 2
        # the shuffle boundary was captured with real partition bytes
        assert rec["stages"], "no stage boundary ingested"
        st = next(iter(rec["stages"].values()))
        assert st["run_count"] == 2
        assert sum(st["last_partition_bytes"]) > 0
        # and the merged record replays bit-stable from disk
        again = statstore.StatStore(stats_on).record(fp)
        assert statstore._dumps(again) == statstore._dumps(rec)
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)


# -- knobs documented --------------------------------------------------------

def test_stats_knobs_are_documented():
    docs = config.generate_docs()
    for opt in (config.STATS_ENABLE, config.STATS_DIR,
                config.STATS_MAX_FINGERPRINTS,
                config.STATS_SKETCH_CENTROIDS,
                config.STATS_ADVISOR_BROADCAST_BYTES,
                config.STATS_ADVISOR_SKEW_FACTOR):
        assert opt.key in docs
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "docs", "configuration.md")) as f:
        committed = f.read()
    assert config.STATS_ENABLE.key in committed, \
        "docs/configuration.md is stale: regenerate via " \
        "config.generate_docs()"

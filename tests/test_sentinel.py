"""Regression sentinel (blaze_tpu/tools/sentinel.py) over saved
/history/rollup payloads: direction inference, noise floors, and the
exit-code contract."""

import json

import pytest

from blaze_tpu.tools import sentinel


# -- direction inference / flatten -------------------------------------------

@pytest.mark.parametrize("key,want", [
    ("q01.wall_s", "lower"),
    ("serve.p99_latency_ms", "lower"),
    ("spill_bytes", "lower"),
    ("stage_recoveries", "lower"),
    ("e2e.rows_per_sec", "higher"),
    ("tenants.acme.qps", "higher"),
    ("expr_cache_hit_rate", "higher"),
    ("device_utilization", "higher"),
    ("mystery_metric", "unknown"),
])
def test_metric_direction(key, want):
    assert sentinel.metric_direction(key) == want


def test_flatten_skips_version_tag_and_bools():
    rec = {"schema_version": 1, "status": "done",
           "value": 2.5, "nested": {"ok": True, "n": 3},
           "list": [1.0, {"x": 4}]}
    flat = sentinel.flatten(rec)
    assert flat == {"value": 2.5, "nested.n": 3.0,
                    "list.0": 1.0, "list.1.x": 4.0}


# -- compare / exit codes ----------------------------------------------------

def _write(tmp_path, name, rec):
    path = str(tmp_path / name)
    with open(path, "w") as f:
        json.dump(rec, f)
    return path


BASE = {"schema_version": 1,
        "q01": {"wall_s": 1.0, "rows_per_sec": 1000.0},
        "oddball": 10.0}


def test_identical_artifacts_exit_zero(tmp_path, capsys):
    b = _write(tmp_path, "rollup_before.json", BASE)
    c = _write(tmp_path, "rollup_after.json", dict(BASE))
    assert sentinel.main(["--baseline", b, "--candidate", c,
                          "--ci"]) == 0
    assert "0 regression(s)" in capsys.readouterr().out


def test_regression_exits_two_and_names_metric(tmp_path, capsys):
    b = _write(tmp_path, "rollup_before.json", BASE)
    worse = {**BASE, "q01": {"wall_s": 1.5, "rows_per_sec": 1000.0}}
    c = _write(tmp_path, "rollup_after.json", worse)
    assert sentinel.main(["--baseline", b, "--candidate", c,
                          "--threshold", "0.10"]) == 2
    out = capsys.readouterr().out
    assert "REGRESSION q01.wall_s" in out
    assert "baseline=1.0 candidate=1.5" in out


def test_improvement_does_not_fail(tmp_path):
    b = _write(tmp_path, "rollup_before.json", BASE)
    better = {**BASE, "q01": {"wall_s": 0.5, "rows_per_sec": 2000.0}}
    c = _write(tmp_path, "rollup_after.json", better)
    assert sentinel.main(["--baseline", b, "--candidate", c,
                          "--threshold", "0.10"]) == 0


def test_throughput_drop_regresses(tmp_path, capsys):
    b = _write(tmp_path, "rollup_before.json", BASE)
    worse = {**BASE, "q01": {"wall_s": 1.0, "rows_per_sec": 500.0}}
    c = _write(tmp_path, "rollup_after.json", worse)
    assert sentinel.main(["--baseline", b, "--candidate", c,
                          "--threshold", "0.10"]) == 2
    assert "q01.rows_per_sec" in capsys.readouterr().out


def test_unknown_direction_fails_on_drift_either_way(tmp_path):
    b = _write(tmp_path, "rollup_before.json", BASE)
    c = _write(tmp_path, "rollup_after.json", {**BASE, "oddball": 20.0})
    assert sentinel.main(["--baseline", b, "--candidate", c,
                          "--threshold", "0.10"]) == 2


def test_change_within_threshold_passes(tmp_path):
    b = _write(tmp_path, "rollup_before.json", BASE)
    mild = {**BASE, "q01": {"wall_s": 1.05, "rows_per_sec": 1000.0}}
    c = _write(tmp_path, "rollup_after.json", mild)
    assert sentinel.main(["--baseline", b, "--candidate", c,
                          "--threshold", "0.10"]) == 0


def test_abs_floor_suppresses_tiny_changes(tmp_path):
    b = _write(tmp_path, "rollup_before.json", {"tiny": 1e-9})
    c = _write(tmp_path, "rollup_after.json", {"tiny": 5e-9})  # +400% but tiny
    assert sentinel.main(["--baseline", b, "--candidate", c,
                          "--threshold", "0.10"]) == 0


def test_missing_metric_fails_only_in_ci_mode(tmp_path):
    b = _write(tmp_path, "rollup_before.json", BASE)
    dropped = {k: v for k, v in BASE.items() if k != "oddball"}
    c = _write(tmp_path, "rollup_after.json", dropped)
    args = ["--baseline", b, "--candidate", c, "--threshold", "0.10"]
    assert sentinel.main(args) == 0
    assert sentinel.main(args + ["--ci"]) == 2


def test_unloadable_input_exits_one(tmp_path):
    b = _write(tmp_path, "rollup_before.json", BASE)
    assert sentinel.main(["--baseline", b,
                          "--candidate", str(tmp_path / "nope.json")]) == 1


def test_metrics_filter_limits_the_diff(tmp_path):
    b = _write(tmp_path, "rollup_before.json", BASE)
    worse = {**BASE, "q01": {"wall_s": 1.5, "rows_per_sec": 1000.0}}
    c = _write(tmp_path, "rollup_after.json", worse)
    assert sentinel.main(["--baseline", b, "--candidate", c,
                          "--threshold", "0.10",
                          "--metrics", "oddball*"]) == 0


def test_json_report_mode(tmp_path, capsys):
    b = _write(tmp_path, "rollup_before.json", BASE)
    worse = {**BASE, "q01": {"wall_s": 1.5, "rows_per_sec": 1000.0}}
    c = _write(tmp_path, "rollup_after.json", worse)
    assert sentinel.main(["--baseline", b, "--candidate", c,
                          "--threshold", "0.10", "--json"]) == 2
    report = json.loads(capsys.readouterr().out)
    assert report["regressions"] == 1
    assert report["findings"][0]["metric"] == "q01.wall_s"
    assert report["findings"][0]["direction"] == "lower"

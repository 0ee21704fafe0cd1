"""Regression sentinel: diff two saved /history/rollup payloads (or any
two JSON documents of numbers) against each other.

    python -m blaze_tpu.tools.sentinel \
        --baseline rollup_before.json --candidate rollup_after.json \
        [--threshold 0.10] [--abs-floor 1e-6] [--metrics 'counters.*'] \
        [--ci] [--json]

`--baseline` / `--candidate` each name one JSON file.  Numeric leaves
are flattened to dotted metric keys and compared pairwise.

A metric regresses when its relative change exceeds `--threshold` in
the WORSE direction — metric names carry the direction (`wall`, `_ms`,
`p99`, `retries`, ... are lower-is-better; `rows_per_sec`, `qps`,
`hit_rate`, ... higher-is-better; unknown names fail on drift in either
direction, the conservative CI posture).  Two noise floors cut flapping
on tiny values: absolute change below `--abs-floor` never fires, and
the relative change is computed against max(|baseline|, 1e-9).

Exit codes (the CI contract):

* ``0`` — no regression (identical runs always exit 0);
* ``1`` — usage / IO error;
* ``2`` — regression: every offending metric is named on stdout.

``--ci`` additionally fails (exit 2) on metrics present in the baseline
but missing from the candidate.
Default thresholds come from `auron.tpu.sentinel.threshold`.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import re
import sys
from typing import Any, Dict, List, Optional

#: top-level keys that tag a payload's shape and are not metrics
_NOT_METRICS = ("schema_version",)

_LOWER_IS_BETTER = re.compile(
    r"(wall|latency|_ms\b|_ns\b|_s\b|seconds|p50|p95|p99|overhead|"
    r"spill|wait|gap|idle|retries|failures|crashes|fallbacks|declines|"
    r"evictions|recoveries|lag|delay|queued|dropped|misses|error|"
    r"lost|reroutes|torn_frames|down_events|"
    # encoding lanes (ISSUE 20): checked before the generic "fraction"
    # higher-is-better rule below, so eviction_fraction scores the
    # right way; remaps are dictionary-merge work at exchange edges
    r"eviction_fraction|dict_exchange_remaps)",
    re.IGNORECASE)
_HIGHER_IS_BETTER = re.compile(
    r"(rows_per_sec|per_sec|qps|throughput|hit_rate|hits\b|"
    r"fraction|utilization|rows\b|completed|coalesces|bytes_saved|"
    r"overlap(?:ped)?|replicas_up|hedge_wins|"
    r"aqe_(rewrites|broadcast_switches|partitions_coalesced|"
    r"skew_splits|history_seeds|stages_elided)|"
    # encoding lanes (ISSUE 20): more columns riding int codes / more
    # decimal work dispatched on the scaled-int tiers = more of the
    # workload device-resident
    r"dict_encoded_columns|decimal_scaled_int\d+_dispatches|"
    r"decimal_limb_dispatches|stage_loop_tasks|device_exchanges)",
    re.IGNORECASE)


def metric_direction(key: str) -> str:
    """'lower' | 'higher' | 'unknown' — which way is better."""
    if _LOWER_IS_BETTER.search(key):
        return "lower"
    if _HIGHER_IS_BETTER.search(key):
        return "higher"
    return "unknown"


def flatten(obj: Any, prefix: str = "") -> Dict[str, float]:
    """Numeric leaves as dotted keys; the payload's version tag is
    skipped."""
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            if not prefix and k in _NOT_METRICS:
                continue
            out.update(flatten(v, f"{prefix}{k}."))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            out.update(flatten(v, f"{prefix}{i}."))
    elif isinstance(obj, bool):
        pass  # ok/flags are not metrics
    elif isinstance(obj, (int, float)):
        out[prefix[:-1]] = float(obj)
    return out


def load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def compare(baseline: Dict[str, Any], candidate: Dict[str, Any], *,
            threshold: float, abs_floor: float = 1e-6,
            metrics: Optional[str] = None,
            ci: bool = False) -> List[Dict[str, Any]]:
    """Findings list, worst first; a finding with kind='regression'
    drives the nonzero exit."""
    base = flatten(baseline)
    cand = flatten(candidate)
    findings: List[Dict[str, Any]] = []
    for key in sorted(base):
        if metrics and not fnmatch.fnmatch(key, metrics):
            continue
        if key not in cand:
            findings.append({
                "metric": key, "kind": "regression" if ci else "missing",
                "direction": "missing", "baseline": base[key],
                "candidate": None, "change": None,
                "detail": "present in baseline, missing from candidate"})
            continue
        b, c = base[key], cand[key]
        if abs(c - b) < abs_floor:
            continue
        rel = (c - b) / max(abs(b), 1e-9)
        if abs(rel) <= threshold:
            continue
        direction = metric_direction(key)
        worse = (direction == "lower" and rel > 0) or \
                (direction == "higher" and rel < 0) or \
                direction == "unknown"
        findings.append({
            "metric": key,
            "kind": "regression" if worse else "improvement",
            "direction": direction, "baseline": b, "candidate": c,
            "change": round(rel, 4),
            "detail": f"{rel:+.1%} vs baseline "
                      f"(threshold {threshold:.0%})"})
    findings.sort(key=lambda f: (f["kind"] != "regression",
                                 -abs(f.get("change") or 1.0)))
    return findings


def _default_threshold() -> float:
    try:
        from blaze_tpu import config
        return float(config.SENTINEL_THRESHOLD.get())
    except Exception:
        return 0.10


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m blaze_tpu.tools.sentinel",
        description="diff two saved /history/rollup payloads; exit 2 "
                    "on regression")
    ap.add_argument("--baseline", required=True,
                    help="baseline JSON file")
    ap.add_argument("--candidate", required=True,
                    help="candidate JSON file")
    ap.add_argument("--threshold", type=float,
                    default=_default_threshold(),
                    help="relative noise floor (default "
                         "auron.tpu.sentinel.threshold)")
    ap.add_argument("--abs-floor", type=float, default=1e-6,
                    help="absolute change below this never fires")
    ap.add_argument("--metrics", default=None,
                    help="fnmatch filter on dotted metric keys")
    ap.add_argument("--ci", action="store_true",
                    help="strict mode: missing metrics also regress")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report on stdout")
    args = ap.parse_args(argv)

    try:
        baseline = load(args.baseline)
        candidate = load(args.candidate)
    except (OSError, ValueError) as e:
        print(f"sentinel: cannot load inputs: {e}", file=sys.stderr)
        return 1

    findings = compare(baseline, candidate, threshold=args.threshold,
                       abs_floor=args.abs_floor, metrics=args.metrics,
                       ci=args.ci)
    regressions = [f for f in findings if f["kind"] == "regression"]
    if args.as_json:
        print(json.dumps({"threshold": args.threshold,
                          "findings": findings,
                          "regressions": len(regressions)},
                         indent=1, sort_keys=True))
    else:
        for f in findings:
            print(f"{f['kind'].upper()} {f['metric']}: "
                  f"baseline={f['baseline']} candidate={f['candidate']} "
                  f"({f['detail']})")
        print(f"sentinel: {len(regressions)} regression(s), "
              f"{len(findings) - len(regressions)} other finding(s) "
              f"at threshold {args.threshold:.0%}")
    return 2 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())

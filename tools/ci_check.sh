#!/usr/bin/env bash
# CI gate: tier-1 tests, then the perf-regression sentinel against the
# committed BENCH_*.json baselines.  A perf regression fails the build
# instead of only being reportable.
#
# Usage:
#   tools/ci_check.sh                    # tier-1 + sentinel over --sentinel
#   CI_BENCH_LEGS="--sentinel --obs" tools/ci_check.sh
#   CI_SKIP_TESTS=1 tools/ci_check.sh   # sentinel only (tests ran already)
#
# Each leg in CI_BENCH_LEGS is re-run into a scratch dir (via the
# BLAZE_BENCH_<LEG>_PATH override every leg honors) and compared
# per-artifact against the committed baseline of the same name — the
# whole committed directory is NOT used as one baseline, because a
# candidate that regenerates only some legs would fail --ci's
# missing-metric check for the rest.
set -euo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"

# CI runs on the host platform and says so; the chip is exercised by
# `python chip_smoke.py` through the chip tool, never from here
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

if [ "${CI_SKIP_TESTS:-0}" != "1" ]; then
    echo "== ci_check: tier-1 tests =="
    python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider
fi

LEGS="${CI_BENCH_LEGS:---sentinel}"
WORK="$(mktemp -d /tmp/blaze-ci-check.XXXXXX)"
trap 'rm -rf "$WORK"' EXIT

# Fast AQE smoke (CI_AQE_FAST=0 to skip): the adaptive-execution test
# module plus a 1-rep skew-leg-only bench run.  --fast emits a reduced
# artifact into scratch and self-gates on its own exit code (skew
# speedup + zero divergence); it is NOT sentinel-compared because the
# reduced artifact carries fewer metrics than the committed baseline.
if [ "${CI_AQE_FAST:-1}" = "1" ]; then
    echo "== ci_check: AQE tests =="
    python -m pytest tests/test_adaptive.py -q -p no:cacheprovider
    echo "== ci_check: bench --aqe --fast (smoke) =="
    env "BLAZE_BENCH_AQE_PATH=$WORK/BENCH_AQE_FAST.json" \
        python bench.py --aqe --fast
fi

# Fast multichip/overlap smoke (CI_MULTICHIP_FAST=0 to skip): the
# overlapped-exchange test module plus a reduced --multichip run —
# 1- and 2-device legs, small per-worker shards, one probe query.
# Self-gating: bench --multichip exits nonzero on a non-monotone
# curve, any sync-vs-overlap divergence, or a barrier-idle reduction
# below the 30% floor.  Not sentinel-compared (reduced legs carry
# fewer metrics than the committed BENCH_SF100 baseline).
if [ "${CI_MULTICHIP_FAST:-1}" = "1" ]; then
    echo "== ci_check: overlapped-exchange tests =="
    python -m pytest tests/test_exchange_overlap.py -q -p no:cacheprovider
    echo "== ci_check: bench --multichip (overlap smoke) =="
    env "BLAZE_BENCH_SF100_PATH=$WORK/BENCH_SF100_FAST.json" \
        BLAZE_BENCH_MULTICHIP_DEVICES=1,2 \
        BLAZE_BENCH_MULTICHIP_ROWS=65536 \
        BLAZE_BENCH_MULTICHIP_REPS=2 \
        BLAZE_BENCH_MULTICHIP_WAVES=2 \
        BLAZE_BENCH_MULTICHIP_QUERIES=q06 \
        BLAZE_BENCH_MULTICHIP_SCALE=0.05 \
        BLAZE_BENCH_MULTICHIP_PROBE_SCALE=0.05 \
        python bench.py --multichip
fi

# Fast fleet smoke (CI_FLEET_FAST=0 to skip): the fleet test module
# plus a reduced --fleet run — a 2-replica loopback fleet over the
# shared socket RSS tier with one seeded mid-run SIGKILL.  Self-gating:
# bench --fleet exits nonzero on any lost query, divergent result,
# duplicate committed block, or a per-replica history rollup that does
# not sum to the completed total.  Not sentinel-compared (the reduced
# artifact carries fewer queries than the committed BENCH_FLEET
# baseline).
if [ "${CI_FLEET_FAST:-1}" = "1" ]; then
    echo "== ci_check: fleet tests =="
    python -m pytest tests/test_fleet.py -q -p no:cacheprovider
    echo "== ci_check: bench --fleet --fast (kill-replica smoke) =="
    env "BLAZE_BENCH_FLEET_PATH=$WORK/BENCH_FLEET_FAST.json" \
        python bench.py --fleet --fast
fi

# Fast encodings smoke (CI_ENCODINGS_FAST=0 to skip): the dictionary-
# string and decimal-lane test modules plus a reduced --encodings run —
# string-group-by and decimal-agg legs, encodings off vs on.  Self-
# gating: bench --encodings exits nonzero on any divergent frame, a
# leg that stays host-placed with the encodings on, any device-lane
# fallback, or an eviction fraction that fails to drop.  Not
# sentinel-compared (the reduced corpus carries different walls than
# the committed BENCH_ENCODINGS baseline).
if [ "${CI_ENCODINGS_FAST:-1}" = "1" ]; then
    echo "== ci_check: encoding-lane tests =="
    python -m pytest tests/test_dict_strings.py tests/test_decimal_lanes.py \
        -q -p no:cacheprovider
    echo "== ci_check: bench --encodings --fast (smoke) =="
    env "BLAZE_BENCH_ENCODINGS_PATH=$WORK/BENCH_ENCODINGS_FAST.json" \
        python bench.py --encodings --fast
fi

fail=0
for leg in $LEGS; do
    name="$(echo "${leg#--}" | tr '[:lower:]' '[:upper:]')"
    art="BENCH_${name}.json"
    if [ ! -f "$art" ]; then
        echo "ci_check: no committed baseline $art for $leg" >&2
        fail=1
        continue
    fi
    echo "== ci_check: bench $leg (candidate -> $WORK/$art) =="
    env "BLAZE_BENCH_${name}_PATH=$WORK/$art" python bench.py "$leg"
    echo "== ci_check: sentinel --ci ($art) =="
    if ! python -m blaze_tpu.tools.sentinel --ci \
            --baseline "$art" --candidate "$WORK/$art"; then
        fail=1
    fi
done

if [ "$fail" != "0" ]; then
    echo "ci_check: FAILED" >&2
    exit 1
fi
echo "ci_check: OK"

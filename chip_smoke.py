#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the wire path still starts on
the chip.

One process, no supervisor or child, NO configuration key set: the
defaults are the thing under test.  Data is made from --seed.  Phases:

  kernels  the aggregation/partition kernels alone, compiled (not
           interpreted) at production shapes and compared with their
           references: mxu_agg.window_table bit for bit against the
           scatter table; hash_agg_step against a numpy group-by; the
           exchange's partition order against a stable argsort.
  pair     the q01 stage pair bench.py builds (scan -> filter -> partial
           hash-agg -> shuffle_writer -> .data/.index -> ipc_reader ->
           final agg) as TaskDefinition bytes through
           NativeExecutionRuntime at SF10, 4 map x 4 reduce tasks,
           against the pyarrow group_by oracle.
  q01 q06  itest q01 at SF10 and q06 at SF1, 4 partitions, through
           DagScheduler.run_collect, against their pandas oracles.

Each engine phase runs twice, cold then warm, and prints both walls, the
programs compiled in each (warm must be 0), H2D/D2H bytes and the lane
evidence.  With more than one chip visible the same phases run over all
of them and the mesh exchange must carry the query shuffles.

Exit code 0 and a last stdout line
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
only when every check held.  Any failed check raises: no try/except turns
a failed phase into a printed field.  Without an accelerator it exits 2
before it generates any data.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import logging
import os
import sys
import tempfile
import time

PHASES = ("kernels", "pair", "q01", "q06")
PAIR_SF = 10.0
QUERY_SF = {"q01": 10.0, "q06": 1.0}
PARTITIONS = 4
DEADLINE_S = 1150  # the contract allows 1200 s, compilation included


def say(*parts) -> None:
    print(*parts, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# ---------------------------------------------------------------------------
# evidence helpers
# ---------------------------------------------------------------------------

class CacheEvents:
    """Persistent-compile-cache traffic, from jax's own monitoring events."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.misses += 1


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))


def lane_evidence(trees) -> dict:
    """{operator: {device_lane_batches, host_lane_batches}} summed over
    the task metric trees of one run."""
    per_op: dict = {}
    for node in _all_nodes(trees):
        vals = node.get("values") or {}
        dev = int(vals.get("device_lane_batches", 0))
        host = int(vals.get("host_lane_batches", 0))
        if dev or host:
            slot = per_op.setdefault(node.get("name") or "?",
                                     {"device_lane_batches": 0,
                                      "host_lane_batches": 0})
            slot["device_lane_batches"] += dev
            slot["host_lane_batches"] += host
    return per_op


EVIDENCE_KEYS = (
    "total_compiles", "h2d_bytes", "d2h_bytes",
    "stage_loop_tasks", "stage_loop_batches", "stage_loop_fallbacks",
    "stage_loop_regrows", "stage_loop_reserves", "stage_loop_rehash_lanes",
    "shuffle_device_exchanges",
    "shuffle_device_fallbacks", "shuffle_host_bytes",
    "unexpected_fallbacks", "partial_agg_skip_events")


def run_leg(name: str, run):
    """One timed run of an engine phase.  Prints its wall, counter
    deltas, lane evidence and fallback reasons, checks them, and returns
    (wall, programs compiled)."""
    from blaze_tpu.bridge import profiling, xla_stats
    seen = {id(t) for t in profiling.recent_metrics()}
    reasons_before = xla_stats.stage_loop_fallback_reasons()
    before = xla_stats.snapshot()
    t0 = time.perf_counter()
    run()
    wall = time.perf_counter() - t0
    delta = xla_stats.delta(before)
    trees = [t for t in profiling.recent_metrics() if id(t) not in seen]
    lanes = lane_evidence(trees)
    reasons = {r: c - reasons_before.get(r, 0)
               for r, c in xla_stats.stage_loop_fallback_reasons().items()
               if c - reasons_before.get(r, 0)}
    ev = {k: int(delta.get(k, 0)) for k in EVIDENCE_KEYS}
    ev["mxu_verify_fallback"] = sum(
        int((t.get("values") or {}).get("mxu_verify_fallback", 0))
        for t in _all_nodes(trees))
    say(f"  [{name}] wall {wall:.2f}s  compiled {ev['total_compiles']}  "
        f"h2d {ev['h2d_bytes']}B  d2h {ev['d2h_bytes']}B")
    say(f"  [{name}] counters " + json.dumps(ev))
    say(f"  [{name}] lanes " + json.dumps(lanes))
    say(f"  [{name}] stage_loop_fallback_reasons " + json.dumps(reasons))
    check_leg(name, ev, lanes, reasons)
    return wall, ev["total_compiles"]


def check_leg(name: str, ev: dict, lanes: dict, reasons: dict) -> None:
    from blaze_tpu.bridge import xla_stats
    check(ev["unexpected_fallbacks"] == 0,
          f"{name}: a device tier fell back on an undeclared error: "
          f"{xla_stats.fallback_errors()}")
    for k in ("shuffle_device_fallbacks", "mxu_verify_fallback"):
        check(ev[k] == 0, f"{name}: {k} = {ev[k]}")
    # the loop sizes its table for the rows about to reach it, in every
    # mode: at these sizes no task has a reason to leave it
    check(not reasons, f"{name}: stage loop fell back for {reasons}")
    agg = {op: v for op, v in lanes.items() if "Agg" in op}
    check(sum(v["device_lane_batches"] for v in agg.values()) > 0,
          f"{name}: the aggregation ran no batch on the device")
    check(sum(v["host_lane_batches"] for v in agg.values()) == 0,
          f"{name}: aggregation batches ran on the host lane: {agg}")


def _all_nodes(trees):
    stack = list(trees)
    while stack:
        node = stack.pop()
        yield node
        stack.extend(node.get("children") or [])


def cold_then_warm(name: str, run) -> None:
    """run() twice; the warm leg must compile nothing."""
    cold_wall, _ = run_leg(f"{name} cold", run)
    warm_wall, warm_compiles = run_leg(f"{name} warm", run)
    check(warm_compiles == 0,
          f"{name}: the warm run compiled {warm_compiles} programs")
    say(f"  [{name}] cold {cold_wall:.2f}s -> warm {warm_wall:.2f}s")


def make_tables(names, scale: float, seed: int) -> dict:
    from blaze_tpu.itest.tpcds_data import GENERATORS
    order = sorted(GENERATORS)
    return {n: GENERATORS[n](scale, seed=seed * 1000 + order.index(n))
            for n in names}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def bits_equal(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and a.tobytes() == b.tobytes())


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from blaze_tpu.kernels import mxu_agg
    from blaze_tpu.parallel.collective import _dest_slots
    from blaze_tpu.parallel.stage import hash_agg_step, init_hash_carry
    rng = np.random.default_rng(seed)

    say("-- mxu_agg.window_table, Mosaic-compiled, vs the scatter table")
    check(jax.default_backend() == "tpu", "kernels need the TPU backend")
    for n, slots, bits in ((32768, 1 << 14, (16, 24)),
                           (65536, 1 << 16, (16, 24)),
                           (65536, 1 << 17, (32, 24)),
                           (32768, 1 << 17, (8,))):
        layout = mxu_agg.plan_layout(slots, bits)
        gid = jnp.asarray(rng.integers(0, slots + 1, n).astype(np.int32))
        arrs = [jnp.asarray(rng.integers(0, 1 << min(b, 31), n)
                            .astype(np.int32)) for b in bits]
        t0 = time.perf_counter()
        got = jax.jit(lambda g, *a, _l=layout: mxu_agg.window_table(
            g, list(a), _l))(gid, *arrs).block_until_ready()
        took = time.perf_counter() - t0
        want = jax.jit(lambda g, *a, _l=layout: mxu_agg.window_table(
            g, list(a), _l, force_ref=True))(gid, *arrs)
        same = bits_equal(got, want)
        say(f"  rows {n} slots {slots} limbs {layout.limbs}: compile+run "
            f"{took:.2f}s bit-identical {same}")
        check(same, f"mxu_agg differs from its reference at {n}x{slots}")

    say("-- hash_agg_step vs numpy group-by")
    # distinct keys <= S/6: the probe walk is bounded at 16 rounds and a
    # fuller table overflows by design (the engine then grows it)
    for n, S, kdt in ((32768, 1 << 16, (np.int64,)),
                      (65536, 1 << 18, (np.int64,)),
                      (32768, 1 << 16, (np.int64, np.int64)),
                      (65536, 1 << 18, (np.int64, np.int64))):
        nkeys = len(kdt)
        span = int((S // 6) ** (1.0 / nkeys))
        kd = [rng.integers(0, span, n).astype(dt) for dt in kdt]
        vals = rng.integers(0, 1000, n).astype(np.float64)
        mask = rng.random(n) > 0.1
        keys = [(jnp.asarray(k), jnp.ones(n, bool)) for k in kd]
        carry = init_hash_carry([jnp.dtype(dt) for dt in kdt], ["sum"],
                                (jnp.float64,), S)
        step = jax.jit(lambda c, k, v, m: hash_agg_step(
            c, k, [("sum", v, None)], m))
        t0 = time.perf_counter()
        out, overflow, groups, rounds = jax.block_until_ready(
            step(carry, keys, jnp.asarray(vals), jnp.asarray(mask)))
        took = time.perf_counter() - t0
        slots_used = np.flatnonzero(np.asarray(out.used))
        out_keys = [np.asarray(k)[slots_used] for k in out.keys]
        out_sums = np.asarray(out.accs[0])[slots_used]
        got = {tuple(int(k[j]) for k in out_keys): float(out_sums[j])
               for j in range(len(slots_used))}
        want: dict = {}
        for i in np.flatnonzero(mask):
            key = tuple(int(k[i]) for k in kd)
            want[key] = want.get(key, 0.0) + vals[i]
        kinds = "+".join(np.dtype(dt).name for dt in kdt)
        say(f"  rows {n} slots {S} keys {kinds}: compile+run {took:.2f}s "
            f"groups {int(groups)} overflow {int(overflow)} "
            f"rounds {int(rounds[0])} full + {int(rounds[1])} narrow "
            f"equal {got == want}")
        check(int(overflow) == 0 and got == want,
              f"hash_agg_step differs from numpy at {n}x{S}x{kinds}")

    say("-- _dest_slots vs numpy stable argsort")
    # its compile time follows the row count, not the partition count
    # (a stable argsort: 17-20 s per shape on a v5e), so two shapes cover
    # both batch sizes and both fan-outs
    for n, parts in ((32768, 4), (65536, 200)):
        pid = rng.integers(0, parts + 1, n).astype(np.int32)
        t0 = time.perf_counter()
        order, dest, overflow = jax.block_until_ready(jax.jit(
            lambda p, _P=parts, _n=n: _dest_slots(p, _P, _n))(
                jnp.asarray(pid)))
        took = time.perf_counter() - t0
        ref = np.argsort(pid, kind="stable")
        same = np.array_equal(np.asarray(order), ref) \
            and int(overflow) == 0
        say(f"  rows {n} partitions {parts}: compile+run {took:.2f}s "
            f"order==stable-argsort {same}")
        check(same, f"_dest_slots differs at {n}x{parts}")

    say("-- the compiler's answer for what it refused, today")
    # f64 on this chip is a float32 pair, not IEEE double: its bits
    # cannot be reinterpreted, so float64 keys cannot be hashed on the
    # device lanes (kernels/hashing.py float64 branch; CHANGES.md PR 21)
    try:
        jax.jit(lambda a: a.view(jnp.int64))(
            jnp.ones(128, jnp.float64)).block_until_ready()
    except jax.errors.JaxRuntimeError as e:  # expected, asserted below
        said = str(e).split("\n")[0]
    else:
        said = None
    say(f"  f64 -> i64 bitcast: {said!r}")
    check(said is not None and "X64 element types" in said,
          f"the f64 bitcast record is stale: the compiler now says "
          f"{said!r}")


def phase_pair(seed: int, work: str) -> None:
    import pyarrow.parquet as pq

    import bench
    say(f"-- q01 stage pair, SF{PAIR_SF:g}, {PARTITIONS} map x "
        f"{PARTITIONS} reduce, TaskDefinition bytes -> "
        f"NativeExecutionRuntime")
    tables = make_tables(["store_returns", "date_dim"], PAIR_SF, seed)
    sr = tables["store_returns"]
    per = -(-sr.num_rows // PARTITIONS)
    sr_paths = []
    for i in range(PARTITIONS):
        path = os.path.join(work, f"store_returns_{i}.parquet")
        pq.write_table(sr.slice(i * per, per), path, row_group_size=1 << 16)
        sr_paths.append(path)
    dd_path = os.path.join(work, "date_dim.parquet")
    pq.write_table(tables["date_dim"], dd_path)
    want_groups, want_sum = bench.run_baseline(sr_paths, dd_path)
    say(f"  {sr.num_rows} store_returns rows in {PARTITIONS} files; "
        f"oracle: {want_groups} groups, sum {want_sum!r}")

    def run():
        shuffle_dir = tempfile.mkdtemp(prefix="shuffle-", dir=work)
        groups, total = bench.run_engine(sr_paths, dd_path, shuffle_dir,
                                         PARTITIONS, PARTITIONS)
        say(f"  engine: {groups} groups, sum {total!r}")
        check(groups == want_groups,
              f"pair: {groups} groups, oracle {want_groups}")
        check(abs(total - want_sum) <= 1e-9 * abs(want_sum),
              f"pair: sum {total!r}, oracle {want_sum!r}")

    cold_then_warm("pair", run)


def phase_query(qname: str, seed: int, work: str) -> None:
    from blaze_tpu import config
    from blaze_tpu.itest.queries import QUERIES
    from blaze_tpu.itest.runner import compare_frames
    from blaze_tpu.itest.tpcds_data import write_parquet_splits
    from blaze_tpu.plan.stages import DagScheduler
    sf = QUERY_SF[qname]
    say(f"-- itest {qname}, SF{sf:g}, {PARTITIONS} partitions, "
        f"DagScheduler.run_collect")
    builder, names = QUERIES[qname]
    tables = make_tables(names, sf, seed)
    paths = write_parquet_splits(tables, os.path.join(work, qname),
                                 PARTITIONS)
    plan, oracle = builder(paths, tables, PARTITIONS)
    scanned = DagScheduler._scan_input_bytes(plan)
    say(f"  scans {scanned} bytes of parquet "
        f"(singleTaskBytes {config.DAG_SINGLE_TASK_BYTES.get()})")
    want = oracle()
    legs = []

    def run():
        with DagScheduler() as sched:
            got = sched.run_collect(plan)
            mode, n_stages = sched.exec_mode, len(sched.stages)
            placement = dict(sched.stage_placement)
        say(f"  mode {mode}, {n_stages} stages, {got.num_rows} rows; "
            f"stage placement {json.dumps(placement, sort_keys=True)}")
        check(mode == "staged" and n_stages > 1,
              f"{qname}: took the single-task shortcut ({mode}, "
              f"{n_stages} stages)")
        err = compare_frames(got.to_pandas(), want)
        check(err is None, f"{qname}: differs from the pandas oracle: {err}")
        legs.append(got)

    cold_then_warm(qname, run)
    check(legs[0].equals(legs[1]), f"{qname}: cold and warm results differ")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for every generated table and test vector")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of %s (default: all)"
                         % ",".join(PHASES))
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}")

    faulthandler.enable()
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)

    # ---- the gate: a chip, or nothing ----
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no accelerator: jax.devices()[0] is "
              f"{d0.platform!r} ({d0.device_kind}), JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}; this smoke runs on "
              f"a TPU only", file=sys.stderr)
        return 2
    t_start = time.perf_counter()
    cache_events = CacheEvents()

    import blaze_tpu
    from blaze_tpu.bridge import native
    from blaze_tpu.bridge.placement import ensure_placement
    say(f"jax {jax.__version__}  platform {d0.platform}  device_kind "
        f"{d0.device_kind!r}  devices {len(devices)}")
    cache_dir = blaze_tpu.COMPILE_CACHE_DIR
    entries_before = cache_entries(cache_dir)
    say(f"compile cache {cache_dir} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f"): {entries_before} entries")
    try:
        built = native.build_native_libs()
    except native.NativeBuildError as e:
        built = (f"NOT BUILT ({e}); pure-Python paths: zstd codec in "
                 f"Python, numpy partition ids, pyarrow host group-by")
    say(f"native libraries: {built}; loaded "
        f"{json.dumps(native.loaded_libraries())}")

    pi = ensure_placement()
    say(f"placement: device_kind {pi.device_kind!r} default_platform "
        f"{pi.default_platform!r} dispatch_rtt_ms {pi.rtt_ms:.3f} policy "
        f"{pi.policy!r}")
    check(pi.policy == "auto", "a placement policy is set; the smoke tests "
                               "the defaults")
    check(pi.device_kind == "tpu",
          f"auto placed stage compute on {pi.device_kind!r}, not the chip "
          f"(dispatch RTT {pi.rtt_ms:.2f} ms)")

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as work:
        if "kernels" in phases:
            phase_kernels(args.seed)
        if "pair" in phases:
            phase_pair(args.seed, work)
        before = _shuffle_device_exchanges()
        for qname in ("q01", "q06"):
            if qname in phases:
                phase_query(qname, args.seed, work)
        exchanges = _shuffle_device_exchanges() - before

    if len(devices) == 1:
        say("mesh leg: skipped (1 device)")
    elif {"q01", "q06"} & set(phases):
        say(f"mesh leg: {len(devices)} devices, shuffle_device_exchanges "
            f"{exchanges}")
        check(exchanges >= 1, "no query shuffle went over the mesh")
    else:
        say("mesh leg: skipped (no query phase selected)")
    for d in devices:
        say(f"device {d.id} peak_bytes_in_use "
            f"{d.memory_stats()['peak_bytes_in_use']}")
    say(f"compile cache: {entries_before} -> {cache_entries(cache_dir)} "
        f"entries; persistent-cache hits {cache_events.hits} misses "
        f"{cache_events.misses}")
    say(f"total wall {time.perf_counter() - t_start:.1f}s")
    say(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


def _shuffle_device_exchanges() -> int:
    from blaze_tpu.bridge import xla_stats
    return int(xla_stats.shuffle_stats()["shuffle_device_exchanges"])


if __name__ == "__main__":
    sys.exit(main())

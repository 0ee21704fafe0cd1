#!/usr/bin/env python3
"""chip_smoke.py — the kernels alone on the chip, compiled and compared.

One process, no supervisor or child, NO configuration key set.  Test
vectors are made from --seed.  One phase:

  kernels  the aggregation/partition kernels, compiled (not interpreted)
           at production shapes and compared with their references:
           mxu_agg.window_table bit for bit against the scatter table;
           hash_agg_step against a numpy group-by; the exchange's
           partition order against a stable argsort.

The proof of the ENGINE path on the chip is a benchmark cell's run, not
this file:
  python3 benchmark/run.py --workload sf10_q01pair_x1 --seed 1 \
      --seconds 10 --trace 0
refuses a placement other than `tpu`, compares every answer with the
oracle and counts the programs compiled inside the window.

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
import time

PHASES = ("kernels",)
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
        slots_used = np.flatnonzero(np.asarray(out.owner) < 0)
        out_keys = [np.asarray(k)[slots_used] for k in out.key_columns]
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
    from blaze_tpu.bridge.placement import ensure_placement
    say(f"jax {jax.__version__}  platform {d0.platform}  device_kind "
        f"{d0.device_kind!r}  devices {len(devices)}")
    cache_dir = blaze_tpu.COMPILE_CACHE_DIR
    entries_before = cache_entries(cache_dir)
    say(f"compile cache {cache_dir} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f"): {entries_before} entries")
    pi = ensure_placement()
    say(f"placement: device_kind {pi.device_kind!r} default_platform "
        f"{pi.default_platform!r} dispatch_rtt_ms {pi.rtt_ms:.3f} policy "
        f"{pi.policy!r}")
    check(pi.policy == "auto", "a placement policy is set; the smoke runs "
                               "the defaults")
    check(pi.device_kind == "tpu",
          f"auto placed stage compute on {pi.device_kind!r}, not the chip "
          f"(dispatch RTT {pi.rtt_ms:.2f} ms)")

    if "kernels" in phases:
        phase_kernels(args.seed)
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


if __name__ == "__main__":
    sys.exit(main())

"""Device time of one hash-aggregation step against table size and load.

The stage loop (blaze_tpu/runtime/loop.py) sizes its table from two
constants, _TRIGGER_LOAD and _TARGET_LOAD.  This is the measurement they
were chosen from (PERF.md section 6, PR 25): one 65,536-lane batch of
the pair cell's shape (two int64 keys, one float64 sum, 41% of the lanes
selected, every selected row a new group) inserted into a table of S
slots that already holds load x S groups, and the same batch with 12
groups in all (the q06 shape).  Run it on the chip:

    chiprun -- python3 tools/fold_grid.py

It prints one JSON line per reading and writes them to
chiprun_out/fold_grid.jsonl.  Times are host-clock medians around
`block_until_ready`, so they mean something only on the device.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import blaze_tpu  # noqa: E402,F401  (x64, the compile cache)
from blaze_tpu.parallel.stage import (hash_agg_step, init_hash_carry,  # noqa: E402
                                      rehash_carry)

LANES = 65536
LIVE_SHARE = 0.41
KINDS = ("sum",)
REPEATS = 5


def _step(probe_rounds):
    def f(carry, k1, k2, v, mask):
        ones = jnp.ones(LANES, bool)
        return hash_agg_step(carry, [(k1, ones), (k2, ones)],
                             [("sum", v, None)], mask,
                             probe_rounds=probe_rounds)
    return jax.jit(f)


def _batch(rng, live, groups=None):
    """(k1, k2, v, mask): `live` selected lanes, each a new group unless
    `groups` bounds the key domain."""
    if groups is None:
        k1 = rng.integers(1, 1 << 40, LANES)
    else:
        k1 = rng.integers(1, groups + 1, LANES)
    k2 = (k1 % 12) + 1
    mask = np.zeros(LANES, bool)
    mask[rng.permutation(LANES)[:live]] = True
    return (jnp.asarray(k1), jnp.asarray(k2),
            jnp.asarray(rng.random(LANES)), jnp.asarray(mask))


def _timed(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return statistics.median(times), out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--log-slots", type=int, nargs="+", default=[18, 20, 22])
    args = ap.parse_args()
    dev = jax.devices()[0]
    out_dir = "chiprun_out"
    os.makedirs(out_dir, exist_ok=True)
    lines = []

    def say(**kw):
        kw["device"] = dev.device_kind
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    step16, fill = _step(16), _step(256)
    for log_s in args.log_slots:
        slots = 1 << log_s
        rng = np.random.default_rng(log_s)
        carry = init_hash_carry([jnp.int64, jnp.int64], KINDS,
                                [jnp.float64], slots)
        # the q06 shape: 12 groups, every lane selected
        few = _batch(rng, LANES, groups=12)
        warm, _, _ = fill(carry, *few)
        t, (_c, ovf, ng) = _timed(step16, warm, *few)
        say(slots=slots, shape="12_groups", load_before=0.0, step_s=t,
            overflow=int(ovf), groups_after=int(ng))
        groups = 0
        for load in (0.0, 1 / 16, 1 / 8, 3 / 16, 1 / 4, 3 / 8, 1 / 2):
            while groups < int(load * slots):
                n = min(LANES, int(load * slots) - groups)
                carry, ovf, ng = fill(carry, *_batch(rng, n))
                assert int(ovf) == 0
                groups = int(ng)
            t, (_c, ovf, ng) = _timed(
                step16, carry, *_batch(rng, int(LIVE_SHARE * LANES)))
            say(slots=slots, shape="new_groups", load_before=groups / slots,
                step_s=t, overflow=int(ovf), groups_after=int(ng))
            if load in (1 / 8, 1 / 4) and log_s <= 20:
                re = jax.jit(lambda c: rehash_carry(c, list(KINDS),
                                                    4 * slots))
                t, (_c, ovf, ng) = _timed(re, carry)
                say(slots=slots, shape="rehash_x4",
                    load_before=groups / slots, step_s=t, overflow=int(ovf),
                    groups_after=int(ng))
    with open(os.path.join(out_dir, "fold_grid.jsonl"), "w") as f:
        for ln in lines:
            f.write(json.dumps(ln) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

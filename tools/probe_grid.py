"""Device time of one probe batch of the device-resident broadcast join
(blaze_tpu/kernels/join.py probe_gather), whole and part by part.

An inner join on a unique fixed-width build key runs one program a probe
batch.  Its build index has two forms (`JoinMap.direct_key`): the
hash-sorted one, searched, and the direct-address one of a dense integer
key, read at `key - min`.  This is the measurement the second was judged
by (PERF.md section 6, PR 41), at the shapes the benchmark's cells build:

  * `q06`: 32,768 lanes of `store_sales` (three int64 columns, the first
    the key) against the filtered `item` rows, 7,200 of 18,000
    consecutive keys: a searched index of 8,191 entries (13 rounds), a
    direct one of 32,768;
  * `q01`: 32,768 lanes of `store_returns` (three int64 columns and a
    float64) against `date_dim`'s year, 366 consecutive keys: 511
    entries (9 rounds), a direct index of 512;
  * both with one build column (the key), every probe row valid, and
    the probe keys drawn evenly from the dimension's whole key range, so
    that the cell's share finds a partner (q06 40%, the rest inside the
    index's range; q01 a year of five, the rest outside it).

Readings, each the median over 7 queues of 24 calls of the host-clock
time a call, the queue waited for once (`block_until_ready`): `whole`
(both forms), and alone `hash` (xxhash64 of the key), `search`
(`searchsorted` of the hashes), `confirm` (the searched form's three
gathers: `urow`, `uh` and the build key, with their compares), `direct`
(the direct form's subtract, bounds and one gather), `build_gathers` (the
build columns at the candidate row: both forms), `pack_front` (both
forms), and `dispatch` (a program that adds one to a scalar: the floor
a queued call reads whatever it computes).  The parts are programs of
their own, so they sum to more than the whole, which fuses them.  Run
it on the chip.  Beside them, what the other joins still pay:
`probe_counts` (the same search with its two gathers, which every hash
join the device-resident probe does not take runs a batch) and, at
`q93`, `merge_bounds` (the merge join's lexicographic search: 72,000
driving rows over 720,000 searched ones and over 1,023, so the
difference over the rounds prices a round).

    chiprun -- python3 tools/probe_grid.py

`--tree DIR` imports blaze_tpu from DIR, a checkout of another commit
unpacked inside this one (a tree whose `probe_gather` has no direct form
reads the searched form and the parts alone).  It prints one JSON line
per reading and writes them to chiprun_out/probe_grid[.<tag>].jsonl.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import statistics
import sys
import time

LANES = 32768
REPEATS = 7
QUEUED = 24
# name -> (build rows, their key range, first key, the range the probe
# keys are drawn from, probe columns' dtypes after the key)
SHAPES = {
    "q06": (7200, 18000, 1, 18000, ("int64", "int64")),
    "q01": (366, 366, 2451545, 1830, ("int64", "int64", "float64")),
}


def _timed(fn, *args):
    """Seconds a call: QUEUED calls dispatched back to back and waited
    for once, so the chip runs one behind the other and the round trip
    of the wait (`dispatch`, 0.6 ms) is paid once a queue, not once a
    call as in a single call timed alone."""
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(QUEUED)]
        jax.block_until_ready(outs)
        times.append((time.perf_counter() - t0) / QUEUED)
    return statistics.median(times)


def _build(rng, rows, span, first):
    """What `_DeviceBuild.place` holds for a build side of `rows` of the
    `span` consecutive keys from `first`: (uh, urow, keys, cols, drow,
    kmin) as numpy, the build rows in random order."""
    import numpy as np

    from blaze_tpu.kernels import hashing as H
    keys = first + rng.permutation(span)[:rows].astype(np.int64)
    h = np.asarray(H.hash_columns([(keys, np.ones(rows, bool), "int64")],
                                  seed=42, xp=np, algo="xxhash64"))
    order = np.argsort(h, kind="stable")
    size = (1 << rows.bit_length()) - 1
    uh = np.full(size, np.iinfo(np.int64).max, np.int64)
    uh[:rows] = h[order]
    urow = np.full(size, -1, np.int32)
    urow[:rows] = order
    cap = 1 << (rows - 1).bit_length()
    data = np.zeros(cap, np.int64)
    data[:rows] = keys
    valid = np.zeros(cap, bool)
    valid[:rows] = True
    kmin = keys.min()
    drow = np.full(1 << (span - 1).bit_length(), -1, np.int32)
    drow[keys - kmin] = np.arange(rows, dtype=np.int32)
    return uh, urow, (data,), ((data, valid),), drow, kmin, keys


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=None,
                    help="import blaze_tpu from this checkout")
    ap.add_argument("--tag", default=None,
                    help="write chiprun_out/probe_grid.<tag>.jsonl")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.abspath(args.tree) if args.tree else root)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import blaze_tpu  # noqa: F401  (x64, the compile cache)
    from blaze_tpu.kernels import join as J

    dev = jax.devices()[0]
    out_dir = os.path.join(root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lines = []

    def say(**kw):
        kw["device"] = dev.device_kind
        kw["tree"] = args.tree or "."
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    whole = J.probe_gather._blaze_jitted
    has_direct = "direct" in inspect.signature(J.probe_gather).parameters
    say(shape="any", part="dispatch", lanes=0,
        step_s=_timed(jax.jit(lambda x: x + 1), jnp.int32(0)))
    for name, (rows, span, first, drawn, more) in SHAPES.items():
        rng = np.random.default_rng(41)
        uh, urow, bkeys, bcols, drow, kmin, keys = jax.device_put(
            _build(rng, rows, span, first))
        keys_np = np.asarray(keys)
        pk = first + rng.integers(0, drawn, LANES)
        ok = jnp.ones(LANES, bool)
        pkey = (jnp.asarray(pk.astype(np.int64)), ok)
        pcols = (pkey,) + tuple(
            (jnp.asarray(rng.integers(0, 1 << 20, LANES).astype(t)), ok)
            for t in more)
        n = jnp.int32(LANES)
        tids = ("int64",)
        shape = dict(shape=name, lanes=LANES, build_rows=rows,
                     index_entries=int(uh.shape[0]),
                     direct_entries=int(drow.shape[0]),
                     columns=f"{len(pcols)}+{len(bcols)}")

        def reading(part, fn, *a, **extra):
            say(part=part, step_s=_timed(fn, *a), **shape, **extra)

        out = whole(uh, urow, bkeys, bcols, (pkey,), pcols, n, None,
                    tids=tids)
        matched = int(out[2])
        reading("whole", lambda: whole(uh, urow, bkeys, bcols, (pkey,),
                                       pcols, n, None, tids=tids),
                index="search", matched=matched)
        if has_direct:
            got = whole(None, None, None, bcols, (pkey,), pcols, n, None,
                        tids=tids, direct=(drow, kmin))
            assert int(got[2]) == matched
            for a, b in zip(jax.tree_util.tree_leaves(got[:2]),
                            jax.tree_util.tree_leaves(out[:2])):
                assert np.array_equal(np.asarray(a)[:matched],
                                      np.asarray(b)[:matched])
            reading("whole", lambda: whole(
                None, None, None, bcols, (pkey,), pcols, n, None,
                tids=tids, direct=(drow, kmin)),
                index="direct", matched=matched)

        h, _null = jax.jit(lambda k: J.hash_valid((k,), tids))(pkey)
        pos = jnp.clip(jnp.searchsorted(uh, h), 0, uh.shape[0] - 1)
        row = jnp.maximum(jnp.take(urow, pos), 0)
        reading("hash", jax.jit(lambda k: J.hash_valid((k,), tids)), pkey)
        reading("search", jax.jit(jnp.searchsorted), uh, h,
                rounds=int(uh.shape[0]).bit_length())

        def confirm(uh, urow, bk, h, pos, k):
            r = jnp.take(urow, pos)
            hit = (jnp.take(uh, pos) == h) & (r >= 0)
            r = jnp.maximum(r, 0)
            return r, hit & (jnp.take(bk, r) == k)

        reading("confirm", jax.jit(confirm), uh, urow, bkeys[0], h, pos,
                pkey[0])
        if has_direct:
            reading("direct", jax.jit(
                lambda d, m, k: J._direct_rows(d, m, k, n, None)),
                drow, kmin, pkey)
        reading("build_gathers", jax.jit(
            lambda cols, r: [(jnp.take(d, r), jnp.take(v, r))
                             for d, v in cols]), bcols, row)
        hit = jnp.asarray(np.isin(pk, keys_np))
        flat = [a for dv in pcols for a in dv] + [
            jnp.take(a, row) for dv in bcols for a in dv]
        reading("pack_front", jax.jit(J.pack_front), hit, flat,
                arrays=len(flat))

        # what every join the device-resident probe leaves still runs
        n_idx = int(uh.shape[0])
        reading("probe_counts", J.probe_counts._blaze_jitted, uh,
                jnp.zeros(n_idx, jnp.int32), jnp.ones(n_idx, jnp.int32),
                h, ~ok, rounds=n_idx.bit_length())

    # the merge join's search at q93's shape: a partition's 72K returns
    # drive, its 720K sales are searched, two int64 keys; and over 1,023
    # searched rows, so the difference prices a round
    rng = np.random.default_rng(93)
    drive, dcap = 72_000, 131_072
    for rows, cap in ((720_000, 1 << 20), (1023, 1 << 10)):
        def side(n, c, hi):
            cols = np.zeros((2, c), np.int64)
            cols[:, :n] = np.sort(rng.integers(0, hi, (2, n)), axis=1)
            valid = jnp.asarray(np.arange(c) < n)
            return tuple((jnp.asarray(k), valid) for k in cols)
        from blaze_tpu.schema import INT64
        bounds = J.merge_bounds._blaze_jitted
        d_cols, s_cols = side(drive, dcap, rows), side(rows, cap, rows)
        say(shape="q93", part="merge_bounds", lanes=dcap,
            build_rows=rows, rounds=cap.bit_length(),
            step_s=_timed(lambda: bounds(
                d_cols, s_cols, jnp.int32(drive), jnp.int32(rows),
                dtypes=(INT64, INT64))))

    name = f"probe_grid.{args.tag}.jsonl" if args.tag else "probe_grid.jsonl"
    with open(os.path.join(out_dir, name), "w") as f:
        for ln in lines:
            f.write(json.dumps(ln) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

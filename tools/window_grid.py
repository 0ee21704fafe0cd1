"""Compile and device time of `WindowExec`'s resident lane
(blaze_tpu/ops/window.py, the program in blaze_tpu/kernels/window.py), at
the shapes the benchmark's q51 scans a reduce task:

  * `sum_store`: 137,000 rows in 262,144 lanes, an int64 item, a date32
    day, one running sum of a float64;
  * `sum_web`: 35,000 rows in 65,536 lanes, the same program;
  * `max_joined`: 172,000 rows in 262,144 lanes, two running maxima of
    float64 columns that are NULL on most rows;
  * `sum_large`: 720,000 rows in 1,048,576 lanes (what a q93-sized
    partition would cost: the largest capacity any cell sorts).

Readings: `compile_s` (the first call of a shape on this process, which
compiles or loads the program), then, each the median over 7 queues of 24
calls of the host-clock time a call (`tools/probe_grid.py` `_timed`):
`scan` (the whole program), and beside it `flags` (the key columns' order
keys compared lane to lane), `assoc` (the `associative_scan` of one
float64 lane alone), `copy_back` (the reverse scan that brings a frame's
last row's value and its `seen` to the rows before it) and `take` (the
same two arrays gathered by int32 positions: the frame end's other form,
which the program had first).  Before the timings each shape's answer is
held to a sequential numpy loop (`checked`).  Run it on the chip:

    chiprun -- python3 tools/window_grid.py

It prints one JSON line per reading and writes them to
chiprun_out/window_grid[.<tag>].jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# name -> (rows, function kind, value columns, share of NULL values)
SHAPES = {
    "sum_web": (35_000, "sum", 1, 0.0),
    "sum_store": (137_000, "sum", 1, 0.0),
    "max_joined": (172_000, "max", 2, 0.5),
    "sum_large": (720_000, "sum", 1, 0.0),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tag", default=None,
                    help="write chiprun_out/window_grid.<tag>.jsonl")
    ap.add_argument("--shrink", type=int, default=1,
                    help="divide every shape's rows (a rehearsal off the "
                         "chip)")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "tools")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    import blaze_tpu  # noqa: F401  (x64, the compile cache)
    from blaze_tpu.batch import bucket_capacity
    from blaze_tpu.kernels import window as K
    from blaze_tpu.schema import DATE32, INT64
    from probe_grid import _timed  # queued calls, waited for once a queue

    dev = jax.devices()[0]
    out_dir = os.path.join(root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lines = []

    def say(**kw):
        kw["device"] = dev.device_kind
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    say(shape="any", part="dispatch", lanes=0,
        step_s=_timed(jax.jit(lambda x: x + 1), jnp.int32(0)))
    rng = np.random.default_rng(51)
    for name in args.shapes.split(","):
        rows, kind, nvals, null_share = SHAPES[name]
        rows //= args.shrink
        cap = bucket_capacity(rows)
        # ~30 days an item, sorted by (item, day), unique pairs
        item = np.sort(rng.integers(1, max(2, rows // 30), rows))
        day = rng.integers(10957, 11322, rows).astype(np.int32)
        order = np.lexsort((day, item))
        item, day = item[order], day[order]

        def padded(v, fill=0):
            out = np.full(cap, fill, v.dtype)
            out[:rows] = v
            return out

        live = np.arange(cap) < rows
        values = [np.round(rng.random(rows) * 300, 2) for _ in range(nvals)]
        valid = [rng.random(rows) >= null_share for _ in range(nvals)]
        part = ((padded(item), live),)
        order_keys = ((padded(day), live),)
        vals = tuple((padded(v), padded(ok, False))
                     for v, ok in zip(values, valid))
        part, order_keys, vals = jax.device_put((part, order_keys, vals))
        kw = dict(part_types=(INT64,), order_types=(DATE32,),
                  funcs=((kind, True),) * nvals)
        shape = dict(shape=name, rows=rows, lanes=cap, functions=nvals)

        def scan():
            return K.segmented_scan(part, order_keys, vals, np.int32(rows),
                                    **kw)

        t0 = time.perf_counter()
        out, _sel = jax.block_until_ready(scan())
        say(part="compile_s", step_s=time.perf_counter() - t0, **shape)
        # a sequential loop a partition, ties sharing the run's last value
        for (data, ok), v, has in zip(out, values, valid):
            want = np.zeros(rows)
            seen = np.zeros(rows, bool)
            acc, met = 0.0, False
            for i in range(rows):
                if i == 0 or item[i] != item[i - 1]:
                    acc, met = 0.0, False
                if has[i]:
                    acc = v[i] + acc if kind == "sum" else \
                        (max(acc, v[i]) if met else v[i])
                    met = True
                want[i], seen[i] = acc, met
            same_run = np.r_[(item[1:] == item[:-1]) & (day[1:] == day[:-1]),
                             False]
            for i in range(rows - 2, -1, -1):   # ties: the run's last row
                if same_run[i]:
                    want[i], seen[i] = want[i + 1], seen[i + 1]
            got, got_ok = np.asarray(data)[:rows], np.asarray(ok)[:rows]
            assert np.array_equal(got_ok, seen), name
            assert np.allclose(got[seen], want[seen], rtol=1e-12, atol=0), \
                name
            assert not np.asarray(ok)[rows:].any()
        say(part="checked", **shape)
        say(part="scan", step_s=_timed(scan), **shape)
        flags = jax.jit(lambda p, o: K._differs(p, (INT64,))
                        | K._differs(o, (DATE32,)))
        say(part="flags", step_s=_timed(flags, part, order_keys), **shape)
        flag = flags(part, order_keys)
        assoc = jax.jit(lambda f, v, ok: jax.lax.associative_scan(
            K._combine(["sum"]), (f, (v, ok)))[1])
        say(part="assoc", step_s=_timed(assoc, flag, *vals[0]), **shape)
        copy = jax.jit(lambda f, v, ok: jax.lax.associative_scan(
            K._copy_back, (f, (v, ok)), reverse=True)[1])
        say(part="copy_back", step_s=_timed(copy, flag, *vals[0]), **shape)
        at = jnp.arange(cap, dtype=jnp.int32)
        take = jax.jit(lambda v, ok, i: (jnp.take(v, i), jnp.take(ok, i)))
        say(part="take", step_s=_timed(take, *vals[0], at), **shape)

    name = f"window_grid.{args.tag}.jsonl" if args.tag \
        else "window_grid.jsonl"
    with open(os.path.join(out_dir, name), "w") as f:
        for ln in lines:
            f.write(json.dumps(ln) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

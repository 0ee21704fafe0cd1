"""Device time of `SortExec`'s resident lane (blaze_tpu/ops/sort.py
`_SortState.sorted_on_device`, programs in blaze_tpu/kernels/sort.py), part
by part, at the shapes the benchmark's q93 sorts a reduce task:

  * `sales`: 720,000 rows of five columns (three int64, an int32, a
    float64) and two int64 keys, staged as 22 tiles of 32,768 lanes, laid
    in 1,048,576 lanes (32 tile places);
  * `returns`: 72,000 rows of four int64 columns and two int64 keys, 3
    tiles, 131,072 lanes (4 places);
  * `top`: 7,700 rows of an int64 and a float64, keyed (float64, int64):
    the query's last sort, whose first key goes through the float32-pair
    digits on a TPU.

Readings, each the median over 7 queues of 24 calls of the host-clock time
a call, the queue waited for once (`tools/probe_grid.py` `_timed`):
`assemble` with the tiles full up to the last and `assemble_ragged` with
every tile a fifth empty (the same program: every tile copied to where the
one before ends, over that tile's padding), `digits`, `pass` (one
`sort_pass`: a gather of the digit and a two-operand stable sort),
`gather` (every data column and the packed validity word by the
permutation), and beside them `gather_1col` (one int64 column and its
validity alone: what a column costs), `gather_bools` (the validity of
every column gathered a column, which the packed word replaces) and
`dispatch` (a program that adds one to a scalar).  Before the timings it
sorts each shape whole, as the lane does, and holds the order to numpy's
(`checked`).  Run it on the chip:

    chiprun -- python3 tools/sort_grid.py

It prints one JSON line per reading and writes them to
chiprun_out/sort_grid[.<tag>].jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

TILE = 32768
# name -> (rows, payload dtypes, key columns (indices into the payload))
SHAPES = {
    "sales": (720_000, ("int64", "int64", "int64", "int32", "float64"),
              (0, 1)),
    "returns": (72_000, ("int64", "int64", "int64", "int64"), (0, 1)),
    "top": (7_700, ("int64", "float64"), (1, 0)),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tag", default=None,
                    help="write chiprun_out/sort_grid.<tag>.jsonl")
    ap.add_argument("--shrink", type=int, default=1,
                    help="divide every shape's rows (a rehearsal off the "
                         "chip)")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "tools")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    import blaze_tpu  # noqa: F401  (x64, the compile cache)
    from blaze_tpu.batch import bucket_capacity
    from blaze_tpu.kernels import sort as K
    from blaze_tpu.schema import DataType, TypeId
    from probe_grid import _timed  # queued calls, waited for once a queue

    dev = jax.devices()[0]
    pair = jax.default_backend() == "tpu"
    out_dir = os.path.join(root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lines = []

    def say(**kw):
        kw["device"] = dev.device_kind
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    say(shape="any", part="dispatch", lanes=0,
        step_s=_timed(jax.jit(lambda x: x + 1), jnp.int32(0)))
    rng = np.random.default_rng(93)
    for name, (rows, dtypes, key_at) in SHAPES.items():
        rows //= args.shrink
        cap = bucket_capacity(rows)
        width = min(TILE, cap)

        def column(dt, n):
            if dt == "float64":   # sums of money, many of them equal
                return rng.integers(-2000, 50000, n) * 3 / 100.0
            return rng.integers(0, 1 << 20, n).astype(dt)

        values = [column(dt, rows) for dt in dtypes]
        ktypes = tuple(DataType(TypeId(dtypes[i])) for i in key_at)

        def tiles_of(fill):
            """The rows as tiles with `fill` of each tile's lanes rows."""
            per = int(width * fill)
            cuts = list(range(0, rows, per))
            out = []
            for at in cuts:
                n = min(per, rows - at)
                cols = tuple((np.pad(v[at:at + n], (0, width - n)),
                              np.arange(width) < n) for v in values)
                out.append((cols + tuple(cols[i] for i in key_at), n))
            spare = (1 << (len(out) - 1).bit_length()) - len(out)
            parts = jax.device_put(tuple(t for t, _n in out))
            counts = np.array([n for _t, n in out] + [0] * spare, np.int32)
            return parts + (parts[0],) * spare, counts

        shape = dict(shape=name, rows=rows, lanes=cap,
                     columns=f"{len(dtypes)}+{len(key_at)}")
        ncols = len(dtypes)
        for part, fill in (("assemble", 1.0), ("assemble_ragged", 0.8)):
            parts, counts = tiles_of(fill)
            cols, total = K.assemble_tiles(parts, counts, cap=cap)
            assert int(total) == rows
            say(part=part, tiles=len(parts), step_s=_timed(
                lambda: K.assemble_tiles(parts, counts, cap=cap)), **shape)
        kw = dict(dtypes=ktypes, descending=(False,) * len(key_at),
                  nulls_first=(True,) * len(key_at), float_pair=pair)
        digits, varies, perm = K.key_digits(cols[ncols:], total, **kw)
        moving = [d for d, m in zip(digits, np.asarray(varies)) if m]
        say(part="digits", digits=len(digits), moving=len(moving),
            step_s=_timed(lambda: K.key_digits(cols[ncols:], total, **kw)),
            **shape)
        for d in reversed(moving):
            perm = K.sort_pass(d, perm)
        out = K.gather_sorted(cols[:ncols], perm, np.int32(rows), out_cap=cap)
        # the order, against numpy's stable sort of the values as the
        # device holds them
        held = [np.asarray(d)[:rows] for d, _v in cols[:ncols]]
        order = np.lexsort(tuple(held[i] for i in reversed(key_at)))
        for (d, v), h in zip(out, held):
            assert np.array_equal(np.asarray(d)[:rows], h[order]), name
            assert np.asarray(v)[:rows].all() and not np.asarray(v)[rows:].any()
        say(part="checked", passes=len(moving), float_pair=pair, **shape)
        say(part="pass", step_s=_timed(K.sort_pass, moving[-1], perm),
            **shape)
        say(part="gather", arrays=ncols + 1, step_s=_timed(
            lambda: K.gather_sorted(cols[:ncols], perm, np.int32(rows),
                                    out_cap=cap)), **shape)
        one = jax.jit(lambda c, p: (jnp.take(c[0], p), jnp.take(c[1], p)))
        say(part="gather_1col", dtype=dtypes[0],
            step_s=_timed(one, cols[0], perm), **shape)
        bools = jax.jit(lambda cs, p: [jnp.take(v, p) for _d, v in cs])
        say(part="gather_bools", arrays=ncols,
            step_s=_timed(bools, cols[:ncols], perm), **shape)

    name = f"sort_grid.{args.tag}.jsonl" if args.tag else "sort_grid.jsonl"
    with open(os.path.join(out_dir, name), "w") as f:
        for ln in lines:
            f.write(json.dumps(ln) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

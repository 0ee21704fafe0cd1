"""Device time of one hash-aggregation step against table size, load and
lane width.

The stage loop (blaze_tpu/runtime/loop.py) sizes its table from two
constants, _TRIGGER_LOAD and _TARGET_LOAD, and the table's probe
(blaze_tpu/parallel/stage.py hash_agg_step) narrows to 1/_NARROW_SHARE
of a batch's lanes once the rows still unplaced fit.  This is the
measurement they were chosen from (PERF.md section 6, PRs 25 and 29):

  * `new_groups`, `12_groups`, `rehash_x4`: one 65,536-lane batch of the
    pair cell's shape (two int64 keys, one float64 sum, 41% of the lanes
    selected, every selected row a new group) inserted into a table of S
    slots that already holds load x S groups; the same batch with 12
    groups in all (the q06 shape); the table re-inserted into one of 4 S
    over its own slots as lanes, and (`rehash_x4_compacted`) over the
    power of two that holds its groups (`stage.rehash_width`);
  * `rehash_cell`: the rehash of `sf100_q01pair_x1`'s reduce tasks, 2^21
    slots at a load of 1/4 into 2^23, both ways: a host-clock reading
    beside the trace's `rehash_device_s`;
  * `slots`: ONE live 32,768-lane batch of the pair's shape (every lane
    a new group: a reduce task's tile) into tables of 2^19, 2^21 and
    2^23 slots at a load of 1/8, as the fold runs it: the table donated
    to the program, so that nothing but the step itself can copy it.  A
    step that costs by its lanes alone reads the same at all three;
    what grows with the table is cost by SLOT (PR 47).  `--only slots`
    runs this and `rehash_cell` alone;
  * `lanes`: the same step at 65,536 down to 4,096 lanes into one table
    at one load: is a round's cost linear in its lanes down there?
  * `compaction`: what the narrow phase pays before its first round (the
    unplaced lanes' positions, then the narrow gathers), beside
    `jnp.nonzero`.

Each reading carries the probe rounds the step ran at full and at narrow
width.  Run it on the chip:

    chiprun -- python3 tools/fold_grid.py

`--tree DIR` imports blaze_tpu from DIR, a checkout of another commit
unpacked inside this one, to read the same grid from its step (a step
that reports no rounds reads `null` there).  It prints one JSON line per
reading and writes them to chiprun_out/fold_grid[.<tag>].jsonl.  Times
are host-clock medians around `block_until_ready`, so they mean
something only on the device.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

LANES = 65536
TILE = 32768
WIDTHS = (65536, 16384, 8192, 4096)
LIVE_SHARE = 0.41
KINDS = ("sum",)
REPEATS = 5


def _step(stage, lanes, probe_rounds):
    import jax
    import jax.numpy as jnp

    def f(carry, k1, k2, v, mask):
        ones = jnp.ones(lanes, bool)
        return stage.hash_agg_step(carry, [(k1, ones), (k2, ones)],
                                   [("sum", v, None)], mask,
                                   probe_rounds=probe_rounds)
    return jax.jit(f)


def _batch(rng, live, groups=None, lanes=LANES):
    """(k1, k2, v, mask): `live` selected lanes, each a new group unless
    `groups` bounds the key domain."""
    import jax.numpy as jnp
    import numpy as np
    if groups is None:
        k1 = rng.integers(1, 1 << 40, lanes)
    else:
        k1 = rng.integers(1, groups + 1, lanes)
    k2 = (k1 % 12) + 1
    mask = np.zeros(lanes, bool)
    mask[rng.permutation(lanes)[:live]] = True
    return (jnp.asarray(k1), jnp.asarray(k2),
            jnp.asarray(rng.random(lanes)), jnp.asarray(mask))


def _timed(fn, *args):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return statistics.median(times), out


def _reading(out):
    """overflow, groups and the rounds at each width of a step's result
    (a step of before PR 29 returns no rounds)."""
    rounds = [int(r) for r in out[3]] if len(out) > 3 else [None, None]
    return dict(overflow=int(out[1]), groups_after=int(out[2]),
                full_rounds=rounds[0], narrow_rounds=rounds[1])


def _filled(fill, rng, carry, groups, want):
    """The table with `want` groups or more: whole new-group batches
    through the 256-round fill step."""
    while groups < want:
        out = fill(carry, *_batch(rng, min(LANES, want - groups)))
        assert int(out[1]) == 0
        carry, groups = out[0], int(out[2])
    return carry, groups


def _slots(stage, say, fill, fresh, log_slots):
    """One live TILE-lane batch into tables of 2^log_slots slots at a
    load of 1/8, the table donated (a copy of it made before each timed
    call, outside the clock)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    step = jax.jit(_step(stage, TILE, 16), donate_argnums=(0,))
    for log_s in log_slots:
        slots = 1 << log_s
        rng = np.random.default_rng(47 + log_s)
        master, groups = _filled(fill, rng, fresh(slots), 0, slots // 8)
        batch = _batch(rng, TILE, lanes=TILE)
        times = []
        for _ in range(REPEATS + 1):
            carry = jax.block_until_ready(
                jax.tree_util.tree_map(jnp.copy, master))
            t0 = time.perf_counter()
            out = step(carry, *batch)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        say(slots=slots, shape="slots", lanes=TILE,
            load_before=groups / slots, step_s=statistics.median(times[1:]),
            **_reading(out))


def _compaction(stage, say, rng):
    """The narrow phase's entry alone, at the pair cell's width: 65,536
    lanes of which 1,700 are unplaced (the worst first round of a reduce
    task's batch), compacted to 8,192."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    width = stage.narrow_width(LANES)
    unplaced = np.zeros(LANES, bool)
    unplaced[rng.permutation(LANES)[:1700]] = True
    cols = [jnp.asarray(rng.integers(1, 1 << 40, LANES)) for _ in range(3)]
    flags = [jnp.ones(LANES, bool)] * 2

    def positions(u):
        return stage._compact_lanes(u, width)

    def entry(u, cols, flags):
        lanes = stage._compact_lanes(u, width)
        return ([jnp.take(c, lanes, mode="clip") for c in cols],
                [jnp.take(f, lanes, mode="clip") for f in flags])

    def nonzero(u):
        return jnp.nonzero(u, size=width, fill_value=LANES)[0]

    u = jnp.asarray(unplaced)
    for name, fn, args in (("positions", positions, (u,)),
                           ("positions_and_gathers", entry, (u, cols, flags)),
                           ("jnp_nonzero", nonzero, (u,))):
        t, _ = _timed(jax.jit(fn), *args)
        say(shape="compaction", part=name, lanes=LANES, width=width,
            step_s=t)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--log-slots", type=int, nargs="+", default=[18, 20, 22])
    ap.add_argument("--cell-log-slots", type=int, default=21,
                    help="log2 of the `rehash_cell` table's slots")
    ap.add_argument("--slots-log-slots", type=int, nargs="+",
                    default=[19, 21, 23],
                    help="log2 of the `slots` reading's tables")
    ap.add_argument("--only", choices=["slots"], default=None,
                    help="`slots`: that reading and `rehash_cell` alone")
    ap.add_argument("--tree", default=None,
                    help="import blaze_tpu from this checkout")
    ap.add_argument("--tag", default=None,
                    help="write chiprun_out/fold_grid.<tag>.jsonl")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.abspath(args.tree) if args.tree else root)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import blaze_tpu  # noqa: F401  (x64, the compile cache)
    from blaze_tpu.parallel import stage

    dev = jax.devices()[0]
    out_dir = os.path.join(root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lines = []

    def say(**kw):
        kw["device"] = dev.device_kind
        kw["tree"] = args.tree or "."
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    def fresh(slots):
        return stage.init_hash_carry([jnp.int64, jnp.int64], KINDS,
                                     [jnp.float64], slots)

    def rehash_both_ways(carry, groups, shape):
        """The table into one of four times its slots: over its own
        slots as lanes and, where this tree's rehash takes a width, over
        the lanes that hold its groups."""
        slots = jax.tree_util.tree_leaves(carry.keys)[0].shape[0]
        widths = [(shape, None)]
        if hasattr(stage, "rehash_width"):
            widths.append((shape + "_compacted",
                           stage.rehash_width(groups, slots)))
        for name, lanes in widths:
            more = () if lanes is None else (lanes,)
            re = jax.jit(lambda c: stage.rehash_carry(
                c, list(KINDS), 4 * slots, *more))
            t, out = _timed(re, carry)
            say(slots=slots, shape=name, lanes=lanes or slots,
                load_before=groups / slots, step_s=t, **_reading(out))

    step16, fill = _step(stage, LANES, 16), _step(stage, LANES, 256)
    _slots(stage, say, fill, fresh, args.slots_log_slots)
    for log_s in ([] if args.only else args.log_slots):
        slots = 1 << log_s
        rng = np.random.default_rng(log_s)
        carry = fresh(slots)
        # the q06 shape: 12 groups, every lane selected
        few = _batch(rng, LANES, groups=12)
        warm = fill(carry, *few)[0]
        t, out = _timed(step16, warm, *few)
        say(slots=slots, shape="12_groups", lanes=LANES, load_before=0.0,
            step_s=t, **_reading(out))
        groups = 0
        for load in (0.0, 1 / 16, 1 / 8, 3 / 16, 1 / 4, 3 / 8, 1 / 2):
            carry, groups = _filled(fill, rng, carry, groups,
                                    int(load * slots))
            t, out = _timed(
                step16, carry, *_batch(rng, int(LIVE_SHARE * LANES)))
            say(slots=slots, shape="new_groups", lanes=LANES,
                load_before=groups / slots, step_s=t, **_reading(out))
            if load in (1 / 8, 1 / 4) and log_s <= 20:
                rehash_both_ways(carry, groups, "rehash_x4")

    # the lane-width axis: one table, two loads, every selected row a
    # new group
    slots = 1 << 20
    rng = np.random.default_rng(29)
    carry, groups = fresh(slots), 0
    for load in (() if args.only else (0.0, 1 / 8)):
        carry, groups = _filled(fill, rng, carry, groups,
                                int(load * slots))
        for lanes in WIDTHS:
            t, out = _timed(
                _step(stage, lanes, 16), carry,
                *_batch(rng, int(LIVE_SHARE * lanes), lanes=lanes))
            say(slots=slots, shape="lanes", lanes=lanes,
                load_before=groups / slots, step_s=t, **_reading(out))

    if hasattr(stage, "_compact_lanes") and not args.only:
        _compaction(stage, say, np.random.default_rng(30))

    # a reduce task of sf100_q01pair_x1 at its third chunk
    slots = 1 << args.cell_log_slots
    carry, groups = _filled(fill, np.random.default_rng(34), fresh(slots),
                            0, slots // 4)
    rehash_both_ways(carry, groups, "rehash_cell")

    name = f"fold_grid.{args.tag}.jsonl" if args.tag else "fold_grid.jsonl"
    with open(os.path.join(out_dir, name), "w") as f:
        for ln in lines:
            f.write(json.dumps(ln) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Window functions over one sorted partition as ONE device program.

`WindowExec`'s resident lane (ops/window.py) hands over the rows of a
task's partition as they lie on the chip, sorted by (partition keys, order
keys): the key columns, one argument column a function that has one, and
the count of rows in front of the padding.  `segmented_scan` computes every
function of the node from them:

  * flags: a row starts a partition if any partition key differs from the
    row before, an order run if any order key does (the order keys of
    kernels/compare.py, so NULL equals NULL and NaN equals NaN); the first
    padding lane starts a partition of its own, so nothing behind the rows
    reaches them from either side;
  * running sum, count, min and max: one `lax.associative_scan` over
    (flag, value, seen) with
        (f1, v1, s1) . (f2, v2, s2) = (f1 | f2,
                                       v2 if f2 else op(v1, v2),
                                       s2 if f2 else s1 | s2),
    every lane of every function in the one scan.  A flag on the right
    drops the left value whole, so a sum RESTARTS at a boundary: no value
    of one partition enters another's total.  `seen` says whether a
    non-NULL value has been met since the boundary: NULLs are skipped and
    a row before its partition's first value reads NULL;
  * the rank family from positions, int32: running maxima and minima of
    the lanes at which a flag stands, and dense rank as one more lane of
    the scan;
  * frames: a running frame gives every row of an order run the run's last
    value (Spark's RANGE frame, as the host lane gives it), the whole
    partition's frame the partition's last value: one reverse scan a kind
    of frame copies the last row's values down to the rows before it.

One program a (capacity, key and argument types, function list).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from blaze_tpu.bridge.xla_stats import meter_jit
from blaze_tpu.kernels import compare

# function descriptors (static): (kind,) for the rank family (row_number,
# rank, dense_rank, percent_rank, cume_dist), (kind, running) for an
# aggregate over a frame
AGGS = ("sum", "count", "min", "max", "avg")


def _differs(keys, dtypes):
    """Lanes at which any key differs from the lane before (lane 0 does)."""
    cap = keys[0][0].shape[0]
    out = jnp.zeros(cap, bool).at[0].set(True)
    for (data, validity), dtype in zip(keys, dtypes):
        bucket, value = compare.order_key(data, validity, dtype)
        step = (bucket[1:] != bucket[:-1]) | (value[1:] != value[:-1])
        out = out | jnp.concatenate([jnp.ones(1, bool), step])
    return out


def _combine(ops):
    """The scan's operator over (flag, (value, seen) a lane)."""
    def combine(a, b):
        f1, f2 = a[0], b[0]
        out = [f1 | f2]
        for op, (v1, s1), (v2, s2) in zip(ops, a[1:], b[1:]):
            if op == "sum":     # NULLs ride as 0
                v = v1 + v2
            else:
                pick = jnp.maximum if op == "max" else jnp.minimum
                v = jnp.where(s1 & s2, pick(v1, v2), jnp.where(s2, v2, v1))
            out.append((jnp.where(f2, v2, v), jnp.where(f2, s2, s1 | s2)))
        return tuple(out)
    return combine


def _copy_back(a, b):
    """A reverse scan's operator: a lane keeps its own values if it is
    flagged, else takes those of the nearest flagged lane behind it."""
    (fa, va), (fb, vb) = a, b
    return fa | fb, jax.tree_util.tree_map(
        lambda x, y: jnp.where(fb, y, x), va, vb)


def _segmented_scan(part, order, args, rows, *, part_types, order_types,
                    funcs, group_limit=None):
    """Every function of a window node over `rows` sorted rows in front of
    the padding.

    part, order: ((data, validity), ...) the partition and order keys;
    args: one (data, validity) a function, None where it has no argument;
    funcs: static descriptors (module docstring), `order` empty makes
    every frame the whole partition's and every row an order run.
    Returns (((data, validity), ...) a function, selection): `selection`
    keeps the rows whose rank is at most `group_limit`, None without one.
    """
    cap = (part or order or [a for a in args if a is not None])[0][0].shape[0]
    idt = jnp.int32
    lanes = jnp.arange(cap, dtype=idt)
    live = lanes < rows
    edge = (lanes == 0) | (lanes == rows)
    part_flag = (_differs(part, part_types) | edge) if part else edge
    change = (_differs(order, order_types) | part_flag) if order \
        else jnp.ones(cap, bool)

    # positions: a partition's first lane, a run's first and last lane, a
    # partition's last lane
    def last_at(flag):
        """The lane of the nearest flag at or before each lane."""
        return jax.lax.associative_scan(jnp.maximum,
                                        jnp.where(flag, lanes, 0))

    def end_of(flag):
        """The last lane before the next flag behind each lane."""
        ends = jnp.concatenate([flag[1:], jnp.ones(1, bool)])
        # not `lax.cummin(reverse=True)`: the TPU compiler takes 57 s over
        # that at 2^18 lanes and 7 s over this (PERF.md, PR 44)
        return jax.lax.associative_scan(
            jnp.minimum, jnp.where(ends, lanes, cap - 1), reverse=True)

    start = last_at(part_flag)
    run_end = functools.cache(lambda: end_of(change))
    part_end = functools.cache(lambda: end_of(part_flag))
    rank = functools.cache(lambda: last_at(change) - start + 1)

    def size():
        return part_end() - start + 1

    # the one scan: a lane a running aggregate (avg is a sum and a count),
    # one for dense rank
    ops, elems, where = [], [], {}

    def lane(key, op, value, seen):
        if key not in where:
            where[key] = len(ops)
            ops.append(op)
            elems.append((value, seen))
        return where[key]

    ones = jnp.ones(cap, bool)
    for i, (fn, arg) in enumerate(zip(funcs, args)):
        kind = fn[0]
        if kind == "dense_rank":
            lane("dense", "sum", (change & ~part_flag).astype(idt), ones)
        if kind not in AGGS:
            continue
        if arg is None:                      # count(*)
            data, valid = jnp.zeros(cap, idt), ones
        else:
            data, valid = arg
        if kind in ("sum", "avg"):
            acc = jnp.float64 if jnp.issubdtype(data.dtype, jnp.floating) \
                else jnp.int64
            lane(("sum", i), "sum",
                 jnp.where(valid, data.astype(acc), jnp.zeros((), acc)),
                 valid)
        if kind in ("count", "avg"):
            lane(("count", i), "sum", valid.astype(idt), ones)
        if kind in ("min", "max"):
            lane((kind, i), kind, data, valid)
    scanned = ()
    if ops:
        scanned = jax.lax.associative_scan(
            _combine(ops), (part_flag,) + tuple(elems))[1:]

    # frames: every lane of an aggregate at its frame's last row, the lanes
    # of a kind of frame in one reverse scan that copies a flagged lane's
    # values down to the lanes before it (a `take` at the row's position
    # costs five times a scan's lane on the chip: PERF.md, PR 44)
    by_frame = {}
    for i, fn in enumerate(funcs):
        if fn[0] in AGGS:
            by_frame.setdefault(fn[1] and bool(order), []).extend(
                k for k in where if k != "dense" and k[1] == i)
    framed = {}
    for running, keys in by_frame.items():
        first = change if running else part_flag
        last = jnp.concatenate([first[1:], jnp.ones(1, bool)])
        copied = jax.lax.associative_scan(
            _copy_back, (last, tuple(scanned[where[k]] for k in keys)),
            reverse=True)[1]
        framed.update(zip(keys, copied))

    out = []
    f64 = jnp.float64
    for i, fn in enumerate(funcs):
        kind = fn[0]
        if kind == "row_number":
            data, valid = lanes - start + 1, live
        elif kind == "rank":
            data, valid = rank(), live
        elif kind == "dense_rank":
            data, valid = scanned[where["dense"]][0] + 1, live
        elif kind == "percent_rank":
            n = size()
            data = jnp.where(n == 1, 0.0, (rank().astype(f64) - 1.0)
                             / jnp.maximum(n - 1, 1).astype(f64))
            valid = live
        elif kind == "cume_dist":
            data = (run_end() + 1 - start).astype(f64) / size().astype(f64)
            valid = live
        elif kind == "count":
            data, valid = framed[("count", i)][0].astype(jnp.int64), live
        elif kind == "avg":
            s, seen = framed[("sum", i)]
            data = s.astype(f64) / jnp.maximum(framed[("count", i)][0], 1)
            valid = seen & live
        elif kind in ("sum", "min", "max"):
            data, seen = framed[(kind, i)]
            valid = seen & live
        else:
            raise TypeError(f"window function {kind!r} has no device form")
        out.append((jnp.where(valid, data, jnp.zeros((), data.dtype)),
                    valid))
    selection = None if group_limit is None \
        else live & (rank() <= group_limit)
    return tuple(out), selection


segmented_scan = meter_jit(
    _segmented_scan, name="window.scan",
    static_argnames=("part_types", "order_types", "funcs", "group_limit"))


def scan_bytes(rows: int, part, order, args, out) -> int:
    """What a scan of `rows` rows has to move at the least: every key and
    argument column and its validity read once, every result and its
    validity written once (`benchmark/kernel_costs_window.py` states the
    same sum from widths)."""
    read = [c for c in tuple(part) + tuple(order) + tuple(args)
            if c is not None]
    return rows * sum(d.dtype.itemsize + v.dtype.itemsize
                      for d, v in read + list(out))

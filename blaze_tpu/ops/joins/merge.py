"""Sort-merge join of one partition as device programs.

`SortMergeJoinExec` takes this path whenever placement is not the host and
the memory manager leaves the partition its two key-sorted sides and its
pairs on the device (`Hold`); the run-cursor merge of smj.py stays for a
partition the manager sheds (the spill discipline) and for the join shapes
`declines` names.

The shape is kernels/join.py's probe over real keys instead of hashes.  One
side drives, the other is searched:

  1. `merge_bounds`: every driving row's equal-key run in the searched
     side (one vectorised lexicographic binary search over the order keys
     of kernels/compare.py), with the pairs' total: the one scalar sync.
  2. `merge_expand_pairs`: the runs expanded into pair index arrays of a
     static power-of-two size (kernels/join.py `expand_pairs`, metered
     under the merge join's name).
  3. `_gather`: the pairs' columns of both sides, gathered on the device.

Both sides arrive sorted on the join keys, so walking the driving side in
order emits the output in key order, which is the operator's contract.  An
inner join is driven by its smaller side (the search costs by the driving
side's rows), an outer, semi, anti or existence join by the side it keeps;
a full outer join is driven by the left side and lays the right side's
partnerless rows in between by position arithmetic (`_full_layout`), with
no further search and no sort.  A join filter is evaluated over the
gathered pairs; semi, anti and existence joins then take their mask from
the pairs that passed.
"""

from __future__ import annotations

from dataclasses import replace
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu.batch import ColumnBatch, DeviceColumn, HostColumn
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.bridge.xla_stats import meter_jit
from blaze_tpu.kernels.join import (_pow2_at_least, merge_bounds,
                                    merge_expand_pairs)
from blaze_tpu.memory import MemConsumer
from blaze_tpu.schema import BOOL
from blaze_tpu.xputil import to_host


def declines(op) -> Optional[str]:
    """Why this join streams through the run cursor whatever arrives, or
    None.  What streams is counted (`smj_streamed_runs`)."""
    from blaze_tpu.ops.joins.exec import JoinType
    for keys, child in ((op.left_keys, op.children[0]),
                        (op.right_keys, op.children[1])):
        if not all(e.data_type(child.schema).is_fixed_width for e in keys):
            # strings and nested values have no order key on the device
            return "a join key that is not fixed-width"
    if op.join_filter is not None and op.join_type in (
            JoinType.LEFT, JoinType.RIGHT, JoinType.FULL):
        # a row all of whose pairs fail the filter is emitted once with
        # NULLs, in its key position: the run cursor does that per run
        return "an outer join with a join filter"
    return None


class Hold(MemConsumer):
    """What one partition's merge holds on the device, registered with the
    memory manager like the `SortExec` states under it: both collected
    sides, then the pairs it writes.

    Rows held for a device join cannot go down a tier and still be joined
    there, so under pressure (the pool over its budget, or this consumer
    far over its fair share: the reduce tasks of a stage run side by side)
    the partition gives the device path up: `spill` only marks it `denied`,
    from whichever thread the manager shed it on, and the owner, which asks
    after every update, moves what it holds to the host and streams."""

    def __init__(self, metrics=None):
        self.metrics = metrics
        super().__init__("smj_merge")
        self.denied = False

    def reserve(self, nbytes: int) -> bool:
        """`nbytes` more; false if the manager wants them back."""
        self.add_mem_used(nbytes)
        return not self.denied

    def spill(self) -> int:
        self.denied = True
        released, self._mem_used = self._mem_used, 0
        return released


def _row_bytes(batch: ColumnBatch) -> int:
    return max(1, batch.nbytes_device() // max(1, batch.capacity))


# ---------------------------------------------------------------------------
# the programs
# ---------------------------------------------------------------------------

def _outer_counts(lo, count, probe_rows, build_cap, build_rows,
                  keep_build: bool):
    """What each driving row of an outer join emits, and which searched
    rows nobody found.

    Returns (emit, total, lonely, lonely_total): a driving row emits its
    run, or one NULL-extended row if it has no partner; a searched row is
    `lonely` if `keep_build` (a full outer join) and no driving row's run
    covers it (a difference array over the runs' bounds, no search)."""
    in_range = jnp.arange(lo.shape[0], dtype=jnp.int32) < probe_rows
    emit = jnp.where(in_range & (count == 0), 1, count)
    total = jnp.sum(emit.astype(jnp.int64))
    if not keep_build:
        return emit, total, None, jnp.int64(0)
    found = count > 0
    edges = jnp.zeros(build_cap + 1, jnp.int32) \
        .at[jnp.where(found, lo, build_cap)].add(found.astype(jnp.int32)) \
        .at[jnp.where(found, lo + count, build_cap)] \
        .add(-found.astype(jnp.int32))
    covered = jnp.cumsum(edges)[:build_cap] > 0
    lonely = ~covered & (jnp.arange(build_cap, dtype=jnp.int32) < build_rows)
    return emit, total, lonely, jnp.sum(lonely.astype(jnp.int64))


outer_counts = meter_jit(_outer_counts, name="smj.outer_counts",
                         static_argnames=("build_cap", "keep_build"))


def _full_layout(lo, count, emit, lonely, probe_rows, p, s_pos, pairs,
                 cap: int):
    """Output positions of a full outer join, in key order.

    The left side drove: `p`, `s_pos` are its `pairs` rows (NULL-extended
    ones included) in left order.  A lonely right row j goes behind the
    rows of every left row whose key is not above its own, and those are
    the left rows with `lo` <= j (`lo` counts the right rows below a left
    row's key).  A left row's rows move back by the lonely right rows below
    its key.  Returns (l_idx, r_idx, l_null, r_null, valid) of size `cap`."""
    cap_l, cap_r = lo.shape[0], lonely.shape[0]
    idt = jnp.int32
    in_range = jnp.arange(cap_l, dtype=idt) < probe_rows
    lonely_before = jnp.concatenate(
        [jnp.zeros(1, idt), jnp.cumsum(lonely.astype(idt))])
    emitted_before = jnp.concatenate(
        [jnp.zeros(1, idt), jnp.cumsum(emit.astype(idt))])
    left_not_above = jnp.cumsum(
        jnp.zeros(cap_r + 1, idt).at[jnp.where(in_range, lo, cap_r)]
        .add(in_range.astype(idt)))[:cap_r]
    t = jnp.arange(p.shape[0], dtype=idt)
    dest_a = jnp.where(t < pairs, t + jnp.take(lonely_before,
                                               jnp.take(lo, p)), cap)
    dest_b = jnp.where(lonely, jnp.take(emitted_before, left_not_above)
                       + lonely_before[:cap_r], cap)
    zeros = jnp.zeros(cap, idt)
    l_idx = zeros.at[dest_a].set(p.astype(idt), mode="drop")
    r_idx = zeros.at[dest_a].set(s_pos.astype(idt), mode="drop") \
        .at[dest_b].set(jnp.arange(cap_r, dtype=idt), mode="drop")
    r_null = jnp.zeros(cap, bool).at[dest_a].set(
        jnp.take(count, p) == 0, mode="drop")
    l_null = jnp.zeros(cap, bool).at[dest_b].set(True, mode="drop")
    total = pairs + lonely_before[cap_r]
    return l_idx, r_idx, l_null, r_null, jnp.arange(cap, dtype=idt) < total


full_layout = meter_jit(_full_layout, name="smj.full_layout",
                        static_argnames=("cap",))


def _gather(cols, idx, valid, null):
    """((data, validity), ...) at `idx`; validity False where the output
    row is padding (`valid`) or NULL-extended on this side (`null`)."""
    keep = valid if null is None else valid & ~null
    return tuple((jnp.take(d, idx, mode="clip"),
                  jnp.take(v, idx, mode="clip") & keep) for d, v in cols)


gather = meter_jit(_gather, name="smj.gather")


def _pair_null(count, p):
    return jnp.take(count, p) == 0


pair_null = meter_jit(_pair_null, name="smj.pair_null")


def _matched_rows(p, keep, cap: int):
    """Driving rows with at least one pair that passed the join filter."""
    return jnp.zeros(cap, bool).at[p].max(keep)


matched_rows = meter_jit(_matched_rows, name="smj.matched_rows",
                         static_argnames=("cap",))


# ---------------------------------------------------------------------------
# one partition
# ---------------------------------------------------------------------------

def _concat(schema, batches: Sequence[ColumnBatch]) -> ColumnBatch:
    if len(batches) == 1 and batches[0].selection is None:
        # a side is a `SortExec`'s output: one batch where the sort kept
        # its partition on the device, already at its bucket
        return batches[0]
    if batches:
        return ColumnBatch.concat(batches)
    return ColumnBatch.from_arrow(
        pa.Table.from_batches([], schema=schema.to_arrow()))


def _key_cols(batch: ColumnBatch, exprs) -> Optional[tuple]:
    out = []
    for e in exprs:
        v = e.evaluate(batch)
        if not v.is_device or v.dictionary is not None:
            return None
        out.append((v.data, v.validity))
    return tuple(out)


def _device_cols(batch: ColumnBatch) -> tuple:
    return tuple((c.data, c.validity) for c in batch.columns
                 if isinstance(c, DeviceColumn))


def _taken(batch: ColumnBatch, idx, valid, null, rows: int) -> List:
    """`batch`'s columns at `idx`: device columns through one `gather`
    program, host (string) columns through Arrow with the indices read
    back."""
    placed = iter(gather(_device_cols(batch), idx, valid, null))
    host = None
    out = []
    for c in batch.columns:
        if isinstance(c, DeviceColumn):
            data, validity = next(placed)
            out.append(replace(c, data=data, validity=validity))
            continue
        if host is None:
            host = to_host((idx, valid if null is None else valid & ~null))
        i_np, keep_np = host[0][:rows], host[1][:rows]
        # a NULL index takes a NULL: NULL-extended rows, and an empty side
        arr = c.array.take(pa.array(i_np, mask=~keep_np))
        out.append(HostColumn(c.dtype, arr))
    return out


class Over(Exception):
    """The memory manager denied the pairs their bytes: the caller
    streams."""


def join(op, left: Sequence[ColumnBatch], right: Sequence[ColumnBatch],
         hold: Hold) -> Optional[ColumnBatch]:
    """One partition joined: one batch in key order, or None for no row.
    Raises `Over` if `hold` cannot reserve the pairs' bytes."""
    from blaze_tpu.ops.joins.exec import JoinType as JT
    jt = op.join_type
    lb = _concat(op.children[0].schema, left)
    rb = _concat(op.children[1].schema, right)
    right_drives = jt in (JT.RIGHT, JT.RIGHT_SEMI, JT.RIGHT_ANTI) or (
        jt == JT.INNER and rb.num_rows < lb.num_rows)
    d, s = (rb, lb) if right_drives else (lb, rb)
    d_keys, s_keys = ((op.right_keys, op.left_keys) if right_drives
                      else (op.left_keys, op.right_keys))
    d_cols, s_cols = _key_cols(d, d_keys), _key_cols(s, s_keys)
    if d_cols is None or s_cols is None:
        raise Over("a join key came out host-resident")
    dtypes = tuple(e.data_type(op.children[0].schema)
                   for e in op.left_keys)
    with tracing.span("smj_merge", rows=d.num_rows + s.num_rows) as attrs:
        lo, count, matches = merge_bounds(
            d_cols, s_cols, d.num_rows, s.num_rows,
            dtypes=dtypes)
        row_mask = jt in (JT.LEFT_SEMI, JT.RIGHT_SEMI, JT.LEFT_ANTI,
                          JT.RIGHT_ANTI, JT.EXISTENCE)
        if row_mask and op.join_filter is None:
            out = _masked(op, d, count > 0)
            pairs = 0
        else:
            out, pairs = _paired(op, d, s, lo, count, matches,
                                 right_drives, row_mask, hold)
        attrs["pairs"] = pairs
    xla_stats.note_sortmerge(smj_device_rows=d.num_rows + s.num_rows,
                             smj_device_pairs=pairs)
    return out


def _masked(op, d: ColumnBatch, matched) -> ColumnBatch:
    """A semi, anti or existence join's output: the driving side's rows
    under a mask, or with the mask as a column."""
    from blaze_tpu.ops.joins.exec import JoinType as JT
    jt = op.join_type
    if jt == JT.EXISTENCE:
        exists = DeviceColumn(BOOL, matched, d.row_mask())
        return ColumnBatch(op.schema, list(d.columns) + [exists],
                           d.num_rows, None)
    anti = jt in (JT.LEFT_ANTI, JT.RIGHT_ANTI)
    return d.with_selection(~matched if anti else matched)


def _paired(op, d, s, lo, count, matches, right_drives: bool,
            row_mask: bool, hold: Hold
            ) -> Tuple[Optional[ColumnBatch], int]:
    from blaze_tpu.ops.joins.exec import JoinType as JT
    jt = op.join_type
    keep_probe = jt in (JT.LEFT, JT.RIGHT, JT.FULL)
    keep_build = jt == JT.FULL
    if keep_probe:
        emit, total, lonely, lonely_total = outer_counts(
            lo, count, d.num_rows, build_cap=s.capacity,
            build_rows=s.num_rows, keep_build=keep_build)
        pairs, lonely_n = (int(x) for x in to_host((total, lonely_total)))
    else:
        emit, pairs, lonely_n = count, int(to_host(matches)), 0
    rows = pairs + lonely_n
    if not hold.reserve(rows * (_row_bytes(d) + _row_bytes(s))):
        raise Over(f"{rows} pairs")
    if rows == 0:
        return None, 0
    p, s_pos, valid, _total = merge_expand_pairs(
        lo, emit, _pow2_at_least(pairs))
    if keep_build:
        l_idx, r_idx, l_null, r_null, valid = full_layout(
            lo, count, emit, lonely, d.num_rows, p, s_pos,
            pairs, cap=_pow2_at_least(rows))
        cols = _taken(d, l_idx, valid, l_null, rows) \
            + _taken(s, r_idx, valid, r_null, rows)
        return ColumnBatch(op.schema, cols, rows, None), rows
    s_null = pair_null(count, p) if keep_probe else None
    d_out = _taken(d, p, valid, None, rows)
    s_out = _taken(s, s_pos, valid, s_null, rows)
    cols = s_out + d_out if right_drives else d_out + s_out
    if op.join_filter is None:
        return ColumnBatch(op.schema, cols, rows, None), rows
    from blaze_tpu.schema import Schema
    joined = ColumnBatch(Schema(list(op.children[0].schema)
                                + list(op.children[1].schema)),
                         cols, rows, None)
    keep = op.join_filter.evaluate(joined).as_mask(joined) & valid
    if not row_mask:  # inner
        return ColumnBatch(op.schema, cols, rows, keep), rows
    return _masked(op, d, matched_rows(p, keep, cap=d.capacity)), rows

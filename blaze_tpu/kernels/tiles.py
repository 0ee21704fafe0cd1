"""Device batches laid end to end where they lie.

A tile is ((data, validity), ...) a batch, one pair a fixed-width column;
`rows` says how many lanes of each tile are rows, as runtime scalars, so a
program's signature is its tiles' widths and column types and nothing else.
`SortExec`'s resident lane lays a whole partition with `_assemble_tiles`
(kernels/sort.py `assemble_tiles`); `CoalesceStream` lays the few batches
it holds behind the rows left of the last tile with `lay_tile` and cuts a
tile of one batch size off the front (ops/base.py).  The exchange's
resident tier lays a map task's batch partition-major where it lies
(`partition_tile`, shuffle/writer.py) and a reduce task's runs of such
batches end to end (`lay_runs`, shuffle/reader.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from blaze_tpu.bridge.xla_stats import meter_jit


def _assemble_tiles(tiles, rows, cap: int):
    """The tiles' rows end to end in columns of `cap` lanes, in arrival
    order, with their count: ((data, validity), ...), total.  Copies alone,
    whatever the tiles hold: tile k is written whole where tile k-1's rows
    end, over what that tile carried behind its rows, so full tiles (an
    exchange reader's) and ragged ones (a filter's) cost the same.  A
    padding lane reads 0 and is not valid."""
    width = max(tile[0][0].shape[0] for tile in tiles)
    starts = jnp.cumsum(rows) - rows
    total = starts[-1] + rows[-1]

    def laid(*parts):
        # room for the last tile's own padding behind the last row
        buf = jnp.zeros((cap + width,), parts[0].dtype)
        for at, part in zip(starts, parts):
            buf = jax.lax.dynamic_update_slice(buf, part, (at,))
        return buf[:cap]

    cols = jax.tree_util.tree_map(laid, *tiles)
    live = jnp.arange(cap, dtype=jnp.int32) < total
    return tuple((jnp.where(live, d, jnp.zeros_like(d)), v & live)
                 for d, v in cols), total


def _lay_tile(held, parts, rows, tile: int, lanes: int):
    """`parts`' rows behind the rows `held` carries (None: a stream's first
    lay; `rows` counts held's and then each part's), cut at `tile` rows:
    (head, rest).  `head` is the first `tile` rows at `lanes` lanes, the
    tile that leaves once the rows reach `tile` and what a stream's tail
    leaves as before that; `rest` is what stays held, at `lanes` plus the
    widest part's width: the rows from `tile` on moved to the front where
    there are that many, every row else.  Both clean behind their rows.
    The caller lays as soon as the rows reach `tile`, so `rest` has room:
    under `tile` rows were held before the last part."""
    tiles = parts if held is None else (held,) + parts
    cap = max(lanes + max(p[0][0].shape[0] for p in parts),
              tiles[0][0][0].shape[0])
    cols, total = _assemble_tiles(tiles, rows, cap=cap)
    cut = total >= tile
    first = jnp.arange(lanes, dtype=jnp.int32) < tile

    def head(a):
        return jnp.where(first, a[:lanes], jnp.zeros_like(a[:lanes]))

    def rest(a):
        return jnp.where(cut, jnp.pad(a[tile:], (0, tile)), a)

    return (jax.tree_util.tree_map(head, cols),
            jax.tree_util.tree_map(rest, cols))


lay_tile = meter_jit(_lay_tile, name="coalesce.lay",
                     static_argnames=("tile", "lanes"))


def _narrow_tile(tile, lanes: int):
    """A tile whose rows fit `lanes` lanes at that width."""
    return jax.tree_util.tree_map(lambda a: a[:lanes], tile)


narrow_tile = meter_jit(_narrow_tile, name="coalesce.tail",
                        static_argnames=("lanes",))


def _lay_runs(held, parts, starts, rows, tile: int, lanes: int):
    """`_lay_tile` over runs that lie anywhere in their tiles: part k's
    rows are its lanes from `starts[k]` on (a reduce partition's run of a
    batch laid partition-major).  A copy brings each run to its tile's
    front; what lies behind a run's rows is overwritten or masked as any
    tile's padding is."""
    def front(a, at):
        return jax.lax.dynamic_slice(
            jnp.pad(a, (0, a.shape[0])), (at,), (a.shape[0],))

    parts = tuple(jax.tree_util.tree_map(lambda a, at=at: front(a, at), part)
                  for part, at in zip(parts, starts))
    return _lay_tile(held, parts, rows, tile, lanes)


lay_runs = meter_jit(_lay_runs, name="exchange.lay",
                     static_argnames=("tile", "lanes"))


def _partition_tile(cols, pids, selection, rows, n_parts: int):
    """A batch's live rows laid partition-major: (columns, counts).  `cols`
    is ((data, validity), ...) over one capacity, `pids` a lane's partition
    id; the first `rows` lanes are rows, those of them that `selection`
    (None: all) keeps are live.  Rows of partition 0 come first, then
    partition 1's, each partition in arrival order (a stable order by
    partition id); `counts[p]` says how many partition p has.  A row's
    place is its partition's start plus its rank among the partition's
    rows, by counting, one prefix sum a partition; no sort.  ONE int32
    scatter turns places into sources and every lane is gathered once.
    Lanes behind the rows read 0 and are not valid."""
    cap = pids.shape[0]
    lane = jnp.arange(cap, dtype=jnp.int32)
    mask = lane < rows
    if selection is not None:
        mask = mask & selection
    hot = (mask[None, :]
           & (pids[None, :] == jnp.arange(n_parts, dtype=pids.dtype)[:, None])
           ).astype(jnp.int32)
    rank = jnp.cumsum(hot, axis=1)
    counts = rank[:, -1]
    starts = jnp.cumsum(counts) - counts
    place = jnp.sum(hot * (starts[:, None] + rank - 1), axis=0)
    # a dead lane's place is past the end, and dropped
    src = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(mask, place, cap)].set(lane, mode="drop")
    live = lane < jnp.sum(counts)
    return tuple((jnp.where(live, d[src], jnp.zeros_like(d)), v[src] & live)
                 for d, v in cols), counts


partition_tile = meter_jit(_partition_tile, name="exchange.partition",
                           static_argnames=("n_parts",))

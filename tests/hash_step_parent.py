"""The hash table's step as it was before the owner lane (PR 29's, at
commit b95edcd), word for word: `ParentCarry`, `parent_init` and
`parent_step` are `HashAggCarry`, `init_hash_carry` and `hash_agg_step`
under other names, and nothing else differs.  Kept so that
tests/test_hash_agg_adversarial.py can hold today's step to it: the same
rounds at both widths, every group in the same slot, the same sums."""

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from blaze_tpu.parallel.stage import (_compact_lanes, _dtype_of,
                                      init_accumulators, narrow_width,
                                      normalize_float_keys,
                                      scatter_accumulate)


class ParentCarry(NamedTuple):
    """Device open-addressing group table (the agg_hash_map.rs analog,
    ref agg_hash_map.rs open-addressing map keyed by grouping bytes).

    TPU-first redesign: linear-probe insertion is expressed as a BOUNDED
    number of scatter/gather rounds — no sort, no per-row loop, no
    data-dependent shapes.  Rounds run over the whole batch while many
    rows are unplaced and over a fixed narrow buffer of the rest
    afterwards (hash_agg_step), and stop when every row is placed.  A
    multi-operand `lax.sort` grouping program takes minutes to compile
    on TPU; this compiles in seconds.  Its cost is its rounds: each op
    of a round costs by the lanes it runs over (PERF.md section 6)."""

    keys: Tuple[jax.Array, ...]        # stored key data, each (S,)
    key_valid: Tuple[jax.Array, ...]
    accs: Tuple[jax.Array, ...]
    acc_valid: Tuple[jax.Array, ...]
    used: jax.Array                    # (S,) bool


def parent_init(key_dtypes: Sequence, acc_kinds: Sequence[str],
                    acc_dtypes: Sequence, num_slots: int) -> ParentCarry:
    keys = tuple(jnp.zeros(num_slots, dtype=dt) for dt in key_dtypes)
    kvalid = tuple(jnp.zeros(num_slots, dtype=bool) for _ in key_dtypes)
    accs, avalid = init_accumulators(acc_kinds, acc_dtypes, num_slots)
    return ParentCarry(keys, kvalid, accs, avalid,
                        jnp.zeros(num_slots, dtype=bool))


def parent_step(carry: ParentCarry,
                  key_cols: Sequence[Tuple[jax.Array, jax.Array]],
                  agg_specs: Sequence[Tuple[str, Optional[jax.Array],
                                            Optional[jax.Array]]],
                  mask: jax.Array, probe_rounds: int = 16):
    """Insert one batch into the table.  Returns (new_carry, overflow,
    num_groups, rounds); ATOMIC: when any row fails to place within
    probe_rounds, the ORIGINAL carry is returned unchanged (overflow > 0)
    so the host can grow/degrade and retry the whole batch losslessly.

    The probe has two widths.  Rounds run over all `n` lanes while more
    than `narrow_width(n)` rows are unplaced (round one always does);
    then the rows still unplaced are compacted, in row order and under
    their original row numbers, and the remaining rounds run over that
    many lanes against the same table.  The winner of a slot is the
    lowest row number either way, so every group lands in the slot it
    would land in at full width.  `rounds` is int32[2]: the rounds run
    at full and at narrow width; together at most `probe_rounds`."""
    from blaze_tpu.kernels import hashing as H
    S = carry.used.shape[0]
    n = mask.shape[0]
    W = narrow_width(n)

    key_cols = normalize_float_keys(key_cols)

    cols = [(d, v, _dtype_of(d).id.value) for d, v in key_cols]
    h = H.hash_columns(cols, seed=42, xp=jnp, algo="xxhash64")
    h = h.astype(jnp.int64) & (S - 1)  # S is a power of two

    def probe(h, key_cols, row_idx, state, wide: bool):
        """Probe rounds over the lanes given (all of the batch, or its
        compacted unplaced rows) until every lane is placed, the rounds
        are spent or, at full width, the rest fits the narrow width."""

        def round_body(state):
            r, used, tkeys, tkvalid, placed, unplaced, _left = state
            slot = (h + r) & (S - 1)
            used_g = jnp.take(used, slot)
            can_claim = unplaced & ~used_g
            # deterministic winner per slot: the lowest row index
            claim = jnp.full(S, n, dtype=jnp.int64).at[
                jnp.where(can_claim, slot, S)].min(row_idx, mode="drop")
            winner = (jnp.take(claim, slot) == row_idx) & can_claim
            wslot = jnp.where(winner, slot, S)
            tkeys = tuple(tk.at[wslot].set(kd, mode="drop")
                          for tk, (kd, _kv) in zip(tkeys, key_cols))
            tkvalid = tuple(tv.at[wslot].set(kv, mode="drop")
                            for tv, (_kd, kv) in zip(tkvalid, key_cols))
            used = used.at[wslot].set(True, mode="drop")
            # match AFTER claims so same-key rows placed this round unify
            eq = jnp.take(used, slot)
            for tk, tv, (kd, kv) in zip(tkeys, tkvalid, key_cols):
                sk = jnp.take(tk, slot)
                sv = jnp.take(tv, slot)
                same = sk == kd
                if jnp.issubdtype(kd.dtype, jnp.floating):
                    # grouping treats NaN as equal to NaN (Spark
                    # normalizes)
                    same = same | (jnp.isnan(sk) & jnp.isnan(kd))
                # SQL grouping: null == null; valid keys compare by value
                eq &= (sv == kv) & jnp.where(kv, same, True)
            ok = unplaced & eq
            placed = jnp.where(ok, slot, placed)
            unplaced = unplaced & ~ok
            return (r + 1, used, tkeys, tkvalid, placed, unplaced,
                    jnp.sum(unplaced, dtype=jnp.int32))

        def round_cond(state):
            r, _used, _tk, _tv, _placed, _unplaced, left = state
            # early exit: most batches place everything in 1-2 rounds — on
            # the host backend the remaining rounds' S-sized claim arrays
            # would dominate the whole step
            more = (r < probe_rounds) & (left > 0)
            if wide and W:
                more &= (r == 0) | (left > W)
            return more

        return jax.lax.while_loop(round_cond, round_body, state)

    def narrow(r, used, tkeys, tkvalid, placed, unplaced, left):
        lanes = _compact_lanes(unplaced, W)
        nkeys = [(jnp.take(kd, lanes, mode="clip"),
                  jnp.take(kv, lanes, mode="clip")) for kd, kv in key_cols]
        r, used, tkeys, tkvalid, nplaced, _unplaced, left = probe(
            jnp.take(h, lanes, mode="clip"), nkeys, lanes.astype(jnp.int64),
            (r, used, tkeys, tkvalid, jnp.full(W, S, dtype=jnp.int64),
             jnp.arange(W, dtype=jnp.int32) < left, left), wide=False)
        placed = placed.at[lanes].set(nplaced, mode="drop")
        return r, used, tkeys, tkvalid, placed, left

    def settled(r, used, tkeys, tkvalid, placed, _unplaced, left):
        return r, used, tkeys, tkvalid, placed, left

    state = probe(
        h, key_cols, jnp.arange(n, dtype=jnp.int64),
        (jnp.int32(0), carry.used, tuple(carry.keys), tuple(carry.key_valid),
         jnp.full(n, S, dtype=jnp.int64),  # S == unplaced sentinel
         mask, jnp.sum(mask, dtype=jnp.int32)), wide=True)
    full_rounds, left = state[0], state[6]
    if W:
        # the rows still unplaced number W or fewer, unless the rounds
        # are spent; a batch that placed in its full rounds pays the
        # count and this branch
        r, used, tkeys, tkvalid, placed, overflow = jax.lax.cond(
            (left > 0) & (full_rounds < probe_rounds), narrow, settled,
            *state)
    else:
        r, used, tkeys, tkvalid, placed, overflow = settled(*state)
    rounds = jnp.stack([full_rounds, r - full_rounds])

    # the S sentinel of an unplaced row drops out of every scatter below
    new_accs, new_avalid = scatter_accumulate(
        placed, agg_specs, mask, carry.accs, carry.acc_valid)

    new_carry = ParentCarry(tkeys, tkvalid,
                             tuple(new_accs), tuple(new_avalid), used)
    keep_new = overflow == 0
    sel = jax.tree_util.tree_map(
        lambda nw, old: jnp.where(keep_new, nw, old), new_carry, carry)
    num_groups = jnp.sum(sel.used.astype(jnp.int32))
    return sel, overflow, num_groups, rounds

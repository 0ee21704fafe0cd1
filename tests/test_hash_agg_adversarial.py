"""Adversarial hash-agg table tests (VERDICT r4 weak #7).

The scatter-probe claim loop early-exits when every row places in a
round or two; these tests force the OTHER regimes:

  * load factor ~1.0 — long probe chains, probe_rounds exhaustion,
  * overflow atomicity — a failed batch must leave the table logically
    unchanged: the same used slots, and the same keys, null bits and
    accumulators at them,
  * the rehash/grow path — re-inserting a full table into a larger one
    must preserve every group and every accumulator exactly,
  * the production grow loop end-to-end against a pandas oracle.

All under jit, like the device path compiles them (ref: the reference's
agg table growth in agg/agg_table.rs is likewise exercised by its
fuzz tests).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.parallel.stage import (FREE, hash_agg_step,
                                      init_hash_carry, rehash_carry,
                                      rehash_width)
from tests.hash_step_parent import parent_init, parent_step


def _insert(carry, keys, vals, probe_rounds=16):
    n = keys.shape[0]
    step = jax.jit(lambda c, k, v, m: hash_agg_step(
        c, [(k, jnp.ones(n, bool))],
        [("sum", v, None), ("count", None, None)],
        m, probe_rounds=probe_rounds))
    return step(carry, keys, vals, jnp.ones(n, bool))


def _table_dict(carry):
    used = np.asarray(carry.used)
    keys = _keys(carry)[0][used]
    sums = np.asarray(carry.accs[0])[used]
    counts = np.asarray(carry.accs[1])[used]
    return {int(k): (float(s), int(c))
            for k, s, c in zip(keys, sums, counts)}


def _keys(carry):
    """The table's key columns, one numpy array a key, slot by slot."""
    return [np.asarray(k) for k in carry.key_columns]


def _assert_leaf_for_leaf(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_owner_is_settled(carry):
    """Between steps a slot's owner is FREE or `-1 - nullbits`: no claim
    outlives its step, and the count on the carry is the used slots'."""
    owner = np.asarray(carry.owner)
    assert owner.dtype == np.int32
    assert ((owner == FREE) | (owner < 0)).all()
    assert int(carry.groups) == int((owner < 0).sum())


def _assert_same_table(got, want):
    """What a step that overflowed owes its caller: the same used slots
    with the same null bits (the whole owner lane), and the same keys
    and accumulators at them.  Key data at a free slot is nobody's."""
    _assert_owner_is_settled(got)
    np.testing.assert_array_equal(np.asarray(got.owner),
                                  np.asarray(want.owner))
    assert int(got.groups) == int(want.groups)
    used = np.asarray(want.used)
    for a, b in zip(jax.tree_util.tree_leaves(
                        (got.keys, got.accs, got.acc_valid)),
                    jax.tree_util.tree_leaves(
                        (want.keys, want.accs, want.acc_valid))):
        np.testing.assert_array_equal(np.asarray(a)[used],
                                      np.asarray(b)[used])


def test_full_load_overflow_is_atomic():
    """64 slots, 80 distinct keys: placement MUST overflow; the returned
    table must be the one given (lossless retry contract)."""
    S = 64
    carry = init_hash_carry([jnp.int64], ["sum", "count"],
                            [jnp.float64, jnp.int64], S)
    keys = jnp.arange(80, dtype=jnp.int64)
    vals = jnp.ones(80, dtype=jnp.float64)
    out, overflow, _, _ = _insert(carry, keys, vals)
    assert int(overflow) > 0
    _assert_same_table(out, carry)


def test_probe_rounds_exhaustion_partial_chain():
    """probe_rounds=1 with distinct keys hashing anywhere: any collision
    in round 0 overflows — and the step still reports it losslessly."""
    S = 64
    carry = init_hash_carry([jnp.int64], ["sum", "count"],
                            [jnp.float64, jnp.int64], S)
    keys = jnp.arange(60, dtype=jnp.int64)
    vals = jnp.ones(60, dtype=jnp.float64)
    out, overflow, num_groups, _ = _insert(carry, keys, vals,
                                           probe_rounds=1)
    if int(overflow) == 0:  # statistically impossible at 60/64 in 1 round
        pytest.fail("60 keys into 64 slots placed in ONE probe round")
    # atomic: nothing was written
    assert not np.asarray(out.used).any()


def test_rehash_grow_preserves_every_group():
    """Fill a 128-slot table near capacity, grow to 512 via rehash_carry,
    keep inserting — final content must equal the pandas oracle."""
    rng = np.random.default_rng(7)
    all_keys = rng.integers(0, 200, 1024).astype(np.int64)
    all_vals = rng.random(1024)

    carry = init_hash_carry([jnp.int64], ["sum", "count"],
                            [jnp.float64, jnp.int64], 128)
    grown = False
    for lo in range(0, 1024, 256):
        k = jnp.asarray(all_keys[lo:lo + 256])
        v = jnp.asarray(all_vals[lo:lo + 256])
        out, overflow, _, _ = _insert(carry, k, v)
        if int(overflow) > 0:
            # production grow loop: rehash into 4x slots, retry batch
            carry, ovf2, _, _ = rehash_carry(carry, ["sum", "count"], 512)
            assert int(ovf2) == 0, "grow re-insert itself overflowed"
            grown = True
            out, overflow, _, _ = _insert(carry, k, v)
            assert int(overflow) == 0
        carry = out
    assert grown, "test never exercised the grow path (tune sizes)"

    got = _table_dict(carry)
    import pandas as pd
    want = pd.DataFrame({"k": all_keys, "v": all_vals}).groupby("k")["v"] \
        .agg(["sum", "count"])
    assert set(got) == set(want.index)
    for key, row in want.iterrows():
        s, c = got[int(key)]
        assert c == int(row["count"])
        np.testing.assert_allclose(s, row["sum"], rtol=1e-12)


def test_adversarial_same_slot_chain():
    """Keys engineered to collide: insert keys one batch at a time whose
    hashes all share low bits (found by sieving), forcing the max-length
    probe chain the early-exit skips in the common case."""
    from blaze_tpu.kernels import hashing as H
    S = 256
    # sieve int keys whose xxhash64 lands in ONE bucket of 256
    cand = np.arange(0, 400_000, dtype=np.int64)
    h = np.asarray(H.hash_columns(
        [(jnp.asarray(cand), jnp.ones(len(cand), bool), "int64")],
        seed=42, xp=jnp, algo="xxhash64")).astype(np.int64) & (S - 1)
    same = cand[h == 0][:24]  # 24 keys, one home slot: 24-long chain
    assert len(same) == 24, "sieve range too small"
    carry = init_hash_carry([jnp.int64], ["sum", "count"],
                            [jnp.float64, jnp.int64], S)
    keys = jnp.asarray(same)
    vals = jnp.ones(len(same), dtype=jnp.float64)
    out, overflow, num_groups, _ = _insert(carry, keys, vals,
                                           probe_rounds=32)
    assert int(overflow) == 0, "32 rounds must place a 24-chain"
    assert int(num_groups) == 24
    got = _table_dict(out)
    assert set(got) == {int(k) for k in same}
    assert all(c == 1 and s == 1.0 for s, c in got.values())

    # second insert of the SAME keys must unify, not duplicate
    out2, overflow2, num_groups2, _ = _insert(out, keys, vals,
                                              probe_rounds=32)
    assert int(overflow2) == 0
    assert int(num_groups2) == 24
    got2 = _table_dict(out2)
    assert all(c == 2 and s == 2.0 for s, c in got2.values())


# -- hostile data against a plain numpy/Python group-by ---------------------
# Keys are normalised as SQL grouping does (NULL = NULL, NaN = NaN
# whatever the payload, -0.0 = 0.0) and tables are compared by GROUP:
# where a group's slot is, is the table's business.

def _nan_payloads(n, rng):
    """float64 NaNs with DIFFERENT bit patterns: quiet, payload-bearing
    and negative-sign.  Grouping makes one group of them."""
    pats = np.array([0x7FF8000000000000, 0x7FF8000000000001,
                     0xFFF8000000000099], dtype=np.uint64)
    return pats[rng.integers(0, 3, n)].view(np.float64)


def _trial_key_col(rng, n, dtype):
    """(data, validity) of one hostile key column."""
    if dtype in (np.float64, np.float32):
        d = (rng.integers(0, 300, n) - 150).astype(dtype)
        zero = rng.random(n) < 0.08
        d = np.where(zero, np.where(rng.random(n) < 0.5, 0.0, -0.0
                                    ).astype(dtype), d)
        nan = rng.random(n) < 0.08
        if dtype == np.float64:
            d = np.where(nan, _nan_payloads(n, rng), d)
        else:
            d = np.where(nan, np.float32(np.nan), d)
    else:
        d = rng.integers(-1000, 1000, n).astype(dtype)
    v = rng.random(n) > 0.15  # SQL NULL keys: still group together
    return d, v


def _group_key(key_cols, i):
    out = []
    for d, v in key_cols:
        if not v[i]:
            out.append(None)
        elif d.dtype.kind == "f" and np.isnan(d[i]):
            out.append("nan")
        elif d.dtype.kind == "f":
            out.append(float(d[i]) + 0.0)  # -0.0 + 0.0 == 0.0
        else:
            out.append(int(d[i]))
    return tuple(out)


_REDUCE = {"sum": sum, "min": min, "max": max}


def _reference(key_cols, specs, mask):
    """{group: [(value, valid) per accumulator]} over the rows `mask`
    selects.  count counts the rows whose argument is valid and is
    itself always valid; the others are valid once one valid argument
    arrived."""
    rows: dict = {}
    for i in np.flatnonzero(mask):
        rows.setdefault(_group_key(key_cols, i), []).append(i)
    out = {}
    for g, idx in rows.items():
        accs = []
        for kind, vd, vv in specs:
            ok = [i for i in idx if vv is None or vv[i]]
            if kind == "count":
                accs.append((len(ok), True))
            elif ok:
                accs.append((_REDUCE[kind](float(vd[i]) for i in ok), True))
            else:
                accs.append((None, False))
        out[g] = accs
    return out


def _table_groups(carry):
    slots = np.flatnonzero(np.asarray(carry.used))
    key_cols = [(np.asarray(k), np.asarray(v))
                for k, v in zip(_keys(carry), carry.key_valid)]
    accs = [(np.asarray(a), np.asarray(v))
            for a, v in zip(carry.accs, carry.acc_valid)]
    got = {}
    for s in slots:
        g = _group_key(key_cols, s)
        assert g not in got, f"group {g} sits in two slots"
        got[g] = [((a[s].item() if v[s] else None), bool(v[s]))
                  for a, v in accs]
    return got


def _assert_same_groups(got, want):
    assert set(got) == set(want)
    for g, accs in want.items():
        for (gv, gok), (wv, wok) in zip(got[g], accs):
            assert gok == wok, (g, got[g], accs)
            if wok:
                np.testing.assert_allclose(gv, wv, rtol=1e-12, atol=1e-12)


_KINDS = ["sum", "min", "max", "count"]
_ACC_DTYPES = (jnp.float64, jnp.float64, jnp.float64, jnp.int64)


@jax.jit
def _step_all_kinds(carry, key_cols, vals, av, mask):
    """Every kind over one argument column, under jit as the engine
    runs it."""
    return hash_agg_step(carry, key_cols, [(k, vals, av) for k in _KINDS],
                         mask)


def _hostile_step(rng, key_dtypes, n=1024, slots=1 << 11):
    """One step over hostile keys with NULL arguments and masked rows:
    (carry, overflow, num_groups, reference)."""
    key_cols = [_trial_key_col(rng, n, dt) for dt in key_dtypes]
    vals = rng.random(n)
    av = rng.random(n) > 0.2
    mask = rng.random(n) > 0.25
    specs = [(k, vals, av) for k in _KINDS]
    carry = init_hash_carry([jnp.dtype(dt) for dt in key_dtypes], _KINDS,
                            _ACC_DTYPES, slots)
    out, overflow, num_groups, _ = _step_all_kinds(
        carry, [(jnp.asarray(d), jnp.asarray(v)) for d, v in key_cols],
        jnp.asarray(vals), jnp.asarray(av), jnp.asarray(mask))
    return out, int(overflow), int(num_groups), \
        _reference(key_cols, specs, mask)


@pytest.mark.parametrize("key_dtypes", [
    [np.int64], [np.int32], [np.float64], [np.float32],
    [np.int64, np.float64]],
    ids=["int64", "int32", "float64", "float32", "int64-float64"])
def test_hash_step_matches_reference(key_dtypes):
    for seed in range(3):
        out, overflow, num_groups, want = _hostile_step(
            np.random.default_rng(seed), key_dtypes)
        assert overflow == 0 and num_groups == len(want)
        _assert_same_groups(_table_groups(out), want)


@pytest.mark.parametrize("case", ["nulls", "invalid_accs"])
def test_rehash_matches_reference(case):
    """A table grown x4 holds the same groups and accumulators: with
    NULL keys among them, and with accumulators that never saw a valid
    argument (they must stay invalid, not turn into the identity)."""
    n, S = 1024, 1 << 10
    rng = np.random.default_rng(21)
    kd = rng.integers(0, 400, n).astype(np.int64)
    if case == "nulls":
        kv = rng.random(n) > 0.1
        av = rng.random(n) > 0.1
    else:
        kv = np.ones(n, bool)
        av = kd % 3 != 0  # every third group: no valid argument, ever
    vals = rng.random(n)
    mask = np.ones(n, bool)
    specs = [(k, vals, av) for k in _KINDS]
    seeded, overflow, _, _ = _step_all_kinds(
        init_hash_carry([jnp.int64], _KINDS, _ACC_DTYPES, S),
        [(jnp.asarray(kd), jnp.asarray(kv))], jnp.asarray(vals),
        jnp.asarray(av), jnp.asarray(mask))
    assert int(overflow) == 0
    want = _reference([(kd, kv)], specs, mask)
    if case == "invalid_accs":
        assert any(not ok for accs in want.values() for _v, ok in accs)
    grown, overflow, num_groups, _ = jax.jit(
        lambda c: rehash_carry(c, _KINDS, 4 * S))(seeded)
    assert int(overflow) == 0 and int(num_groups) == len(want)
    assert grown.used.shape[0] == 4 * S
    _assert_same_groups(_table_groups(grown), want)


@pytest.mark.parametrize("held", [0, 32], ids=["empty", "half_full"])
def test_overflow_returns_the_original_carry(held):
    """The atomic contract where the table already holds groups: a batch
    that cannot place leaves the table as it was."""
    S = 64
    rng = np.random.default_rng(9)

    def batch(keys):
        n = len(keys)
        kv = jnp.asarray(rng.random(n) > 0.1)
        return ([(jnp.asarray(keys.astype(np.float64)), kv)],
                jnp.asarray(rng.random(n)),
                jnp.asarray(rng.random(n) > 0.2), jnp.ones(n, bool))

    carry = init_hash_carry([jnp.float64], _KINDS, _ACC_DTYPES, S)
    if held:
        carry, overflow, groups, _ = _step_all_kinds(
            carry, *batch(np.arange(held)))
        # NULL keys share one group
        assert int(overflow) == 0 and held // 2 < int(groups) <= held
    out, overflow, groups, _ = _step_all_kinds(
        carry, *batch(np.arange(1000, 1080)))
    assert int(overflow) > 0
    assert int(groups) == int(jnp.sum(carry.used))
    _assert_same_table(out, carry)


# -- the probe's two widths against a model of its rounds -------------------
# hash_agg_step probes at full width while more rows are unplaced than
# the narrow width holds (round one always), then compacts the rest and
# probes them at narrow width.  The model below knows rounds and nothing
# of widths beyond counting them: a Python loop over the unplaced rows in
# row order, so "the lowest row index claims" is the order of the loop.
# The table must equal the model's SLOT FOR SLOT: the narrow rounds may
# not move a single group.

from blaze_tpu.kernels import hashing as H  # noqa: E402
from blaze_tpu.parallel.stage import narrow_width  # noqa: E402


def _normalised(key_cols):
    out = []
    for d, v in key_cols:
        if d.dtype.kind == "f":
            d = np.where(d == 0, np.abs(d), d)
            d = np.where(np.isnan(d), np.nan, d)
        out.append((d, v))
    return out


def _home_slots(key_cols, slots):
    cols = [(jnp.asarray(d), jnp.asarray(v), str(d.dtype))
            for d, v in _normalised(key_cols)]
    h = H.hash_columns(cols, seed=42, xp=jnp, algo="xxhash64")
    return np.asarray(h).astype(np.int64) & (slots - 1)


class _ModelTable:
    """The owner lane is the model's too: FREE, or `-1 - nullbits` of the
    group a slot holds (bit i: key i is NULL).  `used` and the keys'
    validity are read from it, as the engine's consumers read them."""

    def __init__(self, key_dtypes, slots):
        self.owner = np.full(slots, FREE, np.int32)
        self.keys = [np.zeros(slots, dt) for dt in key_dtypes]
        self.sums = np.zeros(slots)
        self.counts = np.zeros(slots, np.int64)

    @property
    def used(self):
        return self.owner < 0

    @property
    def valid(self):
        return [((-1 - self.owner) >> i) & 1 == 0
                for i in range(len(self.keys))]

    def copy(self):
        t = _ModelTable([], len(self.owner))
        t.owner = self.owner.copy()
        t.keys = [k.copy() for k in self.keys]
        t.sums, t.counts = self.sums.copy(), self.counts.copy()
        return t

    def _holds(self, s, key_cols, i):
        for tk, tv, (d, v) in zip(self.keys, self.valid, key_cols):
            if tv[s] != v[i]:
                return False
            if v[i] and not (tk[s] == d[i]
                             or (d.dtype.kind == "f" and np.isnan(tk[s])
                                 and np.isnan(d[i]))):
                return False
        return True

    def insert(self, key_cols, vals, counts, mask, probe_rounds=16):
        """(table after, overflow, [full rounds, narrow rounds]); the
        table as it was when a row is left over."""
        t = self.copy()
        S, n = len(t.owner), len(mask)
        key_cols = _normalised(key_cols)
        h = _home_slots(key_cols, S)
        W = narrow_width(n)
        unplaced = [int(i) for i in np.flatnonzero(mask)]
        placed = {}
        rounds = [0, 0]
        r = 0
        while r < probe_rounds and unplaced:
            wide = rounds[1] == 0 and (r == 0 or len(unplaced) > W)
            rounds[0 if wide else 1] += 1
            for i in unplaced:           # ascending: the lowest row claims
                s = (h[i] + r) % S
                if t.owner[s] == FREE:
                    t.owner[s] = -1 - sum(
                        (not v[i]) << c for c, (_d, v) in enumerate(key_cols))
                    for tk, (d, _v) in zip(t.keys, key_cols):
                        tk[s] = d[i]
            still = []
            for i in unplaced:
                s = (h[i] + r) % S
                if t._holds(s, key_cols, i):
                    placed[i] = s
                else:
                    still.append(i)
            unplaced = still
            r += 1
        if unplaced:
            return self, len(unplaced), rounds
        for i in sorted(placed):
            t.sums[placed[i]] += vals[i]
            t.counts[placed[i]] += counts[i]
        return t, 0, rounds


def _assert_slot_for_slot(carry, model):
    _assert_owner_is_settled(carry)
    np.testing.assert_array_equal(np.asarray(carry.owner), model.owner)
    used = np.asarray(carry.used)
    np.testing.assert_array_equal(used, model.used)
    for k, v, mk, mv in zip(_keys(carry), carry.key_valid, model.keys,
                            model.valid):
        np.testing.assert_array_equal(np.asarray(v)[used], mv[used])
        np.testing.assert_array_equal(np.asarray(k)[used], mk[used])
    np.testing.assert_array_equal(np.asarray(carry.accs[1])[used],
                                  model.counts[used])
    np.testing.assert_allclose(np.asarray(carry.accs[0])[used],
                               model.sums[used], rtol=1e-12)


@jax.jit
def _step_sum_count(carry, key_cols, vals, mask):
    return hash_agg_step(carry, key_cols,
                         [("sum", vals, None), ("count", None, None)], mask)


def _fresh(key_dtypes, slots):
    return init_hash_carry([jnp.dtype(dt) for dt in key_dtypes],
                           ["sum", "count"], [jnp.float64, jnp.int64], slots)


def _step_and_model(carry, model, key_cols, vals, mask):
    out, overflow, groups, rounds = _step_sum_count(
        carry, [(jnp.asarray(d), jnp.asarray(v)) for d, v in key_cols],
        jnp.asarray(vals), jnp.asarray(mask))
    after, m_overflow, m_rounds = model.insert(
        key_cols, vals, np.ones(len(mask), np.int64), mask)
    assert int(overflow) == m_overflow
    assert np.asarray(rounds).tolist() == m_rounds
    assert int(groups) == int(after.used.sum())
    _assert_slot_for_slot(out, after)
    return out, after, m_overflow, m_rounds


class _Sieve:
    """int64 keys by their home slot in a table of `slots`."""

    def __init__(self, slots, candidates=3_000_000):
        cand = np.arange(1, candidates, dtype=np.int64) * 1000003 + 17
        home = _home_slots([(cand, np.ones(len(cand), bool))], slots)
        order = np.argsort(home, kind="stable")
        self._cand = cand[order]
        self._start = np.searchsorted(home[order], np.arange(slots + 1))

    def at(self, slot, k):
        lo, hi = self._start[slot], self._start[slot + 1]
        assert hi - lo >= k, "sieve range too small"
        return self._cand[lo:lo + k]


@pytest.fixture(scope="module")
def sieve():
    made = {}

    def get(slots):
        if slots not in made:
            made[slots] = _Sieve(slots)
        return made[slots]
    return get


_LANES = 4096                      # narrow width 512
_WIDTH_CASES = {
    # name: (lanes, slots, [(keys sharing a home slot, how many homes)],
    #        [full rounds, narrow rounds], rows left over)
    "one_round": (_LANES, 1 << 14, [(1, 4096)], [1, 0], 0),
    "fewer_than_width": (_LANES, 1 << 14, [(2, 100), (1, 3896)], [1, 1], 0),
    "exactly_width": (_LANES, 1 << 14, [(2, 512), (1, 3072)], [1, 1], 0),
    "width_plus_one": (_LANES, 1 << 14, [(2, 513), (1, 3070)], [2, 0], 0),
    "wide_then_narrow": (_LANES, 1 << 14, [(2, 600), (3, 300), (1, 1996)],
                         [2, 1], 0),
    "never_under_width": (_LANES, 1 << 12, [(600, 1)], [16, 0], 584),
    "chain_overflows_narrow": (_LANES, 1 << 14, [(1, 4056), (40, 1)],
                               [1, 15], 24),
    "lane_floor": (2048, 1 << 13, [(1, 2038), (10, 1)], [10, 0], 0),
}


def _engineered(sieve, lanes, slots, groups, rng):
    """Distinct keys laid out by home slot: `k` keys on each of `homes`
    home slots, homes four apart so that a loser's next slots are free;
    rows shuffled, lanes beyond the keys masked out."""
    keys, home = [], 0
    for k, homes in groups:
        for _ in range(homes):
            keys.extend(sieve(slots).at(home, k))
            home += 4
    assert home <= slots and len(keys) <= lanes
    kd = np.zeros(lanes, np.int64)
    mask = np.zeros(lanes, bool)
    rows = rng.permutation(lanes)[:len(keys)]
    kd[rows], mask[rows] = keys, True
    return kd, mask


@pytest.mark.parametrize("case", list(_WIDTH_CASES))
def test_step_matches_round_model(case, sieve):
    lanes, slots, groups, want_rounds, want_left = _WIDTH_CASES[case]
    assert narrow_width(lanes) == (512 if lanes == _LANES else 0)
    rng = np.random.default_rng(len(case))
    carry, model = _fresh([np.int64], slots), _ModelTable([np.int64], slots)
    # the table already holds groups: a step that overflows must hand
    # exactly these back
    held = sieve(slots).at(slots - 3, 3)   # fills the last three slots
    hd = np.zeros(lanes, np.int64)
    hd[:3] = held
    carry, model, overflow, _ = _step_and_model(
        carry, model, [(hd, np.ones(lanes, bool))], rng.random(lanes),
        np.arange(lanes) < 3)
    assert overflow == 0
    kd, mask = _engineered(sieve, lanes, slots, groups, rng)
    out, after, overflow, rounds = _step_and_model(
        carry, model, [(kd, np.ones(lanes, bool))], rng.random(lanes), mask)
    assert rounds == want_rounds and overflow == want_left
    if want_left:
        _assert_same_table(out, carry)


def test_null_nan_and_negative_zero_keys_place_in_the_narrow_rounds():
    """The home slots of the NULL, the NaN and the zero key are taken by
    other keys first, so every such row loses round one and is placed by
    a narrow round: under whatever NaN payload, zero sign or data a NULL
    row carries, one group each, in the model's slot."""
    lanes, slots = _LANES, 1 << 12
    rng = np.random.default_rng(29)
    special = [(np.array([np.nan]), np.array([True])),
               (np.array([0.0]), np.array([True])),
               (np.array([7.0]), np.array([False]))]
    homes = [int(_home_slots([kc], slots)[0]) for kc in special]
    assert len(set(homes)) == 3
    cand = np.arange(1.0, 200_000.0)
    cand_home = _home_slots([(cand, np.ones(len(cand), bool))], slots)
    blockers = np.array([cand[cand_home == hm][0] for hm in homes])
    bd = np.zeros(lanes)
    bd[:3] = blockers
    carry, model, overflow, rounds = _step_and_model(
        _fresh([np.float64], slots), _ModelTable([np.float64], slots),
        [(bd, np.ones(lanes, bool))], rng.random(lanes),
        np.arange(lanes) < 3)
    assert overflow == 0 and rounds == [1, 0]

    kd = 1e6 + rng.permutation(lanes).astype(np.float64)  # distinct filler
    kv = np.ones(lanes, bool)
    rows = rng.permutation(lanes)
    kd[rows[:100]] = _nan_payloads(100, rng)
    kd[rows[100:200]] = np.where(rng.random(100) < 0.5, 0.0, -0.0)
    kv[rows[200:300]] = False            # NULL keys over arbitrary data
    mask = rng.random(lanes) > 0.6       # few enough for the load
    vals = rng.random(lanes)
    out, after, overflow, rounds = _step_and_model(
        carry, model, [(kd, kv)], vals, mask)
    assert overflow == 0 and rounds[0] == 1 and rounds[1] >= 1
    for hm in homes:                     # pushed off their home slots
        assert _keys(out)[0][hm] in blockers
    specs = [("sum", vals, None), ("count", None, None)]
    want = _reference([(kd, kv)], specs, mask)
    for b in blockers:
        want[(float(b),)] = None
    got = _table_groups(out)
    assert set(got) == set(want)
    for g, accs in want.items():
        if accs is not None:
            assert got[g][1] == accs[1]
            np.testing.assert_allclose(got[g][0][0], accs[0][0], rtol=1e-12)


@pytest.mark.parametrize("lanes", [None, 2048],
                         ids=["old_slots", "compacted"])
def test_rehash_of_more_used_slots_than_the_narrow_width(lanes):
    """rehash_carry probes the OLD table's slots as lanes: 8,192 lanes,
    narrow width 1,024, about 2,000 of them used.  Compacted to 2,048
    lanes (which have no narrow phase) it runs the same rounds, all at
    full width, and leaves the same table."""
    old_slots = 1 << 13
    rng = np.random.default_rng(31)
    kd = rng.integers(0, 2000, old_slots).astype(np.int64) * 1000003 + 17
    ones = np.ones(old_slots, bool)
    old, old_model, overflow, _ = _step_and_model(
        _fresh([np.int64], old_slots), _ModelTable([np.int64], old_slots),
        [(kd, ones)], rng.random(old_slots), ones)
    assert overflow == 0
    assert narrow_width(old_slots) < int(old_model.used.sum()) <= 2048
    grown, overflow, groups, rounds = jax.jit(
        lambda c: rehash_carry(c, ["sum", "count"], 4 * old_slots,
                               lanes))(old)
    want, m_overflow, m_rounds = _rehashed_model(old_model, 4 * old_slots)
    assert int(overflow) == m_overflow == 0
    assert m_rounds[1] >= 1
    assert np.asarray(rounds).tolist() == (
        m_rounds if lanes is None else [sum(m_rounds), 0])
    assert int(groups) == int(old_model.used.sum())
    _assert_slot_for_slot(grown, want)


# -- the compacted rehash against the uncompacted one ------------------------
# With `lanes` given, rehash_carry compacts the old table's used slots to
# the front of that many lanes, in slot order, and re-inserts those.  The
# lowest lane wins a contested slot at either width, so the table must be
# the uncompacted rehash's leaf for leaf, unused slots included, and the
# round model's slot for slot.

def _rehashed_model(old_model, new_slots, probe_rounds=16):
    return _ModelTable([k.dtype for k in old_model.keys], new_slots).insert(
        [(k, v) for k, v in zip(old_model.keys, old_model.valid)],
        old_model.sums, old_model.counts, old_model.used, probe_rounds)


def _rehash_both_ways(old, kinds, new_slots, lanes, probe_rounds=16):
    """(uncompacted, compacted) results of rehash_carry, under jit; they
    have to agree on everything but the split of the rounds."""
    plain, compact = (
        jax.jit(lambda c, w=w: rehash_carry(c, kinds, new_slots, w,
                                            probe_rounds))(old)
        for w in (None, lanes))
    assert int(plain[1]) == int(compact[1])          # overflow
    assert int(plain[2]) == int(compact[2])          # groups
    assert int(jnp.sum(plain[3])) == int(jnp.sum(compact[3]))
    _assert_leaf_for_leaf(compact[0], plain[0])
    return plain, compact


_OLD_SLOTS, _REHASH_LANES = 1 << 13, 2048


def _table_of_distinct_keys(live, rng):
    """(carry, model) of an 8,192-slot table holding `live` groups
    wherever their hashes put them."""
    lanes = max(live, 16)
    kd = (rng.permutation(1 << 20)[:lanes].astype(np.int64) * 1000003) + 17
    return _step_and_model(
        _fresh([np.int64], _OLD_SLOTS), _ModelTable([np.int64], _OLD_SLOTS),
        [(kd, np.ones(lanes, bool))], rng.random(lanes),
        np.arange(lanes) < live)[:2]


def _table_used_at_its_end(live, sieve, rng):
    """The same with the used slots one contiguous run at the table's
    end: every lane beyond the live ones then gathers a LIVE slot (the
    padding position clips to the last slot) and only the mask keeps it
    out."""
    kd = np.concatenate([sieve(_OLD_SLOTS).at(s, 1)
                         for s in range(_OLD_SLOTS - live, _OLD_SLOTS)])
    kd = kd[rng.permutation(live)]
    old, model = _step_and_model(
        _fresh([np.int64], _OLD_SLOTS), _ModelTable([np.int64], _OLD_SLOTS),
        [(kd, np.ones(live, bool))], rng.random(live),
        np.ones(live, bool))[:2]
    assert np.flatnonzero(model.used).tolist() == list(
        range(_OLD_SLOTS - live, _OLD_SLOTS))
    return old, model


@pytest.mark.parametrize("live", [0, 1, _REHASH_LANES - 1, _REHASH_LANES,
                                  "run_at_the_end"])
def test_compacted_rehash_is_the_uncompacted_one_slot_for_slot(live, sieve):
    rng = np.random.default_rng(41)
    if live == "run_at_the_end":
        old, old_model = _table_used_at_its_end(1500, sieve, rng)
    else:
        old, old_model = _table_of_distinct_keys(live, rng)
        assert int(old_model.used.sum()) == live
    assert rehash_width(int(old_model.used.sum()), _OLD_SLOTS) \
        == _REHASH_LANES
    plain, compact = _rehash_both_ways(old, ["sum", "count"],
                                       4 * _OLD_SLOTS, _REHASH_LANES)
    want, m_overflow, _rounds = _rehashed_model(old_model, 4 * _OLD_SLOTS)
    assert int(compact[1]) == m_overflow == 0
    assert int(compact[2]) == int(old_model.used.sum())
    _assert_slot_for_slot(compact[0], want)
    _assert_slot_for_slot(plain[0], want)


@pytest.mark.parametrize("key_dtypes", [[np.float64], [np.int64, np.float64]],
                         ids=["float64", "int64-float64"])
def test_compacted_rehash_keeps_hostile_keys_and_every_kind(key_dtypes):
    """NULL keys, NaN keys of several payloads and both zeros; min, max
    and a count that re-merges as a sum; accumulators that never saw a
    valid argument stay invalid."""
    rng = np.random.default_rng(43)
    old, overflow, groups, want = _hostile_step(
        rng, key_dtypes, n=_REHASH_LANES, slots=_OLD_SLOTS)
    assert overflow == 0 and 100 < groups <= _REHASH_LANES
    assert any(None in g for g in want) and any("nan" in g for g in want)
    assert any(not ok for accs in want.values() for _v, ok in accs)
    plain, compact = _rehash_both_ways(old, _KINDS, 4 * _OLD_SLOTS,
                                       _REHASH_LANES)
    assert int(compact[1]) == 0 and int(compact[2]) == groups
    _assert_same_groups(_table_groups(compact[0]), want)
    _assert_same_groups(_table_groups(plain[0]), want)


def test_a_rehash_that_overflows_leaves_both_tables_as_they_were(sieve):
    """24 groups that share ONE home slot in the new table cannot place
    in 16 rounds: compacted or not, the rehash reports the eight left
    over and hands back the new table with nothing in it, and the old
    carry, which the caller keeps, is what it was."""
    new_slots = 4 * _OLD_SLOTS
    kd = sieve(new_slots).at(77, 24)
    # they share their home in the old table too: rounds enough for it
    old, overflow, _, _ = _insert(
        _fresh([np.int64], _OLD_SLOTS), jnp.asarray(kd), jnp.ones(24),
        probe_rounds=32)
    assert int(overflow) == 0
    before = jax.tree_util.tree_map(np.asarray, old)
    plain, compact = _rehash_both_ways(old, ["sum", "count"], new_slots,
                                       _REHASH_LANES)
    assert int(compact[1]) == 8 and int(compact[2]) == 0
    _assert_same_table(compact[0], _fresh([np.int64], new_slots))
    _assert_leaf_for_leaf(old, before)
    # and with rounds enough the same groups place, the same way
    plain, compact = _rehash_both_ways(old, ["sum", "count"], new_slots,
                                       _REHASH_LANES, probe_rounds=32)
    assert int(compact[1]) == 0 and int(compact[2]) == 24


@pytest.mark.parametrize("groups, old_slots, want", [
    (0, 1 << 13, 2048), (1, 1 << 13, 2048), (2048, 1 << 13, 2048),
    (2049, 1 << 13, 4096), (5000, 1 << 12, 1 << 12), (100, 1024, 1024),
    # a reduce task at scale factor 100: 2^21 slots at its third chunk
    (503_312, 1 << 21, 1 << 19), (524_288, 1 << 21, 1 << 19),
    (524_289, 1 << 21, 1 << 20)])
def test_rehash_width_is_the_power_of_two_that_holds_the_groups(
        groups, old_slots, want):
    got = rehash_width(groups, old_slots)
    assert got == want and (groups <= got or got == old_slots)


@pytest.mark.parametrize("lanes", [None, _OLD_SLOTS, 2 * _OLD_SLOTS])
def test_without_fewer_lanes_the_rehash_is_the_step_over_the_old_slots(
        lanes):
    """No `lanes`, or as many as the table has: the program is
    hash_agg_step with the old table's slots as the batch, nothing
    more."""
    old = _fresh([np.int64], _OLD_SLOTS)

    def as_before(c):
        # `used` and `key_valid` are read from the owner lane and the
        # key columns from their lanes, over the old slots
        fresh = _fresh([np.int64], 4 * _OLD_SLOTS)
        mask = c.used
        specs = [("sum", c.accs[0], c.acc_valid[0]),
                 ("sum", c.accs[1], c.acc_valid[1])]
        return hash_agg_step(fresh, list(zip(c.key_columns, c.key_valid)),
                             specs, mask, 16)

    def now(c):
        return rehash_carry(c, ["sum", "count"], 4 * _OLD_SLOTS, lanes)

    assert jax.jit(now).lower(old).as_text() \
        == jax.jit(as_before).lower(old).as_text().replace(
            "as_before", "now")


def test_a_second_call_at_the_same_shapes_builds_no_program(sieve):
    """Which rounds run, and at which width, is decided on the device:
    a batch that narrows and one that does not are ONE program."""
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.plan.fused import _hash_step_jit
    step = _hash_step_jit(("sum",))
    slots = 1 << 14
    rng = np.random.default_rng(37)

    def run(case):
        kd, mask = _engineered(sieve, _LANES, slots, _WIDTH_CASES[case][2],
                               rng)
        ones = jnp.ones(_LANES, bool)
        carry = init_hash_carry([jnp.int64], ["sum"], [jnp.float64], slots)
        _c, overflow, _g, rounds = step(
            carry, (jnp.asarray(kd),), (ones,),
            (jnp.asarray(rng.random(_LANES)),), (ones,), jnp.asarray(mask))
        return np.asarray(rounds).tolist()

    assert run("one_round") == [1, 0]
    before = xla_stats.snapshot()
    assert run("wide_then_narrow") == [2, 1]
    assert run("chain_overflows_narrow") == [1, 15]
    assert run("one_round") == [1, 0]
    d = xla_stats.delta(before)
    assert d["total_compiles"] == 0 and d["backend_compiles"] == 0


def test_stage_loop_round_counters_add_up_to_the_models_rounds(tmp_path,
                                                               monkeypatch):
    """Through the stage loop: the rounds a task's fold ran at each
    width reach `xla_stats` with the scalars the fold returns anyway, and
    are the model's, batch by batch."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu import config
    from blaze_tpu.bridge import xla_stats
    from blaze_tpu.memory import MemManager
    from blaze_tpu.plan.column_pruning import prune_columns
    from blaze_tpu.bridge.metrics import MetricNode
    from blaze_tpu.plan.explain import QueryProfile
    from blaze_tpu.plan.fused import fuse_plan
    from blaze_tpu.plan.planner import collapse_filter_project, create_plan
    from blaze_tpu.runtime import loop as device_loop

    batch, batches = _LANES, 4
    rng = np.random.default_rng(41)
    keys = rng.permutation(batch * batches).astype(np.int64) * 1000003 + 17
    vals = rng.random(len(keys))
    path = str(tmp_path / "rounds.parquet")
    pq.write_table(pa.table({"k": pa.array(keys), "v": pa.array(vals)}),
                   path, row_group_size=batch)
    schema = {"fields": [
        {"name": "k", "type": {"id": "int64"}, "nullable": True},
        {"name": "v", "type": {"id": "float64"}, "nullable": True}]}
    plan = {"kind": "hash_agg",
            "groupings": [{"expr": {"kind": "column", "index": 0},
                           "name": "k"}],
            "aggs": [{"fn": "sum", "mode": "final", "name": "s",
                      "args": [{"kind": "column", "index": 1}]}],
            "input": {"kind": "parquet_scan", "schema": schema,
                      "file_groups": [[path]]}}
    settings = {config.STAGE_DEVICE_LOOP_ENABLE.key: "on",
                config.ON_DEVICE_AGG_CAPACITY.key: 16,
                config.BATCH_SIZE.key: batch,
                config.STAGE_DEVICE_LOOP_CHUNK.key: batches,
                config.FUSED_HOST_VECTORIZED_ENABLE.key: False}
    MemManager.init(4 << 30)
    folded_into = []                 # the table's slots at every fold call
    real_factory = device_loop._fold_factory

    def factory(*a, **k):
        fold = real_factory(*a, **k)

        def spy(carry, *rest):
            folded_into.append(int(carry.owner.shape[0]))
            return fold(carry, *rest)
        return spy

    monkeypatch.setattr(device_loop, "_fold_factory", factory)
    for k, v in settings.items():
        config.conf.set(k, v)
    try:
        fused = fuse_plan(prune_columns(collapse_filter_project(
            create_plan(plan))))
        before = xla_stats.snapshot()
        rows = sum(b.compact().to_arrow().num_rows
                   for b in fused.execute(0))
        d = xla_stats.delta(before)
    finally:
        for k in settings:
            config.conf.unset(k)
    assert rows == len(keys)
    assert d["stage_loop_tasks"] == 1 and d["stage_loop_fallbacks"] == 0
    assert d["stage_loop_calls"] == 1 and d["stage_loop_batches"] == batches
    # one chunk: the table is sized once, for all of it
    slots = device_loop._slots_for(len(keys), 16)
    assert folded_into == [slots]
    model = _ModelTable([np.int64], slots)
    ones = np.ones(batch, bool)
    want = [0, 0]
    for b in range(batches):
        lo = b * batch
        model, overflow, rounds = model.insert(
            [(keys[lo:lo + batch], ones)], vals[lo:lo + batch],
            np.ones(batch, np.int64), ones)
        assert overflow == 0
        want = [want[0] + rounds[0], want[1] + rounds[1]]
    assert want[1] > 0, "no batch narrowed (tune the sizes)"
    assert [d["stage_loop_full_rounds"],
            d["stage_loop_narrow_rounds"]] == want
    footer = QueryProfile("q", 0, MetricNode("root"), 1, "local",
                          xla=d).render_text()
    assert f"probe_rounds={want[0]}+{want[1]}narrow" in footer


# -- the owner lane (PR 47) --------------------------------------------------
# One int32 lane says what a slot holds: FREE, or `-1 - nullbits` of its
# group.  Inside a step it also holds claims; none may outlive the step,
# whether the step placed its rows or overflowed and took them back.

def test_owner_is_free_or_negative_after_every_step_of_a_long_stream():
    """200 steps of hostile two-column keys (NULLs in both) into a table
    that starts at 256 slots: steps overflow, the table is regrown as the
    engine regrows it and the batch given again.  After EVERY step, kept
    or undone, the owner lane is settled; at the end the table is a plain
    group-by of the stream."""
    lanes, slots = 64, 256
    rng = np.random.default_rng(47)
    carry = init_hash_carry([jnp.int64, jnp.int32], _KINDS, _ACC_DTYPES,
                            slots)
    seen_k0, seen_v0, seen_k1, seen_v1 = [], [], [], []
    seen_vals, seen_av, seen_mask = [], [], []
    undone = 0
    for step in range(200):
        span = 4 + 2 * step              # the key domain widens: new groups
        k0 = rng.integers(0, span, lanes).astype(np.int64) * 1000003
        k1 = rng.integers(0, 3, lanes).astype(np.int32)
        v0, v1 = rng.random(lanes) > 0.1, rng.random(lanes) > 0.1
        vals, av = rng.random(lanes), rng.random(lanes) > 0.2
        mask = rng.random(lanes) > 0.2
        args = ([(jnp.asarray(k0), jnp.asarray(v0)),
                 (jnp.asarray(k1), jnp.asarray(v1))],
                jnp.asarray(vals), jnp.asarray(av), jnp.asarray(mask))
        out, overflow, groups, _ = _step_all_kinds(carry, *args)
        _assert_owner_is_settled(out)
        while int(overflow) > 0:
            undone += 1
            _assert_same_table(out, carry)
            slots *= 2
            grown, re_overflow, _, _ = jax.jit(
                lambda c, s=slots: rehash_carry(c, _KINDS, s))(carry)
            _assert_owner_is_settled(grown)
            if int(re_overflow) > 0:
                continue
            carry = grown
            out, overflow, groups, _ = _step_all_kinds(carry, *args)
            _assert_owner_is_settled(out)
        carry = out
        for acc, new in zip((seen_k0, seen_v0, seen_k1, seen_v1, seen_vals,
                             seen_av, seen_mask),
                            (k0, v0, k1, v1, vals, av, mask)):
            acc.append(new)
    assert undone >= 3, "the stream never overflowed (tune the sizes)"
    cat = np.concatenate
    key_cols = [(cat(seen_k0), cat(seen_v0)), (cat(seen_k1), cat(seen_v1))]
    want = _reference(key_cols, [(k, cat(seen_vals), cat(seen_av))
                                 for k in _KINDS], cat(seen_mask))
    assert int(carry.groups) == len(want)
    _assert_same_groups(_table_groups(carry), want)


_OVERFLOW_CASES = {
    # name: (groups by home slot, [full rounds, narrow rounds], rows left)
    # forty homes of thirty keys each, four slots apart: their chains run
    # into one another, 1,035 rows are still unplaced after sixteen
    # rounds, more than the narrow width holds, so every round ran at
    # full width
    "in_the_full_rounds": ([(30, 40), (1, 2000)], [16, 0], 1035),
    # one chain of forty: the other rows place in round one, the chain
    # is compacted and spends the fifteen rounds left at narrow width
    "in_the_narrow_rounds": ([(1, 4056), (40, 1)], [1, 15], 24),
}


@pytest.mark.parametrize("case", list(_OVERFLOW_CASES))
def test_an_undone_step_regrown_and_given_again_is_a_numpy_group_by(
        case, sieve):
    """The host's regrow-and-resume over the step's undo: a batch that
    overflows (at either width) hands back the table it was given; that
    table, re-inserted into a larger one by rehash_carry and given the
    SAME batch again, holds exactly what a group-by of every row holds."""
    groups, want_rounds, want_left = _OVERFLOW_CASES[case]
    lanes, slots = _LANES, 1 << 14
    rng = np.random.default_rng(len(case))
    ones = np.ones(lanes, bool)
    # the table already holds 300 groups, NULL among them
    hd = rng.integers(1, 300, lanes).astype(np.int64) * 7919
    hv = rng.random(lanes) > 0.05
    hvals, hmask = rng.random(lanes), rng.random(lanes) > 0.3
    carry, model, overflow, _ = _step_and_model(
        _fresh([np.int64], slots), _ModelTable([np.int64], slots),
        [(hd, hv)], hvals, hmask)
    assert overflow == 0
    kd, mask = _engineered(sieve, lanes, slots, groups, rng)
    vals = rng.random(lanes)
    out, _after, overflow, rounds = _step_and_model(
        carry, model, [(kd, ones)], vals, mask)
    assert rounds == want_rounds and overflow == want_left
    _assert_same_table(out, carry)
    # regrow from the table handed BACK, as a donated fold has no other
    held = int(out.groups)
    grown, re_overflow, re_groups, _ = jax.jit(
        lambda c: rehash_carry(c, ["sum", "count"], 16 * slots,
                               rehash_width(held, slots)))(out)
    assert int(re_overflow) == 0 and int(re_groups) == held
    again, overflow, total, _ = _step_sum_count(
        grown, [(jnp.asarray(kd), jnp.asarray(ones))], jnp.asarray(vals),
        jnp.asarray(mask))
    assert int(overflow) == 0
    _assert_owner_is_settled(again)
    key_col = (np.concatenate([hd, kd]), np.concatenate([hv, ones]))
    all_vals = np.concatenate([hvals, vals])
    want = _reference([key_col],
                      [("sum", all_vals, None), ("count", None, None)],
                      np.concatenate([hmask, mask]))
    assert int(total) == len(want)
    _assert_same_groups(_table_groups(again), want)


# -- today's step against the parent's, kept word for word -------------------
# tests/hash_step_parent.py is `hash_agg_step` as it was before the owner
# lane.  The probe, the round limit, the two widths and the winner are the
# same, so every group lands in the slot it landed in and the rounds at
# both widths are the parent's, digit for digit.

@partial(jax.jit, static_argnames=("rounds",))
def _parent_all_kinds(carry, key_cols, vals, av, mask, rounds=16):
    return parent_step(carry, key_cols, [(k, vals, av) for k in _KINDS],
                       mask, probe_rounds=rounds)


@partial(jax.jit, static_argnames=("rounds",))
def _today_all_kinds(carry, key_cols, vals, av, mask, rounds=16):
    return hash_agg_step(carry, key_cols, [(k, vals, av) for k in _KINDS],
                         mask, probe_rounds=rounds)


def _assert_the_parents_table(got, want):
    """Slot for slot: the same used slots, and at them the same keys,
    the same key validity (read from the owner lane) and accumulators."""
    _assert_owner_is_settled(got)
    used = np.asarray(want.used)
    np.testing.assert_array_equal(np.asarray(got.used), used)
    for gk, gv, wk, wv in zip(_keys(got), got.key_valid, want.keys,
                              want.key_valid):
        np.testing.assert_array_equal(np.asarray(gv)[used],
                                      np.asarray(wv)[used])
        np.testing.assert_array_equal(gk[used], np.asarray(wk)[used])
    for a, b in zip(jax.tree_util.tree_leaves((got.accs, got.acc_valid)),
                    jax.tree_util.tree_leaves((want.accs, want.acc_valid))):
        np.testing.assert_array_equal(np.asarray(a)[used],
                                      np.asarray(b)[used])


_PARENT_STREAMS = {
    # name: ([(key dtype, span of its values or None for the hostile
    #         column of _trial_key_col)], lanes, slots, steps, rounds,
    #        steps that overflow)
    "one_int64_narrow_rounds": ([(np.int64, 5_000)], 4096, 1 << 14, 6, 16,
                                False),
    "pair_of_int64_like_q01": ([(np.int64, 800), (np.int64, 6)], 4096,
                               1 << 14, 6, 16, False),
    "float_and_int32_with_nulls": ([(np.float64, None), (np.int32, 4)],
                                   2048, 1 << 12, 8, 16, False),
    "eight_narrow_columns": ([(np.int32, 5), (np.int16, None),
                              (np.int8, None), (np.int64, 3)] * 2,
                             1024, 1 << 15, 6, 16, False),
    "overflowing_small_table": ([(np.int64, 5_000)], 1024, 1 << 10, 6, 16,
                                True),
    "four_rounds_only": ([(np.int64, 400), (np.int32, 3)], 4096, 1 << 13,
                         5, 4, True),
}


@pytest.mark.parametrize("stream", list(_PARENT_STREAMS))
def test_every_step_is_the_parents_step_slot_for_slot(stream):
    columns, lanes, slots, steps, rounds, overflows = _PARENT_STREAMS[stream]
    rng = np.random.default_rng(len(stream))
    key_dtypes = [jnp.dtype(dt) for dt, _span in columns]
    today = init_hash_carry(key_dtypes, _KINDS, _ACC_DTYPES, slots)
    parent = parent_init(key_dtypes, _KINDS, _ACC_DTYPES, slots)
    overflowed = 0
    for _ in range(steps):
        key_cols = []
        for dt, span in columns:
            d, v = _trial_key_col(rng, lanes, dt)
            if span is not None:
                d = (rng.integers(0, span, lanes) * 7919 - span).astype(dt)
            key_cols.append((jnp.asarray(d), jnp.asarray(v)))
        args = (key_cols, jnp.asarray(rng.random(lanes)),
                jnp.asarray(rng.random(lanes) > 0.2),
                jnp.asarray(rng.random(lanes) > 0.25))
        today, t_over, t_groups, t_rounds = _today_all_kinds(
            today, *args, rounds=rounds)
        parent, p_over, p_groups, p_rounds = _parent_all_kinds(
            parent, *args, rounds=rounds)
        assert np.asarray(t_rounds).tolist() == np.asarray(p_rounds).tolist()
        assert int(t_over) == int(p_over)
        assert int(t_groups) == int(p_groups) == int(today.groups)
        overflowed += int(p_over) > 0
        _assert_the_parents_table(today, parent)
    assert int(today.groups) > lanes // 4 or overflows
    assert (overflowed > 0) == overflows


@pytest.mark.parametrize("case", list(_WIDTH_CASES))
def test_the_round_models_cases_are_the_parents_too(case, sieve):
    """PR 29's eight engineered cases, through both steps: the rounds at
    each width and every group's slot are the parent's."""
    lanes, slots, groups, want_rounds, want_left = _WIDTH_CASES[case]
    rng = np.random.default_rng(len(case))
    kd, mask = _engineered(sieve, lanes, slots, groups, rng)
    args = ([(jnp.asarray(kd), jnp.ones(lanes, bool))],
            jnp.asarray(rng.random(lanes)), jnp.ones(lanes, bool),
            jnp.asarray(mask))
    today, t_over, _, t_rounds = _today_all_kinds(
        init_hash_carry([jnp.int64], _KINDS, _ACC_DTYPES, slots), *args)
    parent, p_over, _, p_rounds = _parent_all_kinds(
        parent_init([jnp.int64], _KINDS, _ACC_DTYPES, slots), *args)
    assert np.asarray(t_rounds).tolist() == np.asarray(p_rounds).tolist() \
        == want_rounds
    assert int(t_over) == int(p_over) == want_left
    _assert_the_parents_table(today, parent)


# -- what a step touches (the structural guard) ------------------------------
# A step costs by its lanes alone: nothing in it is allocated, selected or
# reduced over the table's slots, and a probe round is 3 + 2k indexed
# operations for k key lanes, none wider than 32 bits.

def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, each with
    the loop bodies it lies in (innermost last)."""
    def inner(jaxpr, loops):
        for eqn in jaxpr.eqns:
            yield eqn, loops
            for name, sub in eqn.params.items():
                subs = sub if isinstance(sub, (tuple, list)) else [sub]
                for j in subs:
                    j = getattr(j, "jaxpr", j)
                    if hasattr(j, "eqns"):
                        body = eqn.primitive.name == "while" \
                            and name == "body_jaxpr"
                        yield from inner(j, loops + ((id(eqn),) if body
                                                     else ()))
    yield from inner(jaxpr, ())


_STRUCTURE_CASES = {
    # name: (key dtypes, key lanes)
    "q01s_two_int64_keys": ([np.int64, np.int64], 4),
    "one_int32_key": ([np.int32], 1),
    "int64_float64_int16": ([np.int64, np.float64, np.int16], 4),
}


@pytest.mark.parametrize("case", list(_STRUCTURE_CASES))
def test_a_step_runs_over_its_lanes_and_a_round_is_3_plus_2k(case):
    key_dtypes, k = _STRUCTURE_CASES[case]
    S, n = 1 << 16, 4096

    def step(carry, key_cols, vals, av, mask):
        return hash_agg_step(carry, key_cols,
                             [(kind, vals, av) for kind in _KINDS], mask)

    carry = jax.eval_shape(lambda: init_hash_carry(
        [jnp.dtype(d) for d in key_dtypes], _KINDS, _ACC_DTYPES, S))
    assert sum(len(lanes) for lanes in carry.keys) == k
    args = (carry,
            [(jax.ShapeDtypeStruct((n,), jnp.dtype(d)),
              jax.ShapeDtypeStruct((n,), jnp.bool_)) for d in key_dtypes],
            jax.ShapeDtypeStruct((n,), jnp.float64),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
            jax.ShapeDtypeStruct((n,), jnp.bool_))
    # the lowered text: nothing selected, broadcast or reduced over S
    text = jax.jit(step).lower(*args).as_text()
    wide = [ln.strip() for ln in text.splitlines()
            if f"tensor<{S}x" in ln
            and any(f"stablehlo.{op}" in ln
                    for op in ("select", "broadcast", "reduce", "constant",
                               "iota", "convert"))]
    assert not wide, wide[:3]
    # the program's equations: whatever reads or writes an S-wide value
    # is a scatter, a gather or the control flow that carries the table
    def indexed(name):
        return name == "gather" or name.startswith("scatter")

    carries = ("while", "cond", "pjit", "jit", "closed_call", "core_call",
               "custom_jvp_call")
    rounds = {}
    for eqn, loops in _equations(jax.make_jaxpr(step)(*args).jaxpr):
        name = eqn.primitive.name
        touches = any(getattr(v.aval, "shape", ()) == (S,)
                      for v in (*eqn.invars, *eqn.outvars)
                      if hasattr(v, "aval"))
        assert not touches or indexed(name) or name in carries, \
            str(eqn)[:300]
        if indexed(name) and loops:
            for v in (*eqn.invars, *eqn.outvars):
                # no indexed operation of a round moves a 64-bit lane,
                # but for a float64 key (its bits cannot be split on the
                # chip)
                assert v.aval.dtype.itemsize <= 4 \
                    or v.aval.dtype == np.float64, str(eqn)[:300]
            rounds[loops[-1]] = rounds.get(loops[-1], 0) + 1
    # the two probes (full width, narrow width), each 3 + 2k a round
    assert sorted(rounds.values()) == [3 + 2 * k] * 2, rounds

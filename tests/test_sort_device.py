"""`SortExec`'s resident lane (ops/sort.py `_SortState.sorted_on_device`)
against the host lane, row for row and in order.

The host lane is the operator as the CPU's default placement runs it: every
batch read to Arrow, `np.lexsort` over the host order keys.  The resident
lane is the same operator with `placement.host_resident` patched to false,
as tests/test_smj_device.py does: batches are jax arrays at bucket
capacities, the partition is staged as they arrive, laid end to end, its
order keys cut into 32-bit digits and sorted by `sort_pass`, its columns
gathered by the permutation (kernels/sort.py), all as jitted programs (on
the CPU backend here).  Every table carries `rid`, a row's place on arrival,
so equal `rid` sequences say equal order, ties included.
"""

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

import blaze_tpu.bridge.placement as P
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.exprs import BinaryExpr, col, lit
from blaze_tpu.kernels import sort as ksort
from blaze_tpu.memory import MemManager
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.ops.sort import SortExec, _SortState
from blaze_tpu.schema import Schema

ROWS = 3000
ONE_TILE = [ROWS]
RAGGED = [700, 1, 1299, 130, 870]       # five tiles of four capacities


@contextlib.contextmanager
def device_placement():
    with pytest.MonkeyPatch.context() as m:
        m.setattr(P, "host_resident", lambda: False)
        yield


def _key(kind: str, rng, n: int) -> pa.Array:
    """A key column of few distinct values (ties), a tenth of them NULL."""
    if kind == "int32":
        v = pa.array(rng.integers(-6, 6, n).astype(np.int32))
    elif kind == "int64":
        v = pa.array(rng.choice([-2**62, -3, -1, 0, 1, 2, 2**40, 2**62], n))
    elif kind == "float64":
        pool = np.array([np.nan, -0.0, 0.0, 1.5, -2.25, 1e300, -1e300,
                         np.inf, -np.inf, 1e-300, 1.0000000000000002, 1.0])
        v = pa.array(pool[rng.integers(0, len(pool), n)])
    elif kind == "float32":
        pool = np.array([np.nan, -0.0, 0.0, 1.5, -2.25, 3e38, -3e38],
                        dtype=np.float32)
        v = pa.array(pool[rng.integers(0, len(pool), n)])
    elif kind == "date":
        v = pa.array(rng.integers(-400, 20000, n).astype(np.int32) // 4000,
                     type=pa.int32()).cast(pa.date32())
    elif kind == "bool":
        v = pa.array(rng.random(n) < 0.5)
    elif kind == "decimal":
        v = pa.array((rng.integers(-500, 500, n) // 100).astype(np.int32)) \
            .cast(pa.decimal128(12, 2))
    else:
        raise KeyError(kind)
    nulls = rng.random(n) < 0.1
    return pa.array([None if m else x for x, m in zip(v.to_pylist(), nulls)],
                    type=v.type)


def _table(kinds, n: int = ROWS, seed: int = 5) -> pa.Table:
    rng = np.random.default_rng(seed)
    cols = {f"k{i}": _key(kind, rng, n) for i, kind in enumerate(kinds)}
    cols["rid"] = pa.array(np.arange(n, dtype=np.int64))
    cols["val"] = pa.array([None if i % 7 == 0 else float(i) / 3
                            for i in range(n)], type=pa.float64())
    cols["small"] = pa.array((np.arange(n) % 100).astype(np.int32))
    return pa.table(cols)


def _scan(table: pa.Table, cuts, keep=None) -> MemoryScanExec:
    """The table as batches of `cuts` rows; `keep` (a bool a row) rides as
    each batch's selection mask, not compacted."""
    batches, at = [], 0
    for n in cuts:
        b = ColumnBatch.from_arrow(table.slice(at, n).combine_chunks()
                                   .to_batches()[0])
        if keep is not None:
            mask = np.zeros(b.capacity, dtype=bool)
            mask[:n] = keep[at:at + n]
            b = b.with_selection(mask if P.host_resident()
                                 else jnp.asarray(mask))
        batches.append(b)
        at += n
    assert at == table.num_rows
    return MemoryScanExec(Schema.from_arrow(table.schema), [batches])


def _collect(plan) -> pa.Table:
    return pa.Table.from_batches(
        [b.compact().to_arrow() for b in plan.execute(0)],
        schema=plan.schema.to_arrow())


def _both_lanes(table, specs, cuts, fetch=None, keep=None):
    """(host lane's answer, resident lane's answer, the resident run's
    counters)."""
    want = _collect(SortExec(_scan(table, cuts, keep), specs, fetch=fetch))
    with device_placement():
        before = xla_stats.snapshot()
        got = _collect(SortExec(_scan(table, cuts, keep), specs, fetch=fetch))
        moved = xla_stats.delta(before)
    return want, got, moved


def _assert_same(want: pa.Table, got: pa.Table):
    assert got.schema.equals(want.schema)
    assert got.column("rid").to_pylist() == want.column("rid").to_pylist()
    assert got.to_pandas().equals(want.to_pandas())   # NaN is NaN, NULL NULL


def _assert_same_rows_in_key_order(want: pa.Table, got: pa.Table, nkeys: int):
    """Spilled runs merge with the run in memory ahead of them, so rows of
    equal keys need not keep their arrival order: the keys' sequence and
    the rows are the unspilled ones."""
    keys = [f"k{i}" for i in range(nkeys)]
    assert got.select(keys).to_pandas().equals(want.select(keys).to_pandas())
    by_rid = [t.sort_by("rid").to_pandas() for t in (got, want)]
    assert by_rid[0].equals(by_rid[1])


ORDERS = [(False, True), (False, False), (True, True), (True, False)]
KINDS = ["int32", "int64", "float64", "float32", "date", "bool", "decimal"]


@pytest.mark.parametrize("cuts", [ONE_TILE, RAGGED], ids=["one_tile", "ragged"])
@pytest.mark.parametrize("desc,first", ORDERS,
                         ids=[f"{'desc' if d else 'asc'}-nulls_"
                              f"{'first' if f else 'last'}" for d, f in ORDERS])
@pytest.mark.parametrize("kind", KINDS)
def test_one_key_sorts_as_the_host_lane(kind, desc, first, cuts):
    table = _table([kind])
    want, got, moved = _both_lanes(table, [(col(0), desc, first)], cuts)
    _assert_same(want, got)
    assert moved["sort_resident_rows"] == moved["sort_device_rows"] == ROWS


@pytest.mark.parametrize("kinds,orders", [
    (["int64", "float64"], [(False, True), (True, False)]),
    (["float64", "int32"], [(True, True), (False, False)]),
    (["date", "bool", "int32"], [(False, False), (True, True), (False, True)]),
    (["bool", "decimal", "float64"], [(True, False), (False, True),
                                      (True, True)]),
], ids=["int64-float64", "float64-int32", "date-bool-int32",
        "bool-decimal-float64"])
@pytest.mark.parametrize("cuts", [ONE_TILE, RAGGED], ids=["one_tile", "ragged"])
def test_two_and_three_keys_sort_as_the_host_lane(kinds, orders, cuts):
    table = _table(kinds)
    specs = [(col(i), d, f) for i, (d, f) in enumerate(orders)]
    want, got, moved = _both_lanes(table, specs, cuts)
    _assert_same(want, got)
    assert moved["sort_resident_rows"] == ROWS


def test_equal_keys_keep_their_arrival_order():
    """Every pass is stable and a digit no two rows differ in is not
    sorted at all: one key value, then a computed key of three."""
    table = _table(["int64"]).set_column(
        0, "k0", pa.array(np.full(ROWS, 7, dtype=np.int64)))
    want, got, _ = _both_lanes(table, [(col(0), True, False)], RAGGED)
    assert got.column("rid").to_pylist() == list(range(ROWS))
    _assert_same(want, got)
    by_three = BinaryExpr("%", col(1), lit(3))       # rid % 3: not a column
    want, got, moved = _both_lanes(table, [(by_three, False, True)], RAGGED)
    rid = got.column("rid").to_numpy()
    assert (rid[:1000] % 3 == 0).all() and (np.diff(rid[:1000]) > 0).all()
    _assert_same(want, got)
    assert moved["sort_resident_rows"] == ROWS


@pytest.mark.parametrize("cuts", [ONE_TILE, RAGGED], ids=["one_tile", "ragged"])
def test_a_selection_mask_on_the_input_is_applied_on_the_device(cuts):
    table = _table(["int64", "float64"])
    keep = np.arange(ROWS) % 3 != 1
    specs = [(col(0), False, True), (col(1), True, True)]
    want, got, moved = _both_lanes(table, specs, cuts, keep=keep)
    assert want.num_rows == int(keep.sum())
    _assert_same(want, got)
    assert moved["sort_resident_rows"] == int(keep.sum())


@pytest.mark.parametrize("fetch", [1, 100, 2047, ROWS, ROWS + 50])
def test_fetch_cuts_the_run_on_the_device(fetch):
    table = _table(["float64", "int32"])
    specs = [(col(0), False, False), (col(1), True, True)]
    want, got, moved = _both_lanes(table, specs, RAGGED, fetch=fetch)
    assert got.num_rows == min(fetch, ROWS)
    _assert_same(want, got)
    assert moved["sort_resident_rows"] == ROWS


def test_the_run_is_one_batch_at_its_bucket_with_clean_padding():
    with device_placement():
        out = list(SortExec(_scan(_table(["int64"]), RAGGED),
                            [(col(0), False, True)], fetch=200)
                   .execute(0))
    assert len(out) == 1
    b = out[0]
    assert b.num_rows == 200 and b.capacity == 256 and b.selection is None
    for c in b.columns:
        assert isinstance(c.data, jax.Array)
        assert not np.asarray(c.validity)[200:].any()
        assert not np.asarray(c.data)[200:].any()


# -- what the lane declines ---------------------------------------------------

def _declined(table, specs, cuts=RAGGED):
    want, got, moved = _both_lanes(table, specs, cuts)
    assert got.equals(want)
    assert moved["sort_resident_rows"] == 0
    return moved


def test_a_utf8_payload_column_takes_the_host_lane():
    table = _table(["int64"]).append_column(
        "name", pa.array([f"n{i % 13}" for i in range(ROWS)]))
    moved = _declined(table, [(col(0), False, True)])
    assert moved["sort_device_rows"] == ROWS    # the permutation alone


@pytest.mark.parametrize("as_key", [False, True])
def test_a_dictionary_column_stays_on_the_chip(as_key):
    """A dictionary column is its int32 code lane: a payload is gathered
    like any column, a key orders by its codes' ranks in string order (the
    dictionary here is in first-seen order, not sorted).  The host lane
    sorts the strings themselves."""
    names = pa.array([None if i % 11 == 0 else f"n{(i * 7) % 13}"
                      for i in range(ROWS)]).dictionary_encode()
    table = _table(["int64"]).append_column("name", names)
    specs = [(col(table.num_columns - 1), True, False)] * as_key \
        + [(col(0), False, True)]
    want = _collect(SortExec(_scan(table, RAGGED), specs))
    with device_placement():
        before = xla_stats.snapshot()
        got = _collect(SortExec(_scan(table, RAGGED), specs))
        moved = xla_stats.delta(before)
    assert got.column("rid").equals(want.column("rid"))
    assert got.column("name").to_pylist() == want.column("name").to_pylist()
    assert moved["sort_resident_rows"] == ROWS
    assert moved["dict_rows_coded"] == ROWS


def test_a_host_column_takes_the_host_lane():
    """The decimal twin's `decimal(21,6)` average: past 18 digits a column
    is an Arrow array between operators."""
    wide = pa.array((np.arange(ROWS) * 1000).astype(np.int32)).cast(
        pa.decimal128(21, 6))
    table = _table(["int64"]).append_column("avg", wide)
    _declined(table, [(col(0), True, False)])
    # and as the key itself
    _declined(table, [(col(table.num_columns - 1), False, True)])


def test_a_utf8_key_takes_the_host_lane():
    table = _table(["int64"]).append_column(
        "name", pa.array([f"n{i % 13}" for i in range(ROWS)]))
    _declined(table, [(col(table.num_columns - 1), False, True),
                      (col(0), True, True)])


def test_under_1024_rows_take_the_host_lane():
    table = _table(["int64", "float64"], n=1023)
    specs = [(col(0), False, True), (col(1), False, True)]
    want, got, moved = _both_lanes(table, specs, [500, 23, 500])
    _assert_same(want, got)
    assert moved["sort_resident_rows"] == moved["sort_device_rows"] == 0
    table = _table(["int64", "float64"], n=1024)
    want, got, moved = _both_lanes(table, specs, [500, 24, 500])
    _assert_same(want, got)
    assert moved["sort_resident_rows"] == 1024


def test_host_placement_takes_the_host_lane():
    table = _table(["int64"])
    before = xla_stats.snapshot()
    out = list(SortExec(_scan(table, RAGGED), [(col(0), False, True)])
               .execute(0))
    moved = xla_stats.delta(before)
    assert moved["sort_resident_rows"] == moved["sort_device_rows"] == 0
    assert moved["d2h_bytes"] == 0
    assert sum(b.num_rows for b in out) == ROWS


def test_a_batch_the_lane_cannot_take_moves_the_partition_to_the_host_lane():
    """The third batch arrives with its float column as numpy (not on the
    device): what was staged is read back, the order of arrival kept."""
    table = _table(["int64"])
    with device_placement():
        scan = _scan(table, RAGGED)
        odd = scan._partitions[0][2]
        cols = list(odd.columns)
        cols[2] = type(cols[2])(cols[2].dtype, np.asarray(cols[2].data),
                                np.asarray(cols[2].validity))
        scan._partitions[0][2] = ColumnBatch(odd.schema, cols, odd.num_rows)
        before = xla_stats.snapshot()
        got = _collect(SortExec(scan, [(col(0), False, True)]))
        moved = xla_stats.delta(before)
    want = _collect(SortExec(_scan(table, RAGGED), [(col(0), False, True)]))
    _assert_same(want, got)
    assert moved["sort_resident_rows"] == 0


@pytest.mark.parametrize("tiles,resident", [(5, True), (4, False)])
def test_a_partition_of_more_tiles_than_the_lane_stages_takes_the_host_lane(
        monkeypatch, tiles, resident):
    """RAGGED's five batches under a bound of five and of four: the fifth
    batch sends what is staged through the host lane, arrival order kept."""
    import blaze_tpu.ops.sort as ops_sort
    monkeypatch.setattr(ops_sort, "_RESIDENT_TILES", tiles)
    table = _table(["int64", "float64"])
    specs = [(col(0), True, False), (col(1), False, True)]
    want, got, moved = _both_lanes(table, specs, RAGGED)
    _assert_same(want, got)
    assert moved["sort_resident_rows"] == (ROWS if resident else 0)
    assert moved["sort_device_rows"] == ROWS


# -- what consumes the run: one batch of many tiles' rows -----------------------

def _window(child):
    from blaze_tpu.ops import make_agg
    from blaze_tpu.ops.window import (RankFunc, WindowAggFunc, WindowExec,
                                      WindowRankType)
    return WindowExec(
        child, [RankFunc("rk", WindowRankType.RANK),
                WindowAggFunc("rs", make_agg("sum", [col(4)]), running=True)],
        [col(0)], [(col(1), False, True)])


def _limit(child):
    from blaze_tpu.ops.basic import LimitExec
    return LimitExec(child, 1500, offset=700)


def _project(child):
    from blaze_tpu.ops import ProjectExec
    return ProjectExec(child, [BinaryExpr("+", col(2), lit(1)), col(0)],
                       ["rid1", "k0"])


@pytest.mark.parametrize("consumer", [_window, _limit, _project])
def test_an_operator_over_a_resident_sort_of_many_tiles(consumer):
    """The lane hands its consumer ONE batch at the partition's bucket where
    the host lane hands batches of BATCH_SIZE: a window over (k0, k1), a
    limit with an offset and a projection read it to the same answer."""
    table = _table(["int32", "int64"])
    specs = [(col(0), False, True), (col(1), False, True)]
    want = _collect(consumer(SortExec(_scan(table, RAGGED), specs)))
    with device_placement():
        before = xla_stats.snapshot()
        got = _collect(consumer(SortExec(_scan(table, RAGGED), specs)))
        moved = xla_stats.delta(before)
    assert moved["sort_resident_rows"] == ROWS
    assert got.schema.equals(want.schema)
    assert got.to_pandas().equals(want.to_pandas())


# -- spilling -----------------------------------------------------------------

@pytest.mark.parametrize("spill_after", [0, 2, 4])
def test_a_spill_in_mid_partition_falls_back_to_the_host_lane(
        monkeypatch, spill_after):
    table = _table(["int64", "float64"])
    specs = [(col(0), True, True), (col(1), False, False)]
    want = _collect(SortExec(_scan(table, RAGGED), specs))
    monkeypatch.setattr(MemManager, "_instance", MemManager(1 << 30))
    with device_placement():
        scan = _scan(table, RAGGED)
        op = SortExec(scan, specs)
        state = _SortState(op, op.schema, specs)
        state.set_spillable(MemManager.get())
        before = xla_stats.snapshot()
        try:
            for i, b in enumerate(scan.execute(0)):
                state.insert(b)
                if i == spill_after:
                    assert state.mem_used > 0
                    assert state.spill() > 0
                    assert state.mem_used == 0
            assert state.sorted_on_device(None) is None
            got = pa.Table.from_batches(list(state.merged_output()),
                                        schema=op.schema.to_arrow())
        finally:
            state.unregister()
        moved = xla_stats.delta(before)
    _assert_same_rows_in_key_order(want, got, 2)
    assert moved["sort_resident_rows"] == 0
    assert state.spill_metrics.spill_count == 1
    assert not MemManager.get()._consumers


def test_the_memory_manager_sheds_a_resident_partition(monkeypatch):
    """Under a budget the staged tiles pass, the manager calls `spill` from
    `update_mem_used`: the operator's answer is the unspilled one."""
    table = _table(["int64", "float64"])
    specs = [(col(0), False, True), (col(1), True, True)]
    want = _collect(SortExec(_scan(table, RAGGED), specs))
    manager = MemManager(60_000)
    monkeypatch.setattr(MemManager, "_instance", manager)
    with device_placement():
        before = xla_stats.snapshot()
        op = SortExec(_scan(table, RAGGED), specs)
        got = _collect(op)
        moved = xla_stats.delta(before)
    _assert_same_rows_in_key_order(want, got, 2)
    assert manager.total_spill_count > 0 and not manager._consumers
    assert moved["sort_resident_rows"] == 0
    assert op.metrics.values.get("spill_count", 0) > 0


def test_a_resident_partition_is_charged_and_released(monkeypatch):
    manager = MemManager(1 << 30)
    monkeypatch.setattr(MemManager, "_instance", manager)
    table = _table(["int64"])
    with device_placement():
        op = SortExec(_scan(table, RAGGED), [(col(0), False, True)])
        stream = op.execute(0)
        out = next(stream)
        # the run is the consumer's to reserve: the sort holds nothing
        assert manager.mem_used == 0
        tiles = sum(b.nbytes_device() for b in _scan(table, RAGGED).execute(0))
        assert op.metrics.values["mem_used"] >= tiles
        assert list(stream) == [] and not manager._consumers
    assert out.num_rows == ROWS


# -- what the benchmark reads -------------------------------------------------

def test_nothing_but_a_few_booleans_is_read_back():
    table = _table(["int64", "float64"])
    specs = [(col(0), False, True), (col(1), False, True)]
    with device_placement():
        scan = _scan(table, RAGGED)
        before = xla_stats.snapshot()
        out = list(SortExec(scan, specs).execute(0))
        moved = xla_stats.delta(before)
        got = pa.Table.from_batches([b.to_arrow() for b in out])
    assert moved["sort_resident_rows"] == ROWS
    assert moved["chip0_sort_resident_rows"] == ROWS
    assert 0 < moved["d2h_bytes"] < 1024
    assert moved["h2d_bytes"] == 0
    _assert_same(_collect(SortExec(_scan(table, RAGGED), specs)), got)
    assert xla_stats.chip_stats()[0]["sort_resident_rows"] >= ROWS
    assert "sort_resident_rows" in xla_stats.sortmerge_stats()


def test_programs_are_named_sort_and_the_span_is_emitted():
    table = _table(["int64", "float64"])
    specs = [(col(0), False, True), (col(1), False, True)]
    with device_placement():
        scan = _scan(table, RAGGED)
        calls = {k: v["calls"] for k, v in
                 xla_stats.compile_report()["kernels"].items()}
        tracing.start_tracing()
        try:
            list(SortExec(scan, specs).execute(0))
        finally:
            spans = tracing.stop_tracing()
    mine = [s for s in spans if s["name"] == "sort_device"]
    assert len(mine) == 1
    assert mine[0]["thread"] == threading.current_thread().name
    attrs = mine[0]["attrs"]
    assert attrs["rows"] == ROWS and attrs["lane"] == "resident"
    # two keys: bucket, high and low half each; the buckets differ (NULLs),
    # the int64 key's halves both do, the float64's too
    assert 2 <= attrs["passes"] <= 6
    now = xla_stats.compile_report()["kernels"]
    ran = {k: now[k]["calls"] - calls.get(k, 0) for k in now
           if k.startswith("sort.")}
    assert ran["sort.assemble"] == ran["sort.digits"] == ran["sort.gather"] \
        == 1
    assert ran["sort.pass"] == attrs["passes"]
    assert ran["sort.widen"] == 4      # RAGGED's four narrower tiles
    for fn, name in ((ksort.assemble_tiles, "_assemble_tiles__sort_assemble"),
                     (ksort.key_digits, "_key_digits__sort_digits"),
                     (ksort.gather_sorted, "_gather_sorted__sort_gather"),
                     (ksort.widen_tile, "_widen_tile__sort_widen"),
                     (ksort.sort_pass, "lsd_pass__sort_pass")):
        assert fn._blaze_jitted.__name__ == name and "__sort_" in name


def test_the_tile_count_is_rounded_so_few_programs_serve_many_partitions():
    table = _table(["int64"], n=4000)
    with device_placement():
        def compiles():
            return xla_stats.compile_report()["kernels"].get(
                "sort.assemble", {"compiles": 0})["compiles"]
        list(SortExec(_scan(table, [800] * 5), [(col(0), False, True)])
             .execute(0))
        before = compiles()
        for cuts in ([800] * 4 + [400] * 2, [700] * 5 + [500], [600] * 6 + [400],
                     [520] * 7 + [360]):      # 6, 6, 7, 8 tiles of 1,024 lanes
            list(SortExec(_scan(table, cuts), [(col(0), False, True)])
                 .execute(0))
        assert compiles() == before


def test_sort_pass_lowers_to_the_parents_text():
    """The pass is the program in place (its compile time is why it is one
    two-operand sort): the parent's function word for word, under the
    program's name, lowers to the same module."""
    from blaze_tpu.bridge.xla_stats import meter_jit

    def lsd_pass(digit, perm):
        return jax.lax.sort((jnp.take(digit, perm), perm), num_keys=1,
                            is_stable=True)[1]

    parents = meter_jit(lsd_pass, name="sort.pass")
    args = (jnp.zeros(1 << 20, jnp.uint32), jnp.zeros(1 << 20, jnp.int32))
    mine = ksort.sort_pass._blaze_jitted.lower(*args).as_text()
    assert mine == parents._blaze_jitted.lower(*args).as_text()
    assert "module @jit_lsd_pass__sort_pass" in mine


# -- the digits ---------------------------------------------------------------

def _digit_order(digits, n):
    """Row order by the digits, most significant first, stable."""
    return np.lexsort(tuple(np.asarray(d)[:n] for d in reversed(digits)))


@pytest.mark.parametrize("desc", [False, True])
def test_float_pair_digits_order_as_the_values(desc):
    """Where a float64 is a pair of float32 (the TPU) the digits are the
    pair's halves.  Values that ARE such pairs, as every value there is,
    order exactly as their sum."""
    from blaze_tpu.schema import DataType, TypeId
    rng = np.random.default_rng(3)
    n, cap = 2000, 2048
    hi = rng.choice(np.array([0.0, 1.0, -1.0, 3.5, -3.5, 1e30, -1e30, 2e-30,
                              16777216.0], dtype=np.float32), n)
    lo = (hi * np.float32(2.0 ** -25)
          * rng.integers(-1, 2, n).astype(np.float32)).astype(np.float32)
    values = hi.astype(np.float64) + lo.astype(np.float64)
    values[::97] = np.inf
    values[::89] = -np.inf
    values[::83] = np.nan
    values[::79] = -0.0
    valid = rng.random(n) > 0.1
    data = jnp.asarray(np.pad(values, (0, cap - n)))
    validity = jnp.asarray(np.pad(valid, (0, cap - n)))
    kw = dict(dtypes=(DataType(TypeId.FLOAT64),), descending=(desc,),
              nulls_first=(True,))
    pair, moves, lanes = ksort.key_digits(((data, validity),), jnp.int32(n),
                                          float_pair=True, **kw)
    bits, _, _ = ksort.key_digits(((data, validity),), jnp.int32(n),
                                  float_pair=False, **kw)
    assert (_digit_order(pair, n) == _digit_order(bits, n)).all()
    assert len(pair) == len(bits) == 3 and np.asarray(moves).all()
    for d in pair:
        assert (np.asarray(d)[n:] == ksort.PAD_DIGIT).all()
    assert (np.asarray(lanes) == np.arange(cap)).all()


def test_digits_no_two_rows_differ_in_are_named():
    from blaze_tpu.schema import DataType, TypeId
    data = jnp.asarray(np.arange(256, dtype=np.int64) % 50)   # no NULL, small
    moves = np.asarray(ksort.key_digits(
        ((data, jnp.ones(256, bool)),), jnp.int32(200),
        dtypes=(DataType(TypeId.INT64),), descending=(False,),
        nulls_first=(True,), float_pair=False)[1])
    assert moves.tolist() == [False, False, True]   # bucket, high half, low


def test_tiles_full_and_ragged_are_laid_end_to_end():
    """`assemble_tiles` over tiles full up to the last one with a row, all
    full, ragged and all but empty, each with what compaction leaves behind
    its rows: the answer is the rows end to end, clean behind them."""
    width, cap = 256, 1024

    def tile(rows, base):
        d = np.zeros(width, np.int64)
        d[:rows] = base + np.arange(rows)
        d[rows:] = -1                          # what compaction leaves behind
        v = np.zeros(width, bool)
        v[:rows] = True
        v[rows:] = True
        return ((jnp.asarray(d), jnp.asarray(v)),)

    for counts in ([256, 256, 100, 0], [256, 256, 256, 256], [10, 0, 256, 3],
                   [0, 0, 0, 5]):
        tiles = tuple(tile(n, 1000 * i) for i, n in enumerate(counts))
        (col0,), total = ksort.assemble_tiles(
            tiles, np.array(counts, np.int32), cap=cap)
        want = np.concatenate([1000 * i + np.arange(n)
                               for i, n in enumerate(counts)])
        assert int(total) == len(want)
        assert (np.asarray(col0[0])[:len(want)] == want).all()
        assert not np.asarray(col0[0])[len(want):].any()
        assert np.asarray(col0[1]).tolist() == \
            [True] * len(want) + [False] * (cap - len(want))

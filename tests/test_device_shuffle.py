"""Device-resident shuffle (ISSUE 6): the shared Spark-compatible
partition-id definition across host and device lanes, the DeviceExchange
collective runner with its bucket-ladder capacity retry, the planner's
device-exchange eligibility pass, and the staged scheduler's device path
(bit-identical to the file shuffle, with the `shuffle:` explain footer)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax
import jax.numpy as jnp

from blaze_tpu import config, faults
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.bridge import xla_stats
from blaze_tpu.exprs import col
from blaze_tpu.kernels import hashing as H
from blaze_tpu.memory import MemManager
from blaze_tpu.parallel.collective import partition_ids_for_keys
from blaze_tpu.parallel.stage import DeviceExchange, DeviceExchangeError
from blaze_tpu.plan.planner import exchange_device_spec
from blaze_tpu.plan.stages import DagScheduler
from blaze_tpu.shuffle import HashPartitioning

SENT = -(1 << 60)  # stand-in for NULL keys in multiset comparisons


@pytest.fixture(autouse=True)
def clean_slate():
    faults.clear()
    MemManager.init(4 << 30)
    try:
        yield
    finally:
        faults.clear()


@pytest.fixture
def staged_device():
    """Force the staged DAG path and the device shuffle lane."""
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    config.conf.set(config.SHUFFLE_DEVICE.key, "on")
    try:
        yield
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)
        config.conf.unset(config.SHUFFLE_DEVICE.key)


# -- satellite 1: ONE hash definition, host and device lanes ----------------

def _alt_nan(dtype):
    """A NaN with a non-canonical bit pattern (payload bit set)."""
    if dtype == np.float64:
        return np.array([0x7FF8000000000001], dtype=np.uint64
                        ).view(np.float64)[0]
    return np.array([0x7FC00001], dtype=np.uint32).view(np.float32)[0]


def _key_case(tid, n=257, seed=11):
    """(data, valid, host_tid) for one key dtype, NULLs included."""
    rng = np.random.default_rng(seed)
    valid = rng.random(n) > 0.15
    if tid in ("int32", "date32"):
        data = rng.integers(np.iinfo(np.int32).min,
                            np.iinfo(np.int32).max, n).astype(np.int32)
    elif tid in ("int64", "timestamp_us"):
        data = rng.integers(np.iinfo(np.int64).min,
                            np.iinfo(np.int64).max, n, dtype=np.int64)
    elif tid in ("float32", "float64"):
        dt = np.float32 if tid == "float32" else np.float64
        data = (rng.random(n) * 2e4 - 1e4).astype(dt)
        # normalization corner cases: +/-0.0 collapse, every NaN bit
        # pattern hashes as the one canonical NaN
        data[:6] = [0.0, -0.0, np.nan, _alt_nan(dt), np.inf, -np.inf]
    elif tid == "bool":
        data = rng.random(n) > 0.5
    else:  # pragma: no cover
        raise AssertionError(tid)
    return data, valid, tid


@pytest.mark.parametrize("tid", ["bool", "int32", "int64", "float32",
                                 "float64", "date32", "timestamp_us"])
def test_partition_ids_host_device_bitwise_agree(tid):
    """The property behind the device exchange's correctness: the host
    file-shuffle lane (numpy) and the device collective lane (jit'd
    jnp, post arrow->flat re-tagging: date32 rides int32, timestamp_us
    rides int64) put every row in the same reduce partition."""
    data, valid, _ = _key_case(tid)
    for p in (3, 8):
        host = H.spark_partition_ids([(data, valid)], [tid], p, xp=np)
        dev = np.asarray(partition_ids_for_keys(
            [(jnp.asarray(data), jnp.asarray(valid))], p))
        assert host.tolist() == dev.tolist()


def test_partition_ids_match_hash_partitioning_lane():
    """...and both agree with the full HashPartitioning expression lane
    that the file shuffle writer actually runs."""
    data, valid, _ = _key_case("int64")
    t = pa.table({"k": pa.array(data, mask=~valid, type=pa.int64())})
    hp = HashPartitioning([col(0)], 5)
    ids = hp.partition_ids(ColumnBatch.from_arrow(t))
    want = H.spark_partition_ids([(data, valid)], ["int64"], 5, xp=np)
    assert np.asarray(ids)[:len(data)].tolist() == want.tolist()


# -- per-destination staging ------------------------------------------------

@pytest.mark.parametrize("parts,cap", [(4, 512), (7, 64), (16, 128)])
def test_dest_slots_buffers_match_stable_argsort(parts, cap):
    """The per-destination buffers the all-to-all ships, against numpy:
    rows keep their order within a destination, parked pids (>= parts)
    go nowhere, and rows past `cap` are counted, not written."""
    from blaze_tpu.parallel.collective import _dest_slots
    rng = np.random.default_rng(parts)
    n = 2000
    pid = rng.integers(0, parts + 2, n).astype(np.int64)  # some parked
    col_ = rng.random(n) + 1.0  # never 0.0, the buffers' fill
    order, dest, overflow = jax.jit(
        lambda p: _dest_slots(p, parts, cap))(jnp.asarray(pid))
    assert np.array_equal(np.asarray(order), np.argsort(pid, kind="stable"))
    buf = jnp.zeros((parts, cap)).at[dest].set(
        jnp.take(jnp.asarray(col_), order), mode="drop")
    want = np.zeros((parts, cap))
    dropped = 0
    for p in range(parts):
        rows = np.flatnonzero(pid == p)
        want[p, :min(cap, len(rows))] = col_[rows[:cap]]
        dropped += max(0, len(rows) - cap)
    assert np.array_equal(np.asarray(buf), want)
    assert int(overflow) == dropped
    assert (dropped > 0) == (cap == 64)  # one case climbs, two fit


# -- DeviceExchange unit ----------------------------------------------------

def _kv_columns(n=5000, seed=3, null_rate=0.1):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 200, n, dtype=np.int64)
    kv = rng.random(n) > null_rate
    v = rng.random(n)
    return ([k, v], [kv, np.ones(n, dtype=bool)])


def _multiset(datas, valids):
    k, v = datas
    kval, _ = valids
    return sorted((int(k[i]) if kval[i] else SENT, float(v[i]))
                  for i in range(len(k)))


LAYOUTS = ["host", "spread"]


def _exchange(mesh, layout, cols, valids, key_idx, n_out):
    """`host`: the columns as one host set, cut evenly over the mesh.
    `spread`: the same rows as the output of one map task a device,
    lying on that device (ragged: task t holds t+1 shares), taken where
    they lie."""
    ex = DeviceExchange(mesh)
    if layout == "host":
        return ex.exchange(cols, valids, key_idx, n_out)
    import jax
    devices = list(mesh.devices.reshape(-1))
    shares = np.cumsum([0] + [t + 1 for t in range(len(devices))])
    cuts = (shares * len(cols[0])) // shares[-1]
    tasks = [([jax.device_put(c[lo:hi], d) for c in cols],
              [jax.device_put(v[lo:hi], d) for v in valids], int(hi - lo))
             for d, lo, hi in zip(devices, cuts[:-1], cuts[1:])]
    return ex.drain(ex.dispatch_placed(tasks, key_idx, n_out))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_device_exchange_routes_like_host_hash(device_mesh, layout):
    cols, valids = _kv_columns()
    xla_stats.reset()
    parts = _exchange(device_mesh, layout, cols, valids, [0], 3)
    host_pids = H.spark_partition_ids(
        [(cols[0], valids[0])], ["int64"], 3, xp=np)
    assert len(parts) == 3
    for r in range(3):
        sel = host_pids == r
        want = _multiset([c[sel] for c in cols], [v[sel] for v in valids])
        assert _multiset(*parts[r]) == want
    ss = xla_stats.shuffle_stats()
    assert ss["shuffle_device_exchanges"] == 1
    assert ss["shuffle_device_rows"] == len(cols[0])
    assert ss["shuffle_device_bytes"] > 0
    assert ss["shuffle_device_collectives"] >= 2


@pytest.mark.parametrize("layout", LAYOUTS)
def test_device_exchange_skew_climbs_bucket_ladder(device_mesh, layout):
    """Pathological skew: every row hashes to ONE destination, so the
    per-destination buckets sized for uniform traffic overflow and the
    runner must climb the capacity ladder (the last rung — the full
    per-device row count — can always hold the rows)."""
    n = 4096
    cols = [np.full(n, 7, dtype=np.int64),
            np.arange(n, dtype=np.float64)]
    valids = [np.ones(n, dtype=bool), np.ones(n, dtype=bool)]
    config.conf.set(config.MESH_EXCHANGE_SKEW.key, 1.0)
    try:
        xla_stats.reset()
        parts = _exchange(device_mesh, layout, cols, valids, [0], 3)
    finally:
        config.conf.unset(config.MESH_EXCHANGE_SKEW.key)
    target = int(H.spark_partition_ids(
        [(cols[0][:1], None)], ["int64"], 3, xp=np)[0])
    sizes = [len(parts[r][0][0]) for r in range(3)]
    assert sizes[target] == n and sum(sizes) == n
    assert _multiset(*parts[target]) == _multiset(cols, valids)
    assert xla_stats.shuffle_stats()["shuffle_device_exchanges"] == 1
    # the first rung overflowed: more than one dispatch's collectives
    assert xla_stats.shuffle_stats()["shuffle_device_collectives"] > 6
    assert xla_stats.placement_stats()["cross_chip_bytes"] == 0


def test_device_exchange_empty_and_degenerate(device_mesh):
    ex = DeviceExchange(device_mesh)
    parts = ex.exchange([np.zeros(0, np.int64)], [np.zeros(0, bool)],
                        [0], 4)
    assert len(parts) == 4
    assert all(len(d[0]) == 0 for d, _ in parts)
    with pytest.raises(DeviceExchangeError):
        ex.exchange([], [], [0], 2)


# -- planner eligibility ----------------------------------------------------

_HASH_PART = {"kind": "hash",
              "exprs": [{"kind": "column", "index": 0}],
              "num_partitions": 3}
_KV_SCHEMA = {"fields": [
    {"name": "k", "type": {"id": "int64"}, "nullable": True},
    {"name": "v", "type": {"id": "float64"}, "nullable": True}]}


def _with_shuffle_device(mode):
    config.conf.set(config.SHUFFLE_DEVICE.key, mode)


def test_planner_marks_eligible_hash_exchange():
    _with_shuffle_device("on")
    try:
        spec = exchange_device_spec(_HASH_PART, _KV_SCHEMA)
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)
    assert spec == {"key_indices": [0], "num_partitions": 3}


def test_planner_declines_ineligible_exchanges():
    _with_shuffle_device("on")
    try:
        # variable-width columns still need the host row format
        utf8 = {"fields": [
            {"name": "s", "type": {"id": "utf8"}, "nullable": True}]}
        assert exchange_device_spec(_HASH_PART, utf8) is None
        # non-column key exprs: pid not computable on device
        part = dict(_HASH_PART,
                    exprs=[{"kind": "add",
                            "left": {"kind": "column", "index": 0},
                            "right": {"kind": "literal", "value": 1}}])
        assert exchange_device_spec(part, _KV_SCHEMA) is None
        # round-robin/single exchanges keep the host path
        assert exchange_device_spec(
            {"kind": "single", "num_partitions": 1}, _KV_SCHEMA) is None
        assert exchange_device_spec(None, _KV_SCHEMA) is None
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)


def test_planner_respects_mode_gates():
    _with_shuffle_device("off")
    try:
        assert exchange_device_spec(_HASH_PART, _KV_SCHEMA) is None
    finally:
        config.conf.unset(config.SHUFFLE_DEVICE.key)
    # default 'auto': declines while compute is host-resident (the CPU
    # test platform), so existing staged runs keep the file shuffle
    from blaze_tpu.bridge.placement import host_resident
    if host_resident():
        assert exchange_device_spec(_HASH_PART, _KV_SCHEMA) is None


# -- staged end-to-end ------------------------------------------------------

def _two_stage_plan(tmp_path, n=6000, n_reduce=3, wide=False):
    rng = np.random.default_rng(7)
    # `wide`: the same keys spread over 2^48, so that the aggregation
    # takes the hash table (a compact key range takes the dense lane,
    # which the stage loop leaves to its own fold)
    keys = rng.integers(0, 200, n) << (40 if wide else 0)
    t = pa.table({"k": pa.array(keys, type=pa.int64()),
                  "v": pa.array(rng.random(n))})
    paths = []
    for i in range(2):
        p = str(tmp_path / f"in-{i}.parquet")
        pq.write_table(t.slice(i * (n // 2), n // 2), p)
        paths.append(p)
    schema = {"fields": [
        {"name": "k", "type": {"id": "int64"}, "nullable": True},
        {"name": "v", "type": {"id": "float64"}, "nullable": True}]}
    return {
        "kind": "hash_agg",
        "groupings": [{"expr": {"kind": "column", "index": 0},
                       "name": "k"}],
        "aggs": [{"fn": "sum", "mode": "final", "name": "s",
                  "args": [{"kind": "column", "index": 1}]}],
        "input": {
            "kind": "local_exchange",
            "partitioning": {"kind": "hash",
                             "exprs": [{"kind": "column", "index": 0}],
                             "num_partitions": n_reduce},
            "input": {
                "kind": "hash_agg",
                "groupings": [{"expr": {"kind": "column", "name": "k"},
                               "name": "k"}],
                "aggs": [{"fn": "sum", "mode": "partial", "name": "s",
                          "args": [{"kind": "column", "name": "v"}]}],
                "input": {"kind": "parquet_scan", "schema": schema,
                          "file_groups": [[paths[0]], [paths[1]]]}}}}


def _sorted_df(tbl):
    return tbl.to_pandas().sort_values("k").reset_index(drop=True)


@pytest.mark.parametrize("resident", ["host", "devices"])
def test_staged_device_shuffle_bit_identical_to_file(tmp_path, device_mesh,
                                                     staged_device,
                                                     resident, monkeypatch):
    """`devices`: batches live on the devices, the map tasks come out of
    the stage loop as device columns on the devices they ran on, and the
    exchange takes them where they lie."""
    if resident == "devices":
        import blaze_tpu.bridge.placement as P
        monkeypatch.setattr(P, "host_resident", lambda: False)
    plan = _two_stage_plan(tmp_path, wide=resident == "devices")
    config.conf.set(config.SHUFFLE_DEVICE.key, "off")
    clean = _sorted_df(DagScheduler(
        work_dir=str(tmp_path / "dag-file")).run_collect(plan))
    config.conf.set(config.SHUFFLE_DEVICE.key, "on")

    xla_stats.reset()
    sched = DagScheduler(work_dir=str(tmp_path / "dag-dev"))
    got = _sorted_df(sched.run_collect(plan))

    assert got.equals(clean)
    assert any(st.device_spec for st in sched.stages)
    ss = xla_stats.shuffle_stats()
    assert ss["shuffle_device_exchanges"] >= 1
    assert ss["shuffle_device_rows"] > 0
    assert ss["shuffle_device_fallbacks"] == 0
    assert ss["shuffle_host_bytes"] == 0
    if resident == "devices":
        producer = sched.stages[0].sid
        assert sched.stage_placement[producer]["compute"] == "device-loop"
        assert sorted(chip for (sid, _m), chip in sched.task_chips.items()
                      if sid == producer) == [0, 1]
        assert xla_stats.placement_stats()["cross_chip_bytes"] == 0


def test_staged_auto_keeps_file_shuffle_on_host(tmp_path):
    """`auto` must not engage the device lane while compute is
    host-resident — the whole point of the placement gate."""
    from blaze_tpu.bridge.placement import host_resident
    if not host_resident():
        pytest.skip("device-resident platform: auto legitimately engages")
    plan = _two_stage_plan(tmp_path, n=2000)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)
    try:
        xla_stats.reset()
        sched = DagScheduler(work_dir=str(tmp_path / "dag"))
        sched.run_collect(plan)
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)
    assert all(st.device_spec is None for st in sched.stages)
    assert xla_stats.shuffle_stats()["shuffle_device_exchanges"] == 0


def test_explain_analyze_reports_shuffle_footer(tmp_path, device_mesh,
                                                staged_device):
    from blaze_tpu.plan.explain import QueryProfile
    xla_stats.reset()
    before = xla_stats.snapshot()
    plan = _two_stage_plan(tmp_path)
    sched = DagScheduler(work_dir=str(tmp_path / "dag"))
    sched.run_collect(plan)
    profile = QueryProfile(
        query_id="q-shuffle", wall_ns=1, tree=sched.collect_metrics(),
        partitions=3, exec_mode="staged", xla=xla_stats.delta(before),
        kernels={}, placement="device", output_rows=0)
    text = profile.render_text()
    assert "shuffle: device=" in text
    assert "exchanges" in text
    assert "fallbacks=0" in text

"""Cross-process shuffle tests: TWO real CPU processes exchange
.data/.index files through HostShuffleService, each writing its map
outputs and reducing its assigned partitions (VERDICT r1 #10; the
BlockManager/RSS transport analog)."""

import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

pytestmark = pytest.mark.dist  # deselect with -m 'not dist'

# child processes run on the host platform, stated in their environment
_CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}

WORKER = r"""
import json, os, sys
import pyarrow as pa
import pyarrow.parquet as pq
import blaze_tpu
from blaze_tpu.memory import MemManager
from blaze_tpu.parallel.distributed import HostShuffleService
from blaze_tpu.plan import create_plan
from blaze_tpu.shuffle.exchange import read_index_file

cfg = json.loads(sys.argv[1])
MemManager.init(4 << 30)
svc = HostShuffleService(cfg["root"], cfg["shuffle_id"],
                         num_maps=cfg["num_maps"],
                         num_reduces=cfg["num_reduces"])

# ---- map side: this process owns one map task ----
map_id = cfg["process_id"]
data, index = svc.map_output_paths(map_id)
plan = {
    "kind": "shuffle_writer",
    "partitioning": {"kind": "hash",
                     "exprs": [{"kind": "column", "index": 0}],
                     "num_partitions": cfg["num_reduces"]},
    "data_file": data, "index_file": index,
    "input": {"kind": "hash_agg",
              "groupings": [{"expr": {"kind": "column", "name": "k"},
                             "name": "k"}],
              "aggs": [{"fn": "sum", "mode": "partial", "name": "s",
                        "args": [{"kind": "column", "name": "v"}]}],
              "input": {"kind": "parquet_scan",
                        "schema": {"fields": [
                            {"name": "k", "type": {"id": "int64"},
                             "nullable": True},
                            {"name": "v", "type": {"id": "float64"},
                             "nullable": True}]},
                        "file_groups": [[cfg["input"]]]}}}
p = create_plan(plan)
for _ in p.execute(0):
    pass
svc.commit_map(map_id)

# ---- reduce side: wait for ALL processes' maps, reduce our partition ----
svc.wait_for_maps(timeout_s=90)
rid = f"xproc-{cfg['shuffle_id']}"
svc.register_reader(rid)
reduce_id = cfg["process_id"]
final = {
    "kind": "hash_agg",
    "groupings": [{"expr": {"kind": "column", "index": 0}, "name": "k"}],
    "aggs": [{"fn": "sum", "mode": "final", "name": "s",
              "args": [{"kind": "column", "index": 1}]}],
    "input": {"kind": "ipc_reader", "resource_id": rid,
              "schema": {"fields": [
                  {"name": "k", "type": {"id": "int64"},
                   "nullable": True},
                  {"name": "s.sum", "type": {"id": "float64"},
                   "nullable": True}]},
              "num_partitions": cfg["num_reduces"]}}
fp = create_plan(final)
out = [b.compact().to_arrow() for b in fp.execute(reduce_id)]
out = [b for b in out if b.num_rows]
tbl = (pa.Table.from_batches(out) if out
       else pa.table({"k": pa.array([], type=pa.int64()),
                      "s": pa.array([], type=pa.float64())}))
pq.write_table(tbl, cfg["result"])
print("OK", tbl.num_rows)
"""


def test_two_processes_exchange_shuffle_files(tmp_path):
    import pyarrow.parquet as pq
    rng = np.random.default_rng(0)
    n = 20_000
    t = pa.table({"k": pa.array(rng.integers(0, 300, n), type=pa.int64()),
                  "v": pa.array(rng.random(n))})
    # each process scans its own half of the input (its "executor split")
    half = n // 2
    inputs = []
    for i, sl in enumerate((t.slice(0, half), t.slice(half))):
        p = str(tmp_path / f"input-{i}.parquet")
        pq.write_table(sl, p)
        inputs.append(p)

    root = str(tmp_path / "exchange")
    procs = []
    results = [str(tmp_path / f"result-{i}.parquet") for i in range(2)]
    for pid in range(2):
        cfg = {"root": root, "shuffle_id": "t1", "num_maps": 2,
               "num_reduces": 2, "process_id": pid,
               "input": inputs[pid], "result": results[pid]}
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER, json.dumps(cfg)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            env=_CPU_ENV,
            cwd=os.path.dirname(os.path.dirname(__file__))))
    try:
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err.decode()[-2000:]
            assert out.decode().startswith("OK")
    finally:
        for p in procs:  # never orphan a hung worker
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)

    got = pa.concat_tables([pq.read_table(r) for r in results]).to_pandas()
    want = t.to_pandas().groupby("k", as_index=False).v.sum()
    got = got.sort_values("k").reset_index(drop=True)
    want = want.sort_values("k").reset_index(drop=True)
    assert len(got) == len(want)
    # every key must land in exactly one reducer
    assert got.k.is_unique
    np.testing.assert_allclose(got["s"].to_numpy(), want.v.to_numpy(),
                               rtol=1e-9)


def test_wait_for_maps_times_out(tmp_path):
    from blaze_tpu.parallel.distributed import HostShuffleService
    svc = HostShuffleService(str(tmp_path), "never", num_maps=1,
                             num_reduces=1)
    with pytest.raises(TimeoutError):
        svc.wait_for_maps(timeout_s=0.2, poll_s=0.05)


def test_init_distributed_smoke():
    """jax.distributed bootstrap in a subprocess (single-process world:
    the multi-host path with num_processes=1)."""
    code = (
        "from blaze_tpu.parallel.distributed import init_distributed\n"
        "n = init_distributed('127.0.0.1:12355', 1, 0)\n"
        "print('DEVICES', n)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       timeout=120, env=_CPU_ENV,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr.decode()[-2000:]
    assert b"DEVICES" in r.stdout

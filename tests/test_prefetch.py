"""Async pipeline executor semantics (ops/base.py PrefetchIterator):
ordering, exception propagation, clean close (no leaked threads),
synchronous degradation, and the default-on wiring at the IO edges."""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu import config
from blaze_tpu.bridge import xla_stats
from blaze_tpu.ops.base import PrefetchIterator, prefetch


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("blaze-prefetch")]


# the threads are the process's: a file that ran earlier on this worker
# may have left workers of its own blocked (a task that failed with its
# scan half read), and those are not this test's to wait for
_inherited: set = set()


@pytest.fixture(autouse=True)
def _threads_of_earlier_tests():
    _inherited.clear()
    _inherited.update(_prefetch_threads())
    yield


def _wait_no_threads(timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        alive = [t for t in _prefetch_threads()
                 if t.is_alive() and t not in _inherited]
        if not alive:
            return True
        time.sleep(0.01)
    return False


def test_ordering_preserved():
    items = list(range(200))
    assert list(prefetch(iter(items), depth=3)) == items
    assert _wait_no_threads()


def test_transform_applied_on_worker():
    worker_threads = set()

    def xform(x):
        worker_threads.add(threading.current_thread().name)
        return x * 2

    out = list(prefetch(iter(range(50)), depth=2, transform=xform,
                        name="xform"))
    assert out == [x * 2 for x in range(50)]
    assert all(n.startswith("blaze-prefetch") for n in worker_threads)
    assert _wait_no_threads()


def test_exception_reraised_at_consumer_in_position():
    def gen():
        yield 1
        yield 2
        raise ValueError("decode failed")

    it = prefetch(gen(), depth=2)
    assert next(it) == 1
    assert next(it) == 2
    with pytest.raises(ValueError, match="decode failed"):
        next(it)
    # exhausted after the error; worker gone
    with pytest.raises(StopIteration):
        next(it)
    assert _wait_no_threads()


def test_transform_exception_propagates():
    def boom(x):
        if x == 3:
            raise RuntimeError("transform blew up")
        return x

    it = prefetch(iter(range(10)), depth=2, transform=boom)
    assert [next(it), next(it), next(it)] == [0, 1, 2]
    with pytest.raises(RuntimeError, match="transform blew up"):
        for _ in it:
            pass
    assert _wait_no_threads()


def test_close_drains_blocked_worker():
    produced = []

    def gen():
        for i in range(10_000):
            produced.append(i)
            yield i

    it = prefetch(gen(), depth=2)
    assert next(it) == 0
    it.close()
    assert _wait_no_threads(), "close() must join the worker"
    # bounded queue: the worker never ran away from the consumer
    assert len(produced) <= 10
    with pytest.raises(StopIteration):
        next(it)
    it.close()  # idempotent


def test_no_leaked_threads_after_full_consumption():
    for _ in range(5):
        assert len(list(prefetch(iter(range(100)), depth=4))) == 100
    assert _wait_no_threads()


def test_depth_zero_is_synchronous():
    base = len(_prefetch_threads())
    it = prefetch(iter(range(10)), depth=0, transform=lambda x: x + 1)
    assert len(_prefetch_threads()) == base, "depth=0 must not spawn"
    assert list(it) == list(range(1, 11))


def test_kill_switch_disables_thread():
    with config.scoped(**{"auron.tpu.io.prefetch": False}):
        base = len(_prefetch_threads())
        it = prefetch(iter(range(5)))
        assert len(_prefetch_threads()) == base
        assert list(it) == list(range(5))


def test_default_depth_from_config():
    with config.scoped(**{"auron.tpu.io.prefetch.depth": 3}):
        it = prefetch(iter(range(5)))
        assert it._queue is not None and it._queue.maxsize == 3
        assert list(it) == list(range(5))
        assert _wait_no_threads()


def test_prefetch_stats_counted():
    before = xla_stats.snapshot()
    list(prefetch(iter(range(20)), depth=2))
    d = xla_stats.delta(before)
    assert d["prefetch_batches"] == 20
    assert d["prefetch_wait_ns"] >= 0


def test_empty_source():
    assert list(prefetch(iter(()), depth=2)) == []
    assert _wait_no_threads()


# -- default-on wiring at the IO edges ---------------------------------------

def _parquet(tmp_path, n=3000):
    rng = np.random.default_rng(0)
    t = pa.table({"k": pa.array(rng.integers(0, 9, n)),
                  "v": pa.array(rng.random(n))})
    path = str(tmp_path / "t.parquet")
    import pyarrow.parquet as pq
    pq.write_table(t, path, row_group_size=700)
    return path, t


def test_parquet_scan_prefetches_by_default(tmp_path):
    from blaze_tpu.ops.scan import ParquetScanExec
    from blaze_tpu.schema import Schema
    path, t = _parquet(tmp_path)
    scan = ParquetScanExec(Schema.from_arrow(t.schema), [[path]],
                           batch_rows=512)
    before = xla_stats.snapshot()
    rows = sum(b.num_rows for b in scan.execute(0))
    assert rows == t.num_rows
    assert xla_stats.delta(before)["prefetch_batches"] > 0
    assert _wait_no_threads()


def test_parquet_scan_prefetch_kill_switch_matches(tmp_path):
    from blaze_tpu.ops.scan import ParquetScanExec
    from blaze_tpu.schema import Schema
    path, t = _parquet(tmp_path)

    def collect():
        scan = ParquetScanExec(Schema.from_arrow(t.schema), [[path]],
                               batch_rows=512)
        out = [b.compact().to_arrow() for b in scan.execute(0)]
        return pa.Table.from_batches([b for b in out if b.num_rows])

    on = collect()
    with config.scoped(**{"auron.tpu.io.prefetch": False}):
        before = xla_stats.snapshot()
        off = collect()
        assert xla_stats.delta(before)["prefetch_batches"] == 0
    assert on.equals(off)


def test_explain_analyze_surfaces_prefetch_stats(tmp_path):
    from blaze_tpu.ops.scan import ParquetScanExec
    from blaze_tpu.plan import explain_analyze
    from blaze_tpu.schema import Schema
    path, t = _parquet(tmp_path)
    scan = ParquetScanExec(Schema.from_arrow(t.schema), [[path]],
                           batch_rows=512)
    prof = explain_analyze(scan, record=False)
    assert prof.output_rows == t.num_rows
    assert prof.xla.get("prefetch_batches", 0) > 0
    assert "prefetch:" in prof.render_text()
    assert _wait_no_threads()


def test_shuffle_roundtrip_under_prefetch():
    """Map-side materialization + reduce-side IPC reads run through the
    prefetcher by default and stay byte-identical to the synchronous
    path."""
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.shuffle import HashPartitioning, LocalShuffleExchange

    rng = np.random.default_rng(1)
    t = pa.table({"k": pa.array(rng.integers(0, 32, 5000)),
                  "v": pa.array(rng.random(5000))})

    def run():
        scan = MemoryScanExec.from_arrow(t, num_partitions=2,
                                         batch_rows=700)
        ex = LocalShuffleExchange(scan, HashPartitioning([col(0, "k")], 4))
        parts = []
        for p in range(4):
            rows = [b.compact().to_arrow() for b in ex.execute(p)]
            tab = (pa.Table.from_batches([r for r in rows if r.num_rows],
                                         schema=ex.schema.to_arrow())
                   if rows else None)
            parts.append(tab.sort_by([("k", "ascending"),
                                      ("v", "ascending")])
                         if tab is not None else None)
        ex.cleanup()
        return parts

    before = xla_stats.snapshot()
    on = run()
    assert xla_stats.delta(before)["prefetch_batches"] > 0
    with config.scoped(**{"auron.tpu.io.prefetch": False}):
        off = run()
    for a, b in zip(on, off):
        assert (a is None) == (b is None)
        if a is not None:
            assert a.equals(b)
    assert _wait_no_threads()

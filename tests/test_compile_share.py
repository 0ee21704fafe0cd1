"""bridge/compile_share.py: a single-device program is compiled once and
kept once in the persistent cache, whichever device asks for it."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.bridge import compile_share

HIT = "/jax/compilation_cache/cache_hits"
ASK = "/jax/core/compile/backend_compile_duration"


class _Counts:
    def __init__(self):
        self.hits = self.asks = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._secs)

    def _event(self, event, **_kw):
        self.hits += event == HIT

    def _secs(self, event, _secs, **_kw):
        self.asks += event == ASK


@pytest.fixture(scope="module")
def counts():
    compile_share.install()   # as `parallel/mesh.task_device` does
    return _Counts()


def _fresh_program():
    """A program no cache has seen: the clock's nanoseconds and the
    process id, digit by digit, are constants in its text (small
    integers, so the float sums stay exact in any order)."""
    salt = [int(d) for d in f"{time.time_ns()}{os.getpid()}"]

    def body(xp, x):
        y = xp.sort(x) * 3
        for d in salt:
            y = y + d
        return y.sum()

    return jax.jit(lambda x: body(jnp, x)), lambda x: body(np, x)


def test_the_second_device_loads_what_the_first_compiled(counts):
    if len(jax.devices()) < 3:
        pytest.skip("needs several devices")
    f, ref = _fresh_program()
    x = np.arange(4096.0)[::-1].copy()
    seen = []
    for dev in jax.devices()[:3]:
        h0, a0 = counts.hits, counts.asks
        y = f(jax.device_put(x, dev))
        seen.append((counts.asks - a0, counts.hits - h0))
        assert y.devices() == {dev}
        assert float(y) == ref(x)
    assert seen == [(1, 0), (1, 1), (1, 1)]


def test_threads_on_four_devices_compile_a_program_once(counts):
    if len(jax.devices()) < 4:
        pytest.skip("needs several devices")
    f, ref = _fresh_program()
    x = np.arange(2048.0)
    out = {}

    def one(i):
        y = f(jax.device_put(x, jax.devices()[i]))
        out[i] = (float(y), y.devices())

    h0, a0 = counts.hits, counts.asks
    threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert out == {i: (ref(x), {jax.devices()[i]}) for i in range(4)}
    assert (counts.asks - a0, counts.hits - h0) == (4, 3)


def test_a_program_over_a_mesh_keeps_its_own_key(counts, device_mesh):
    """Only a one-device program is re-keyed: a program over several
    devices is asked for as JAX asks for it."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    f, ref = _fresh_program()
    x = jax.device_put(np.arange(8.0 * len(jax.devices())),
                       NamedSharding(device_mesh, P("dp")))
    assert float(f(x)) == ref(np.asarray(x))


def test_install_says_so_when_jax_has_moved(monkeypatch):
    """What would follow in silence (every chip compiling every program
    for itself) is the state that never finished a cold run."""
    from jax._src import compiler
    monkeypatch.setattr(compile_share, "_installed", False)
    monkeypatch.delattr(compiler, "compile_or_get_cached")
    with pytest.raises(RuntimeError, match="compile_share"):
        compile_share.install()
    assert not compile_share._installed


def test_a_process_of_one_device_leaves_jax_alone(monkeypatch):
    """`task_device` installs the sharing only where it hands out one
    chip of several."""
    from blaze_tpu import config
    from blaze_tpu.parallel import mesh
    calls = []
    monkeypatch.setattr(compile_share, "install",
                        lambda: calls.append(1))
    with config.scoped(**{"auron.tpu.mesh.devices": 1}):
        assert mesh.task_device(3) is None
    assert not calls
    if len(jax.devices()) > 1:
        with config.scoped(**{"auron.tpu.mesh.devices": 2}):
            assert mesh.task_device(3) == jax.devices()[1]
        assert calls == [1]

"""Chip bring-up contracts that a CPU host can hold the code to (ISSUE 21):
where the compile cache lives, that a forced device placement without a
device raises, and that chip_smoke.py refuses to run without a chip."""

import os
import subprocess
import sys

import pytest

import jax

from blaze_tpu import config
from blaze_tpu.bridge import placement as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PRINT_CACHE = ("import jax, blaze_tpu; "
                "print(jax.config.jax_compilation_cache_dir); "
                "print(blaze_tpu.COMPILE_CACHE_DIR)")


def _cache_dirs(cwd, **env_changes):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    r = subprocess.run([sys.executable, "-c", _PRINT_CACHE], cwd=cwd,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


# -- (a) compile cache resolution --------------------------------------------

def test_cache_dir_from_environment_is_left_to_jax(tmp_path):
    given = str(tmp_path / "given-cache")
    jax_dir, engine_dir = _cache_dirs(str(tmp_path),
                                      JAX_COMPILATION_CACHE_DIR=given)
    assert jax_dir == given and engine_dir == given
    # the engine created nothing of its own there or anywhere it names
    assert not os.path.exists(given)


def test_cache_dir_default_is_fixed_inside_the_checkout(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir()
    b.mkdir()
    seen = [_cache_dirs(str(cwd), JAX_COMPILATION_CACHE_DIR=None,
                        HOME=str(cwd)) for cwd in (a, b)]
    assert seen == [[want, want], [want, want]]
    assert os.path.isdir(want)
    assert not hasattr(config, "COMPILE_CACHE_DIR")  # the key is gone


# -- (b) forced device placement without a device ----------------------------

def test_placement_device_without_accelerator_raises():
    assert jax.default_backend() == "cpu"
    saved = P._info
    config.conf.set(config.PLACEMENT.key, "device")
    P._info = None
    try:
        with pytest.raises(RuntimeError, match="no accelerator"):
            P.ensure_placement()
        assert P.placement_info() is None  # nothing was decided
    finally:
        config.conf.unset(config.PLACEMENT.key)
        P._info = saved


# -- one process per chip ------------------------------------------------------

def test_child_that_would_contend_for_the_chip_is_refused(monkeypatch):
    from blaze_tpu.parallel.workers import WorkerPool, _Slot
    held = P.PlacementInfo("tpu", "tpu", 1.0, "auto")
    monkeypatch.setattr(P, "_info", held)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(RuntimeError, match="holds the tpu"):
        WorkerPool._child_env(_Slot(0))
    with pytest.raises(RuntimeError, match="replica r9"):
        from blaze_tpu.fleet import spawn_replica
        spawn_replica("r9", platform="tpu")
    # a child told to stay on the host is fine, and its platform is stated
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert WorkerPool._child_env(_Slot(0))["JAX_PLATFORMS"] == "cpu"
    # a parent on the host platform may spawn whatever it is given
    monkeypatch.setattr(P, "_info", P.PlacementInfo("cpu", "cpu", 0.0,
                                                    "auto"))
    P.refuse_chip_contention({}, "x")


def test_worker_hello_states_its_platform():
    from blaze_tpu.parallel.workers import WorkerPool
    pool = WorkerPool(count=1, liveness_ms=60000).start()
    try:
        assert pool.run({"fn": "blaze_tpu.parallel.workers:_task_echo",
                         "args": (1,)}, timeout_s=60) is not None
        assert pool.health()[0]["platform"] == \
            (os.environ.get("JAX_PLATFORMS") or "default")
    finally:
        pool.shutdown()


# -- (c) the smoke refuses a machine without a chip --------------------------

def test_chip_smoke_exits_nonzero_without_a_chip(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=str(tmp_path), env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 2
    assert "no accelerator" in r.stderr and "'cpu'" in r.stderr
    # named the missing chip BEFORE any data: no phase line, no result
    assert r.stdout == ""
    assert not os.listdir(tmp_path)

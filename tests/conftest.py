"""Test config: tests run on the CPU platform with 8 virtual devices so
sharding / collective paths are exercised without TPU hardware (the
reference's analog: spark-local[N] exercising the full shuffle path
without a cluster, SURVEY.md §4).

The platform is forced here, in code, so that a bare `pytest` on a machine
with a chip attached still runs the suite on the host and never takes the
chip; `JAX_PLATFORMS=cpu` in the environment says the same thing.  The
chip is not pytest's: the engine path is proven there by a benchmark
cell's run (`python3 benchmark/run.py --workload <cell> ...`, every
answer compared), the kernels alone by `python chip_smoke.py`.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


import pytest  # noqa: E402


@pytest.fixture(scope="session")
def device_mesh():
    """Session-wide dp mesh over every virtual device (8 on the forced
    host platform above); multi-device collective tests share it so the
    shard_map programs compile once per session."""
    from blaze_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("multi-device mesh unavailable")
    return make_mesh(len(jax.devices()))


def _build_native_libs() -> None:
    """Build the C++ libs (zstd IPC codec + host bridge) so their tests
    are always load-bearing instead of skipped."""
    from blaze_tpu.bridge.native import NativeBuildError, build_native_libs
    try:
        build_native_libs()
    except NativeBuildError as e:  # missing toolchain: tests fall to skips
        import warnings
        warnings.warn(f"{e}; bridge/codec tests will skip")


_build_native_libs()

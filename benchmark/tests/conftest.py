"""The harness's own tests run on the CPU platform with 8 virtual devices,
as `tests/conftest.py` sets them up, so that a four-chip cell finds four.
Run them with `python -m pytest benchmark/tests -q`.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture
def device_path(monkeypatch):
    """The deployment the cells time, as the CPU can rehearse it: batches
    on the devices and every plan staged.  Returns what sets the mesh for a
    cell (`auron.tpu.mesh.devices` to its chips)."""
    from blaze_tpu import config
    import blaze_tpu.batch as B
    import blaze_tpu.bridge.placement as P
    monkeypatch.setattr(P, "host_resident", lambda: False)
    monkeypatch.setattr(B, "_host_resident", lambda: False)
    config.conf.set(config.DAG_SINGLE_TASK_BYTES.key, 0)

    def for_chips(chips: int) -> None:
        config.conf.set(config.MESH_DEVICES.key, chips)

    try:
        yield for_chips
    finally:
        config.conf.unset(config.DAG_SINGLE_TASK_BYTES.key)
        config.conf.unset(config.MESH_DEVICES.key)

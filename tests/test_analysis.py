"""Static-verifier tests: plan rules, protocol models, epoch lint,
fixtures, and the CLI gate (ISSUE: every rule family needs at least one
passing case on real seed artifacts AND one seeded-bug fixture it flags).

The HLO family (which compiles real programs) lives in
``test_analysis_hlo.py``; everything here is host-only and fast.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bluefog_tpu import topology_util as tu
from bluefog_tpu.analysis import (
    Severity,
    epoch_rules,
    fixtures,
    plan_rules,
    registry,
    seqlock_model,
)
from bluefog_tpu.core.plan import compile_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# plan family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(plan_rules.CORPUS_TOPOLOGIES))
@pytest.mark.parametrize("n", [2, 5, 8, 16, 63])
def test_seed_plans_pass_all_plan_rules(name, n):
    topo = plan_rules.CORPUS_TOPOLOGIES[name](n)
    plan = compile_plan(topo)
    report = plan_rules.check_plan(plan, topo, f"{name}@{n}")
    assert report.ok, report.summary() + "\n" + "\n".join(
        str(f) for f in report.findings)


def test_spectral_gap_matches_eig_by_hand():
    # ring@4 with uniform 1/3 weights: W = circulant(1/3,1/3,0,1/3),
    # eigvals {1, 1/3, -1/3, 1/3} -> gap = 2/3
    plan = compile_plan(tu.RingGraph(4))
    gap = plan_rules.spectral_gap(plan.mixing_matrix())
    assert abs(gap - 2.0 / 3.0) < 1e-9


def test_dynamic_one_peer_steps_are_single_class():
    report = registry.run(families=["plan"])
    assert report.ok, "\n".join(str(f) for f in report.errors())
    # the corpus metric must be present and positive for every family
    gaps = {k: v for k, v in report.metrics.items()
            if k.startswith("plan.min_spectral_gap/")}
    assert set(gaps) == {
        f"plan.min_spectral_gap/{fam}" for fam in plan_rules.CORPUS_TOPOLOGIES}
    assert all(v > 0 for v in gaps.values()), gaps


def test_mixing_matrix_row_sum_rule_fires_on_tamper():
    findings = fixtures.run_fixture("plan-tampered-weights")
    assert findings and all(f.rule == "plan.mixing-stochastic"
                            for f in findings)


# ---------------------------------------------------------------------------
# protocol family: the models accept the real protocol, reject seeded bugs
# ---------------------------------------------------------------------------


def test_real_seqlock_has_no_torn_reads():
    for n_writers, deposits in ((1, 2), (2, 1), (2, 2)):
        m = seqlock_model.seqlock_model(n_writers=n_writers,
                                        deposits=deposits)
        assert seqlock_model.explore(m) == []


def test_seqlock_model_matches_native_spec():
    """The model's writer program is asserted against
    shm_native.SEQLOCK_WRITER_STEPS at build time — a drifted spec raises
    here rather than silently verifying the wrong protocol."""
    seqlock_model.seqlock_model(1, 1)  # assertion lives in the builder


@pytest.mark.parametrize("fixture", [
    "seqlock-skip-odd-phase",
    "seqlock-publish-before-payload",
    "seqlock-no-writer-lock",
])
def test_broken_seqlock_variants_produce_torn_reads(fixture):
    findings = fixtures.run_fixture(fixture)
    assert findings and any("torn read" in f.message for f in findings)


def test_collect_conserves_mass_and_split_variant_loses_it():
    assert seqlock_model.explore(seqlock_model.collect_model(3)) == []
    bad = seqlock_model.explore(
        seqlock_model.collect_model(2, atomic_collect=False))
    assert bad and any("lost deposit" in v for v in bad)


def test_barrier_never_deadlocks_and_bugged_order_does():
    assert seqlock_model.explore(seqlock_model.barrier_model(3, 2)) == []
    bad = seqlock_model.explore(
        seqlock_model.barrier_model(2, 2, reset_before_release=False))
    assert bad and any("deadlock" in v for v in bad)


# ---------------------------------------------------------------------------
# epoch family
# ---------------------------------------------------------------------------


def test_canonical_window_traces_pass():
    for label, trace in epoch_rules.CANONICAL_TRACES.items():
        findings = epoch_rules.check_trace(trace, subject=label)
        assert findings == [], (label, [str(f) for f in findings])


def test_use_after_free_and_get_clobber_fire():
    for name in ("epoch-use-after-free", "epoch-get-clobbers-put"):
        findings = fixtures.run_fixture(name)
        assert findings and findings[0].severity == Severity.ERROR, name


def test_put_after_accumulate_warns():
    findings = epoch_rules.check_trace([
        ("win_create", "w"), ("win_accumulate", "w"), ("win_put", "w"),
        ("win_update", "w")])
    assert len(findings) == 1
    assert findings[0].severity == Severity.WARNING
    assert "discards the accumulated" in findings[0].message


def test_recorded_live_trace_passes_epoch_lint(devices):
    """End-to-end: record a REAL win-op session via windows.record_win_ops
    and lint the trace — the runtime's own idiom must satisfy the rules it
    is checked against."""
    import jax.numpy as jnp

    import bluefog_tpu as bf
    from bluefog_tpu import windows

    bf.init(local_size=2)
    try:
        x = jnp.zeros((8, 4))
        with windows.record_win_ops() as trace:
            bf.win_create(x, "lint_me")
            bf.win_accumulate(x, "lint_me")
            bf.win_update_then_collect("lint_me")
            bf.win_put(x, "lint_me")
            bf.win_update("lint_me")
            bf.win_free("lint_me")
        assert ("win_create", "lint_me") in trace
        assert epoch_rules.check_trace(trace, "live-session") == []
    finally:
        bf.win_free()
        bf.shutdown()


# ---------------------------------------------------------------------------
# fixture corpus + CLI gate
# ---------------------------------------------------------------------------


def test_every_fixture_fires():
    dead = [name for name in fixtures.FIXTURES
            if not fixtures.run_fixture(name)]
    assert dead == [], f"seeded bugs never caught: {dead}"


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.analysis", *args],
        capture_output=True, text=True, timeout=240, cwd=REPO,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))


def test_cli_gate_passes_on_seed_corpus():
    """The CI gate: CLI exits 0 over the default (non-hlo) corpus and
    nonzero on a seeded-bug fixture."""
    proc = _run_cli("--no-hlo", "--json")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    payload = json.loads(proc.stdout)
    assert payload["ok"] and payload["subjects_checked"] > 400


def test_cli_exits_nonzero_on_seeded_bug():
    proc = _run_cli("--fixture", "plan-dropped-edge")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "plan.edge-cover" in proc.stdout


# `slow`: `make selftest` is this gate (`make verify` runs it before the tests),
# and `test_every_fixture_fires` holds the fixtures in the timed run; 138 s
# here, 153-240 s under load against `_run_cli`'s own 240
@pytest.mark.slow
def test_cli_self_test_catches_every_seeded_bug():
    proc = _run_cli("--self-test")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "self-test OK" in proc.stdout

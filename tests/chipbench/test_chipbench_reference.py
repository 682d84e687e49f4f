"""The plain references against the program at the `rehearsal` sizes, the
mixing matrices against the program's topologies, and the controls: a step or
a payload computed in a lower precision than the configuration states has to
fail the comparison that decides `correct`, and so has a timed path that is
broken underneath."""

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bluefog_tpu as bf

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import check, control, manifest, optimizers, runner, seeded  # noqa: E402


def test_mixing_matrices_are_the_topologies_written_from_their_definitions():
    from bluefog_tpu import topology_util

    exp2 = manifest.load_module(os.path.join(REPO, "chipbench/mixing/exp2.py"))
    for n in (1, 2, 4, 8):
        W = topology_util.GetWeightMatrix(topology_util.ExponentialTwoGraph(n))
        np.testing.assert_allclose(exp2.matrix(n), W, atol=1e-12)
    np.testing.assert_allclose(exp2.matrix(4)[0], [1 / 3, 0, 1 / 3, 1 / 3])
    ring = manifest.load_module(os.path.join(REPO, "chipbench/mixing/ring1_pushsum.py"))
    assert ring.matrix(1).tolist() == [[0.5]]  # no edge: the deposit is dropped
    M = ring.matrix(4)
    np.testing.assert_allclose(M.sum(axis=0), 1.0)  # column-stochastic
    assert M[1, 0] == 0.5 and M[0, 0] == 0.5 and M[0, 3] == 0.5 and M[2, 0] == 0
    # the edges are the program's ring: i -> i+1
    G = topology_util.RingGraph(4, connect_style=1)
    assert sorted(G.edges()) == sorted(
        (i, j) for i in range(4) for j in range(4) if i != j and M[j, i] > 0)


def test_mix_is_M_x_over_M_1_and_a_rounded_payload_shows():
    x = np.random.default_rng(0).normal(size=(4, 5, 3)).astype(np.float32)
    ring = manifest.load_module(os.path.join(REPO, "chipbench/mixing/ring1_pushsum.py"))
    got, p = check.mix(ring.matrix(4), x)
    np.testing.assert_allclose(p, 1.0)
    x64 = x.astype(np.float64)
    np.testing.assert_allclose(got[1], 0.5 * x64[1] + 0.5 * x64[0], rtol=1e-12)
    one, p1 = check.mix(ring.matrix(1), x[:1])
    assert p1.tolist() == [0.5]
    np.testing.assert_array_equal(one, x[:1])  # (x/2) / (1/2)
    low, _ = check.mix(ring.matrix(4), x, lower_payload=True)
    rel = np.linalg.norm(low - got) / np.linalg.norm(got)
    assert 1e-4 < rel < 1e-2  # bfloat16 keeps 8 bits of mantissa
    own, _ = check.mix(ring.matrix(1), x[:1], lower_payload=True,
                       payload_includes_self=True)
    assert np.linalg.norm(own - one) > 0


def _first_cell_of_every_configuration():
    """{configuration: the first cell that names it}, from the manifest, so
    that a new configuration is a new case."""
    first = {}
    for w in manifest.load_manifest()["workloads"]:
        first.setdefault(w["config"], w["name"])
    return first


FIRST_CELLS = _first_cell_of_every_configuration()


@pytest.mark.parametrize("workload", FIRST_CELLS.values(), ids=list(FIRST_CELLS))
def test_reference_agrees_with_the_program_at_rehearsal_sizes(workload):
    import optax

    from bluefog_tpu.training import apply_accepts_labels

    cell = manifest.resolve(workload)
    sizes = cell.sizes(rehearse=True)
    ref = cell.module("reference")
    program = cell.module("program").build(sizes)
    params, stats = seeded.make_weights(ref, sizes, seed=7)
    (x, y), = seeded.make_batches(ref, sizes, 7, ranks=1, pool=1)
    x, y = x[0], y[0]
    # the program's own loss where it gives one (a model that returns the
    # chunked scalar loss hands over the identity), as the job passes it on
    loss_of = program.get("loss_fn", lambda logits, labels: (
        optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()))

    def program_loss(p):
        variables = {"params": seeded.nest(p)}
        if program["has_batch_stats"]:
            variables["batch_stats"] = seeded.nest(stats)
            out, _ = program["apply_fn"](variables, x, mutable=["batch_stats"])
        elif apply_accepts_labels(program["apply_fn"]):
            out = program["apply_fn"](variables, x, labels=y)
        else:
            out = program["apply_fn"](variables, x)
        return loss_of(out, y)

    lp, gp = jax.jit(jax.value_and_grad(program_loss))(params)
    (lr, new_stats), gr = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, stats, x, y, sizes), has_aux=True))(params)
    assert abs(float(lp) - float(lr)) < 2e-3
    assert set(gp) == set(gr) == set(ref.param_shapes(sizes)[0])
    for path in gr:
        a, b = np.asarray(gp[path], np.float64), np.asarray(gr[path], np.float64)
        scale = max(np.linalg.norm(b), 1e-3)
        assert np.linalg.norm(a - b) / scale < 0.05, path
    if stats:
        assert set(new_stats) == set(stats)


def test_seeded_inputs_repeat_for_a_seed_and_differ_between_seeds():
    cell = manifest.resolve("bert-base-pushsum-1chip")
    sizes, ref = cell.sizes(rehearse=True), cell.module("reference")
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    a = seeded.make_batches(ref, sizes, big, ranks=2, pool=2)
    b = seeded.make_batches(ref, sizes, big, ranks=2, pool=2)
    c = seeded.make_batches(ref, sizes, big + 1, ranks=2, pool=2)
    np.testing.assert_array_equal(a[1][0], b[1][0])
    assert not np.array_equal(a[0][0], a[1][0]) and not np.array_equal(a[0][0], c[0][0])
    x = np.asarray(a[0][0]).reshape(-1, sizes["seq_len"])
    assert len({row.tobytes() for row in x}) == len(x)  # rows all differ


@pytest.mark.parametrize("workload,controls", [
    ("bert-base-pushsum-1chip", ["step", "payload"]),
    # exp2(1) has no edge: nothing travels, so there is no payload to round
    ("bert-base-atc-b128-1chip", ["step"]),
    ("smallthinker-21b-a3b-atc-b2-s8k-1chip", ["step"]),
    # the same; the learning rate warms up, so the three steps run at 1.5e-7 to
    # 4.5e-7 and the limits have to hold at changes that small
    ("smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip", ["step"]),
])
def test_sound_readings_pass_and_the_controls_fail(workload, controls):
    """chipbench.control at the rehearsal sizes, one CPU device: the program
    against the reference is inside every limit; the reference computed in
    float8, and where a payload travels the payload rounded to bfloat16, each
    fail at least one."""
    ses = runner.Session(manifest.resolve(workload), rehearse=True)
    try:
        row = control.readings(ses, 2**31 + 5, controls)
    finally:
        bf.shutdown()
    limits = ses.reference.LIMITS

    def failed(part):
        return [k for k, v in row[part].items() if k in limits and not v <= limits[k]]

    assert failed("sound") == [], row["sound"]
    for name in controls:
        assert failed("control_" + name), row["control_" + name]


def test_the_references_step_donates_and_the_start_is_kept_on_the_host():
    """The device holds p, mu, nu and the gradient, 16 bytes a parameter,
    beside the step's activations: the step takes its parameters and its
    optimizer state for its own, and `reference_run` returns the start as the
    host's copy with every other capture."""
    ses = runner.Session(manifest.resolve("bert-base-atc-b128-1chip"), rehearse=True)
    try:
        ses.load(5)
        params, stats = ses._ref_weights(ses.key)
        start = {p: np.array(a) for p, a in params.items()}
        step = check.local_step_fn(ses.reference, ses.sizes, ses.opt_spec)
        opt_state = optimizers.make(ses.opt_spec).init(params)
        x, y = (a[0] for a in ses.batches[0])
        new_p, _, new_o, loss, g = step(params, stats, opt_state, x, y)
        given = jax.tree_util.tree_leaves((params, opt_state))
        assert all(a.is_deleted() for a in given)
        assert not any(a.is_deleted() for a in jax.tree_util.tree_leaves((new_p, new_o, g)))
        ref = check.reference_run(
            ses.reference, ses.sizes, ses.opt_spec, ses.M, ses.seed, ses.batches,
            weights=ses._ref_weights(ses.key))
    finally:
        bf.shutdown()
    assert set(ref) == {"losses", "assoc_p", "params0", "grad_norms", "params1",
                        "delta_norms"}
    assert ref["losses"][0, 0] == float(loss)
    for p, a in start.items():
        np.testing.assert_array_equal(ref["params0"][p][0], a)
        np.testing.assert_array_equal(ref["params1"][p][0], np.asarray(new_p[p]))
        assert ref["grad_norms"][p].shape == (1,) and ref["delta_norms"][p].shape == (1,)
    assert np.isfinite(ref["losses"]).all() and ref["losses"].shape == (check.STEPS, 1)
    assert min(float(v[0]) for v in ref["delta_norms"].values()) > 0


class _Frozen:
    """A timed path broken underneath: the step runs and returns a loss, but
    the state it leaves behind is the state it was given (a copy of it: the
    jitted step donates its own)."""

    def __init__(self, job):
        self.job = job

    def __getattr__(self, name):
        return getattr(self.job, name)

    def step(self, k):
        before = jax.tree_util.tree_map(jnp.copy, self.job.state)
        out = self.job.step(k)
        self.job.state = before
        return out


def _broken_run(workload, wrap_job, capsys):
    cell = manifest.resolve(workload)
    args = argparse.Namespace(workload=cell.name, seed=9, seconds=0.3,
                              trace=0, rehearse=True)
    result = runner.run(args, time.perf_counter(), cell, wrap_job=wrap_job)
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert result["failed"] == 0 and result["attempted"] > 0  # it ran; it is wrong
    assert "NOT OK" in out
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device",
                           "checks"}
    return result["checks"], out


DECODER_CELLS = ["smallthinker-21b-a3b-atc-b2-s8k-1chip",
                 "smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip"]


@pytest.mark.parametrize("workload", [
    "bert-base-pushsum-1chip", "bert-base-atc-b128-1chip", *DECODER_CELLS])
def test_a_step_that_returns_its_state_unchanged_is_not_correct(workload, capsys):
    checks, out = _broken_run(workload, _Frozen, capsys)
    assert "check delta_norm_gap" in out
    assert checks["delta_norm_gap"]["value"] > checks["delta_norm_gap"]["limit"]


def _half_batch(job):
    """A timed path broken underneath: the second half of every batch is left
    out and the mean taken over the rest (the first half stands in its place:
    the same mean, the same shapes).  The reference keeps the whole batch."""
    def halve(a):
        half = a.shape[1] // 2
        return jnp.concatenate([a[:, :half], a[:, :half]], axis=1)

    job.spec.batches = [tuple(halve(a) for a in batch)
                        for batch in job.spec.batches]
    return job


@pytest.mark.parametrize("workload", DECODER_CELLS)
def test_half_of_the_batch_left_out_is_not_correct(workload, capsys):
    checks, _ = _broken_run(workload, _half_batch, capsys)
    over = [name for name, c in checks.items() if c["value"] > c["limit"]]
    assert over and set(over) <= {"loss_gap", "grad_norm_gap", "delta_norm_gap",
                                  "change1_rel_l2"}, checks

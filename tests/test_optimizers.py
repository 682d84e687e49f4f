"""Optimizer tests (mirrors the reference's ``test/torch_optimizer_test.py``
— SURVEY.md §4: small-model training-loss-decreases per variant, plus exact
algebraic checks of the ATC/AWC/allreduce update rules)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import topology_util as tu
from bluefog_tpu.optim import CommunicationType

SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(devices):
    bf.init(local_size=2)
    yield
    bf.win_free()
    bf.shutdown()


def rank_params(shape=(3,)):
    r = jnp.arange(SIZE, dtype=jnp.float32).reshape((SIZE,) + (1,) * len(shape))
    return {"w": jnp.broadcast_to(r, (SIZE,) + shape)}


def test_atc_exact_update():
    """ATC with SGD: params' = W (params - lr * grad)."""
    bf.set_topology(tu.RingGraph(SIZE))
    lr = 0.1
    opt = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(lr))
    params = rank_params()
    grads = {"w": jnp.ones_like(params["w"])}
    state = opt.init(params)
    new_params, _ = opt.step(params, grads, state)
    W = tu.GetWeightMatrix(tu.RingGraph(SIZE))
    adapted = np.asarray(params["w"]) - lr
    expected = (W @ adapted.reshape(SIZE, -1)).reshape(adapted.shape)
    np.testing.assert_allclose(np.asarray(new_params["w"]), expected, rtol=1e-5)


def test_awc_exact_update():
    """AWC with SGD: params' = W params - lr * grad."""
    bf.set_topology(tu.RingGraph(SIZE))
    lr = 0.1
    opt = bf.DistributedAdaptWithCombineOptimizer(optax.sgd(lr))
    params = rank_params()
    grads = {"w": jnp.ones_like(params["w"])}
    state = opt.init(params)
    new_params, _ = opt.step(params, grads, state)
    W = tu.GetWeightMatrix(tu.RingGraph(SIZE))
    combined = (W @ np.asarray(params["w"]).reshape(SIZE, -1)).reshape(
        params["w"].shape
    )
    expected = combined - lr
    np.testing.assert_allclose(np.asarray(new_params["w"]), expected, rtol=1e-5)


def test_gradient_allreduce_equals_mean_gradient():
    lr = 0.5
    opt = bf.DistributedGradientAllreduceOptimizer(optax.sgd(lr))
    params = {"w": jnp.zeros((SIZE, 2))}
    g = jnp.arange(SIZE, dtype=jnp.float32)[:, None] * jnp.ones((SIZE, 2))
    state = opt.init(params)
    new_params, _ = opt.step(params, {"w": g}, state)
    expected = -lr * (SIZE - 1) / 2.0
    np.testing.assert_allclose(np.asarray(new_params["w"]), expected, rtol=1e-6)


def test_num_steps_per_communication():
    bf.set_topology(tu.RingGraph(SIZE))
    opt = bf.DistributedAdaptThenCombineOptimizer(
        optax.sgd(0.0), num_steps_per_communication=2
    )
    params = rank_params()
    grads = {"w": jnp.zeros_like(params["w"])}
    state = opt.init(params)
    # step 1 of 2: no communication, zero lr -> params unchanged
    p1, state = opt.step(params, grads, state)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(params["w"]), rtol=1e-6)
    # step 2 of 2: gossip fires
    p2, state = opt.step(p1, grads, state)
    W = tu.GetWeightMatrix(tu.RingGraph(SIZE))
    expected = (W @ np.asarray(params["w"]).reshape(SIZE, -1)).reshape(
        params["w"].shape
    )
    np.testing.assert_allclose(np.asarray(p2["w"]), expected, rtol=1e-5)


def test_empty_communication_type_is_local_sgd():
    opt = bf.DistributedAdaptThenCombineOptimizer(
        optax.sgd(0.1), communication_type=CommunicationType.empty
    )
    params = rank_params()
    grads = {"w": jnp.ones_like(params["w"])}
    state = opt.init(params)
    new_params, _ = opt.step(params, grads, state)
    np.testing.assert_allclose(
        np.asarray(new_params["w"]), np.asarray(params["w"]) - 0.1, rtol=1e-6
    )


def test_hierarchical_communication_type():
    bf.set_machine_topology(tu.RingGraph(4))
    opt = bf.DistributedAdaptThenCombineOptimizer(
        optax.sgd(0.0),
        communication_type=CommunicationType.hierarchical_neighbor_allreduce,
    )
    params = rank_params()
    state = opt.init(params)
    new_params, _ = opt.step(params, {"w": jnp.zeros_like(params["w"])}, state)
    out = np.asarray(new_params["w"])
    # all local ranks of a machine identical after hierarchical gossip
    for m in range(4):
        np.testing.assert_allclose(out[2 * m], out[2 * m + 1], rtol=1e-6)


def test_winput_optimizer_consensus():
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    opt = bf.DistributedWinPutOptimizer(optax.sgd(0.0))
    params = rank_params()
    state = opt.init(params)
    mean0 = np.asarray(params["w"]).mean(axis=0)
    cur = params
    for _ in range(25):
        cur, state = opt.step(cur, {"w": jnp.zeros_like(params["w"])}, state)
    np.testing.assert_allclose(
        np.asarray(cur["w"]), np.tile(mean0, (SIZE, 1)), atol=1e-3
    )
    opt.free()


def test_winput_fused_matches_per_leaf():
    """Leaf fusion (one packed window per dtype) is exactly the per-leaf
    schedule: same topology weights apply to every leaf."""
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    params = {
        "a": rank_params((3,))["w"],
        "b": rank_params((2, 2))["w"] * 2.0,
        "c": jnp.ones((SIZE, 5), jnp.float32) * jnp.arange(SIZE)[:, None],
    }
    grads = {k: jnp.ones_like(v) * 0.1 for k, v in params.items()}
    results = {}
    for fuse in (False, True):
        opt = bf.DistributedWinPutOptimizer(
            optax.sgd(0.05), window_prefix=f"fuse_eq_{fuse}", fuse=fuse
        )
        state = opt.init(params)
        cur = params
        for _ in range(4):
            cur, state = opt.step(cur, grads, state)
        results[fuse] = cur
        opt.free()
    for k in params:
        np.testing.assert_allclose(
            np.asarray(results[True][k]), np.asarray(results[False][k]), rtol=1e-6
        )


def _quadratic_loss_grads(params, targets):
    # per-rank quadratic: L_r = 0.5 || w_r - t_r ||^2, grad = w_r - t_r
    return {"w": params["w"] - targets}


def _descend(opt, params, state, targets, steps=300):
    for _ in range(steps):
        grads = _quadratic_loss_grads(params, targets)
        params, state = opt.step(params, grads, state)
        # one eager step in flight on the CPU mesh, never two: see
        # tests/test_algorithms.py::run
        jax.block_until_ready(params)
    return params


_SCHED = optax.exponential_decay(0.3, 1, 0.985)  # decaying step: exact consensus


@pytest.mark.parametrize(
    "opt_ctor",
    [
        lambda: bf.DistributedAdaptThenCombineOptimizer(optax.sgd(_SCHED)),
        lambda: bf.DistributedAdaptWithCombineOptimizer(optax.sgd(_SCHED)),
        lambda: bf.DistributedGradientAllreduceOptimizer(optax.sgd(0.2)),
    ],
)
def test_decentralized_optimization_converges(opt_ctor):
    """Decentralized least squares: each rank sees only its own target; the
    consensus solution is the mean of targets.  Every optimizer variant must
    drive all ranks there (arXiv:2111.04287 experiment family).  Decaying
    stepsizes (required by decentralized-SGD theory for exact consensus)
    for the gossip variants."""
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    rng = np.random.default_rng(3)
    targets = jnp.asarray(rng.normal(size=(SIZE, 3)).astype(np.float32))
    opt = opt_ctor()
    params = {"w": jnp.zeros((SIZE, 3))}
    state = opt.init(params)
    params = _descend(opt, params, state, targets)
    target_mean = np.asarray(targets).mean(axis=0)
    np.testing.assert_allclose(
        np.asarray(params["w"]), np.tile(target_mean, (SIZE, 1)), atol=5e-2
    )


def test_adam_atc_reaches_consensus_and_descends():
    """Adaptive base optimizers normalize per-rank gradients, so the gossip
    fixed point is not the mean of targets; assert consensus + global-loss
    descent instead (matches the reference's loss-decreases assertions)."""
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    rng = np.random.default_rng(5)
    targets = jnp.asarray((2.0 + rng.normal(size=(SIZE, 3))).astype(np.float32))
    opt = bf.DistributedAdaptThenCombineOptimizer(
        optax.adam(optax.exponential_decay(0.05, 1, 0.99))
    )
    params = {"w": jnp.zeros((SIZE, 3))}
    state = opt.init(params)

    def global_loss(p):
        return 0.5 * float(jnp.sum((p["w"] - targets) ** 2))

    loss0 = global_loss(params)
    params = _descend(opt, params, state, targets)
    w = np.asarray(params["w"])
    assert w.std(axis=0).max() < 0.1  # consensus
    assert global_loss(params) < 0.6 * loss0  # descent


def test_broadcast_parameters_and_state():
    params = rank_params()
    out = bf.broadcast_parameters(params, root_rank=3)
    np.testing.assert_allclose(np.asarray(out["w"]), 3.0)
    opt = optax.adam(0.1)
    state = opt.init(params)
    bstate = bf.broadcast_optimizer_state(state, root_rank=2)
    mu = jax.tree_util.tree_leaves(bstate)
    assert len(mu) > 0


def test_dynamic_one_peer_plan_schedule():
    """ATC with a rotating one-peer plan must preserve the global average
    and contract to consensus (the reference's dynamic-topology optimizer
    path)."""
    from bluefog_tpu.optim import one_peer_plan_schedule

    plans = one_peer_plan_schedule(SIZE)
    assert len(plans) == 3  # offsets 1, 2, 4
    assert all(len(p.classes) == 1 for p in plans)
    opt = bf.DistributedAdaptThenCombineOptimizer(optax.sgd(0.0))
    rng = np.random.default_rng(9)
    params = {"w": jnp.asarray(rng.normal(size=(SIZE, 4)).astype(np.float32))}
    mean0 = np.asarray(params["w"]).mean(axis=0)
    state = opt.init(params)
    grads = {"w": jnp.zeros_like(params["w"])}
    for t in range(9):
        params, state = opt.step(params, grads, state, plan=plans[t % len(plans)])
    out = np.asarray(params["w"])
    np.testing.assert_allclose(out.mean(axis=0), mean0, rtol=1e-5)
    assert out.std(axis=0).max() < 1e-4  # 9 one-peer exp2 rounds => consensus

"""Exact decentralized algorithms (r3 verdict next-round #4): on
DELIBERATELY heterogeneous quadratic shards, gradient tracking / EXTRA /
Push-DIGing must reach the CENTRALIZED optimum (consensus spread -> 0 AND
loss -> global minimum) at constant step size — where plain ATC gossip
provably plateaus at an O(lr * heterogeneity) bias.

Mirrors the convergence-demo role of the reference's
``examples/pytorch_optimization.py`` [U] as a test.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import topology_util as tu
from bluefog_tpu.algorithms import column_stochastic_plan

SIZE = 8
DIM = 6
LR = 0.05
ITERS = 600


@pytest.fixture(autouse=True)
def fresh_context(devices):
    bf.init()
    yield
    bf.shutdown()


def heterogeneous_quadratics(rng):
    """Per-rank f_r(w) = 0.5 (w - c_r)^T A_r (w - c_r) with well-spread
    centers c_r: the global optimum solves sum A_r (w - c_r) = 0 and is
    FAR from every local minimizer."""
    As, cs = [], []
    for r in range(SIZE):
        M = rng.normal(size=(DIM, DIM))
        A = M @ M.T / DIM + np.eye(DIM)  # SPD, moderately conditioned
        As.append(A)
        cs.append(rng.normal(size=(DIM,)) * 3.0)
    A = np.stack(As)
    c = np.stack(cs)
    w_star = np.linalg.solve(A.sum(0), np.einsum("rij,rj->i", A, c))
    return jnp.asarray(A, jnp.float32), jnp.asarray(c, jnp.float32), w_star


def run(opt, A, c, iters=ITERS):
    grad_fn = jax.jit(jax.vmap(
        lambda w, A_r, c_r: A_r @ (w - c_r), in_axes=(0, 0, 0)))
    params = {"w": jnp.zeros((SIZE, DIM))}
    state = opt.init(params)
    for _ in range(iters):
        grads = {"w": grad_fn(params["w"], A, c)}
        params, state = opt.step(params, grads, state)
        # one eager step in flight on the 8-device CPU mesh, never two: with
        # steps queued behind one another and the host loaded, one of a
        # step's eight participants gets no thread, the other seven wait out
        # XLA's 40 s rendezvous limit and the process aborts (a wait every 25
        # steps still lost 5 of 6 copies run beside 16 busy processes; a wait
        # every step lost none of 18)
        jax.block_until_ready(params)
    w = np.asarray(params["w"], np.float64)
    return w


def global_suboptimality(w, A, c, w_star):
    """f(mean iterate) - f(w*) for the GLOBAL objective."""
    A = np.asarray(A, np.float64)
    c = np.asarray(c, np.float64)

    def f(x):
        d = x[None, :] - c
        return 0.5 * np.einsum("rd,rde,re->", d, A, d)

    return f(w.mean(0)) - f(w_star)


@pytest.mark.parametrize("algo", ["gt", "extra"])
def test_exact_methods_reach_centralized_optimum(algo):
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    rng = np.random.default_rng(0)
    A, c, w_star = heterogeneous_quadratics(rng)
    opt = {
        "gt": bf.DistributedGradientTrackingOptimizer,
        "extra": bf.DistributedEXTRAOptimizer,
    }[algo](LR)
    w = run(opt, A, c)
    spread = np.abs(w - w.mean(0)).max()
    err = np.abs(w.mean(0) - w_star).max()
    # EXTRA's exactness rests on a telescoping sum, which in f32
    # accumulates rounding noise as a random walk — its floor is ~1e-4
    # and grows ~sqrt(iters) (verified against a step-matched numpy
    # reference: the implementation tracks it to f32 ulps).  GT's tracker
    # is self-correcting and floors at f32 resolution.
    tol = 1e-4 if algo == "gt" else 1e-3
    assert spread < tol, f"{algo}: consensus spread {spread:.2e}"
    assert err < tol, f"{algo}: distance to centralized optimum {err:.2e}"


def test_push_diging_reaches_optimum_on_directed_graph():
    """Directed, IRREGULAR graph (ring + extra edges out of rank 0): no
    doubly-stochastic matrix exists, plain row-stochastic gossip is biased
    even on homogeneous data — push-sum de-biasing must still reach w*."""
    import networkx as nx

    G = nx.DiGraph()
    G.add_nodes_from(range(SIZE))
    for r in range(SIZE):
        G.add_edge(r, (r + 1) % SIZE)
    G.add_edge(0, 2)
    G.add_edge(0, 4)
    bf.set_topology(tu.RingGraph(SIZE))  # installed topo is irrelevant...
    rng = np.random.default_rng(1)
    A, c, w_star = heterogeneous_quadratics(rng)

    # ...because the optimizer derives its column-stochastic plan from the
    # digraph we install here:
    class _Opt(bf.DistributedPushDIGingOptimizer):
        def _plan(self, ctx):
            return column_stochastic_plan(G)

    w = run(_Opt(LR), A, c, iters=1200)
    spread = np.abs(w - w.mean(0)).max()
    err = np.abs(w.mean(0) - w_star).max()
    assert spread < 1e-3, f"push-diging consensus spread {spread:.2e}"
    assert err < 1e-3, f"push-diging distance to optimum {err:.2e}"


def test_plain_atc_plateaus_where_gt_converges():
    """The motivating contrast: at the same constant step on the same
    heterogeneous shards, ATC gossip stalls at an O(lr) bias while
    gradient tracking drives suboptimality orders of magnitude lower."""
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    rng = np.random.default_rng(2)
    A, c, w_star = heterogeneous_quadratics(rng)

    w_atc = run(bf.DistributedAdaptThenCombineOptimizer(optax.sgd(LR)), A, c)
    w_gt = run(bf.DistributedGradientTrackingOptimizer(LR), A, c)

    sub_atc = global_suboptimality(w_atc, A, c, w_star)
    sub_gt = global_suboptimality(w_gt, A, c, w_star)
    err_atc = np.abs(w_atc.mean(0) - w_star).max()
    err_gt = np.abs(w_gt.mean(0) - w_star).max()
    assert err_atc > 1e-2, (
        f"ATC unexpectedly exact ({err_atc:.2e}) — heterogeneity too weak "
        "for the contrast this test documents")
    assert err_gt < 1e-4, f"GT distance to optimum {err_gt:.2e}"
    assert sub_gt < sub_atc / 100, (
        f"GT suboptimality {sub_gt:.2e} not << ATC {sub_atc:.2e}")

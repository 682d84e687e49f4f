"""Asynchronous island window ops — true multi-process one-sided semantics.

Sibling of the reference's ``test/torch_win_ops_test.py`` [U], but for the
island runtime (:mod:`bluefog_tpu.islands`): each rank is a real OS process
exchanging deposits through the native shared-memory mailbox.  Following the
reference's strategy for async ops (SURVEY.md §4), the asynchronous tests
assert *conservation + convergence with tolerances* rather than step
determinism, while barriered runs are checked exactly against the analytic
``x_{t+1} = W x_t`` trajectory.
"""

import os
import time

import networkx as nx
import numpy as np
import pytest

from bluefog_tpu import islands, topology_util
from bluefog_tpu.native import shm_native

# ---------------------------------------------------------------------------
# transport layer (single process)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("force_fallback", ["0", "1"])
def test_transport_roundtrip(force_fallback, monkeypatch, tmp_path):
    monkeypatch.setenv("BLUEFOG_SHM_FALLBACK", force_fallback)
    if force_fallback == "1":
        monkeypatch.setattr(shm_native, "_FALLBACK_DIR", str(tmp_path))
    job = f"t{os.getpid()}_{force_fallback}"
    w = shm_native.make_window(job, "x", rank=0, nranks=2, maxd=2,
                               shape=(3,), dtype=np.float32)
    w.write(0, 1, np.array([1.0, 2.0, 3.0]), p=0.5)
    a, p, v = w.read(1)
    assert np.allclose(a, [1, 2, 3]) and p == 0.5 and v == 1
    w.write(0, 1, np.ones(3), p=0.25, accumulate=True)
    a, p, v = w.read(1, collect=True)
    assert np.allclose(a, [2, 3, 4]) and p == 0.75 and v == 2
    a, p, _ = w.read(1)  # collect drained it
    assert np.allclose(a, 0) and p == 0.0
    w.expose(np.full(3, 9.0), p=2.0)
    a, p, v = w.read_exposed(0)
    assert np.allclose(a, 9) and p == 2.0 and v == 1
    j = shm_native.make_job(job, 0, 1)
    j.mutex_acquire(0)
    j.mutex_release(0)
    j.barrier()
    w.close(unlink=True)
    j.close(unlink=True)


def test_transport_raw_dtype_rejects_accumulate():
    job = f"raw{os.getpid()}"
    w = shm_native.make_window(job, "i", rank=0, nranks=1, maxd=1,
                               shape=(2,), dtype=np.int32)
    w.write(0, 0, np.array([7, 8], np.int32))
    a, _, _ = w.read(0)
    assert a.dtype == np.int32 and list(a) == [7, 8]
    with pytest.raises(TypeError):
        w.write(0, 0, np.array([1, 1], np.int32), accumulate=True)
    w.close(unlink=True)


# ---------------------------------------------------------------------------
# island workers (top-level: must pickle under the spawn start method)
# ---------------------------------------------------------------------------


def _worker_diffuse(rank, size, steps):
    islands.set_topology(topology_util.RingGraph(size))
    x = np.arange(3, dtype=np.float64) + rank
    islands.win_create(x, "d")
    for _ in range(steps):
        islands.win_put(islands.win_sync("d"), "d")
        islands.barrier()  # realize the synchronous schedule exactly
        islands.win_update("d")
        islands.barrier()
    out = islands.win_sync("d").copy()
    islands.win_free("d")
    return out


def _worker_deterministic_suite(rank, size, steps):
    """Diffusion + pull-combine + versions + broadcast in ONE process set
    (keeps the spawn count down: each spawn pays a fresh JAX import per
    child)."""
    diffused = _worker_diffuse(rank, size, steps)
    pulled = _worker_get(rank, size)
    versions = _worker_versions(rank, size)
    tree = {"a": np.full((3,), float(rank)), "b": np.arange(2.0) * rank}
    bcast = islands.broadcast_parameters(tree, root=1)
    return diffused, pulled, versions, bcast


def _worker_pushsum(rank, size, steps):
    islands.set_topology(topology_util.ExponentialTwoGraph(size))
    islands.turn_on_win_ops_with_associated_p()
    x = np.full((3,), float(rank * 10), np.float64)
    islands.win_create(x, "ps", zero_init=True)
    rng = np.random.default_rng(rank)
    for _ in range(steps):
        islands.push_sum_round("ps")
        time.sleep(float(rng.random()) * 0.002)  # genuine desynchronization
    # ranks finish at different times; the leftover in-flight mass is
    # collected by drain rounds after a global barrier.  Barriered, each
    # takes a third off what is left of the spread on exp2(4) whatever the
    # host's load: 20 of them bring the 30 between the starting values under
    # 1e-8, however little the asynchronous rounds mixed (on a loaded host a
    # rank may take all its rounds before another takes its first; with 4
    # drain rounds two whole runs of six workers read 1.2e-7 and 1.5e-7
    # against the tests' 1e-7)
    islands.barrier()
    for _ in range(20):
        islands.push_sum_round("ps")
        islands.barrier()
    val = islands.win_sync("ps") / islands.win_associated_p("ps")
    p = islands.win_associated_p("ps")
    islands.win_free("ps")
    return val.copy(), p


def _worker_get(rank, size):
    islands.set_topology(topology_util.RingGraph(size))
    x = np.full((2,), float(rank), np.float64)
    islands.win_create(x, "g", zero_init=True)
    islands.barrier()  # all exposures published
    islands.win_get("g")
    # win_update re-exposes the combined value; barrier so no rank's get
    # observes a neighbor's post-update exposure (one-sidedness is real)
    islands.barrier()
    out = islands.win_update("g")
    islands.win_free("g")
    return out.copy()


def _worker_versions(rank, size):
    islands.set_topology(topology_util.RingGraph(size))
    islands.win_create(np.zeros(2), "v")
    for i in range(5):
        islands.win_put(np.full(2, float(i)), "v")
    islands.barrier()
    ver = islands.get_win_version("v")
    islands.win_free("v")
    return ver


def _worker_mutex(rank, size, path):
    islands.set_topology(topology_util.FullyConnectedGraph(size))
    for _ in range(25):
        with islands.win_mutex("w", ranks=[0]):
            with open(path, "a") as f:
                f.write(f"{rank} start\n")
                f.flush()
                time.sleep(0.001)
                f.write(f"{rank} end\n")
    return True


def _worker_fallback_diffuse(rank, size, steps):
    # env inherited from the parent forces the lockf fallback transport
    assert os.environ.get("BLUEFOG_SHM_FALLBACK") == "1"
    return _worker_diffuse(rank, size, steps)


# ---------------------------------------------------------------------------
# multi-process tests
# ---------------------------------------------------------------------------


def _weight_matrix(topo: nx.DiGraph) -> np.ndarray:
    n = topo.number_of_nodes()
    W = np.zeros((n, n))
    for d in range(n):
        nbrs = sorted(topo.predecessors(d))
        u = 1.0 / (len(nbrs) + 1)
        W[d, d] = u
        for s in nbrs:
            W[d, s] = u
    return W


def _report_jax_backend(rank, size):
    import jax
    import jax.numpy as jnp

    jnp.zeros(1).block_until_ready()  # what takes the chip on a TPU host
    return os.environ.get("JAX_PLATFORMS"), jax.default_backend()


def test_spawn_children_initialise_only_the_cpu_backend(monkeypatch):
    """One process holds the chip: children of a parent whose environment
    would hand them the TPU are pinned to the CPU backend by spawn itself,
    and the parent's environment is left as it was."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    out = islands.spawn(_report_jax_backend, 2)
    assert out == [("cpu", "cpu")] * 2
    assert os.environ["JAX_PLATFORMS"] == "tpu"


def test_island_deterministic_suite():
    """Barriered diffusion matches the analytic W^k trajectory; win_get
    pull-combine matches the closed form; deposit versions count."""
    size, steps = 4, 7
    res = islands.spawn(_worker_deterministic_suite, size, args=(steps,), timeout=300.0)
    topo = topology_util.RingGraph(size)
    W = np.linalg.matrix_power(_weight_matrix(topo), steps)
    x0 = np.stack([np.arange(3, dtype=np.float64) + r for r in range(size)])
    expected = W @ x0
    for d in range(size):
        diffused, pulled, versions, bcast = res[d]
        np.testing.assert_allclose(diffused, expected[d], rtol=0, atol=1e-12)
        nbrs = sorted(topo.predecessors(d))
        u = 1.0 / (len(nbrs) + 1)
        want = u * d + sum(u * s for s in nbrs)
        np.testing.assert_allclose(pulled, np.full(2, want), atol=1e-12)
        assert versions == {s: 6 for s in nbrs}, versions
        # broadcast_parameters: every rank holds root 1's leaves
        np.testing.assert_allclose(bcast["a"], np.full(3, 1.0), atol=0)
        np.testing.assert_allclose(bcast["b"], np.arange(2.0), atol=0)


def test_island_async_pushsum_exact_average():
    """Fully asynchronous push-sum (random per-rank sleeps, no barriers in
    the hot loop) converges to the EXACT global average: the atomic
    collect conserves Σx and Σp under any interleaving."""
    size, steps = 4, 60
    res = islands.spawn(_worker_pushsum, size, args=(steps,), timeout=240.0)
    mean = np.mean([r * 10.0 for r in range(size)])
    for val, p in res:
        assert p > 0
        # asymptotic tolerance: a fixed round count of async push-sum lands
        # ~1e-8 from the mean with timing-dependent wobble across the slots
        np.testing.assert_allclose(val, np.full(3, mean), rtol=0, atol=1e-7)


def test_island_mutex_mutual_exclusion(tmp_path):
    path = str(tmp_path / "mutex.log")
    islands.spawn(_worker_mutex, 2, args=(path,), timeout=300.0)
    lines = open(path).read().splitlines()
    assert len(lines) == 2 * 2 * 25
    for i in range(0, len(lines), 2):
        r_start, kind_start = lines[i].split()
        r_end, kind_end = lines[i + 1].split()
        assert (kind_start, kind_end) == ("start", "end")
        assert r_start == r_end, f"interleaved critical sections at line {i}"


def test_island_fallback_transport_end_to_end(monkeypatch):
    monkeypatch.setenv("BLUEFOG_SHM_FALLBACK", "1")
    size, steps = 2, 4
    res = islands.spawn(_worker_fallback_diffuse, size, args=(steps,), timeout=300.0)
    topo = topology_util.RingGraph(size)
    W = np.linalg.matrix_power(_weight_matrix(topo), steps)
    x0 = np.stack([np.arange(3, dtype=np.float64) + r for r in range(size)])
    expected = W @ x0
    for r in range(size):
        np.testing.assert_allclose(res[r], expected[r], atol=1e-12)


def _worker_fused_tree(rank, size):
    islands.set_topology(topology_util.RingGraph(size))
    tree = {
        "w": np.full((2, 3), float(rank), np.float32),
        "b": np.full((4,), float(rank), np.float32),
    }
    islands.win_create(tree, "ft")
    islands.barrier()
    islands.win_put(tree, "ft")
    islands.barrier()
    out = islands.win_update("ft")
    islands.barrier()
    sync = islands.win_sync("ft")
    islands.win_free("ft")
    return (out["w"][0, 0], out["b"][0],
            sync["w"].shape, sync["b"].shape)


def test_island_fused_pytree_window():
    """Pytree (fused) windows in the island runtime: tree in, tree out,
    gossip math identical to the per-array window."""
    size = 4
    res = islands.spawn(_worker_fused_tree, size, timeout=300)
    W = topology_util.GetWeightMatrix(topology_util.RingGraph(size))
    expected = W @ np.arange(size, dtype=np.float64)
    for r, (w00, b0, wshape, bshape) in enumerate(res):
        assert wshape == (2, 3) and bshape == (4,)
        np.testing.assert_allclose(w00, expected[r], rtol=1e-6)
        np.testing.assert_allclose(b0, expected[r], rtol=1e-6)


def test_spawn_surfaces_child_failure():
    with pytest.raises(RuntimeError, match="island spawn failed"):
        islands.spawn(_worker_boom, 2, timeout=60.0)


def _worker_boom(rank, size):
    if rank == 1:
        raise ValueError("intentional")
    return True


def test_launcher_islands_mode(tmp_path):
    """bftpu-run --islands N: one process per rank with the island env set,
    shared-memory job wired up (the reference's `bfrun -np N` shape)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "ranks.txt"
    script = (
        "import os\n"
        "from bluefog_tpu import islands\n"
        "islands.init()\n"
        "import numpy as np\n"
        "islands.win_create(np.full(2, float(islands.rank())), 'x')\n"
        "islands.win_put(np.full(2, float(islands.rank())), 'x')\n"
        "islands.barrier()\n"
        "v = islands.win_update('x')\n"
        f"open({str(out)!r}, 'a').write("
        "f'{islands.rank()} {v[0]:.6f}\\n')\n"
        "islands.barrier()\n"
        "islands.shutdown(unlink=(islands.rank() == 0))\n"
    )
    env = dict(os.environ, PYTHONPATH=repo)
    proc = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.run.launcher", "--islands", "2",
         "--job", f"launch{os.getpid()}", "--", sys.executable, "-c", script],
        env=env, capture_output=True, text=True, timeout=180, cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = sorted(open(out).read().splitlines())
    # ring of 2: each rank averages self with the other -> 0.5
    assert lines == ["0 0.500000", "1 0.500000"], lines


def test_launcher_islands_failure_no_hang(tmp_path):
    """A rank that dies before the teardown barrier must not hang the
    launcher: siblings blocked in the shm barrier are reaped and the exit
    code is nonzero (the sequential-wait hang regression)."""
    import subprocess
    import sys
    import time as _t

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = (
        "import os, time\n"
        "from bluefog_tpu import islands\n"
        "islands.init()\n"
        "if islands.rank() == 1:\n"
        "    raise SystemExit(3)\n"
        "islands.barrier()\n"  # rank 0 blocks here forever
    )
    env = dict(os.environ, PYTHONPATH=repo)
    t0 = _t.time()
    proc = subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.run.launcher", "--islands", "2",
         "--job", f"fail{os.getpid()}", "--", sys.executable, "-c", script],
        env=env, capture_output=True, text=True, timeout=120, cwd=repo,
    )
    assert proc.returncode == 3, (proc.returncode, proc.stderr[-500:])
    assert _t.time() - t0 < 100


def _worker_recreate(rank, size):
    islands.set_topology(topology_util.RingGraph(size))
    islands.win_create(np.full(2, 7.0), "r")
    islands.win_accumulate(np.full(2, 1.0), "r")
    islands.barrier()
    islands.win_free("r")
    # re-create under the same name: must see a FRESH segment, not the old
    # deposits (win_free unlinks between barriers)
    islands.win_create(np.zeros(2), "r", zero_init=True)
    out = islands.win_update("r")
    islands.win_free("r")
    return out.copy()


def test_island_recreate_after_free_is_fresh():
    res = islands.spawn(_worker_recreate, 4, timeout=300.0)
    for r in range(4):
        np.testing.assert_allclose(res[r], np.zeros(2), atol=0)


def test_island_update_rejects_unknown_neighbor(tmp_path):
    job = f"single{os.getpid()}"
    islands.init(0, 1, job)
    try:
        islands.win_create(np.zeros(2), "w")
        with pytest.raises(KeyError, match="non-in-neighbor"):
            islands.win_update("w", neighbor_weights={5: 1.0})
        islands.win_free("w")
    finally:
        islands.shutdown(unlink=True)


def _worker_tcp_suite(rank, size, steps, path):
    """Diffusion + async push-sum + mutex over the TCP transport in ONE
    process set (each spawn pays a fresh JAX import per child)."""
    assert os.environ.get("BLUEFOG_ISLAND_TRANSPORT") == "tcp"
    diffused = _worker_diffuse(rank, size, steps)
    pushed = _worker_pushsum(rank, size, 40)
    _worker_mutex(rank, size, path)
    return diffused, pushed


def test_island_tcp_transport_suite(monkeypatch, tmp_path):
    """The TCP (cross-host/DCN) transport: barriered diffusion matches the
    analytic trajectory; asynchronous push-sum reaches the exact average
    (the write ack gives MPI_Win_flush-style completion); the remote mutex
    excludes."""
    monkeypatch.setenv("BLUEFOG_ISLAND_TRANSPORT", "tcp")
    path = str(tmp_path / "mutex.log")
    size, steps = 4, 5
    res = islands.spawn(_worker_tcp_suite, size, args=(steps, path),
                        timeout=300.0)
    topo = topology_util.RingGraph(size)
    W = np.linalg.matrix_power(_weight_matrix(topo), steps)
    x0 = np.stack([np.arange(3, dtype=np.float64) + r for r in range(size)])
    expected = W @ x0
    mean = np.mean([r * 10.0 for r in range(size)])
    for r in range(size):
        diffused, (val, p) = res[r]
        np.testing.assert_allclose(diffused, expected[r], atol=1e-12)
        assert p > 0
        # asymptotic tolerance: a fixed round count of async push-sum lands
        # ~1e-8 from the mean with timing-dependent wobble across the slots
        np.testing.assert_allclose(val, np.full(3, mean), rtol=0, atol=1e-7)
    lines = open(path).read().splitlines()
    assert len(lines) == 2 * size * 25
    for i in range(0, len(lines), 2):
        assert lines[i].split()[0] == lines[i + 1].split()[0]


def _worker_exp2_suite(rank, size, steps):
    """np=4 e2e over the exp2 topology (round-6 ask: multi-process
    evidence past np=2): barriered weighted diffusion through the v2
    chunked transport's put_dual/update_fused fast path, then the
    accumulate idiom with an atomic reset drain."""
    islands.set_topology(topology_util.ExponentialTwoGraph(size))
    x = np.arange(3, dtype=np.float64) + rank
    islands.win_create(x, "e2")
    for _ in range(steps):
        islands.win_put(islands.win_sync("e2"), "e2")
        islands.barrier()
        islands.win_update("e2")
        islands.barrier()
    diffused = islands.win_sync("e2").copy()
    islands.win_free("e2")
    # accumulate idiom: deposits stack in the mailbox; win_update with
    # reset=True drains them atomically (collect)
    islands.win_create(np.zeros(2), "ea", zero_init=True)
    islands.barrier()
    for _ in range(3):
        islands.win_accumulate(np.ones(2), "ea")
    islands.barrier()
    drained = islands.win_update("ea", reset=True).copy()
    islands.barrier()
    # post-drain update sees empty slots: only the self term survives
    again = islands.win_update("ea").copy()
    islands.win_free("ea")
    return diffused, drained, again


@pytest.mark.island_e2e
def test_island_exp2_np4_end_to_end():
    """Four processes on ExponentialTwoGraph(4) (in-degree 2 per rank —
    the fused multi-slot combine path), checked against the analytic
    trajectory and wall-time budgeted so tier-1 stays fast."""
    size, steps = 4, 5
    t0 = time.monotonic()
    res = islands.spawn(_worker_exp2_suite, size, args=(steps,),
                        timeout=240.0)
    elapsed = time.monotonic() - t0
    topo = topology_util.ExponentialTwoGraph(size)
    W = np.linalg.matrix_power(_weight_matrix(topo), steps)
    x0 = np.stack([np.arange(3, dtype=np.float64) + r for r in range(size)])
    expected = W @ x0
    for d in range(size):
        diffused, drained, again = res[d]
        np.testing.assert_allclose(diffused, expected[d], rtol=0, atol=1e-12)
        # 2 in-neighbors x 3 stacked unit deposits, uniform weight 1/3
        np.testing.assert_allclose(drained, np.full(2, 2.0), atol=1e-12)
        # after the atomic drain only the self term remains
        np.testing.assert_allclose(again, drained / 3.0, atol=1e-12)
    # budget: a hung transport would eat the spawn timeout; a healthy run
    # is dominated by 4 child JAX imports
    assert elapsed < 120.0, f"np=4 e2e blew its wall-time budget: {elapsed:.1f}s"


def _mean_over_ranks(x, name, rounds=10):
    """Barriered gossip of a small vector on exp2(4) at thirds: every rank
    gets the mean over ranks of ``x``, to ``3**-rounds`` of its spread."""
    x = np.asarray(x, np.float64)
    islands.win_create(x, name)
    for _ in range(rounds):
        islands.win_put(x, name)
        islands.barrier()
        x = islands.win_update(name)
        islands.barrier()
    islands.win_free(name)
    return x


def _step_settle_look(opt, params, state, rank, size, steps, deadline,
                      after_step=lambda: None):
    """Async WinPut optimizer on per-rank quadratics: local loss
    0.5*(w - c_r)^2 with c_r = rank; decentralized SGD + gossip pulls every
    rank toward the global optimum mean(c) = (size-1)/2.

    The ranks step at their own pace, and on a loaded host one of them may
    take all its steps before another takes its first, against deposits that
    are still the starting points.  So a count of steps says nothing: after
    every ``steps`` the ranks settle, look, and go on from the settled point
    until every rank is within 0.3 of the optimum and of the others, or
    ``deadline`` (the parent's clock) has passed on any of them.  The two
    gossiped flags land on 0, 1/4, ... 1 to within 2e-5, so every rank reads
    the same verdict and none waits alone in a barrier."""
    c = float(rank)
    target = (size - 1) / 2.0
    rng = np.random.default_rng(rank)
    while True:
        for _ in range(steps):
            grads = {"w": params["w"] - c, "b": params["b"] * 0.0}
            params, state = opt.step(params, grads, state)
            after_step()
            time.sleep(float(rng.random()) * 0.0005)
        islands.barrier()
        params = opt.settle(params, rounds=10)
        w = np.asarray(params["w"], np.float64)
        mean_w = _mean_over_ranks(w, "look")  # collective: every rank calls
        near = (np.all(np.abs(w - target) < 0.3)
                and np.all(np.abs(w - mean_w) < 0.15))
        all_near, any_late = _mean_over_ranks(
            [float(near), float(time.time() > deadline)], "verdict")
        if all_near > 0.99 or any_late > 0.01:
            return params


def _spawn_until_near(worker, size, steps, limit=240.0):
    # the workers stop looking a quarter of the limit early, so that a run
    # that has not converged fails on its values and not on the spawn
    return islands.spawn(worker, size,
                         args=(steps, time.time() + 0.75 * limit),
                         timeout=limit)


def _worker_winput_opt(rank, size, steps, deadline):
    import jax.numpy as jnp
    import optax

    islands.set_topology(topology_util.ExponentialTwoGraph(size))
    params = {"w": jnp.full((3,), 10.0 + rank, jnp.float32),
              "b": jnp.zeros((2,), jnp.float32)}
    opt = islands.DistributedWinPutOptimizer(
        optax.sgd(0.2), num_steps_per_communication=2
    )
    state = opt.init(params)
    params = _step_settle_look(opt, params, state, rank, size, steps,
                               deadline)
    opt.free()
    return np.asarray(params["w"]).copy(), np.asarray(params["b"]).copy()


def test_island_winput_optimizer_converges():
    size, steps = 4, 50
    res = _spawn_until_near(_worker_winput_opt, size, steps)
    target = (size - 1) / 2.0  # mean of the per-rank optima
    ws = np.stack([w for w, _ in res])
    # every rank near the global optimum and near consensus
    assert np.all(np.abs(ws - target) < 0.3), ws
    assert ws.std(axis=0).max() < 0.05, ws
    for _, b in res:
        np.testing.assert_allclose(b, 0.0, atol=1e-6)


def _worker_routed_suite(rank, size, steps):
    """Hierarchical transport (hostmap "a,a,b,b": ranks 0-1 via shm,
    2-3 via shm, cross-pairs via TCP loopback): diffusion + async push-sum
    + pull-combine + recreate-after-free in ONE process set."""
    assert os.environ.get("BLUEFOG_ISLAND_HOSTMAP") == "a,a,b,b"
    diffused = _worker_diffuse(rank, size, steps)
    pushed = _worker_pushsum(rank, size, 40)
    pulled = _worker_get(rank, size)
    # recreate-after-free exercises the per-host designated unlink
    islands.win_create(np.zeros(2), "g", zero_init=True)
    fresh = islands.win_update("g")
    islands.win_free("g")
    return diffused, pushed, pulled, fresh.copy()


def test_island_hierarchical_transport_suite(monkeypatch):
    """shm intra-host + TCP inter-host, one window: the ring 0-1-2-3 has
    intra-host edges 0<->1, 2<->3 and inter-host edges 1<->2, 3<->0, so
    both transport legs carry traffic in every phase."""
    monkeypatch.setenv("BLUEFOG_ISLAND_HOSTMAP", "a,a,b,b")
    size, steps = 4, 6
    res = islands.spawn(_worker_routed_suite, size, args=(steps,),
                        timeout=300.0)
    topo = topology_util.RingGraph(size)
    W = np.linalg.matrix_power(_weight_matrix(topo), steps)
    x0 = np.stack([np.arange(3, dtype=np.float64) + r for r in range(size)])
    expected = W @ x0
    mean = np.mean([r * 10.0 for r in range(size)])
    for d in range(size):
        diffused, (val, p), pulled, fresh = res[d]
        np.testing.assert_allclose(diffused, expected[d], atol=1e-12)
        assert p > 0
        # asymptotic tolerance: a fixed round count of async push-sum lands
        # ~1e-8 from the mean with timing-dependent wobble across the slots
        np.testing.assert_allclose(val, np.full(3, mean), rtol=0, atol=1e-7)
        nbrs = sorted(topo.predecessors(d))
        u = 1.0 / (len(nbrs) + 1)
        want = u * d + sum(u * s for s in nbrs)
        np.testing.assert_allclose(pulled, np.full(2, want), atol=1e-12)
        np.testing.assert_allclose(fresh, np.zeros(2), atol=0)


def _worker_winput_opt_overlap(rank, size, steps, deadline):
    """Same quadratic as _worker_winput_opt, but with overlap=True: the
    gossip round runs on the optimizer's background thread while the
    caller computes the next gradient (one-step-stale combine)."""
    import jax.numpy as jnp
    import optax

    islands.set_topology(topology_util.ExponentialTwoGraph(size))
    params = {"w": jnp.full((3,), 10.0 + rank, jnp.float32),
              "b": jnp.zeros((2,), jnp.float32)}
    opt = islands.DistributedWinPutOptimizer(
        optax.sgd(0.2), window_prefix="ov", overlap=True
    )
    state = opt.init(params)
    saw_inflight = []

    def look_for_a_round_in_flight():
        # overlap contract: the round is (at least sometimes) still in
        # flight when step() returns (pending is the progress engine's
        # [(put_handle, update_handle)] per window group)
        if opt._pending is not None and not all(
                h.done() for pair in opt._pending for h in pair):
            saw_inflight.append(True)

    # settle() drains the round in flight before it gossips
    params = _step_settle_look(opt, params, state, rank, size, steps,
                               deadline, look_for_a_round_in_flight)
    params = opt.finish(params)
    assert opt._pending is None
    opt.free()
    return (np.asarray(params["w"]).copy(), np.asarray(params["b"]).copy(),
            bool(saw_inflight))


def test_island_winput_optimizer_overlap_converges():
    size, steps = 4, 50
    res = _spawn_until_near(_worker_winput_opt_overlap, size, steps)
    target = (size - 1) / 2.0
    ws = np.stack([w for w, _, _ in res])
    assert np.all(np.abs(ws - target) < 0.3), ws
    assert ws.std(axis=0).max() < 0.05, ws
    for _, b, _ in res:
        np.testing.assert_allclose(b, 0.0, atol=1e-6)
    # at least one rank observed a genuinely in-flight background round
    assert any(inflight for _, _, inflight in res)

"""HLO perf-contract tests (r3 verdict next-round #3).

Compile each communication path at n=8 on the CPU mesh and assert its
COLLECTIVE INVENTORY from the post-partitioner HLO — the strongest
multi-chip perf evidence obtainable without multi-chip hardware, and a
tripwire against GSPMD regressions on jax upgrades (an accidental
all-gather sneaking into the neighbor path would silently turn O(deg)
gossip into O(n) traffic; the reference's equivalent property is that
``MPI_Neighbor_allgather`` runs exactly along the graph communicator's
edges, ``bluefog/common/mpi_controller.cc`` [U]).

Method follows ``benchmarks/scan_gather_probe.py``: ``jit(...).lower(...)
.compile().as_text()`` and count collective opcodes.  ``-start`` forms
count once; ``-done`` forms are ignored.

The assertions are the analysis engine's declarative HLO rules
(``bluefog_tpu.analysis.hlo_rules``) — the same rule objects the
``python -m bluefog_tpu.analysis`` CLI runs over its compiled corpus —
so a contract has one definition with three consumers (pytest, CLI, CI)
and a test failure prints the same rule id and message as a CLI
violation.
"""

import functools
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu import ops_spmd, topology_util as tu
from bluefog_tpu.core import basics
from bluefog_tpu.core.basics import LOCAL_AXIS, MACHINES_AXIS, NODES_AXIS

from bluefog_tpu.analysis.hlo_rules import (
    CollectiveBudget,
    NoFullAxisAllGather,
    assert_clean,
)
from bluefog_tpu.common.hlo_inspect import collective_counts

SIZE = 8


@pytest.fixture(autouse=True)
def fresh_context(devices):
    bf.init(local_size=2)
    yield
    bf.shutdown()


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _rank_major(spmd_fn, mesh):
    return jax.shard_map(spmd_fn, mesh=mesh, in_specs=P(NODES_AXIS),
                         out_specs=P(NODES_AXIS))


def _assert_only(counts: Counter, expected: dict):
    """Exact inventory via the shared CollectiveBudget rule: every listed
    opcode at its exact count, every unlisted collective at zero."""
    findings = CollectiveBudget(expected).check_counts(counts)
    assert not findings, "HLO contract violated:\n" + "\n".join(
        f"  {f}" for f in findings)


def test_allreduce_is_one_allreduce():
    ctx = basics.context()
    x = jnp.zeros((SIZE, 4))
    fn = _rank_major(
        functools.partial(ops_spmd.allreduce, axis_name=NODES_AXIS,
                          average=True), ctx.mesh)
    counts = collective_counts(_compiled_text(fn, x))
    _assert_only(counts, {"all-reduce": 1})


def test_neighbor_allreduce_exp2_is_three_permutes():
    """exp2@8 has shift classes {1, 2, 4}: exactly log2(8) = 3
    collective-permutes, zero all-gathers — O(deg) gossip, the whole point
    of the shift-class plan compiler (core/plan.py)."""
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    ctx = basics.context()
    x = jnp.zeros((SIZE, 4))
    fn = _rank_major(
        functools.partial(ops_spmd.neighbor_allreduce, plan=ctx.plan,
                          axis_name=NODES_AXIS), ctx.mesh)
    counts = collective_counts(_compiled_text(fn, x))
    _assert_only(counts, {"collective-permute": 3})


def test_neighbor_allreduce_ring_is_two_permutes():
    bf.set_topology(tu.RingGraph(SIZE))
    ctx = basics.context()
    x = jnp.zeros((SIZE, 4))
    fn = _rank_major(
        functools.partial(ops_spmd.neighbor_allreduce, plan=ctx.plan,
                          axis_name=NODES_AXIS), ctx.mesh)
    counts = collective_counts(_compiled_text(fn, x))
    _assert_only(counts, {"collective-permute": 2})


def test_dynamic_one_peer_is_one_permute():
    """The one-peer exp2 rotation moves ONE hop per step — its compiled
    program must hold exactly one collective-permute."""
    from bluefog_tpu.ops import _dynamic_plan

    gen = tu.GetDynamicOnePeerSendRecvRanks(SIZE, 0)
    to_ranks, from_ranks = next(gen)
    # rank-major dynamic args: every rank sends to (rank + 1) % SIZE this
    # step (the rotation is uniform across ranks by construction)
    dst = [{(r + 1) % SIZE: 1.0} for r in range(SIZE)]
    plan = _dynamic_plan(SIZE, None, None, dst)
    ctx = basics.context()
    x = jnp.zeros((SIZE, 4))
    fn = _rank_major(
        functools.partial(ops_spmd.neighbor_allreduce, plan=plan,
                          axis_name=NODES_AXIS), ctx.mesh)
    counts = collective_counts(_compiled_text(fn, x))
    _assert_only(counts, {"collective-permute": 1})


def test_hierarchical_is_local_reduce_plus_machine_permutes():
    """hierarchical = ONE local all-reduce (the pmean) + machine-axis
    permutes only (ring@4 machines -> 2 shift classes); the implicit local
    broadcast must be free (pmean already leaves local ranks identical)."""
    bf.set_machine_topology(tu.RingGraph(4))
    ctx = basics.context()
    mplan = ctx.machine_plan
    x = jnp.zeros((SIZE, 4))

    def spmd(t):
        return ops_spmd.hierarchical_neighbor_allreduce(
            t, machine_plan=mplan, machines_axis=MACHINES_AXIS,
            local_axis=LOCAL_AXIS)

    fn = jax.shard_map(spmd, mesh=ctx.hier_mesh,
                       in_specs=P((MACHINES_AXIS, LOCAL_AXIS)),
                       out_specs=P((MACHINES_AXIS, LOCAL_AXIS)))
    counts = collective_counts(_compiled_text(fn, x))
    _assert_only(counts, {"all-reduce": 1, "collective-permute": 2})


def test_window_exchange_one_permute_per_shift_class():
    """The fused window exchange (win_put + mailbox update in one program)
    must move data with exactly one permute per shift class — the ppermute
    lowering of MPI_Put (windows.py module docstring)."""
    from bluefog_tpu.windows import _build_exchange

    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    ctx = basics.context()
    plan = ctx.plan
    nclasses = len(plan.classes)
    maxd = plan.max_in_degree
    x = jnp.zeros((SIZE, 4), jnp.float32)
    mail = jnp.zeros((SIZE, maxd, 4), jnp.float32)
    ver = jnp.zeros((SIZE, maxd), jnp.int32)
    p_self = jnp.ones((SIZE,), jnp.float32)
    p_mail = jnp.ones((SIZE, maxd), jnp.float32)
    scales = jnp.ones((nclasses, SIZE), jnp.float32)
    active = jnp.ones((nclasses, SIZE), jnp.float32)

    f = _build_exchange(plan, accumulate=False, with_p=False, donate=False)
    text = f.lower(x, mail, ver, p_self, p_mail, scales, active).compile().as_text()
    counts = collective_counts(text)
    _assert_only(counts, {"collective-permute": nclasses})


def test_zero_packed_one_gather_one_scatter():
    """ZeRO-1 packed step: params assemble through exactly ONE all-gather
    and gradients shard through exactly ONE reduce-scatter; any extra
    gather would break the memory story the 8B table depends on.  The
    scalar loss mean is the only all-reduce allowed."""
    from bluefog_tpu.parallel.zero import make_zero_gossip_train_step

    ctx = basics.context()
    # single machine x 8 local: pure ZeRO, no gossip permutes
    bf.init(local_size=8)
    ctx = basics.context()
    mesh = ctx.hier_mesh

    def apply_fn(p, x):
        return jnp.tanh(x @ p["w"]) @ p["v"]

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    init_fn, step_fn, _ = make_zero_gossip_train_step(
        apply_fn, loss_fn, mesh, None, learning_rate=0.1)
    params = {"w": jnp.zeros((16, 32)), "v": jnp.zeros((32, 8))}
    state = init_fn(params)
    data_sh = NamedSharding(mesh, P(MACHINES_AXIS, LOCAL_AXIS))
    batch = jax.device_put(jnp.zeros((1, 8, 4, 16)), data_sh)
    labels = jax.device_put(jnp.zeros((1, 8, 4, 8)), data_sh)
    # step_fn is a plain wrapper around an inner jit; jitting the wrapper
    # inlines the inner program so its collectives appear in one HLO
    text = jax.jit(step_fn).lower(state, batch, labels).compile().as_text()
    counts = collective_counts(text)
    assert counts.get("all-gather", 0) == 1, counts
    assert counts.get("reduce-scatter", 0) == 1, counts
    assert counts.get("all-to-all", 0) == 0, counts
    assert counts.get("collective-permute", 0) == 0, counts
    # scalar loss mean (and nothing bigger) may all-reduce
    assert counts.get("all-reduce", 0) <= 2, counts


def test_tp_block_is_one_allreduce():
    """Megatron column->row parallel MLP, grads w.r.t. both kernels: the
    forward's psum (the g operator) is the ONLY collective — the f
    operator's custom VJP keeps the backward free of extra reductions and
    nothing may all-gather the sharded kernels."""
    from bluefog_tpu.parallel import tensor_parallel as tp

    ctx = basics.context()

    def loss(x, k1, k2):
        h = tp.column_parallel_dense(x, k1)
        y = tp.row_parallel_dense(jnp.tanh(h), k2, axis_name=NODES_AXIS)
        return jnp.sum(y ** 2)

    fn = jax.shard_map(
        jax.grad(loss, argnums=(1, 2)), mesh=ctx.mesh,
        in_specs=(P(), P(None, NODES_AXIS), P(NODES_AXIS, None)),
        out_specs=(P(None, NODES_AXIS), P(NODES_AXIS, None)))
    counts = collective_counts(_compiled_text(
        fn, jnp.ones((4, 16)), jnp.ones((16, 32)), jnp.ones((32, 16))))
    _assert_only(counts, {"all-reduce": 1})


def test_pp_fwd_bwd_is_two_permutes_one_allreduce():
    """GPipe pipeline fwd+bwd: ONE collective-permute per scan body (fwd
    stream + its transpose) and the masked result psum — stage-to-stage
    traffic must stay nearest-neighbor, never an all-gather."""
    from bluefog_tpu.parallel import pipeline as pp

    ctx = basics.context()

    def stage_fn(p, x):
        return jnp.tanh(x @ p)

    def loss(x, params):
        return jnp.sum(pp.pipeline_apply(
            stage_fn, params[0], x, NODES_AXIS, num_microbatches=SIZE) ** 2)

    fn = jax.shard_map(jax.grad(loss, argnums=1), mesh=ctx.mesh,
                       in_specs=(P(), P(NODES_AXIS)),
                       out_specs=P(NODES_AXIS))
    counts = collective_counts(_compiled_text(
        fn, jnp.ones((SIZE, 4, 16)), jnp.ones((SIZE, 16, 16))))
    _assert_only(counts, {"collective-permute": 2, "all-reduce": 1})


def test_ep_fwd_bwd_is_three_alltoalls_one_allreduce():
    """Switch-MoE fwd+bwd: the dispatch/return all_to_all pair plus their
    (merged) transpose and the aux-loss reduction — token routing must
    ride all_to_all, never gather the full token or expert set."""
    from bluefog_tpu.parallel import expert as ep

    ctx = basics.context()
    D, F, E = 16, 32, SIZE  # one expert per device
    p = ep.init_moe_params(jax.random.PRNGKey(1), D, F, E)
    stacked = {
        "router": jnp.broadcast_to(p["router"][None],
                                   (SIZE,) + p["router"].shape),
        "wi": p["wi"].reshape((SIZE, E // SIZE) + p["wi"].shape[1:]),
        "wo": p["wo"].reshape((SIZE, E // SIZE) + p["wo"].shape[1:]),
    }

    def loss(x, p):
        local = jax.tree_util.tree_map(lambda a: a[0], p)
        y, aux = ep.switch_moe(x[0], local, NODES_AXIS,
                               capacity_factor=float(E))
        return jnp.sum(y ** 2) + jnp.sum(aux)

    espec = jax.tree_util.tree_map(lambda a: P(NODES_AXIS), stacked)
    fn = jax.shard_map(jax.grad(loss, argnums=1), mesh=ctx.mesh,
                       in_specs=(P(NODES_AXIS), espec), out_specs=espec)
    counts = collective_counts(_compiled_text(
        fn, jnp.ones((SIZE, 4, D)), stacked))
    _assert_only(counts, {"all-to-all": 3, "all-reduce": 1})


def test_scan_stacked_leaves_never_gather_whole():
    """Round-5 inversion of the r4 pin (which asserted scan-stacked FSDP
    leaves all-gather with the FULL layer axis, and shipped 8B unrolled
    because of it).  The whole-stack gathers turned out to come from two
    now-fixed resolutions — the dense-W gossip einsum (machines-axis
    all-gather of every leaf; replaced by the plan's ppermute combine)
    and unconstrained activations (batch-replicated model) — so 8B now
    SHIPS scan-stacked with the constraint set below at 15.6 GB/device
    (benchmarks/zero_8b.py --compile).  This pin protects the new
    design: NO all-gather may carry the full stacked layer axis, and the
    gossip combine must ride collective-permutes."""
    from bluefog_tpu.models.transformer import LlamaLM
    from bluefog_tpu.parallel.zero import (
        fsdp_act_constraint,
        fsdp_onehot_constraint,
        fsdp_param_io_constraint,
        fsdp_state_struct,
        make_fsdp_gossip_train_step,
    )

    bf.init(local_size=4)
    ctx = basics.context()
    bf.set_machine_topology(tu.RingGraph(2))
    layers = 6
    lm = LlamaLM(vocab_size=96, hidden_size=32, num_layers=layers,
                 num_heads=4, dff=64, remat=True, scan_layers=True,
                 dtype=jnp.float32, head_chunks=4, spmd_vocab=True,
                 act_constraint=fsdp_act_constraint(ctx.hier_mesh),
                 onehot_constraint=fsdp_onehot_constraint(ctx.hier_mesh),
                 weight_constraint=fsdp_param_io_constraint(ctx.hier_mesh))
    ids0 = jnp.ones((2, 16), jnp.int32)
    p_shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0), ids0)["params"]

    def apply_fn(p, ids):
        return lm.apply({"params": p}, ids, labels=ids)

    def loss_fn(out, labels):
        return out

    _, step_fn, _ = make_fsdp_gossip_train_step(
        apply_fn, loss_fn, ctx.hier_mesh, ctx.machine_plan,
        learning_rate=0.1)
    master = jax.tree_util.tree_map(
        lambda l: fsdp_state_struct(l, ctx.hier_mesh), p_shapes)
    mu = jax.tree_util.tree_map(
        lambda l: fsdp_state_struct(l, ctx.hier_mesh), p_shapes)
    data_sh = NamedSharding(ctx.hier_mesh, P(MACHINES_AXIS, LOCAL_AXIS))
    ids_s = jax.ShapeDtypeStruct((2, 4 * 2, 16), jnp.int32, sharding=data_sh)
    text = step_fn.lower(
        {"master": master, "opt": (mu,)}, ids_s, ids_s).compile().as_text()

    # no all-gather result may carry the full [layers, ...] axis — the
    # scan-stacked FSDP memory story (8B at 15.6 GB/device) depends on no
    # whole-stack gathers; same rule the analysis CLI runs
    assert_clean(text, [NoFullAxisAllGather(
        axis_size=layers, subject="fsdp_gossip_step")])
    counts = collective_counts(text)
    assert counts.get("collective-permute", 0) >= 1, (
        f"gossip combine lost its permutes: {dict(counts)}"
    )


def test_ring_attention_sp_is_nearest_neighbor_only():
    """Sequence-parallel ring attention (striped causal): the kv blocks
    rotate one hop per step — exactly 2(n-1) collective-permutes forward
    (k and v each rotate n-1 times) and 4(n-1) for fwd+bwd, ZERO
    all-gathers/all-to-alls: per-hop traffic is nearest-neighbor and
    rides ICI regardless of sequence length (the long-context scaling
    story; scale law pinned at n=16/32 by test_hlo_contract_scale)."""
    from bluefog_tpu.parallel import ring_attention as ra

    ctx = basics.context()
    n = SIZE
    T, H, D = n * 16, 2, 8

    def spmd(q, k, v):
        return ra.ring_attention(q[0], k[0], v[0], NODES_AXIS, n,
                                 causal=True, striped=True)[None]

    fn = jax.shard_map(spmd, mesh=ctx.mesh, in_specs=(P(NODES_AXIS),) * 3,
                       out_specs=P(NODES_AXIS))
    x = jnp.ones((n, 1, T // n, H, D), jnp.float32)
    counts = collective_counts(_compiled_text(fn, x, x, x))
    _assert_only(counts, {"collective-permute": 2 * (n - 1)})

    def loss(q, k, v):
        return jnp.sum(jnp.sin(fn(q, k, v)))

    g = jax.grad(loss, argnums=(0, 1, 2))
    counts = collective_counts(_compiled_text(g, x, x, x))
    _assert_only(counts, {"collective-permute": 4 * (n - 1)})


def _exact_method_counts(tx, plan_topology=None):
    """Compile one optimizer-update step of an exact-method transform on
    the 8-rank mesh and return its collective inventory.  State comes from
    ``tx.init`` inside the compiled program (tree zeros — no collectives),
    so the counts are exactly one update's communication."""
    if plan_topology is not None:
        bf.set_topology(plan_topology)
    ctx = basics.context()

    def spmd(p, g):
        state = tx(ctx).init(p)
        updates, _ = tx(ctx).update(g, state, p)
        return updates

    fn = jax.shard_map(spmd, mesh=ctx.mesh, in_specs=(P(NODES_AXIS),) * 2,
                       out_specs=P(NODES_AXIS))
    x = jnp.zeros((SIZE, 4))
    return collective_counts(_compiled_text(fn, x, x))


def test_gradient_tracking_exp2_is_three_permutes():
    """Exactness costs ZERO extra collectives: gradient tracking's
    x-descent and y-tracker ride ONE ``fuse=True`` neighbor_allreduce
    round (packed into one buffer per shift class), so its inventory
    equals plain gossip's (exp2@8 = 3 permutes).  A regression to
    separate x/y rounds would double every count here."""
    from bluefog_tpu import algorithms

    counts = _exact_method_counts(
        lambda ctx: algorithms.gradient_tracking_spmd(0.1, ctx.plan),
        tu.ExponentialTwoGraph(SIZE))
    _assert_only(counts, {"collective-permute": 3})


def test_extra_exp2_is_three_permutes():
    """EXTRA's Wt = (I + W)/2 is one mixing round + local FMA — same
    3-permute inventory as plain exp2 gossip (both lax.cond branches
    share the single comm round placed outside the cond)."""
    from bluefog_tpu import algorithms

    counts = _exact_method_counts(
        lambda ctx: algorithms.extra_spmd(0.1, ctx.plan),
        tu.ExponentialTwoGraph(SIZE))
    _assert_only(counts, {"collective-permute": 3})


def test_push_diging_directed_ring_is_one_permute():
    """Push-DIGing on a directed ring: u-descent, the push-sum weight v,
    AND the y-tracker all ride one ``fuse=True`` column-stochastic round
    over the single shift class — exactly ONE collective-permute, zero
    all-gathers, for full exact directed optimization.  (Unfused, the
    odd-shaped v rides its own permute: the CPU backend's combiner merges
    the two same-shaped tree leaves but not the scalar — measured 2
    permutes; the TPU compiler merges none at all (PERF.md section 6,
    PR 27) — which is exactly why the fusion buffer is guaranteed in code.)"""
    import networkx as nx

    from bluefog_tpu import algorithms

    G = nx.DiGraph()
    G.add_nodes_from(range(SIZE))
    for r in range(SIZE):
        G.add_edge(r, (r + 1) % SIZE)
    plan = algorithms.column_stochastic_plan(G)

    counts = _exact_method_counts(
        lambda ctx: algorithms.push_diging_spmd(0.1, plan))
    _assert_only(counts, {"collective-permute": 1})

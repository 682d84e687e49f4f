"""How the ATC train step groups its leaves into permutes: the order comes
from the step's own jaxpr, the buckets from ``ops_spmd.gossip_grouping``.
Nothing here runs a training loop; the one test that runs a step runs two,
on a four-device mesh, at the benchmark configuration's rehearsal sizes."""

import functools
import hashlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu import models, ops_spmd, topology_util as tu, training
from bluefog_tpu.core import basics
from bluefog_tpu.core.basics import NODES_AXIS
from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models.resnet import BottleneckBlock
from bluefog_tpu.models.transformer import BertEncoder, MixedAttentionMoELM
from bluefog_tpu.optim import CommunicationType
from bluefog_tpu.telemetry import registry as telemetry
from bluefog_tpu.training import make_decentralized_train_step


def _mesh(devices, n):
    bf.init(devices=devices[:n])
    bf.set_topology(tu.ExponentialTwoGraph(n))
    return basics.context()


@pytest.fixture
def four(devices):
    yield _mesh(devices, 4)
    bf.shutdown()


@pytest.fixture
def one(devices):
    yield _mesh(devices, 1)
    bf.shutdown()


def _rank_major(tree, n):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype), tree)


def _resnet_step(ctx, model, image, batch, **kw):
    """The step and the shapes of its arguments: (params, stats, opt, x, y)."""
    n = ctx.size
    v = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, image, image, 3))))
    init_fn, step_fn = make_decentralized_train_step(
        model.apply, optax.sgd(0.1, momentum=0.9), ctx.mesh, plan=ctx.plan,
        has_batch_stats=True, donate=False, **kw)
    params = _rank_major(v["params"], n)
    shapes = (params, _rank_major(v["batch_stats"], n),
              jax.eval_shape(init_fn, params),
              jax.ShapeDtypeStruct((n, batch, image, image, 3), jnp.float32),
              jax.ShapeDtypeStruct((n, batch), jnp.int32))
    return step_fn, shapes


def _tiny_resnet():
    # chipbench/configs/resnet50.json, "rehearsal"
    return models.ResNet(stage_sizes=[1, 1], block_cls=BottleneckBlock,
                         num_classes=10, num_filters=8)


def test_resnet50_buckets_follow_the_backward_pass(four, monkeypatch, tmp_path):
    """ResNet-50's 161 leaves with the ranks of a real trace of the step:
    two buckets, cut in the order of the backward pass (which is neither the
    dict's key order nor its reverse), and the gauges say the same."""
    monkeypatch.setenv("BFTPU_TELEMETRY", str(tmp_path))
    telemetry.reset()
    seen = []
    real = training._value_and_grad_in_order

    def spy(loss_of, p):
        out, order = real(loss_of, p)
        seen.append(order)
        return out, order

    monkeypatch.setattr(training, "_value_and_grad_in_order", spy)
    try:
        step_fn, shapes = _resnet_step(
            four, models.ResNet50(num_classes=1000), image=32, batch=2)
        jax.eval_shape(step_fn, *shapes)  # one trace, nothing compiled
        gauges = {g["name"]: g["value"] for g in
                  telemetry.get_registry().snapshot()["gauges"]}
    finally:
        telemetry.reset()
    assert len(seen) == 1  # the step is traced once
    paths, ranks = zip(*jax.tree_util.tree_leaves_with_path(seen[0]))
    names = [jax.tree_util.keystr(p) for p in paths]
    leaves = [jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
              for a in jax.tree_util.tree_leaves(shapes[0])]
    assert len(leaves) == 161

    g = ops_spmd.gossip_grouping(leaves, ranks, len(four.plan.classes))
    assert sorted(i for b in g.buckets for i in b) == list(range(161))
    assert len(g.buckets) == 2
    first, last = g.buckets
    assert max(ranks[i] for i in first) <= min(ranks[i] for i in last)
    size = lambda idxs: sum(int(np.prod(leaves[i].shape)) for i in idxs)
    total = size(range(161))
    assert total == 25_557_032
    # the cut is at the leaf boundary nearest the constant
    assert abs(size(last) / total - ops_spmd.TAIL_SHARE) < 0.01
    bucket_of = {names[i]: k for k, b in enumerate(g.buckets) for i in b}
    assert {k for n, k in bucket_of.items() if "Dense_0" in n} == {0}
    assert {k for n, k in bucket_of.items()
            if "conv_init" in n or "bn_init" in n} == {1}
    assert {k for n, k in bucket_of.items() if "BottleneckBlock_0'" in n} == {1}
    assert {k for n, k in bucket_of.items() if "BottleneckBlock_15" in n} == {0}

    # 91.2 % of the bytes go in as whole 8 x 128 tiles; the rest is the head
    # [2048, 1000], the 64-column convolutions and the vectors
    assert (g.leaves, len(g.buckets), g.permutes, g.packed_bytes,
            g.tiled_bytes) == (161, 2, 4, 102_228_128, 93_192_192)
    assert gauges == {"gossip.leaves": 161, "gossip.buckets": 2,
                      "gossip.permutes": 4, "gossip.packed_bytes": 102_228_128,
                      "gossip.tiled_bytes": 93_192_192}
    # what PR 26 did, from the same function: a permute per leaf and class
    assert ops_spmd.gossip_grouping(leaves, None, 2)[1:] == (161, 322, 0, 0)
    # four shift classes or more: one bucket, as many permutes as classes
    assert ops_spmd.gossip_grouping(leaves, ranks, 4)[1:] == (
        161, 4, 102_228_128, 93_192_192)


def test_one_rank_step_takes_the_old_path(one, monkeypatch):
    """exp2(1) has no shift class: the step asks for no order, traces
    ``value_and_grad`` directly and lowers with nothing packed or sent."""

    def never(*a, **k):
        raise AssertionError("a step that sends nothing needs no order")

    monkeypatch.setattr(training, "_value_and_grad_in_order", never)
    step_fn, shapes = _resnet_step(one, _tiny_resnet(), image=32, batch=4)
    text = jax.jit(step_fn).lower(*shapes).as_text()
    assert "collective_permute" not in text
    assert "concatenate" not in text


# sha256 of the lowered one-rank ATC step on the parent's tree (60fb42e),
# from `_one_rank_step` run there; the same on this tree.  The four one-chip
# cells of the benchmark run such a step: a change to how gossip packs its
# buckets must not show in them.  A PR that means to change what a step
# without a neighbour lowers to changes its line here.  PR 40 changed the
# decoder's: the names on the flash forward rule's output and logsumexp lower
# to nothing but move the numbers MLIR's symbol table gives private functions
# in interpret mode; with `@name_<n>` written `@name_N` the text (1,430,350
# characters) is 6f0fa38's.  PR 50 changed the decoder's again: its expert
# layer gathers a pass's weights inside the pass and names the experts held by
# comparison (d96fcf3a... before), and PR 51 once more: the chunked head and
# loss takes its gradient in its forward loop (8908aae9... before); ResNet-50's
# and BERT's are 60fb42e's still.
PARENT = {
    "resnet50": "256a819480d47ec6178251a6c84ba21859746b606ce444f1c967201f2400471a",
    "bert-base": "d576b07eb83dc4deeb004ee577bd1aaa7df21de5ff06f0ba2ee104953b6367b0",
    "smallthinker-21b-a3b":
        "137d99f56e3d28aeeecd76dfc146a4774b95e6971099bc8bdad7b6e13271ffbc",
}


def _one_rank_step(name, ctx):
    """The ATC step over exp2(1) at the sizes of chipbench/configs/<name>.json's
    ``rehearsal`` with the optimizer of the configuration's one-chip ATC cell,
    and the shapes of its arguments."""
    loss = {}
    if name == "resnet50":
        model = _tiny_resnet()
        x = jax.ShapeDtypeStruct((1, 4, 32, 32, 3), jnp.float32)
        y = jax.ShapeDtypeStruct((1, 4), jnp.int32)
        v = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))))
        apply_fn, tx, stats = (
            model.apply, optax.sgd(0.1, momentum=0.9), v["batch_stats"])
    elif name == "bert-base":
        model = BertEncoder(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, dff=128, max_len=32, num_classes=2,
                            dtype=jnp.bfloat16)
        x = jax.ShapeDtypeStruct((1, 4, 16), jnp.int32)
        y = jax.ShapeDtypeStruct((1, 4), jnp.int32)
        v = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))
        apply_fn, tx, stats = (
            model.apply, optax.adamw(2e-5, weight_decay=1e-4), {})
    else:
        model = MixedAttentionMoELM(
            vocab_size=128, hidden_size=64, num_heads=4, num_kv_heads=2,
            head_dim=16, layer_windows=(None, 24, 24, 24), num_experts=4,
            top_k=4, experts_held=(0, 1), expert_dff=32, rope_base=1500000,
            head_chunks=2, dtype=jnp.bfloat16,
            attention_fn=functools.partial(
                flash_attention, causal=True, block_q=16, block_k=16))
        x = y = jax.ShapeDtypeStruct((1, 2, 64), jnp.int32)
        v = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))
        apply_fn, loss["loss_fn"] = training.make_lm_loss_fns(model)
        tx, stats = optax.adamw(3e-4, weight_decay=0.1), {}
    init_fn, step_fn = make_decentralized_train_step(
        apply_fn, tx, ctx.mesh, plan=ctx.plan, has_batch_stats=bool(stats),
        **loss)
    params = _rank_major(v["params"], 1)
    return step_fn, (params, _rank_major(stats, 1), jax.eval_shape(init_fn, params),
                     x, y)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_one_rank_step_lowers_to_the_parents_text(one, name):
    step_fn, shapes = _one_rank_step(name, one)
    text = jax.jit(step_fn).lower(*shapes).as_text()
    assert "collective_permute" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT[name]


# [shape, packed as whole tiles]: ResNet-50's kinds of leaf, a transformer's
# (all whole tiles), and the edges of the rule
_LEAVES = [
    ((3, 3, 256, 256), True), ((1, 1, 512, 2048), True), ((768, 3072), True),
    ((2, 8, 128), True), ((2, 4, 128), False), ((2048, 1000), False), ((3, 3, 64, 64), False),
    ((7, 7, 3, 64), False), ((3, 128), False), ((1024,), False), ((), False),
    ((0, 128), True),
]


def _counted(shape, dtype, start=0):
    n = int(np.prod(shape))
    return ((jnp.arange(n, dtype=jnp.int32) + start) % 251).astype(
        dtype).reshape(shape)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("shape,tiled", _LEAVES, ids=lambda v: str(v))
def test_pack_then_unpack_is_the_identity(shape, tiled, dtype):
    """A bucket is whole 8 x 128 tiles whatever the dtype (the v5e compiler
    keeps 2-byte elements as ``T(8,128)(2,1)``: 8 rows still).  A leaf that
    is whole tiles goes in tile by tile; the others follow, raveled one after
    another with zeros behind the last; unpacking into the combine's dtype
    gives every leaf back in that dtype.  The leaf under test sits between a
    vector and a matrix, so both regions have a neighbour."""
    a = _counted(shape, dtype)
    leaves = [_counted((5,), dtype, 7), a, _counted((16, 256), dtype, 3)]
    assert [ops_spmd._tileable(l) for l in leaves] == [False, tiled, True]
    tiles = ops_spmd._pack_bucket(leaves)
    whole = (a.size if tiled else 0) + 16 * 256
    ragged = 5 + (0 if tiled else a.size)
    assert tiles.shape == (whole // 1024 + -(-ragged // 1024), 8, 128)
    assert tiles.dtype == a.dtype
    flat = np.asarray(tiles).reshape(-1)
    if tiled and a.size:
        # tile t of a leaf [..., 128 k] holds rows 8 (t // k) ... + 8 of the
        # leaf's column block t % k: the order the TPU keeps them in
        k, t = shape[-1] // 128, a.size // 1024 - 1
        rows = np.asarray(a).reshape(-1, shape[-1])
        np.testing.assert_array_equal(
            np.asarray(tiles[t]),
            rows[8 * (t // k):8 * (t // k) + 8, 128 * (t % k):128 * (t % k) + 128])
    else:
        np.testing.assert_array_equal(flat[whole + 5:whole + 5 + a.size],
                                      np.asarray(a).reshape(-1))
    assert not flat[whole + ragged:].any()  # the padding
    back = ops_spmd._unpack_bucket(tiles.astype(jnp.float32), leaves)
    for leaf, got in zip(leaves, back):
        assert got.shape == leaf.shape and got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(leaf, np.float32))


@pytest.mark.parametrize("mode,comm", [
    ("awc", CommunicationType.neighbor_allreduce),
    ("atc", CommunicationType.allreduce),
])
def test_steps_whose_combine_waits_for_no_gradient_ask_no_order(
        four, monkeypatch, mode, comm):
    def never(*a, **k):
        raise AssertionError(f"{mode} over {comm} needs no order")

    monkeypatch.setattr(training, "_value_and_grad_in_order", never)
    step_fn, shapes = _resnet_step(
        four, _tiny_resnet(), image=32, batch=4, mode=mode,
        communication_type=comm)
    text = jax.jit(step_fn).lower(*shapes).as_text()
    assert "concatenate" not in text


def test_call_without_an_order_is_per_leaf(four):
    tree = {"a": jnp.ones((4, 3, 5)), "b": jnp.ones((4, 7)), "c": jnp.ones((4, 2))}
    fn = jax.jit(jax.shard_map(
        lambda t: ops_spmd.neighbor_allreduce(t, four.plan, NODES_AXIS),
        mesh=four.mesh, in_specs=P(NODES_AXIS), out_specs=P(NODES_AXIS)))
    text = fn.lower(tree).as_text()
    assert text.count("collective_permute") == 3 * len(four.plan.classes)
    assert "concatenate" not in text


# Both programs are written ``sw * a + w1 * r1 + w2 * r2`` an element, in that
# order.  XLA's CPU backend contracts one multiply of an add into a fused
# multiply-add and picks it by what else shares the kernel: with the grouping
# the SGD update does, without it nothing.  So at exp2(4)'s thirds the two
# steps part in the last bit of three leaves HERE, and ISSUE 30's "bit for
# bit" is not met on this backend.  Nor on the TPU, whose compiler factors the
# common third out of the per-leaf path and not out of a matrix that leaves a
# bucket (`chip_smoke.phase_buckets_vs_per_leaf`; PERF.md section 6, PR 30).
# What stands in here: the same two cases in a process whose backend has no
# fused multiply-add (the test after this one: the two programs do the same
# arithmetic), and the cases in which every product is exact.
_CONTRACTS = pytest.mark.xfail(
    strict=False, reason="XLA:CPU contracts another multiply into the add")


def _halves(ctx):
    """exp2(4) with weights 1/2, 1/4, 1/4: every product is exact."""
    graph = tu.ExponentialTwoGraph(4)
    for u, v in graph.edges:
        graph[u][v]["weight"] = 0.25  # a half stays with the rank itself
    bf.set_topology(graph)
    ctx = basics.context()
    assert set(ctx.plan.self_weights) == {0.5} and len(ctx.plan.classes) == 2
    return ctx


@pytest.mark.parametrize("weights", [
    pytest.param("thirds", marks=_CONTRACTS), "halves"])
@pytest.mark.parametrize("every", [1, 2], ids=["every-step", "every-2nd-step"])
def test_grouped_step_gives_the_per_leaf_parameters(four, monkeypatch, every,
                                                    weights):
    """Two steps of the rehearsal ResNet on four ranks, with the grouping
    and with the order withheld: every leaf of the state bit for bit.
    ``num_steps_per_communication=2`` puts the buckets inside a cond."""
    if weights == "halves":
        four = _halves(four)
    model = _tiny_resnet()
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
    params = training.replicate_for_mesh(v["params"], 4)
    stats = training.replicate_for_mesh(v["batch_stats"], 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 32, 32, 3))
    y = jnp.arange(16, dtype=jnp.int32).reshape(4, 4) % 10

    def run():
        init_fn, step_fn = make_decentralized_train_step(
            model.apply, optax.sgd(0.1, momentum=0.9), four.mesh,
            plan=four.plan, has_batch_stats=True, donate=False,
            num_steps_per_communication=every)
        state = (params, stats, init_fn(params))
        permutes = jax.jit(step_fn).lower(*state, x, y).as_text().count(
            "collective_permute")
        for _ in range(2):
            *state, _loss, _acc = step_fn(*state, x, y)
        return state, permutes

    grouped, n_grouped = run()
    real = ops_spmd.neighbor_allreduce
    monkeypatch.setattr(ops_spmd, "neighbor_allreduce",
                        lambda *a, order=None, **k: real(*a, **k))
    plain, n_plain = run()
    n_leaves = len(jax.tree_util.tree_leaves(v["params"]))
    assert (n_grouped, n_plain) == (4, 2 * n_leaves)
    for a, b in zip(jax.tree_util.tree_leaves(grouped),
                    jax.tree_util.tree_leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grouped_step_gives_the_per_leaf_parameters_at_thirds_without_fma():
    """The two cases above at exp2(4)'s own weights, unchanged, in a process
    whose XLA compiles for a CPU without fused multiply-add (the flag is read
    once a process, so it takes one): bit for bit, so the two programs do the
    same arithmetic at thirds and only this backend's contraction parts them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        "--xla_force_host_platform_device_count=8 --xla_cpu_max_isa=AVX"))
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "no:cacheprovider",
         "--runxfail", "-k", "thirds",
         f"{__file__}::test_grouped_step_gives_the_per_leaf_parameters"],
        env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0 and "2 passed" in run.stdout, run.stdout[-4000:]


def test_chip_smokes_gossip_witness_sees_a_wrong_unpack(four, monkeypatch):
    """`chip_smoke.phase_buckets_vs_per_leaf` is what holds the bucketed
    gossip to the per-leaf gossip on the chips, at thirds and at the cell's
    shapes, with the SGD update and ``p + (c - p)`` around it: to the last
    places, not to the bit (its docstring says what the compilers do).  Here
    its rehearsal: it counts its permutes (a bucket and class, a leaf and
    class), compares every leaf and passes; with an unpack that hands the
    first leaf of every bucket back upside down it raises."""
    import chip_smoke

    witness = lambda: chip_smoke.phase_buckets_vs_per_leaf(
        four, chip_smoke.TINY["resnet"], 0, chip_smoke._CompileClock())
    leaves, gap = witness()
    assert leaves <= 4 and gap <= chip_smoke.BUCKETS_GAP_RTOL

    real = ops_spmd._unpack_bucket

    def upside_down(tiles, leaves):
        out = real(tiles, leaves)
        return [out[0][::-1]] + out[1:]

    monkeypatch.setattr(ops_spmd, "_unpack_bucket", upside_down)
    with pytest.raises(AssertionError, match="from the per-leaf gossip's"):
        witness()

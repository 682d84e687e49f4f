"""How the ATC train step groups its leaves into permutes: the order comes
from the step's own jaxpr, the buckets from ``ops_spmd.gossip_grouping``.
Nothing here runs a training loop; the one test that runs a step runs two,
on a four-device mesh, at the benchmark configuration's rehearsal sizes."""

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
from bluefog_tpu.models.resnet import BottleneckBlock
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

    assert (g.leaves, len(g.buckets), g.permutes, g.packed_bytes) == (
        161, 2, 4, 102_228_128)
    assert gauges == {"gossip.leaves": 161, "gossip.buckets": 2,
                      "gossip.permutes": 4, "gossip.packed_bytes": 102_228_128}
    # what the parent did, from the same function: a permute per leaf and class
    assert ops_spmd.gossip_grouping(leaves, None, 2)[1:] == (161, 322, 0)
    # four shift classes or more: one bucket, as many permutes as classes
    assert ops_spmd.gossip_grouping(leaves, ranks, 4)[1:] == (
        161, 4, 102_228_128)


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


@pytest.mark.parametrize("every", [1, 2], ids=["every-step", "every-2nd-step"])
def test_grouped_step_gives_the_per_leaf_parameters(four, monkeypatch, every):
    """Two steps of the rehearsal ResNet on four ranks, with the grouping
    and with the order withheld: every leaf of the state bit for bit.
    ``num_steps_per_communication=2`` puts the buckets inside a cond."""
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

"""Decentralized SPMD train-step builder — the idiomatic TPU path.

This is the flagship composition the whole framework exists for (SURVEY.md
§7 stage 3/6): a single jitted SPMD program in which every rank computes its
local forward/backward on its batch shard and the decentralized optimizer's
gossip (``ppermute`` rounds) is scheduled by XLA *inside* the step —
overlapping communication with compute exactly where the reference relied on
its background thread + per-parameter hooks (SURVEY.md §3.3).

Works on any mesh: flat ``(bf_nodes,)`` for rank-level gossip, factored
``(bf_machines, bf_local)`` for hierarchical.  BatchNorm state stays local
per rank (data-parallel semantics, like the reference); only parameters are
communicated.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.extend.core import Literal
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu.core import basics
from bluefog_tpu.core.basics import LOCAL_AXIS, MACHINES_AXIS, NODES_AXIS
from bluefog_tpu.core.plan import CommPlan
from bluefog_tpu.ops_spmd import gossip_grouping
from bluefog_tpu.optim import (
    CommunicationType,
    adapt_then_combine_spmd,
    adapt_with_combine_spmd,
    gradient_allreduce_spmd,
    make_spmd_comm_fn,
)
from bluefog_tpu.telemetry import registry as _telemetry
from bluefog_tpu.timeline import _register_step_program, timeline_context

__all__ = [
    "apply_accepts_labels",
    "make_decentralized_train_step",
    "make_lm_loss_fns",
    "replicate_for_mesh",
]


def apply_accepts_labels(apply_fn: Callable) -> bool:
    """True when ``apply_fn`` declares a ``labels`` parameter — the contract
    marker by which train-step builders (here and in ``parallel/zero.py``)
    thread the true targets through to a model that computes its own loss
    (the chunked LM head).  Wrappers around such an apply_fn must preserve
    the ``labels`` parameter or targets silently revert to inputs-as-labels.
    """
    import inspect

    try:
        return "labels" in inspect.signature(apply_fn).parameters
    except (TypeError, ValueError):
        return False


def softmax_cross_entropy(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def make_lm_loss_fns(model):
    """(apply_fn, loss_fn) for LM pretraining with a ``LlamaLM``-style
    model where inputs are their own labels.  The chunked-vs-full choice
    is read off ``model.head_chunks`` — the one place it is configured.

    With ``head_chunks > 1`` the model computes the chunked scalar loss
    itself (``apply(variables, ids, labels=ids)`` — the full
    ``[B, T, vocab]`` logits never materialize, and the head's gradient is
    taken in that one loop, a ``jax.custom_vjp``: reverse mode only) and
    ``loss_fn`` is the identity; otherwise the model returns logits and
    ``loss_fn`` is the standard shifted cross-entropy.  One definition shared by
    ``chip_smoke.py`` and ``examples/jax_llama_pretrain.py`` so the
    chunked-loss contract cannot drift between them.
    """
    if getattr(model, "head_chunks", 0) > 1:
        # labels flow through apply (train-step builders detect the
        # ``labels`` parameter and pass them) so masked/instruction-tuning
        # targets are honored, not silently replaced by inputs-as-labels
        # (r3 advisor finding); bare 2-arg calls keep the ids-as-labels
        # LM-pretraining default
        def apply_fn(variables, ids, labels=None):
            return model.apply(variables, ids, labels=ids if labels is None else labels)

        def loss_fn(out, labels):
            return out

    else:

        def apply_fn(variables, ids):
            return model.apply(variables, ids)

        def loss_fn(logits, labels):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], labels[:, 1:]
            ).mean()

    return apply_fn, loss_fn


def _producer_ranks(jaxpr) -> list:
    """For each output of ``jaxpr``, the index of the equation that produces
    it: the order in which a program that runs the equations in turn has its
    results ready.  Outputs of one equation (a scanned stack, a remat block)
    share a rank; an input or a literal passed through is ready at once."""
    produced = {}
    for k, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            produced[v] = k
    return [-1 if isinstance(v, Literal) else produced.get(v, -1)
            for v in jaxpr.outvars]


def _value_and_grad_in_order(loss_of, p):
    """``jax.value_and_grad(loss_of, has_aux=True)(p)``, and a pytree like
    ``p`` that gives each gradient the rank at which the backward pass has
    it ready.  ``loss_of`` is traced once, as an inner ``jit``: the order is
    read off its jaxpr, and the call that follows finds that trace cached
    and adds one equation to the step.  (Evaluating the jaxpr equation by
    equation instead cost the first call 6 s on the chip's host.)"""
    vg = jax.jit(jax.value_and_grad(loss_of, has_aux=True))
    ranks = _producer_ranks(vg.trace(p).jaxpr.jaxpr)
    out = vg(p)
    grads, treedef = jax.tree_util.tree_flatten(out[1])
    ranks = ranks[len(ranks) - len(grads):]  # the outputs end in the gradients
    return out, jax.tree_util.tree_unflatten(treedef, ranks)


def _note_gossip_grouping(params, order, plan):
    """At trace time: the gauges that say how this step's gossip groups its
    leaves into permutes (``ops_spmd.gossip_grouping`` on the same inputs)."""
    reg = _telemetry.get_registry()
    if not reg.enabled:
        return
    g = gossip_grouping(
        jax.tree_util.tree_leaves(params),
        None if order is None else jax.tree_util.tree_leaves(order),
        len(plan.classes))
    reg.gauge("gossip.leaves").set(g.leaves)
    reg.gauge("gossip.buckets").set(len(g.buckets))
    reg.gauge("gossip.permutes").set(g.permutes)
    reg.gauge("gossip.packed_bytes").set(g.packed_bytes)
    reg.gauge("gossip.tiled_bytes").set(g.tiled_bytes)


def make_decentralized_train_step(
    apply_fn: Callable,
    base_optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    communication_type: CommunicationType = CommunicationType.neighbor_allreduce,
    plan: Optional[CommPlan] = None,
    machine_plan: Optional[CommPlan] = None,
    mode: str = "atc",
    loss_fn: Callable = softmax_cross_entropy,
    has_batch_stats: bool = False,
    num_steps_per_communication: int = 1,
    donate: bool = True,
    steps_per_call: int = 1,
):
    """Build ``(init_fn, step_fn)`` for decentralized training on ``mesh``.

    Data layout: every array is *rank-major sharded* — params/opt_state/
    batch leading axis is the global rank axis.  ``step_fn(train_state,
    batch, labels) -> (train_state, metrics)`` with ``train_state =
    (params, batch_stats, opt_state)``.

    The returned functions are jit-compiled once per shape; inside, each
    rank's loss/grad runs on its shard and the optimizer transform carries
    the gossip.

    ``steps_per_call=k`` fuses k FULL training steps (forward, backward,
    optimizer, gossip) into one compiled program; ``batch``/``labels`` then
    carry a leading sub-step axis ``[k, ranks, B, ...]`` and the returned
    loss/acc are the last sub-step's.  Where each dispatch carries a fixed
    cost this amortizes it, at the price of k× compile time.

    An ATC step that gossips over at least one shift class reads, from its
    own jaxpr, the order in which the backward pass produces the gradients,
    and hands it to the gossip, which packs the leaves into the few buckets
    the TPU scheduler will keep in flight
    (:func:`bluefog_tpu.ops_spmd.neighbor_allreduce`): the large early
    bucket travels while the backward pass still runs.  The values are the
    same, and every other step keeps one permute per leaf.
    """
    apply_takes_labels = apply_accepts_labels(apply_fn)

    axes = mesh.axis_names
    if set(axes) == {MACHINES_AXIS, LOCAL_AXIS}:
        spec = P((MACHINES_AXIS, LOCAL_AXIS))
        axis_name = (MACHINES_AXIS, LOCAL_AXIS)
    else:
        spec = P(NODES_AXIS)
        axis_name = NODES_AXIS

    if communication_type == CommunicationType.allreduce:
        tx = gradient_allreduce_spmd(
            base_optimizer, axis_name, num_steps_per_communication
        )
    else:
        comm_fn = make_spmd_comm_fn(communication_type, plan, machine_plan)
        builder = {"atc": adapt_then_combine_spmd, "awc": adapt_with_combine_spmd}[mode]
        tx = builder(base_optimizer, comm_fn, num_steps_per_communication)
    # only ATC's combine waits for the gradients (AWC mixes the parameters
    # the step came in with), and a plan without a shift class sends nothing
    orders_gossip = (
        communication_type == CommunicationType.neighbor_allreduce
        and mode == "atc" and plan is not None and len(plan.classes) > 0
    )

    def value_and_grad(loss_of, p):
        if orders_gossip:
            return _value_and_grad_in_order(loss_of, p)
        return jax.value_and_grad(loss_of, has_aux=True)(p), None

    def local_step(params, batch_stats, opt_state, batch, labels):
        # strip the local rank-major axis (length 1 per device)
        p = jax.tree_util.tree_map(lambda a: a[0], params)
        bs = jax.tree_util.tree_map(lambda a: a[0], batch_stats)
        os_ = jax.tree_util.tree_map(
            lambda a: a[0] if a.ndim >= 1 and a.shape[0] == 1 else a, opt_state
        )
        x, y = batch[0], labels[0]

        if has_batch_stats:

            def loss_of(p_):
                logits, mut = apply_fn(
                    {"params": p_, "batch_stats": bs}, x, mutable=["batch_stats"]
                )
                return loss_fn(logits, y), (logits, mut["batch_stats"])

            with jax.named_scope("forward_backward"):
                ((loss, (logits, new_bs)), grads), order = value_and_grad(
                    loss_of, p)
        else:

            def loss_of(p_):
                if apply_takes_labels:
                    logits = apply_fn({"params": p_}, x, labels=y)
                else:
                    logits = apply_fn({"params": p_}, x)
                return loss_fn(logits, y), logits

            with jax.named_scope("forward_backward"):
                ((loss, logits), grads), order = value_and_grad(loss_of, p)
            new_bs = bs

        # tx.update opens the optimizer-update and gossip scopes of optim.py
        if order is None:
            updates, new_os = tx.update(grads, os_, p)
        else:
            updates, new_os = tx.update(grads, os_, p, grad_order=order)
        if communication_type == CommunicationType.neighbor_allreduce:
            _note_gossip_grouping(p, order, plan)
        with jax.named_scope("optimizer_update"):
            new_p = optax.apply_updates(p, updates)
        if logits.ndim >= 2:
            acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        else:
            # apply_fn returned a scalar loss directly (e.g. the chunked
            # LM head, where full logits never exist) — NaN marks the
            # accuracy "not computed" rather than a measured 0%
            acc = jnp.full_like(loss, jnp.nan)
        # re-attach the rank-major axis
        expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
        # the compiler fuses a leaf's update with what follows it and names
        # the fusion after its last op: the new state's axis goes back on
        # inside the scope, or the optimizer's fusions carry no scope at all
        with jax.named_scope("optimizer_update"):
            new_os_out = jax.tree_util.tree_map(
                lambda new, old: new[None] if old.ndim >= 1 and old.shape[0] == 1 else new,
                new_os,
                opt_state,
            )
            new_p = expand(new_p)
        return (
            new_p,
            expand(new_bs),
            new_os_out,
            expand(loss),
            expand(acc),
        )

    if steps_per_call > 1:
        # k fused steps per dispatch: batch/labels gain a leading sub-step
        # axis, consumed by a python-unrolled loop (lax.scan over a body
        # this size has failed to compile before; unroll is safe)
        def body(params, batch_stats, opt_state, batch, labels):
            for i in range(steps_per_call):
                params, batch_stats, opt_state, loss, acc = local_step(
                    params, batch_stats, opt_state, batch[i], labels[i]
                )
            return params, batch_stats, opt_state, loss, acc

        data_spec = P(None, *spec)

        def _check_substep_axis(batch):
            lead = {a.shape[0] for a in jax.tree_util.tree_leaves(batch)}
            if lead != {steps_per_call}:
                raise ValueError(
                    f"steps_per_call={steps_per_call} needs batch/labels "
                    f"with a leading [{steps_per_call}] sub-step axis; got "
                    f"leading dims {sorted(lead)}"
                )
    else:
        body = local_step
        data_spec = spec

    def _opt_state_spec(opt_state):
        return jax.tree_util.tree_map(
            lambda a: spec if getattr(a, "ndim", 0) >= 1 else P(), opt_state
        )

    def init_fn(params, batch_stats=None):
        """params/batch_stats: rank-major pytrees.  Returns opt_state,
        rank-major leaves sharded over ``mesh`` like the params."""

        def build(params):
            os_local = tx.init(jax.tree_util.tree_map(lambda a: a[0], params))
            n = mesh.devices.size
            # broadcast rank-major leaves across ranks; scalars replicated
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape)
                if a.ndim >= 1
                else a,
                os_local,
            )

        # placed where it is created: left to the default device, every
        # rank's state would sit on chip 0 until the first step re-scatters
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            _opt_state_spec(jax.eval_shape(build, params)),
            is_leaf=lambda s: isinstance(s, P),
        )
        return jax.jit(build, out_shardings=shardings)(params)

    compiled = {}

    def step_fn(params, batch_stats, opt_state, batch, labels):
        # the step's one span (jitted training records no per-op host spans):
        # all the host work of a call, from the cache lookup on the state's
        # structure to the dispatch of the fused program.  The reference's
        # per-tensor spans are a background-thread artifact; this is the
        # honest TPU equivalent
        with timeline_context("train_step"):
            if steps_per_call > 1:
                # a [ranks, B, ...] batch here would silently shard the RANK
                # axis as the sub-step axis and train on wrong slices
                _check_substep_axis((batch, labels))
            key = jax.tree_util.tree_structure(opt_state)
            if key not in compiled:
                os_spec = _opt_state_spec(opt_state)
                compiled[key] = jax.jit(
                    jax.shard_map(
                        body,
                        mesh=mesh,
                        in_specs=(spec, spec, os_spec, data_spec, data_spec),
                        out_specs=(spec, spec, os_spec, spec, spec),
                    ),
                    donate_argnums=(0, 1, 2) if donate else (),
                )
                # what timeline.step_scopes() is read from, noted here once
                _register_step_program(
                    compiled[key], (params, batch_stats, opt_state, batch, labels))
            reg = _telemetry.get_registry()
            if reg.enabled:
                # one host call may run several fused sub-steps
                reg.counter("train.steps").add(max(1, int(steps_per_call)))
            return compiled[key](params, batch_stats, opt_state, batch, labels)

    return init_fn, step_fn


def replicate_for_mesh(tree, n: int):
    """Replicate a single-rank pytree into rank-major layout [n, ...], one
    row per device of the initialized context's mesh (rows created where
    they live — built on the default device, all ``n`` replicas would sit
    on chip 0 until the first step re-scatters them)."""

    def rep(t):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), t
        )

    if not basics.is_initialized() or basics.context().size != n:
        return rep(tree)  # no mesh of n ranks to place on
    return jax.jit(rep, out_shardings=basics.rank_major_sharding())(tree)

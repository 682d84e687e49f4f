"""Decentralized optimizers — optax-native transforms + Bluefog-parity classes.

TPU-native sibling of the reference's ``bluefog/torch/optimizers.py`` [U]
(SURVEY.md §2.2, §3.3).  The reference hooks per-parameter backward callbacks
to overlap nonblocking gossip with backprop; under XLA the gossip sits
*inside* the jitted train step and the compiler schedules it (SURVEY.md §3.3
TPU mapping), so the hook/handle machinery dissolves into pure functions.
The overlap does not fall out by itself: the TPU scheduler keeps five
collective-permutes in flight, so of a permute per leaf all but five wait
behind the last gradient.  The train step therefore tells the gossip the
order in which the backward pass produces the gradients, and the gossip
packs the leaves into that few buckets (``ops_spmd.neighbor_allreduce``,
``training.make_decentralized_train_step``; PERF.md section 6, PR 27).

Two layers:

- **SPMD builders** (``*_spmd``): optax ``GradientTransformation`` factories
  parameterized by a comm function, for use inside user ``jit``/``shard_map``
  train steps — the idiomatic TPU path (used by the flagship benchmark).
- **Parity classes** (``DistributedAdaptThenCombineOptimizer`` etc.):
  eager, rank-major ``init``/``step`` mirroring the reference's usage shape,
  including ``CommunicationType`` and ``num_steps_per_communication``.

Algorithms (arXiv:2111.04287 §2):
  ATC  (adapt-then-combine):  w_{t+1} = W (w_t - α u_t)
  AWC  (adapt-with-combine):  w_{t+1} = W w_t - α u_t
  Gradient allreduce (Horovod-equivalent DP): u_t averaged globally.
  Win-put (async push-style): local adapt, deposit to out-neighbors'
  mailboxes, merge mailboxes — no global barrier semantics.
"""

from __future__ import annotations

import enum
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from bluefog_tpu import ops, ops_spmd, windows
from bluefog_tpu.telemetry import registry as _telemetry
from bluefog_tpu.core import basics
from bluefog_tpu.core.basics import LOCAL_AXIS, MACHINES_AXIS, NODES_AXIS
from bluefog_tpu.core.plan import CommPlan
from bluefog_tpu.timeline import timeline_context

__all__ = [
    "CommunicationType",
    "adapt_then_combine_spmd",
    "adapt_with_combine_spmd",
    "gradient_allreduce_spmd",
    "DistributedAdaptThenCombineOptimizer",
    "DistributedAdaptWithCombineOptimizer",
    "DistributedGradientAllreduceOptimizer",
    "DistributedWinPutOptimizer",
    "one_peer_plan_schedule",
    "broadcast_parameters",
    "broadcast_optimizer_state",
]


class CommunicationType(enum.Enum):
    """Reference ``bf.CommunicationType`` [U]."""

    allreduce = "allreduce"
    neighbor_allreduce = "neighbor.allreduce"
    hierarchical_neighbor_allreduce = "hierarchical.neighbor.allreduce"
    empty = "empty"


CommFn = Callable[[Any], Any]  # pytree -> pytree, inside SPMD context


def make_spmd_comm_fn(
    comm_type: CommunicationType,
    plan: Optional[CommPlan] = None,
    machine_plan: Optional[CommPlan] = None,
    axis_name: str = NODES_AXIS,
    machines_axis: str = MACHINES_AXIS,
    local_axis: str = LOCAL_AXIS,
) -> CommFn:
    """Build the in-SPMD communication function for a CommunicationType.

    The function takes the pytree.  ``neighbor_allreduce``'s also takes, by
    keyword, an ``order``: a pytree like it of the ranks at which its leaves
    are ready, by which it packs them into the few buckets the TPU scheduler
    will keep in flight (:func:`ops_spmd.neighbor_allreduce`); without one
    it permutes leaf by leaf, and the TPU compiler merges none of those
    permutes."""
    if comm_type == CommunicationType.empty:
        return lambda x: x
    if comm_type == CommunicationType.allreduce:
        return lambda x: ops_spmd.allreduce(x, axis_name, average=True)
    if comm_type == CommunicationType.neighbor_allreduce:
        if plan is None:
            raise ValueError("neighbor_allreduce needs a CommPlan")
        return lambda x, order=None: ops_spmd.neighbor_allreduce(
            x, plan, axis_name, order=order)
    if comm_type == CommunicationType.hierarchical_neighbor_allreduce:
        if machine_plan is None:
            raise ValueError("hierarchical_neighbor_allreduce needs a machine CommPlan")
        return lambda x: ops_spmd.hierarchical_neighbor_allreduce(
            x, machine_plan, machines_axis, local_axis
        )
    raise ValueError(f"unknown communication type {comm_type}")


class GossipState(NamedTuple):
    base: Any
    step: jnp.ndarray  # int32 counter for num_steps_per_communication


def _every_k(comm_fn: CommFn, k: int) -> Callable[[Any, jnp.ndarray], Any]:
    """Communicate only on every k-th call (reference
    ``num_steps_per_communication`` [U]); k==1 avoids the cond entirely."""
    if k <= 1:
        return lambda x, step, **kw: comm_fn(x, **kw)

    def maybe(x, step, **kw):
        return jax.lax.cond((step + 1) % k == 0,
                            functools.partial(comm_fn, **kw), lambda t: t, x)

    return maybe


def adapt_then_combine_spmd(
    base: optax.GradientTransformation,
    comm_fn: CommFn,
    num_steps_per_communication: int = 1,
) -> optax.GradientTransformation:
    """ATC as an optax transform: the returned updates satisfy
    ``params + updates == comm(params + base_updates)``.

    Mirrors ``DistributedAdaptThenCombineOptimizer`` [U]: local adapt first,
    then neighbor-combine the adapted parameters.
    """
    maybe_comm = _every_k(comm_fn, num_steps_per_communication)

    def init(params):
        return GossipState(base=base.init(params), step=jnp.zeros((), jnp.int32))

    def update(grads, state, params=None, *, grad_order=None):
        if params is None:
            raise ValueError("ATC requires params")
        with jax.named_scope("optimizer_update"):
            updates, base_state = base.update(grads, state.base, params)
            adapted = optax.apply_updates(params, updates)
        # an adapted leaf is ready when its gradient is, so the order of the
        # gradients is the order of what is gossiped; only a caller whose
        # comm_fn takes one (make_spmd_comm_fn's neighbor_allreduce) gives it
        kw = {} if grad_order is None else {"order": grad_order}
        with jax.named_scope("gossip_combine"):
            combined = maybe_comm(adapted, state.step, **kw)
        with jax.named_scope("optimizer_update"):
            out = jax.tree_util.tree_map(
                lambda c, p: (c - p).astype(p.dtype), combined, params)
        return out, GossipState(base=base_state, step=state.step + 1)

    return optax.GradientTransformationExtraArgs(init, update)


def adapt_with_combine_spmd(
    base: optax.GradientTransformation,
    comm_fn: CommFn,
    num_steps_per_communication: int = 1,
) -> optax.GradientTransformation:
    """AWC: ``params + updates == comm(params) + base_updates`` — combine and
    adapt simultaneously (``DistributedAdaptWithCombineOptimizer`` [U])."""
    maybe_comm = _every_k(comm_fn, num_steps_per_communication)

    def init(params):
        return GossipState(base=base.init(params), step=jnp.zeros((), jnp.int32))

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("AWC requires params")
        with jax.named_scope("optimizer_update"):
            updates, base_state = base.update(grads, state.base, params)
        with jax.named_scope("gossip_combine"):
            combined = maybe_comm(params, state.step)
        with jax.named_scope("optimizer_update"):
            out = jax.tree_util.tree_map(
                lambda c, u, p: (c + u - p).astype(p.dtype), combined, updates, params
            )
        return out, GossipState(base=base_state, step=state.step + 1)

    return optax.GradientTransformation(init, update)


def gradient_allreduce_spmd(
    base: optax.GradientTransformation,
    axis_name: str = NODES_AXIS,
    num_steps_per_communication: int = 1,
) -> optax.GradientTransformation:
    """Horovod-equivalent synchronous DP: average gradients globally before
    the base update (``DistributedGradientAllreduceOptimizer`` [U])."""
    comm = _every_k(lambda g: ops_spmd.allreduce(g, axis_name, average=True),
                    num_steps_per_communication)

    def init(params):
        return GossipState(base=base.init(params), step=jnp.zeros((), jnp.int32))

    def update(grads, state, params=None):
        with jax.named_scope("gradient_allreduce"):
            avg = comm(grads, state.step)
        with jax.named_scope("optimizer_update"):
            updates, base_state = base.update(avg, state.base, params)
        return updates, GossipState(base=base_state, step=state.step + 1)

    return optax.GradientTransformation(init, update)


# --------------------------------------------------------------------------
# Parity classes — eager, rank-major
# --------------------------------------------------------------------------


def _state_specs(state, size, axis_spec):
    """Per-leaf partition specs for optimizer state: leaves mirroring
    rank-major params (leading dim == size) shard over ranks; scalars such
    as optax step counts stay replicated."""
    return jax.tree_util.tree_map(
        lambda x: axis_spec
        if getattr(x, "ndim", 0) >= 1 and x.shape[0] == size
        else P(),
        state,
    )


class _EagerDistributedOptimizer:
    """Shared machinery: jit-compiled rank-major step over the global mesh."""

    _mode = "atc"

    def __init__(
        self,
        base_optimizer: optax.GradientTransformation,
        communication_type: CommunicationType = CommunicationType.neighbor_allreduce,
        num_steps_per_communication: int = 1,
    ):
        self.base = base_optimizer
        self.communication_type = communication_type
        self.k = int(num_steps_per_communication)
        self._tx = None
        self._tx_key = None
        self._step_fns = {}

    def _transform(self) -> optax.GradientTransformation:
        ctx = basics.context()
        plan = ctx.plan
        mplan = (
            ctx.machine_plan
            if self.communication_type
            == CommunicationType.hierarchical_neighbor_allreduce
            else None
        )
        key = (plan, mplan)
        if self._tx_key != key:
            comm_fn = make_spmd_comm_fn(self.communication_type, plan, mplan)
            builder = {
                "atc": adapt_then_combine_spmd,
                "awc": adapt_with_combine_spmd,
            }[self._mode]
            self._tx = builder(self.base, comm_fn, self.k)
            self._tx_key = key
        return self._tx

    def _mesh_specs(self):
        ctx = basics.context()
        if (
            self.communication_type
            == CommunicationType.hierarchical_neighbor_allreduce
        ):
            return ctx.hier_mesh, P((MACHINES_AXIS, LOCAL_AXIS))
        return ctx.mesh, P(NODES_AXIS)

    def init(self, params):
        """params: rank-major pytree ([size, ...] leaves).

        Runs the init eagerly on the global arrays: standard optax inits are
        elementwise (zeros_like etc.), so rank-major params produce
        rank-major state and replicated scalars directly.
        """
        return self._transform().init(params)

    def step(self, params, grads, state, plan: "CommPlan" = None):
        """One distributed step: returns (new_params, new_state).

        ``plan`` overrides the installed topology's plan for this call —
        the reference's *dynamic topology* optimizer path (one-peer
        rotations etc.).  Rotating through a small set of plans (e.g. the
        log(n) exp-2 one-peer permutations) reuses cached compilations.
        """
        if plan is not None:
            if self.communication_type != CommunicationType.neighbor_allreduce:
                raise ValueError("per-step plan override requires neighbor_allreduce")
            world = basics.context().size
            if plan.size != world:
                raise ValueError(
                    f"plan is for {plan.size} ranks, mesh has {world}"
                )

            def build_tx():
                comm_fn = make_spmd_comm_fn(self.communication_type, plan)
                builder = {
                    "atc": adapt_then_combine_spmd,
                    "awc": adapt_with_combine_spmd,
                }[self._mode]
                return builder(self.base, comm_fn, self.k)

            tx_key = (plan,)
        else:
            build_tx = self._transform
            tx_key = self._tx_key
        mesh, spec = self._mesh_specs()
        ctx = basics.context()
        state_spec = _state_specs(state, ctx.size, spec)
        key = (tx_key, jax.tree_util.tree_structure(state))

        if key not in self._step_fns:
            tx = build_tx()

            def whole(params, grads, state):
                updates, new_state = tx.update(grads, state, params)
                return optax.apply_updates(params, updates), new_state

            self._step_fns[key] = jax.jit(
                jax.shard_map(
                    whole,
                    mesh=mesh,
                    in_specs=(spec, spec, state_spec),
                    out_specs=(spec, state_spec),
                )
            )
        reg = _telemetry.get_registry()
        if reg.enabled:
            reg.counter(
                "optim.steps", optimizer=self._mode,
                comm=self.communication_type.name).inc()
        # the whole fused step is one dispatch, so its one span is this
        # one (per-op spans exist only on the eager op path)
        with timeline_context(
            f"optimizer_step_{self._mode}_{self.communication_type.name}"
        ):
            return self._step_fns[key](params, grads, state)


class DistributedAdaptThenCombineOptimizer(_EagerDistributedOptimizer):
    """Reference ``bf.DistributedAdaptThenCombineOptimizer`` [U]."""

    _mode = "atc"


class DistributedAdaptWithCombineOptimizer(_EagerDistributedOptimizer):
    """Reference ``bf.DistributedAdaptWithCombineOptimizer`` [U]."""

    _mode = "awc"


class DistributedGradientAllreduceOptimizer(_EagerDistributedOptimizer):
    """Reference ``bf.DistributedGradientAllreduceOptimizer`` [U]."""

    def __init__(
        self,
        base_optimizer: optax.GradientTransformation,
        num_steps_per_communication: int = 1,
    ):
        super().__init__(
            base_optimizer,
            communication_type=CommunicationType.allreduce,
            num_steps_per_communication=num_steps_per_communication,
        )

    def _transform(self) -> optax.GradientTransformation:
        return gradient_allreduce_spmd(self.base, NODES_AXIS, self.k)


class DistributedWinPutOptimizer:
    """Asynchronous win-put optimizer (reference
    ``bf.DistributedWinPutOptimizer`` [U]): each step does a local adapt,
    deposits parameters to out-neighbors via ``win_put``, and merges the
    mailbox with ``win_update`` — no global reduction.

    Uses the window emulation, so the realized schedule is the synchronous
    one (see :mod:`bluefog_tpu.windows` docstring).
    """

    def __init__(
        self,
        base_optimizer: optax.GradientTransformation,
        window_prefix: str = "winput_opt",
        num_steps_per_communication: int = 1,
        fuse: bool = True,
    ):
        self.base = base_optimizer
        self.prefix = window_prefix
        self.k = int(num_steps_per_communication)
        self.fuse = fuse
        self._step_count = 0
        self._created = False
        self._groups = None  # fused mode: [leaf_indices] per dtype group

    def init(self, params):
        leaves = jax.tree_util.tree_leaves(params)
        if self.fuse:
            # Tensor fusion, TPU-style: the reference coalesced small tensors
            # into its fusion buffer on the background thread
            # (BLUEFOG_FUSION_THRESHOLD, SURVEY.md §3.2); here all leaves of a
            # dtype pack into ONE rank-major window so a whole model's
            # win_put+win_update is two dispatches instead of 2 x num_leaves.
            by_dtype: Dict[Any, list] = {}
            for i, leaf in enumerate(leaves):
                by_dtype.setdefault(jnp.asarray(leaf).dtype, []).append(i)
            self._groups = []
            for g, (_, idxs) in enumerate(
                sorted(by_dtype.items(), key=lambda kv: str(kv[0]))
            ):
                # a LIST of leaves is a pytree: windows fuses it into one
                # packed window and packs/unpacks inside the compiled
                # exchange programs (no separate pack dispatches here)
                if not windows.win_create(
                    [leaves[i] for i in idxs], f"{self.prefix}.fused{g}"
                ):
                    raise RuntimeError(
                        f"window '{self.prefix}.fused{g}' already exists — "
                        f"two optimizers share window_prefix={self.prefix!r}, "
                        "or a prior instance was not win_free'd"
                    )
                self._groups.append(idxs)
        else:
            for i, leaf in enumerate(leaves):
                if not windows.win_create(leaf, f"{self.prefix}.{i}"):
                    raise RuntimeError(
                        f"window '{self.prefix}.{i}' already exists — two "
                        f"optimizers share window_prefix={self.prefix!r}, "
                        "or a prior instance was not win_free'd"
                    )
        self._created = True
        return self.base.init(params)

    def step(self, params, grads, state):
        ctx = basics.context()
        mesh = ctx.mesh

        def local(params, grads, state):
            updates, new_state = self.base.update(grads, state, params)
            return optax.apply_updates(params, updates), new_state

        key = ("local", jax.tree_util.tree_structure(state))
        if not hasattr(self, "_fns"):
            self._fns = {}
        if key not in self._fns:
            sspec = _state_specs(state, ctx.size, P(NODES_AXIS))
            self._fns[key] = jax.jit(
                jax.shard_map(
                    local,
                    mesh=mesh,
                    in_specs=(P(NODES_AXIS), P(NODES_AXIS), sspec),
                    out_specs=(P(NODES_AXIS), sspec),
                )
            )
        adapted, state = self._fns[key](params, grads, state)
        self._step_count += 1
        reg = _telemetry.get_registry()
        if reg.enabled:
            reg.counter("optim.steps", optimizer="winput").inc()
        if self._step_count % self.k == 0:
            if reg.enabled:
                reg.counter("optim.gossip_rounds", optimizer="winput").inc()
            flat, treedef = jax.tree_util.tree_flatten(adapted)
            if self.fuse:
                for g, idxs in enumerate(self._groups):
                    name = f"{self.prefix}.fused{g}"
                    parts = windows.win_put_update(
                        [flat[i] for i in idxs], name
                    )
                    for i, part in zip(idxs, parts):
                        flat[i] = part
            else:
                for i, leaf in enumerate(flat):
                    name = f"{self.prefix}.{i}"
                    windows.win_put(leaf, name)  # also refreshes the exposure
                    flat[i] = windows.win_update(name)
            adapted = jax.tree_util.tree_unflatten(treedef, flat)
        return adapted, state

    def close(self):
        """API parity with the island optimizer's ``close()``: the
        emulation has no background pipeline to drain, so this is a
        documented no-op — teardown code written against the island
        surface (``finish``/``close``/``free``) runs unchanged here."""

    def finish(self, params):
        """Parity with the island optimizer: no overlap pipeline to
        apply, so the params come back unchanged (after ``close``)."""
        self.close()
        return params

    def free(self):
        self.close()
        if self._created:
            ctx = basics.context()
            for name in [n for n in ctx.windows if n.startswith(self.prefix + ".")]:
                windows.win_free(name)
            self._created = False


def one_peer_plan_schedule(size: int):
    """The exp-2 one-peer rotation as a list of CommPlans to cycle through
    (``opt.step(..., plan=plans[t % len(plans)])``) — the compiled-variant
    set SURVEY.md §7 prescribes for dynamic topologies (each plan is a
    single ppermute; log2(n) distinct compilations total)."""
    import math as _math

    from bluefog_tpu.core.plan import plan_from_neighbor_lists
    from bluefog_tpu.topology_util import GetDynamicOnePeerSendRecvRanks

    if size <= 1:
        return [plan_from_neighbor_lists(size, [[] for _ in range(size)])]
    nbits = max(1, int(_math.ceil(_math.log2(size))))
    gens = [GetDynamicOnePeerSendRecvRanks(size, r) for r in range(size)]
    return [
        plan_from_neighbor_lists(size, [next(g)[1] for g in gens])
        for _ in range(nbits)
    ]


# --------------------------------------------------------------------------
# Parameter/state broadcast helpers
# --------------------------------------------------------------------------


def broadcast_parameters(params, root_rank: int = 0):
    """Give every rank the root's parameters (reference
    ``bf.broadcast_parameters`` [U]) — consistent initialization."""
    return ops.broadcast(params, root_rank=root_rank)


def broadcast_optimizer_state(state, root_rank: int = 0):
    """Reference ``bf.broadcast_optimizer_state`` [U]."""
    return jax.tree_util.tree_map(
        lambda x: ops.broadcast(x, root_rank=root_rank)
        if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 1
        else x,
        state,
    )

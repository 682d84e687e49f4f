"""ZeRO-1-style sharded optimizer state composing with machine-axis gossip.

BEYOND PARITY: the reference has no optimizer-state sharding — its 8B-class
configs assume enough HBM per rank for full f32 state.  On a 16 GB v5e,
1.05B params is the replicated-state ceiling (3 f32 copies = 12.6 GB,
measured round 2); going past it needs the state split across chips.  This
module is the TPU-native composition of two axes of the hierarchical mesh
(``core.basics.hier_mesh``):

- ``bf_local`` (intra-machine, ICI): data-parallel grads are
  ``psum_scatter``-ed so each chip keeps only 1/local_size of the f32
  master weights + optimizer state (the ZeRO-1 partition; Rajbhandari et
  al. 2020), and the working bf16 params are ``all_gather``-ed per step.
- ``bf_machines`` (inter-machine, DCN): the updated master SHARDS gossip
  with the neighbor-weighted combine over the machine topology — shard i
  only ever mixes with shard i, so decentralized averaging commutes with
  the partition and each machine pays 1/local_size of the gossip bytes.

Everything runs inside ONE jitted ``shard_map`` over the hierarchical mesh:
all_gather + fwd/bwd + psum_scatter + shard update + gossip ppermutes are
scheduled together by XLA (SURVEY.md §3.2's controller dissolved into the
compiled program).

Elementwise optimizers (SGD+momentum, AdamW) act identically on a packed
flat vector as on the tree, so the state lives as ONE padded f32 vector
per replica — the same fusion idea as the window packing.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu.core.basics import LOCAL_AXIS, MACHINES_AXIS
from bluefog_tpu.core.plan import CommPlan
from bluefog_tpu import ops_spmd
from bluefog_tpu.training import apply_accepts_labels

__all__ = [
    "make_zero_gossip_train_step",
    "make_fsdp_gossip_train_step",
    "fsdp_act_constraint",
    "fsdp_onehot_constraint",
    "fsdp_param_io_constraint",
    "fsdp_count_struct",
    "fsdp_state_struct",
    "packed_layout",
    "unpack_params",
]


def fsdp_act_constraint(hier_mesh: "Mesh"):
    """Activation constraint for models running under
    :func:`make_fsdp_gossip_train_step` (e.g. ``LlamaLM.act_constraint``).

    Pins the leading (batch) dim of every block-boundary activation to
    ``bf_local`` — the GSPMD FSDP recipe's load-bearing half.  Weights are
    sharded over ``bf_local`` on their largest dim, so an unconstrained
    ``x @ W`` lets propagation choose between gathering W (FSDP, what we
    want) and gathering x's batch (tensor-parallel-style, locally cheaper
    because x is the smaller operand).  Without this pin the 8B compile
    measured the latter everywhere: full-batch f32 temps ~2.5 GB/layer and
    zero reduce-scatters.  Runs inside the machines-vmap, so the spec
    covers the per-machine view; ``spmd_axis_name=MACHINES_AXIS`` on the
    vmap supplies the machines dim."""

    def constrain(x):
        spec = P(LOCAL_AXIS, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(hier_mesh, spec))

    return constrain


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _marked_read(w, fwd_sh, grad_sh, grad_dtype):
    return lax.with_sharding_constraint(w, fwd_sh)


def _marked_read_fwd(w, fwd_sh, grad_sh, grad_dtype):
    return lax.with_sharding_constraint(w, fwd_sh), None


def _marked_read_bwd(fwd_sh, grad_sh, grad_dtype, _, g):
    if grad_dtype is not None:
        g = g.astype(grad_dtype)
    return (lax.with_sharding_constraint(g, grad_sh),)


_marked_read.defvjp(_marked_read_fwd, _marked_read_bwd)


def fsdp_onehot_constraint(hier_mesh: "Mesh"):
    """Pins the one-hot embedding operand ``[B, T, vocab]`` vocab-sharded
    (``LlamaLM.onehot_constraint``): the embedding dot then partitions on
    its CONTRACTING dim — each device contracts its vocab shard and the
    [B, T, d] partials reduce — instead of GSPMD's default, which
    all-gathers the f32 table (2.1 GB/device at 128k vocab, measured on
    the 8B compile)."""

    def constrain(oh):
        spec = P(*([None] * (oh.ndim - 1) + [LOCAL_AXIS]))
        return lax.with_sharding_constraint(
            oh, NamedSharding(hier_mesh, spec))

    return constrain


def fsdp_param_io_constraint(hier_mesh: "Mesh", grad_dtype=None):
    """Per-read FSDP marker for model weights (``LlamaLM.weight_constraint``).

    Forward: re-pins the leaf (or, in a scanned model, the per-layer
    SLICE) to its own FSDP shard spec — an identity that stops sharding
    propagation from re-resolving the read toward a replicated layout.
    A "gather here" (replicated-forward) marker was measured strictly
    worse: under ``nn.scan`` GSPMD hoists the resulting gather to the
    WHOLE stacked leaf ahead of the loop (37.5 GB of temps at
    8B/32-layer).

    Backward: the custom VJP pins the cotangent to the same shard spec AT
    ITS PRODUCTION SITE — without it the 128k-vocab head/embedding
    gradients accumulate replicated in f32 (measured ~2.1 GB per buffer,
    the largest single temps item of the 8B compile) — and optionally
    rounds it to ``grad_dtype`` (bf16 = the standard bf16-gradient
    contract; halves gradient liveness).

    The rounding must be ONE-SHOT per leaf: a scan-sliced block weight's
    cotangent is that layer's gradient alone (no cross-layer sum), but a
    leaf read INSIDE a loop body — the chunked LM head reads its kernel
    once per chunk — would have each per-read cotangent rounded and then
    summed in ``grad_dtype`` by the scan transpose.  For such sites use
    the attached ``.sharding_only`` variant (same sharding pin, no cast)
    inside the loop and apply the full marker once outside, so the chunk
    cotangents accumulate in f32 and round once
    (``LlamaLM.weight_constraint`` does this wiring)."""
    _, local = hier_mesh.devices.shape

    def _make(cast_dtype):
        def constrain(w):
            i = _shard_dim(w.shape, local)
            parts = [None] * w.ndim
            if i is not None:
                parts[i] = LOCAL_AXIS
            sh = NamedSharding(hier_mesh, P(*parts))
            return _marked_read(w, sh, sh, cast_dtype)

        return constrain

    constrain = _make(grad_dtype)
    constrain.sharding_only = _make(None)
    return constrain


class _Layout(NamedTuple):
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    treedef: Any
    total: int      # unpadded element count
    padded: int     # total padded to a multiple of local_size


def packed_layout(params, local_size: int) -> _Layout:
    """Works on real arrays AND ShapeDtypeStructs (the 8B lower-only
    feasibility path builds the layout without materializing buffers)."""
    flat, treedef = jax.tree_util.tree_flatten(params)
    shapes = tuple(tuple(getattr(l, "shape", None) or np.shape(l))
                   for l in flat)
    sizes = tuple(int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes)
    total = int(sum(sizes))
    padded = ((total + local_size - 1) // local_size) * local_size
    return _Layout(shapes, sizes, treedef, total, padded)


def _pack(flat, layout: _Layout, dtype=jnp.float32):
    vec = jnp.concatenate(
        [jnp.ravel(l).astype(dtype) for l in flat]
    )
    return jnp.pad(vec, (0, layout.padded - layout.total))


def unpack_params(vec, layout: _Layout, dtype):
    """Padded flat vector -> the params tree in ``dtype``."""
    leaves = []
    off = 0
    for shape, size in zip(layout.shapes, layout.sizes):
        leaves.append(vec[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def _make_update_rule(optimizer: str, lr: float, momentum: float,
                      weight_decay: float):
    """Elementwise update rule on a flat f32 shard (identical math to the
    tree form — the partition is invisible to elementwise optimizers).

    Returns ``(init, update)``: ``init(zeros_f32, zeros_i32)`` builds the
    state tuple from the two zero-factories; ``update(g, state, w) ->
    (delta, state)``.  "sgdm": state (mu,), ``momentum`` is the momentum
    coefficient, ``weight_decay`` is L2 folded into the gradient.
    "adamw": state (mu, nu, count); ``momentum`` maps to b1 and
    ``weight_decay`` is DECOUPLED (applied to w, not g) per Loshchilov &
    Hutter — with weight_decay=0 this is exactly ``optax.adam``.
    """
    wd = float(weight_decay)
    if optimizer == "sgdm":
        mom = float(momentum)

        def init(zeros_f32, zeros_i32):
            del zeros_i32
            return (zeros_f32(),)

        def update(g, state, w):
            (mu,) = state
            if wd:
                g = g + wd * w
            # accumulate in f32, store at the state's dtype: with a bf16
            # momentum buffer (momentum_dtype=bf16, the 134M/1B bench
            # configs' choice) this is optax's accumulator_dtype contract —
            # halves the optimizer shard, identical math at f32 state
            mu_f = mom * mu.astype(jnp.float32) + g
            return -lr * mu_f, (mu_f.astype(mu.dtype),)

        return init, update
    if optimizer == "adamw":
        b1, b2, eps = float(momentum), 0.999, 1e-8

        def init(zeros_f32, zeros_i32):
            # nu is pinned f32 REGARDLESS of the caller's accumulator
            # dtype: its EMA decays by (1-b2) = 0.1%/step, below bf16's
            # ~0.39% ulp — a bf16 nu can never decay and freezes at
            # early-training values (mu's 10%/step increments survive
            # bf16 fine, so mu honors the caller's dtype)
            return (zeros_f32(), zeros_f32(jnp.float32), zeros_i32())

        def update(g, state, w):
            mu, nu, count = state
            count = count + 1
            # f32-accumulate, store at the state's dtype (same contract as
            # sgdm above — without the cast-back, momentum_dtype=bf16
            # state silently drifts to f32 after the first step)
            mu_f = b1 * mu.astype(jnp.float32) + (1 - b1) * g
            nu_f = b2 * nu.astype(jnp.float32) + (1 - b2) * g * g
            c = count.astype(jnp.float32)
            mu_hat = mu_f / (1 - b1 ** c)
            nu_hat = nu_f / (1 - b2 ** c)
            delta = -lr * (mu_hat / (jnp.sqrt(nu_hat) + eps) + wd * w)
            return delta, (mu_f.astype(mu.dtype), nu_f.astype(nu.dtype),
                           count)

        return init, update
    raise ValueError(f"optimizer must be 'sgdm' or 'adamw', got {optimizer!r}")


def make_zero_gossip_train_step(
    apply_fn: Callable,
    loss_fn: Callable,
    hier_mesh: Mesh,
    machine_plan: Optional[CommPlan],
    *,
    learning_rate: float = 1e-3,
    momentum: float = 0.9,
    optimizer: str = "sgdm",
    weight_decay: float = 0.0,
    compute_dtype=jnp.bfloat16,
):
    """Build ``(init_fn, step_fn, params_of)`` for ZeRO-1 + gossip training.

    ``init_fn(params)`` -> state with the f32 master and every optimizer
    slot (``optimizer="sgdm"``: momentum; ``"adamw"``: mu/nu/count) as
    ``[machines, local, padded/local]`` arrays sharded over BOTH mesh
    axes (each chip stores exactly its shard — the ZeRO-1 partition
    covers Adam's second moment too, the case the 8B table needs).

    ``step_fn(state, batch, labels) -> (state, mean_loss)`` — batch/labels
    lead with ``[machines, local, ...]``.

    ``params_of(state)`` -> full params tree in ``compute_dtype`` (machine
    0's replica) for eval/checkpoint.
    """
    machines, local = hier_mesh.devices.shape
    lr = float(learning_rate)
    _takes_labels = apply_accepts_labels(apply_fn)
    opt_init, opt_update = _make_update_rule(
        optimizer, lr, momentum, weight_decay)
    layout_box = {}

    def _layout_for(params):
        if "l" not in layout_box:
            layout_box["l"] = packed_layout(params, local)
        return layout_box["l"]

    def init_fn(params):
        layout = _layout_for(params)
        flat = jax.tree_util.tree_leaves(params)
        vec = _pack(flat, layout)                       # [padded] f32
        shard_len = layout.padded // local
        # every machine starts from the same point (consistent-start
        # idiom); each (machine, local) device stores one shard
        grid = jnp.broadcast_to(
            vec.reshape(local, shard_len)[None], (machines, local, shard_len)
        )
        sharding = NamedSharding(hier_mesh, P(MACHINES_AXIS, LOCAL_AXIS))
        master = jax.device_put(grid, sharding)
        opt = opt_init(
            lambda dtype=None: jax.device_put(
                jnp.zeros_like(grid, dtype=dtype), sharding),
            # per-replica step counter as [machines, local, 1] int32 so
            # every state leaf shares the (machines, local) spec
            lambda: jax.device_put(
                jnp.zeros((machines, local, 1), jnp.int32), sharding),
        )
        return {"master": master, "opt": opt}

    def _step(master, opt, batch, labels, layout):
        # shard_map body: master [1, 1, shard_len], opt leaves [1, 1, *]
        shard = master[0, 0]
        full = lax.all_gather(shard, LOCAL_AXIS, tiled=True)  # [padded] f32
        params = unpack_params(full, layout, compute_dtype)

        def local_loss(p):
            if _takes_labels:
                out = apply_fn(p, batch[0, 0], labels=labels[0, 0])
            else:
                out = apply_fn(p, batch[0, 0])
            return loss_fn(out, labels[0, 0])

        loss, grads = jax.value_and_grad(local_loss)(params)
        g = _pack(jax.tree_util.tree_leaves(grads), layout)
        # mean over the data-parallel (intra-machine) axis, scattered so
        # each chip keeps only its shard of the gradient
        g_shard = lax.psum_scatter(
            g, LOCAL_AXIS, scatter_dimension=0, tiled=True
        ) / local
        delta, opt_new = opt_update(
            g_shard, tuple(o[0, 0] for o in opt), shard)
        shard = shard + delta
        # decentralized averaging across machines, PER SHARD: shard i of
        # machine m mixes with shard i of its machine-topology neighbors
        if machine_plan is not None and machines > 1:
            shard = ops_spmd.neighbor_allreduce(
                shard, machine_plan, MACHINES_AXIS
            )
        loss = lax.pmean(lax.pmean(loss, LOCAL_AXIS), MACHINES_AXIS)
        return (shard[None, None],
                tuple(o[None, None] for o in opt_new), loss)

    def step_fn_factory(layout):
        body = functools.partial(_step, layout=layout)
        sharded = jax.shard_map(
            body,
            mesh=hier_mesh,
            in_specs=(P(MACHINES_AXIS, LOCAL_AXIS),
                      P(MACHINES_AXIS, LOCAL_AXIS),
                      P(MACHINES_AXIS, LOCAL_AXIS),
                      P(MACHINES_AXIS, LOCAL_AXIS)),
            out_specs=(P(MACHINES_AXIS, LOCAL_AXIS),
                       P(MACHINES_AXIS, LOCAL_AXIS), P()),
        )
        return jax.jit(sharded, donate_argnums=(0, 1))

    step_box = {}

    def _layout():
        if "l" not in layout_box:
            raise RuntimeError(
                "call init_fn(params) first: the packed layout "
                "(shapes/offsets) comes from the params tree — when "
                "restoring state from a checkpoint, still call init_fn "
                "with a matching params tree to rebuild it"
            )
        return layout_box["l"]

    def step_fn(state, batch, labels):
        layout = _layout()
        if "f" not in step_box:
            step_box["f"] = step_fn_factory(layout)
        master, opt, loss = step_box["f"](
            state["master"], state["opt"], batch, labels
        )
        return {"master": master, "opt": opt}, loss

    def params_of(state):
        layout = _layout()
        grid = state["master"]
        vec = jnp.reshape(grid[0], (-1,))  # machine 0's replica
        return unpack_params(vec, layout, compute_dtype)

    return init_fn, step_fn, params_of


# ---------------------------------------------------------------------------
# FSDP-style variant: per-leaf sharding via GSPMD (the 8B memory path)
# ---------------------------------------------------------------------------


def _shard_dim(shape, local_size: int):
    """The dimension to partition over ``bf_local``: the largest one
    divisible by local_size (None -> replicate the leaf; only tiny leaves
    like norms fall through)."""
    best = None
    for i, d in enumerate(shape):
        if d % local_size == 0 and d >= local_size and (
            best is None or d > shape[best]
        ):
            best = i
    return best


def _fsdp_spec(shape, local_size: int) -> P:
    """The PartitionSpec a ``[machines, *shape]`` state leaf gets under
    :func:`make_fsdp_gossip_train_step` — the single source of truth used
    by both ``init_fn`` and AOT callers (``fsdp_state_struct``)."""
    parts = [MACHINES_AXIS] + [None] * len(shape)
    i = _shard_dim(shape, local_size)
    if i is not None:
        parts[i + 1] = LOCAL_AXIS
    return P(*parts)


def fsdp_count_struct(leaf, hier_mesh: Mesh):
    """ShapeDtypeStruct for an adamw per-leaf step counter with EXACTLY
    ``init_fn``'s layout ([machines, 1, ...] int32, machines-sharded) —
    the AOT twin of the count factory in ``make_fsdp_gossip_train_step``
    so feasibility checks cannot drift from the runtime state."""
    machines, _ = hier_mesh.devices.shape
    return jax.ShapeDtypeStruct(
        (machines,) + (1,) * len(leaf.shape), jnp.int32,
        sharding=NamedSharding(hier_mesh, P(MACHINES_AXIS)))


def fsdp_state_struct(leaf, hier_mesh: Mesh, dtype=jnp.float32):
    """ShapeDtypeStruct for one master/momentum leaf with the EXACT
    sharding ``init_fn`` would give it — lets feasibility checks lower
    the step without materializing any buffer (benchmarks/zero_8b.py).
    ``dtype``: f32 for master leaves; pass the builder's ``momentum_dtype``
    for momentum structs."""
    machines, local = hier_mesh.devices.shape
    shape = tuple(leaf.shape)
    sh = NamedSharding(hier_mesh, _fsdp_spec(shape, local))
    return jax.ShapeDtypeStruct((machines,) + shape, dtype,
                                sharding=sh)


def make_fsdp_gossip_train_step(
    apply_fn: Callable,
    loss_fn: Callable,
    hier_mesh: Mesh,
    machine_plan: Optional[CommPlan],
    *,
    learning_rate: float = 1e-3,
    momentum: float = 0.9,
    optimizer: str = "sgdm",
    weight_decay: float = 0.0,
    compute_dtype=jnp.bfloat16,
    momentum_dtype=jnp.float32,
):
    """FSDP-style ZeRO + gossip: per-LEAF sharding under GSPMD.

    Unlike :func:`make_zero_gossip_train_step` (one packed vector, whole
    gradient materialized before the scatter), this keeps every leaf of
    the f32 master + momentum sharded over ``bf_local`` on its largest
    divisible dimension and lets XLA insert the per-use all-gathers in
    the forward and reduce-scatters on the gradients (the standard GSPMD
    FSDP recipe) — peak transient memory is per-OPERAND, not per-model,
    which is what closes the memory math at 8B.

    Decentralized semantics: each MACHINE holds its own replica (leaves
    gain a leading ``[machines]`` axis, sharded over ``bf_machines``);
    after the local update the replicas mix with the machine topology via
    the shift-class plan — ``ops_spmd.neighbor_allreduce`` inside a
    machines-manual/local-auto ``shard_map``, one ppermute per class
    (exactly ``CommPlan.mixing_matrix`` by construction; the earlier
    dense-W einsum spelling all-gathered every leaf's f32 shard over the
    machines axis, which broke the 8B memory budget — see the mix-site
    comment).

    ``batch``/``labels``: ``[machines, per_machine_batch, ...]``.
    """
    machines, local = hier_mesh.devices.shape
    lr = float(learning_rate)
    _takes_labels = apply_accepts_labels(apply_fn)
    opt_init, opt_update = _make_update_rule(
        optimizer, lr, momentum, weight_decay)
    do_mix = machine_plan is not None and machines > 1

    def _sharding(shape):
        return NamedSharding(hier_mesh, _fsdp_spec(shape, local))

    def init_fn(params):
        def place(leaf):
            leaf = jnp.asarray(leaf, jnp.float32)
            stacked = jnp.broadcast_to(leaf[None], (machines,) + leaf.shape)
            return jax.device_put(stacked, _sharding(leaf.shape))

        master = jax.tree_util.tree_map(place, params)
        opt = opt_init(
            lambda dtype=None: jax.tree_util.tree_map(
                lambda a: jnp.zeros_like(a, dtype=dtype or momentum_dtype),
                master),
            # per-replica, per-leaf step counter: [machines, 1, ...]
            # int32, broadcastable against its leaf
            lambda: jax.tree_util.tree_map(
                lambda a: jax.device_put(
                    jnp.zeros((machines,) + (1,) * (a.ndim - 1), jnp.int32),
                    NamedSharding(hier_mesh, P(MACHINES_AXIS))),
                master),
        )
        return {"master": master, "opt": opt}

    data_sharding_box = {}

    def step_fn(state, batch, labels):
        if "f" not in data_sharding_box:
            data_sharding_box["f"] = _build_step()
        return data_sharding_box["f"](state, batch, labels)

    def lower_step(state, batch, labels):
        """AOT-lower the step on ShapeDtypeStructs — the 8B feasibility
        check traces/lowers the full program with real dims but never
        materializes a buffer (benchmarks/zero_8b.py)."""
        if "f" not in data_sharding_box:
            data_sharding_box["f"] = _build_step()
        return data_sharding_box["f"].lower(state, batch, labels)

    step_fn.lower = lower_step

    def _build_step():
        data_spec = NamedSharding(hier_mesh, P(MACHINES_AXIS, LOCAL_AXIS))

        def step(state, batch, labels):
            master, opt = state["master"], state["opt"]

            def total_loss(master):
                p = jax.tree_util.tree_map(
                    lambda a: a.astype(compute_dtype), master)

                def one(pm, bm, lm):
                    if _takes_labels:
                        return loss_fn(apply_fn(pm, bm, labels=lm), lm)
                    return loss_fn(apply_fn(pm, bm), lm)

                # spmd_axis_name: inside the vmap, sharding constraints
                # (fsdp_act_constraint in the model) see the UNBATCHED
                # per-machine shapes; the batched machines dim is pinned
                # to MACHINES_AXIS here so the two compose into the full
                # P(machines, local, ...) layout
                losses = jax.vmap(one, spmd_axis_name=MACHINES_AXIS)(
                    p, batch, labels)
                return jnp.sum(losses), losses

            (_, losses), grads = jax.value_and_grad(
                total_loss, has_aux=True)(master)
            # force the reduce-scatter: gradient leaves live in the same
            # per-leaf partition as the master they update
            grads = jax.tree_util.tree_map(
                lambda g, m: lax.with_sharding_constraint(
                    g, _sharding(m.shape[1:])), grads, master)
            # the elementwise update rule, leaf by leaf (state slots are
            # trees shaped like master; the count slot broadcasts)
            m_leaves, tdef = jax.tree_util.tree_flatten(master)
            g_leaves = jax.tree_util.tree_leaves(grads)
            o_leaves = [jax.tree_util.tree_leaves(o) for o in opt]
            new_m, new_o = [], [[] for _ in opt]
            for i, (w, g) in enumerate(zip(m_leaves, g_leaves)):
                delta, o_new = opt_update(
                    g, tuple(ol[i] for ol in o_leaves), w)
                new_m.append(w + delta)
                for slot, val in zip(new_o, o_new):
                    slot.append(val)
            master = jax.tree_util.tree_unflatten(tdef, new_m)
            opt = tuple(jax.tree_util.tree_unflatten(tdef, slot)
                        for slot in new_o)
            if do_mix:
                # gossip combine via the shift-class plan (ONE ppermute per
                # class inside a machines-manual/local-auto shard_map), NOT
                # the dense-W einsum: the einsum's lowering all-gathers the
                # machines axis of every leaf's f32 shard — machines× the
                # whole state as temps, measured 16 of the 18 GB/device
                # that broke the 8B/32-layer budget.  ppermute keeps one
                # in-flight shard + accumulator per leaf.  Same W by
                # construction (machine_plan IS the matrix's source).
                # FULLY manual over both mesh axes (the local shard rides
                # through untouched — permute + weighted sum is
                # elementwise-linear, so permuting each local shard
                # independently IS the leaf permute).  A machines-manual/
                # local-auto spelling leaves the partitioner to rewrite
                # the region, and its reshard of a collective operand
                # between manual-subgroup and auto shardings is broken on
                # the CPU backend (CHECK in spmd_partitioner.cc); the
                # machine index rides in as a sharded iota rather than
                # lax.axis_index for the same reason (partition-id).
                def _mix_body(t, midx):
                    sq = jax.tree_util.tree_map(lambda a: a[0], t)
                    mixed = ops_spmd.neighbor_allreduce(
                        sq, plan=machine_plan, axis_name=MACHINES_AXIS,
                        rank_index=midx[0])
                    return jax.tree_util.tree_map(lambda a: a[None], mixed)

                midx = jnp.arange(machines, dtype=jnp.int32)
                mix_specs = jax.tree_util.tree_map(
                    lambda a: _fsdp_spec(a.shape[1:], local), master)
                master = jax.shard_map(
                    _mix_body, mesh=hier_mesh,
                    in_specs=(mix_specs, P(MACHINES_AXIS)),
                    out_specs=mix_specs,
                    axis_names=frozenset({MACHINES_AXIS, LOCAL_AXIS}))(
                        master, midx)
                master = jax.tree_util.tree_map(
                    lambda a: lax.with_sharding_constraint(
                        a, _sharding(a.shape[1:])), master)
            return {"master": master, "opt": opt}, jnp.mean(losses)

        return jax.jit(
            step,
            in_shardings=(None, data_spec, data_spec),
            donate_argnums=(0,),
        )

    def params_of(state):
        return jax.tree_util.tree_map(
            lambda a: a[0].astype(compute_dtype), state["master"])

    return init_fn, step_fn, params_of

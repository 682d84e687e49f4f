"""SPMD collective primitives — the compute core, used inside ``shard_map``.

TPU-native sibling of the reference's controller execution layer
(``MPIController::NeighborAllreduce`` / ``NCCLController::NeighborAllreduce``
in ``bluefog/common/{mpi,nccl}_controller.cc`` [U], SURVEY.md §3.2): where the
reference drains a queue on a background thread, negotiates order and issues
``MPI_Neighbor_allgather``/grouped ``ncclSend/Recv`` plus a local weighted
combine, here each op is a pure traced function — one ``lax.ppermute`` per
shift class of the compiled :class:`~bluefog_tpu.core.plan.CommPlan`, fused
by XLA with the weighted FMA combine.  XLA's scheduler runs the permutes
asynchronously, but only a few at a time: how many a step issues decides
how much of them it hides (see :func:`neighbor_allreduce`).

Every function takes the mesh axis name(s) explicitly and works on arbitrary
pytrees.  They are usable directly inside user ``jit``/``shard_map`` code
(the idiomatic TPU path) and are wrapped by :mod:`bluefog_tpu.ops` for the
eager rank-major veneer.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.core.plan import CommPlan

__all__ = [
    "allreduce",
    "broadcast",
    "allgather",
    "neighbor_allreduce",
    "gossip_grouping",
    "neighbor_allgather",
    "hierarchical_neighbor_allreduce",
    "pairwise_gossip",
]


def _weight_dtype(x: jnp.ndarray) -> jnp.dtype:
    return x.dtype if jnp.issubdtype(x.dtype, jnp.inexact) else jnp.float32


def allreduce(x, axis_name: str, *, average: bool = True):
    """Global (p)sum/(p)mean over ``axis_name`` (reference ``bf.allreduce``,
    default average=True [U])."""
    op = lax.pmean if average else lax.psum
    return jax.tree_util.tree_map(lambda a: op(a, axis_name), x)


def broadcast(x, root_rank: int, axis_name: str):
    """Every rank gets ``root_rank``'s value (reference ``bf.broadcast`` [U]).

    Lowered as a masked psum — the XLA-native broadcast over a mesh axis.
    """

    def bcast(a):
        idx = lax.axis_index(axis_name)
        wdt = _weight_dtype(a)
        masked = jnp.where(idx == root_rank, a, jnp.zeros_like(a)).astype(wdt)
        return lax.psum(masked, axis_name).astype(a.dtype)

    return jax.tree_util.tree_map(bcast, x)


def allgather(x, axis_name: str):
    """Concatenate every rank's tensor along a new leading axis
    (reference ``bf.allgather`` concatenates along axis 0 [U]; reshape the
    leading two axes to recover exactly that layout)."""
    return jax.tree_util.tree_map(
        lambda a: lax.all_gather(a, axis_name, axis=0, tiled=False), x
    )


# The TPU scheduler keeps at most this many collective-permutes outstanding
# (read from steps compiled for a described v5e 2x2, PERF.md section 6,
# PR 27): a `-done` sits next to the combine that consumes it, and with more
# permutes than slots every further `-done` forces a `-start` right beside
# it, so only this many starts float up into the backward pass.
# tests/test_tpu_compile.py notices a compiler that lifts the limit.
MAX_PERMUTES_OUTSTANDING = 5
# The share of a dtype's elements left to the bucket that is ready last: its
# permutes start after the last gradient and are covered only by the
# combine, so it is small; but what it holds is also what is still being
# computed while the large bucket travels, so it is not nothing.  Measured
# on ResNet-50 over exp2(4) (PERF.md section 6, PR 27): 10 / 5 / 2 / 1 /
# 0.5 % step in 50.14 / 49.93 / 49.87 / 50.11 / 50.17 ms; 5 % sits on the
# flat side of that optimum.
TAIL_SHARE = 0.05


class GossipGrouping(NamedTuple):
    """How one ``neighbor_allreduce`` groups its leaves into permutes: a pure
    function of shapes, dtypes, readiness order and the plan's shift classes
    (:func:`gossip_grouping`).  ``buckets`` holds leaf positions in flatten
    order; no bucket means one permute per leaf per class."""

    buckets: Tuple[Tuple[int, ...], ...]
    leaves: int
    permutes: int
    packed_bytes: int


def _dtype_groups(leaves) -> list:
    groups = {}  # dtype -> leaf positions, insertion-ordered
    for i, leaf in enumerate(leaves):
        groups.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    return list(groups.values())


def _split_by_share(idxs, sizes, k):
    """Cut ``idxs`` (in readiness order) into ``k`` runs, or as many as it
    has leaves: the last holds about ``TAIL_SHARE`` of the elements, the
    others equal shares of the rest.  Every cut is at the leaf boundary
    nearest its target that leaves each run a leaf, so one large leaf that
    is ready last becomes the tail; it does not swallow the split."""
    n = len(idxs)
    k = min(k, n)
    cum = [0]
    for i in idxs:
        cum.append(cum[-1] + sizes[i])
    head = cum[-1] * (1.0 - TAIL_SHARE)
    cuts = [0]
    for j in range(1, k):
        target = head * j / (k - 1)
        cuts.append(min(range(cuts[-1] + 1, n - (k - 1 - j)),
                        key=lambda c: abs(cum[c] - target)))
    cuts.append(n)
    return [idxs[a:b] for a, b in zip(cuts, cuts[1:])]


def gossip_grouping(leaves, order, n_classes: int, *,
                    fuse: bool = False) -> GossipGrouping:
    """The grouping :func:`neighbor_allreduce` uses for ``leaves`` (anything
    with ``shape`` and ``dtype``, in flatten order).

    ``order`` gives each leaf the rank at which it is ready (the index of
    the equation that produces its gradient; ties allowed), or is None.
    With an order the leaves are packed, dtype by dtype, into
    ``B = max(1, (MAX_PERMUTES_OUTSTANDING - 1) // n_classes)`` buckets over
    all dtypes together: every dtype is one bucket, and the splits that are
    left go to the dtype with the most bytes, cut in readiness order so that
    its last bucket holds ``TAIL_SHARE`` of its elements.  (More dtypes than
    B still cost one bucket each: a payload is never cast to share one.)
    ``fuse=True`` is one bucket per dtype whatever the order.  Without
    either, with no shift class, or with a single leaf: per leaf."""
    n = len(leaves)
    if n_classes == 0 or n <= 1 or not (fuse or order is not None):
        return GossipGrouping((), n, n * n_classes, 0)
    sizes = [math.prod(l.shape) for l in leaves]
    nbytes = [s * jnp.dtype(l.dtype).itemsize for s, l in zip(sizes, leaves)]
    groups = _dtype_groups(leaves)
    if not fuse:
        key = lambda i: (order[i], i)
        groups = sorted((sorted(g, key=key) for g in groups),
                        key=lambda g: key(g[0]))
        spare = max(1, (MAX_PERMUTES_OUTSTANDING - 1) // n_classes) - len(groups)
        if spare > 0:
            big = max(range(len(groups)),
                      key=lambda j: sum(nbytes[i] for i in groups[j]))
            groups[big:big + 1] = _split_by_share(groups[big], sizes, 1 + spare)
    packed = sum(nbytes[i] for g in groups if len(g) > 1 for i in g)
    return GossipGrouping(tuple(tuple(g) for g in groups), n,
                          len(groups) * n_classes, packed)


def neighbor_allreduce(
    x,
    plan: CommPlan,
    axis_name: str,
    *,
    self_weight: Optional[float] = None,
    average_dtype=None,
    fuse: bool = False,
    rank_index=None,
    order=None,
):
    """Weighted neighbor averaging: ``out_d = w_dd * x_d + sum_{s in N_in(d)}
    w_ds * x_s`` — the reference's hot path (SURVEY.md §3.2).

    The per-rank weights ride as trace-time constant vectors indexed by
    ``axis_index`` so a single compiled program serves every rank (SPMD).
    ``self_weight`` overrides the plan's per-rank self weights uniformly.

    ``rank_index`` optionally supplies this rank's index along
    ``axis_name`` as a traced scalar (e.g. the caller's shard of a
    mesh-sharded iota).  Inside a PARTIALLY-manual ``shard_map`` (some
    mesh axes still auto) ``lax.axis_index`` lowers to a
    ``partition-id`` instruction, which the SPMD partitioner rejects
    on some backends (CPU raises UNIMPLEMENTED); a sharded-iota
    operand is the partitioner-friendly spelling of the same value.

    **How many permutes.**  By default one ``ppermute`` per leaf per shift
    class.  The TPU compiler merges none of them (322 in the lowered text
    of a ResNet-50 step on exp2(4), 322 start/done pairs compiled) and its
    scheduler keeps at most ``MAX_PERMUTES_OUTSTANDING`` in flight, so
    inside a train step all but that many run one after another behind the
    last gradient (PERF.md section 6, PR 27).  Two groupings pack leaves of
    one dtype into flat buffers, one permute per class each, through one
    pack/unpack routine (:func:`gossip_grouping`):

    - ``order``: a pytree like ``x`` of integers, the rank at which each
      leaf is ready (the train step reads it off its own jaxpr).  The leaves
      go into as few buckets as the scheduler will hold, split in that
      order, so the large early bucket travels under the rest of the
      backward pass and only a small tail is left for the end.
    - ``fuse=True``: one buffer per dtype, whatever the order — the
      reference's fusion buffer (``BLUEFOG_FUSION_THRESHOLD``,
      ``operations.cc`` [U]); what the exact methods in
      :mod:`bluefog_tpu.algorithms` use for their small trees.

    Both are exact: the weighted combine is linear and the per-edge weights
    are leaf-independent, so every element sees the same operations in the
    same order.  Output leaves are in their accumulation dtype either way.
    """

    def nar(a):
        wdt = average_dtype or _weight_dtype(a)
        idx = lax.axis_index(axis_name) if rank_index is None else rank_index
        if self_weight is None:
            sw = jnp.asarray(plan.self_weights, dtype=wdt)[idx]
        else:
            sw = jnp.asarray(self_weight, dtype=wdt)
        acc = a.astype(wdt) * sw
        # permute in the NARROWER of storage/average dtype: bf16 params with
        # fp32 accumulation send 2 bytes/elem over ICI (the neighbor's exact
        # stored value either way), and an explicit narrow average_dtype
        # still shrinks the wire for wide params
        wire = a if a.dtype.itemsize <= jnp.dtype(wdt).itemsize else a.astype(wdt)
        for cls in plan.classes:
            recvd = lax.ppermute(wire, axis_name, cls.perm).astype(wdt)
            w = jnp.asarray(cls.recv_weights, dtype=wdt)[idx]
            acc = acc + w * recvd
        return acc

    leaves, treedef = jax.tree_util.tree_flatten(x)
    if order is not None:
        order = treedef.flatten_up_to(order)
    buckets = gossip_grouping(leaves, order, len(plan.classes),
                              fuse=fuse).buckets
    if not buckets:
        return jax.tree_util.tree_map(nar, x)
    out = [None] * len(leaves)
    for idxs in buckets:
        if len(idxs) == 1:
            out[idxs[0]] = nar(leaves[idxs[0]])
            continue
        mixed = nar(jnp.concatenate([leaves[i].ravel() for i in idxs]))
        off = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = mixed[off:off + n].reshape(leaves[i].shape)
            off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def neighbor_allgather(x, plan: CommPlan, axis_name: str):
    """Gather in-neighbor tensors, stacked on a new leading axis ordered by
    ascending source rank (reference ``bf.neighbor_allgather`` concatenation
    order [U]).

    SPMD requires static shapes, so the output leading dim is the *max*
    in-degree; ranks with smaller in-degree have zero-padded trailing slots
    (plan.in_degrees gives the valid count — exact for regular topologies,
    which all built-in constructors are).
    """
    maxd = plan.max_in_degree

    def nag(a):
        idx = lax.axis_index(axis_name)
        out = jnp.zeros((maxd,) + a.shape, dtype=a.dtype)
        for cls in plan.classes:
            recvd = lax.ppermute(a, axis_name, cls.perm)
            slot = jnp.asarray(cls.slot_index)[idx]
            valid = jnp.asarray(cls.recv_mask)[idx].astype(bool)
            updated = lax.dynamic_update_index_in_dim(
                out, recvd, jnp.maximum(slot, 0), axis=0
            )
            out = jnp.where(valid, updated, out)
        return out

    return jax.tree_util.tree_map(nag, x)


def hierarchical_neighbor_allreduce(
    x,
    machine_plan: CommPlan,
    machines_axis: str,
    local_axis: str,
    *,
    self_weight: Optional[float] = None,
):
    """Intra-machine average -> machine-level gossip -> (implicit) local
    broadcast (reference ``bf.hierarchical_neighbor_allreduce``: local
    allreduce, cross-machine neighbor exchange, local bcast — SURVEY.md
    §2.1 NCCL-controller row [U]).

    On the factored ``(machines, local)`` mesh the local pmean already leaves
    every local rank with the machine value, so the machine-level gossip
    runs replicated across the local axis and no final broadcast is needed.
    """

    def hnar(a):
        wdt = _weight_dtype(a)
        local_avg = lax.pmean(a.astype(wdt), local_axis)
        return neighbor_allreduce(
            local_avg, machine_plan, machines_axis, self_weight=self_weight
        )

    return jax.tree_util.tree_map(hnar, x)


def pairwise_gossip(
    x,
    send_to: Tuple[Tuple[int, int], ...],
    size: int,
    axis_name: str,
    *,
    self_weight: float = 0.5,
    peer_weight: float = 0.5,
):
    """One-peer dynamic gossip step: a single ``ppermute`` along the given
    (src, dst) pairs plus weighted combine — the lowering of the reference's
    dynamic one-peer topologies (``GetDynamicOnePeerSendRecvRanks`` [U]).

    Ranks that receive nothing this step keep their value (weight 1)."""
    recv_ranks = {d for _, d in send_to}
    mask_host = [1.0 if d in recv_ranks else 0.0 for d in range(size)]

    def g(a):
        wdt = _weight_dtype(a)
        recvd = lax.ppermute(a, axis_name, send_to).astype(wdt)
        idx = lax.axis_index(axis_name)
        mask = jnp.asarray(mask_host, dtype=wdt)[idx]
        keep = self_weight + (1.0 - mask) * peer_weight
        return keep * a.astype(wdt) + (mask * peer_weight) * recvd

    return jax.tree_util.tree_map(g, x)

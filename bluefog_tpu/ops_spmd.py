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


# A TPU keeps an array of two or more dimensions as tiles of 8 rows by 128
# columns of its last two dimensions, row of tiles by row of tiles, the
# leading dimensions outermost, and pads each of the two up to whole tiles.
# So a leaf whose last two dimensions are whole tiles (every matrix and
# convolution kernel of the models here but the first layers and the head)
# holds no padding, and its tiles lie in memory as 8 rows of all the leading
# dimensions together.  2-byte elements too (the v5e compiler's
# `T(8,128)(2,1)`: 8 rows, two to a word; 16-row tiles compile to a pass each
# way), so the count is in elements, whatever the dtype.
# tests/test_tpu_compile.py notices a compiler that tiles otherwise.
_TILE = (8, 128)


def _tileable(leaf) -> bool:
    """Whether ``leaf`` (anything with ``shape``) is whole tiles: its last
    dimension whole lanes, the one before whole sublanes.  (``[N, 4, 128]``
    is not, although its rows together are a multiple of 8: the TPU pads the
    4 to 8 under every ``N``, and taking the padding out is a pass.)"""
    shape = leaf.shape
    return (len(shape) >= 2 and shape[-1] % _TILE[1] == 0
            and shape[-2] % _TILE[0] == 0)


def _tile_grid(shape):
    """(rows of tiles, tiles a row) of a shape that is whole tiles."""
    return math.prod(shape[:-1]) // _TILE[0], shape[-1] // _TILE[1]


def _pack_bucket(leaves):
    """``leaves`` (of one dtype) as one ``[n, 8, 128]`` buffer of tiles: every
    leaf that is whole tiles in the order the TPU keeps its tiles in memory
    (a bitcast there), then all the others raveled one after another and
    zero-padded together to whole tiles."""
    sub, lanes = _TILE
    parts, ragged = [], []
    for a in leaves:
        if _tileable(a):
            r, c = _tile_grid(a.shape)
            parts.append(a.reshape(r, sub, c, lanes).transpose(0, 2, 1, 3)
                         .reshape(r * c, sub, lanes))
        else:
            ragged.append(a.ravel())
    if ragged:
        flat = jnp.concatenate(ragged)
        n = -(-flat.size // (sub * lanes))
        parts.append(jnp.pad(flat, (0, n * sub * lanes - flat.size))
                     .reshape(n, sub, lanes))
    return jnp.concatenate(parts)


def _unpack_bucket(tiles, leaves) -> list:
    """Inverse of :func:`_pack_bucket`: arrays shaped like ``leaves`` out of
    ``tiles``, in the dtype of ``tiles``; the padding is dropped."""
    sub, lanes = _TILE
    out, off = [None] * len(leaves), 0
    for i, like in enumerate(leaves):
        if _tileable(like):
            r, c = _tile_grid(like.shape)
            out[i] = (tiles[off:off + r * c].reshape(r, c, sub, lanes)
                      .transpose(0, 2, 1, 3).reshape(like.shape))
            off += r * c
    flat, off = tiles[off:].reshape(-1), 0
    for i, like in enumerate(leaves):
        if out[i] is None:
            out[i] = flat[off:off + like.size].reshape(like.shape)
            off += like.size
    return out


class GossipGrouping(NamedTuple):
    """How one ``neighbor_allreduce`` groups its leaves into permutes: a pure
    function of shapes, dtypes, readiness order and the plan's shift classes
    (:func:`gossip_grouping`).  ``buckets`` holds leaf positions in flatten
    order; no bucket means one permute per leaf per class."""

    buckets: Tuple[Tuple[int, ...], ...]
    leaves: int
    permutes: int
    packed_bytes: int
    tiled_bytes: int  # of packed_bytes, what goes in as whole tiles


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
        return GossipGrouping((), n, n * n_classes, 0, 0)
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
    packed = [i for g in groups if len(g) > 1 for i in g]
    tiled = [i for i in packed if _tileable(leaves[i])]
    return GossipGrouping(tuple(tuple(g) for g in groups), n,
                          len(groups) * n_classes,
                          sum(nbytes[i] for i in packed),
                          sum(nbytes[i] for i in tiled))


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
    one dtype into one buffer each, one permute per class each, through one
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

    Both do the per-leaf path's arithmetic: the per-edge weights are
    leaf-independent and every leaf is combined by itself, ``sw * a + w1 * r1
    + w2 * r2`` in that order, from what is unpacked out of each bucket that
    arrives.  Called by itself on the CPU mesh that is bit for bit the
    per-leaf path (``tests/test_ops.py``).  Inside a step the compiler decides:
    where the weights fold to one constant the TPU compiler factors it out,
    ``(a + r1 + r2) * c``, in the per-leaf path and for a bucket's vectors
    but not for a leaf that leaves a bucket through a reshape, and XLA's CPU
    fuses one multiply into an add by what else shares the kernel; either
    parts the two in the last places of some leaves
    (``chip_smoke.py --chips 4 --only buckets_vs_per_leaf``; PERF.md section
    6, PR 30).  Output leaves are in their accumulation dtype either way.

    **The bucket's layout.**  A bucket is ``[n, 8, 128]``: whole TPU tiles.
    Gossip is element-wise and every rank runs this program, so a bucket may
    hold its elements in any order that is the same on every rank, and it holds
    them in the order the TPU keeps each leaf in memory.  A leaf of two or more
    dimensions lives there as tiles of 8 rows by 128 columns of its last two
    dimensions, so a leaf whose last dimension is whole lanes and whose
    second-to-last is whole sublanes (read off its shape, nothing else) goes in
    tile by tile and comes out the same way, and the compiler takes both as a
    change of shape alone.  A row-major 1-D bucket costs such a leaf a pass
    over memory each way (``ravel`` before, ``reshape`` after: in the
    optimizer-and-gossip part of a ResNet-50 step on exp2(4), 92 relayout
    passes of a megabyte or more, and 8 with this layout, all of them the
    head's; ``tests/test_tpu_compile.py``, PERF.md section 6, PR 30).  The
    other leaves (vectors, 64 or 1000 columns) follow in the same buffer,
    raveled one after another and zero-padded together to whole tiles; the
    padding travels and is never read back.  Because unpacking costs nothing,
    nothing is combined at the bucket's size: a mixed bucket would be a second
    buffer as large as the parameters, written once and sliced apart again for
    consumers that are per leaf anyway.  On CPU and GPU, which keep arrays
    row-major, the tile order is a real transpose each way: the library is
    TPU-first, no measured path runs there, and one path stands.
    """

    def nar(group):
        """The leaves of ``group`` (one dtype) mixed, through one permute per
        class: what is sent is packed, what arrives is unpacked, and every
        leaf is combined by itself."""
        wdt = average_dtype or _weight_dtype(group[0])
        idx = lax.axis_index(axis_name) if rank_index is None else rank_index
        if self_weight is None:
            sw = jnp.asarray(plan.self_weights, dtype=wdt)[idx]
        else:
            sw = jnp.asarray(self_weight, dtype=wdt)
        accs = [a.astype(wdt) * sw for a in group]
        # permute in the NARROWER of storage/average dtype: bf16 params with
        # fp32 accumulation send 2 bytes/elem over ICI (the neighbor's exact
        # stored value either way), and an explicit narrow average_dtype
        # still shrinks the wire for wide params
        narrow = group[0].dtype.itemsize <= jnp.dtype(wdt).itemsize
        wire = group if narrow else [a.astype(wdt) for a in group]
        one = len(group) == 1
        sent = wire[0] if one else _pack_bucket(wire)
        for cls in plan.classes:
            recvd = lax.ppermute(sent, axis_name, cls.perm)
            recvd = [recvd] if one else _unpack_bucket(recvd, group)
            w = jnp.asarray(cls.recv_weights, dtype=wdt)[idx]
            accs = [acc + w * r.astype(wdt) for acc, r in zip(accs, recvd)]
        return accs

    leaves, treedef = jax.tree_util.tree_flatten(x)
    if order is not None:
        order = treedef.flatten_up_to(order)
    buckets = gossip_grouping(leaves, order, len(plan.classes),
                              fuse=fuse).buckets
    out = [None] * len(leaves)
    for idxs in buckets or [(i,) for i in range(len(leaves))]:
        for i, mixed in zip(idxs, nar([leaves[i] for i in idxs])):
            out[i] = mixed
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

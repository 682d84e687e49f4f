"""One-sided window ops — device-memory mailbox emulation.

TPU-native sibling of the reference's RMA window layer
(``bluefog/torch/mpi_win_ops.cc``, ``MPI_Win_create/Put/Get/Accumulate``
paths in ``bluefog/common/mpi_controller.cc`` [U]; SURVEY.md §3.4, §7
stage 5).  The reference gives every rank one registered buffer **per
in-neighbor** per named window so concurrent writers never collide; a
``win_put`` deposits into the writer's dedicated slot at the destination and
``win_update`` locally combines the slots.

XLA has no one-sided RMA, so the same window model is emulated with
rank-major mailbox arrays living in device memory:

- ``win_create(name)`` allocates ``mail[size, max_in_degree, ...]`` — rank
  d's slot k holds the last deposit from its k-th in-neighbor (ascending
  rank order), exactly the reference's per-writer-buffer model.
- ``win_put/win_get/win_accumulate`` lower to one ``lax.ppermute`` per shift
  class of the window's topology, scattering into the destination slots.
- ``win_update`` is the purely local weighted combine, as upstream.

Semantic deviation (documented, by design): deposits are dispatched
asynchronously by the JAX runtime but become visible at the next collective
exchange point, so the execution realizes the *synchronous schedule* of the
asynchronous algorithm (bounded staleness 0).  Every consensus/push-sum
algorithm expressible upstream runs unchanged; what is lost is only
wall-clock desynchronization between ranks.  ``win_mutex`` therefore
degenerates to a no-op shim (SURVEY.md §5.2): there are never concurrent
writers to a slot.

Push-sum support: when associated-p mode is on (reference
``turn_on_win_ops_with_associated_p`` [U]) a scalar weight p rides along
with every deposit and is combined identically, enabling directed-graph
push-sum averaging (x/p debiasing).
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from bluefog_tpu.common.logging_util import logger
from bluefog_tpu.core import basics
from bluefog_tpu.core.basics import NODES_AXIS
from bluefog_tpu.core.plan import CommPlan
from bluefog_tpu.telemetry import registry as _telemetry
from bluefog_tpu.timeline import timeline_context

__all__ = [
    "win_create",
    "win_free",
    "win_put",
    "win_put_nonblocking",
    "win_get",
    "win_get_nonblocking",
    "win_accumulate",
    "win_accumulate_nonblocking",
    "win_put_async",
    "win_accumulate_async",
    "win_update_async",
    "win_update",
    "win_put_update",
    "win_update_then_collect",
    "win_wait",
    "win_poll",
    "win_mutex",
    "get_win_version",
    "win_associated_p",
    "win_set_exposed",
    "turn_on_win_ops_with_associated_p",
    "turn_off_win_ops_with_associated_p",
    "record_win_ops",
    "degraded_update_weights",
]

WeightsArg = Union[None, Sequence[Dict[int, float]]]

# ``record_win_ops`` trace target; None = recording off.  The events come
# from the telemetry op stream (telemetry.note_op) — one bookkeeping path
# shared by this module, the island runtime, and the win_ops.total counter.
_OP_LOG: Optional[List[Tuple[str, str]]] = None


def _op_log_listener(op: str, name: str) -> None:
    log = _OP_LOG
    if log is not None:
        log.append((op, name))


@contextlib.contextmanager
def record_win_ops():
    """Record ``(op, window_name)`` for every public win op in the block,
    yielding the live event list.  The epoch-ordering lint
    (``bluefog_tpu.analysis.epoch_rules.check_trace``) consumes this trace,
    so a real training loop's window usage can be checked against the
    use-before-create / use-after-free / mixed-deposit-epoch rules exactly
    as the analysis CLI checks canned traces.  A thin consumer of the
    telemetry op stream: both this module's SPMD ops and the island
    runtime's publish through ``telemetry.note_op``, so one recorder covers
    both execution modes.  Nested recorders share the outer list;
    ``win_free(None)`` logs with name ``"*"``."""
    global _OP_LOG
    prev = _OP_LOG
    log = [] if prev is None else prev
    _OP_LOG = log
    if prev is None:
        _telemetry.add_op_listener(_op_log_listener)
    try:
        yield log
    finally:
        _OP_LOG = prev
        if prev is None:
            _telemetry.remove_op_listener(_op_log_listener)


def _log_op(op: str, name: Optional[str]) -> None:
    _telemetry.note_op(op, name)


def _note_nbytes(span, tensor) -> None:
    """On a live span, the bytes of the array or leaves the op was handed
    (``span`` is None while nothing records: nothing is computed then)."""
    if span is not None:
        span.nbytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(tensor))


class _Window:
    """Per-name window state (the reference's window registry entry [U])."""

    def __init__(self, name: str, tensor: jnp.ndarray, plan: CommPlan, zero_init: bool):
        ctx = basics.context()
        self.name = name
        self.plan = plan
        self.shape = tensor.shape  # rank-major [size, ...]
        self.dtype = tensor.dtype
        maxd = max(plan.max_in_degree, 1)
        # Place every buffer with the mesh's rank-major sharding UP FRONT:
        # the exchange jits return mesh-sharded outputs, so an unplaced
        # initial buffer would change the call signature after the first
        # exchange (one wasted recompile) and pay a full reshard on entry.
        shard = NamedSharding(ctx.mesh, P(NODES_AXIS))
        self.self_tensor = jax.device_put(jnp.asarray(tensor), shard)
        init = jnp.zeros((ctx.size, maxd) + tensor.shape[1:], dtype=tensor.dtype)
        if not zero_init:
            # Reference initializes each neighbor buffer with the local
            # tensor value so a pre-put win_update is a no-op average.
            init = init + jnp.expand_dims(jnp.asarray(tensor), 1)
        self.mail = jax.device_put(init, shard)
        self.versions = jax.device_put(
            jnp.zeros((ctx.size, maxd), dtype=jnp.int32), shard)
        # push-sum associated scalars (mailbox follows the tensor-mailbox
        # init convention: zero_init -> empty, else neighbor's initial p=1)
        self.p_self = jax.device_put(
            jnp.ones((ctx.size,), dtype=jnp.float32), shard)
        self.p_mail = jax.device_put(
            jnp.zeros((ctx.size, maxd), dtype=jnp.float32)
            if zero_init
            else jnp.ones((ctx.size, maxd), dtype=jnp.float32), shard)
        # device-resident host constants for the default-weights fused path
        self.default_consts = None


def _ctx():
    return basics.context()


def _win(name: str) -> _Window:
    w = _ctx().windows.get(name)
    if w is None:
        raise KeyError(f"no window named {name!r}; call win_create first")
    return w


def _class_scales(
    plan: CommPlan,
    weights: WeightsArg,
    side: str,
) -> Tuple[np.ndarray, np.ndarray]:
    """Per-class scale + active-edge mask, both [num_classes, size] indexed
    by the *receiving* rank's mask position.

    side='send': scales[c, s] = weight rank s applies to what it sends in
    class c (keyed by that class's destination) — the reference's
    ``dst_weights``.  side='recv': scales[c, d] = weight rank d applies to
    what it receives in class c — the reference's ``src_weights``.

    When a weights sequence is given it also *selects* the edges: an edge
    not listed in the dict does not transfer at all (the reference's
    selective put/get — a put with ``dst_weights={1: w}`` touches only rank
    1's window [U]).  ``active[c, d] = 0`` suppresses the slot update at
    receiver d for that class.
    """
    C = len(plan.classes)
    scales = np.ones((C, plan.size), dtype=np.float32)
    active = np.ones((C, plan.size), dtype=np.float32)
    if weights is None:
        return scales, active
    if len(weights) != plan.size:
        raise ValueError(f"weights must be a length-{plan.size} sequence of dicts")
    for c, cls in enumerate(plan.classes):
        for s, d in cls.perm:
            listed = d in weights[s] if side == "send" else s in weights[d]
            if not listed:
                active[c, d] = 0.0
                scales[c, s if side == "send" else d] = 0.0
            elif side == "send":
                scales[c, s] = float(weights[s][d])
            else:
                scales[c, d] = float(weights[d][s])
    return scales, active


def _exchange_body(plan, accumulate, with_p, x, mail0, ver0, p_self, pm0,
                   scales, active, idx):
    """Per-rank exchange: deposit (scaled) payloads into destination
    mailbox slots — the ppermute lowering of MPI_Put/MPI_Accumulate [U].
    Local shapes: x [1,...], mail0 [maxd,...], ver0 [maxd], p_self [1],
    pm0 [maxd], scales/active [C,1] (sharded by rank)."""
    for c, cls in enumerate(plan.classes):
        wdt = x.dtype if jnp.issubdtype(x.dtype, jnp.inexact) else jnp.float32
        scale = scales[c, 0].astype(wdt)
        payload = (x[0].astype(wdt) * scale).astype(x.dtype)
        recvd = lax.ppermute(payload, NODES_AXIS, cls.perm)
        slot = jnp.asarray(cls.slot_index)[idx]
        valid = jnp.asarray(cls.recv_mask)[idx].astype(bool) & (active[c, 0] > 0)
        slot_c = jnp.maximum(slot, 0)
        cur = lax.dynamic_index_in_dim(mail0, slot_c, axis=0, keepdims=False)
        new = cur + recvd if accumulate else recvd
        mail0 = jnp.where(
            valid, lax.dynamic_update_index_in_dim(mail0, new, slot_c, axis=0), mail0
        )
        ver0 = jnp.where(
            valid,
            lax.dynamic_update_index_in_dim(
                ver0, lax.dynamic_index_in_dim(ver0, slot_c, 0, keepdims=False) + 1,
                slot_c, axis=0,
            ),
            ver0,
        )
        if with_p:
            p_recvd = lax.ppermute(p_self[0] * scales[c, 0], NODES_AXIS, cls.perm)
            p_cur = lax.dynamic_index_in_dim(pm0, slot_c, 0, keepdims=False)
            p_new = p_cur + p_recvd if accumulate else p_recvd
            pm0 = jnp.where(
                valid,
                lax.dynamic_update_index_in_dim(pm0, p_new, slot_c, axis=0),
                pm0,
            )
    return mail0, ver0, pm0


# The names a profile shows the window programs under: "jit_" + the name of
# the function handed to jax.jit.  chipbench's push-sum cell finds the window
# programs by these (chipbench/jobs/eager_window_pushsum.py: WINDOW_PROGRAMS,
# `^jit_spmd`, `^jit__combine`), so `spmd` below and `_combine` keep their
# names until the patterns move with them (ROADMAP R6);
# tests/test_timeline_spans.py lowers each program and holds it to these.
EXCHANGE_PROGRAM = "jit_spmd"  # _build_exchange and _build_put_update
COMBINE_PROGRAM = "jit__combine"  # win_update on a bare-array window


def _build_exchange(plan: CommPlan, accumulate: bool, with_p: bool,
                    donate: bool = True):
    """Jitted rank-major exchange (see :func:`_exchange_body`).

    ``donate=False`` when the result is called from inside another jit
    (donation only applies at the outermost dispatch; the fused-window
    wrappers donate on their own outer jit instead)."""
    ctx = _ctx()

    def spmd(x, mail, versions, p_self, p_mail, scales, active):
        idx = lax.axis_index(NODES_AXIS)
        mail0, ver0, pm0 = _exchange_body(
            plan, accumulate, with_p, x, mail[0], versions[0], p_self,
            p_mail[0], scales, active, idx,
        )
        return mail0[None], ver0[None], pm0[None]

    # mail/versions/p_mail are returned and reassigned by every caller, so
    # the input buffers are dead after the call: donating them lets XLA
    # update in place instead of copying the full mailbox each exchange
    return jax.jit(
        jax.shard_map(
            spmd,
            mesh=ctx.mesh,
            in_specs=(P(NODES_AXIS), P(NODES_AXIS), P(NODES_AXIS), P(NODES_AXIS),
                      P(NODES_AXIS), P(None, NODES_AXIS), P(None, NODES_AXIS)),
            out_specs=(P(NODES_AXIS), P(NODES_AXIS), P(NODES_AXIS)),
        ),
        donate_argnums=(1, 2, 4) if donate else (),
    )


def _build_put_update(plan: CommPlan, accumulate: bool, with_p: bool, wdt,
                      donate: bool = True):
    """One compiled program for put/accumulate + local weighted combine —
    the fused hot path of :func:`win_put_update` (one dispatch instead of
    an exchange jit plus a combine jit; XLA schedules the ppermute rounds
    together with the FMA combine)."""
    ctx = _ctx()

    def spmd(x, mail, versions, p_self, p_mail, scales, active, wmat, swvec):
        idx = lax.axis_index(NODES_AXIS)
        mail0, ver0, pm0 = _exchange_body(
            plan, accumulate, with_p, x, mail[0], versions[0], p_self,
            p_mail[0], scales, active, idx,
        )
        extra = (1,) * (x.ndim - 1)  # x local [1, ...]: payload rank is ndim-1
        w = wmat[0].astype(wdt).reshape(wmat.shape[1:2] + extra)
        sw = swvec[0].astype(wdt)
        combined = sw * x[0].astype(wdt) + (w * mail0.astype(wdt)).sum(axis=0)
        if with_p:
            p_new = swvec[0] * p_self[0] + (wmat[0] * pm0).sum()
        else:
            p_new = p_self[0]
        return (combined.astype(x.dtype)[None], mail0[None], ver0[None],
                pm0[None], p_new[None])

    # mail/versions/p_self/p_mail are returned and reassigned by
    # win_put_update after every call (the input buffers are dead):
    # donation lets XLA update the mailbox state in place
    return jax.jit(
        jax.shard_map(
            spmd,
            mesh=ctx.mesh,
            in_specs=(P(NODES_AXIS), P(NODES_AXIS), P(NODES_AXIS), P(NODES_AXIS),
                      P(NODES_AXIS), P(None, NODES_AXIS), P(None, NODES_AXIS),
                      P(NODES_AXIS), P(NODES_AXIS)),
            out_specs=(P(NODES_AXIS), P(NODES_AXIS), P(NODES_AXIS),
                       P(NODES_AXIS), P(NODES_AXIS)),
        ),
        donate_argnums=(1, 2, 3, 4) if donate else (),
    )


def _exchange(
    win: _Window, x, scales: np.ndarray, active: np.ndarray, accumulate: bool,
    op: str,
) -> None:
    """``x`` is in the window's dtype (the callers cast; ``win_get`` sends
    the exposed tensor).  ``op`` names the public call this runs under: the
    program call's span is ``<op>/exchange``."""
    ctx = _ctx()
    with_p = ctx.win_associated_p_enabled
    key = ("win_exchange", win.plan, accumulate, with_p, win.dtype, win.shape[1:])
    f = ctx.jit_cache(key, lambda: _build_exchange(win.plan, accumulate, with_p))
    scales, active = jnp.asarray(scales), jnp.asarray(active)
    with timeline_context(f"{op}/exchange"):
        mail, versions, p_mail = f(
            x, win.mail, win.versions, win.p_self, win.p_mail, scales, active
        )
    # always reassign: the jit donated the old p_mail buffer, so the
    # previous win.p_mail is invalid even when the p machinery is off
    win.mail, win.versions, win.p_mail = mail, versions, p_mail


# --------------------------------------------------------------------------
# Public API
# --------------------------------------------------------------------------


class _FusionMeta:
    """Pack/unpack metadata for a pytree (fused) window: the reference's
    tensor-fusion buffer (``BLUEFOG_FUSION_THRESHOLD`` [U]) as an API-level
    feature — a whole parameter tree rides ONE window, so each gossip round
    is one exchange instead of one per leaf (a builder reading of 27x on
    BERT-base, 2026-07, where each dispatch cost milliseconds; not
    re-measured; the script that read it is gone)."""

    __slots__ = ("treedef", "shapes", "sizes")

    def __init__(self, treedef, shapes, sizes):
        self.treedef = treedef
        self.shapes = shapes
        self.sizes = sizes


def _fusion_split(tensor):
    """(meta, packed) for a pytree input; (None, tensor) for a bare array."""
    leaves, treedef = jax.tree_util.tree_flatten(tensor)
    if treedef == jax.tree_util.tree_structure(0):
        return None, basics.to_rank_major_global(tensor)
    if not leaves:
        raise ValueError("win_create: empty pytree")
    if isinstance(tensor, (list, tuple)) and all(
        np.ndim(l) == 0 for l in leaves
    ):
        # nested-list-of-scalars spelling of a bare array
        return None, jnp.asarray(tensor)
    ctx = _ctx()
    # multi-host: each leaf may arrive as this process's rank rows; the
    # converter assembles global arrays (single process: plain asarray).
    # One call — a list is a pytree, and per-leaf calls would redo the
    # context/sharding setup per leaf.
    leaves = basics.to_rank_major_global(leaves)
    dts = {jnp.asarray(l).dtype for l in leaves}
    if len(dts) > 1:
        raise ValueError(
            f"fused windows need a uniform leaf dtype, got {sorted(map(str, dts))}; "
            "create one window per dtype group (cf. islands.DistributedWinPutOptimizer)"
        )
    bad = [tuple(np.shape(l)) for l in leaves
           if np.ndim(l) == 0 or np.shape(l)[0] != ctx.size]
    if bad:
        raise ValueError(
            f"every fused-window leaf must be rank-major with leading dim "
            f"{ctx.size}; offending leaf shapes: {bad[:4]}"
        )
    n = ctx.size
    shapes = [tuple(np.shape(l)[1:]) for l in leaves]
    sizes = [int(np.prod(s, dtype=np.int64)) for s in shapes]
    meta = _FusionMeta(treedef, shapes, sizes)
    return meta, _fusion_pack(meta, leaves, n)


def _pack_leaves(meta, leaves, n, dtype=None):
    """Traceable pack body — the ONE place the packed layout is defined."""
    ls = [l.astype(dtype) if dtype is not None else l for l in leaves]
    return jnp.concatenate([l.reshape(n, -1) for l in ls], axis=1)


def _unpack_leaves(meta, packed, n):
    """Traceable unpack body (inverse of :func:`_pack_leaves`)."""
    out, off = [], 0
    for s, sz in zip(meta.shapes, meta.sizes):
        out.append(packed[:, off:off + sz].reshape((n,) + s))
        off += sz
    return out


def _fusion_pack(meta, leaves, n):
    # ONE compiled program per tree structure: eagerly this is ~2 dispatches
    # per leaf, which on dispatch-expensive platforms costs more than the
    # gossip itself (a builder reading of 15x on BERT-base, 2026-07)
    f = _ctx().jit_cache(
        ("win_fusion_pack", meta.treedef, tuple(meta.shapes), n),
        lambda: jax.jit(lambda ls: _pack_leaves(meta, ls, n)),
    )
    return f([jnp.asarray(l) for l in leaves])


def _check_fused_leaves(meta, leaves, n):
    bad = [(tuple(np.shape(l)), (n,) + tuple(exp))
           for l, exp in zip(leaves, meta.shapes)
           if tuple(np.shape(l)) != (n,) + tuple(exp)]
    if bad:
        # same-size-different-shape leaves would pack without error and
        # unpack as silently corrupted data
        raise ValueError(f"leaf shapes do not match the window's: {bad[:4]}")


def _fusion_pack_tree(meta, tree, n):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if treedef != meta.treedef:
        raise ValueError(
            f"pytree structure does not match the window's: {treedef} vs "
            f"{meta.treedef}"
        )
    _check_fused_leaves(meta, leaves, n)
    return _fusion_pack(meta, leaves, n)


def _pack_input(name, tensor):
    """Pack a pytree op input when ``name`` is a fused window (one program;
    ``win_set_exposed``, its only caller, shows it as ``.../pack``)."""
    meta = _ctx().win_fusion.get(name)
    if meta is None:
        return tensor
    with timeline_context("win_set_exposed/pack"):
        return _fusion_pack_tree(meta, tensor, _ctx().size)


def _fused_exchange(win, name, meta, tree, scales, active, accumulate, op):
    """Pack + exchange in ONE compiled program (fused windows): leaves go
    in, the packed exposure comes back alongside the new mailbox state —
    a separate eager pack would cost an extra dispatch per gossip round.
    Its span is ``<op>/exchange``."""
    ctx = _ctx()
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if treedef != meta.treedef:
        raise ValueError(
            f"pytree structure does not match the window's: {treedef} vs "
            f"{meta.treedef}"
        )
    _check_fused_leaves(meta, leaves, ctx.size)
    with_p = ctx.win_associated_p_enabled
    n = ctx.size
    key = ("win_fused_exchange", meta.treedef, tuple(meta.shapes), win.plan,
           accumulate, with_p, win.dtype)

    def build():
        inner = _build_exchange(win.plan, accumulate, with_p, donate=False)

        def f(ls, mail, versions, p_self, p_mail, scales, active):
            x = _pack_leaves(meta, ls, n, dtype=win.dtype)
            mail, versions, p_mail = inner(
                x, mail, versions, p_self, p_mail, scales, active
            )
            return x, mail, versions, p_mail

        # donate at the outermost jit (nested donation is ignored)
        return jax.jit(f, donate_argnums=(1, 2, 4))

    f = ctx.jit_cache(key, build)
    scales, active = jnp.asarray(scales), jnp.asarray(active)
    with timeline_context(f"{op}/exchange"):
        x, mail, versions, p_mail = f(
            leaves, win.mail, win.versions, win.p_self, win.p_mail,
            scales, active,
        )
    win.self_tensor = x
    # always reassign (the old p_mail buffer was donated)
    win.mail, win.versions, win.p_mail = mail, versions, p_mail


def win_create(tensor, name: str, zero_init: bool = False) -> bool:
    """Collectively create a named window from a rank-major tensor — or a
    whole rank-major PYTREE, which is fused into one packed window (every
    subsequent op on ``name`` then accepts/returns the same tree structure)
    (reference ``bf.win_create(tensor, name, zero_init)`` [U]; the pytree
    form subsumes its fusion buffer).  The window's neighbor structure
    snapshots the currently-installed topology."""
    _log_op("win_create", name)
    ctx = _ctx()
    # _fusion_split performs the multi-host conversion for both forms
    meta, tensor = _fusion_split(tensor)
    t = jnp.asarray(tensor)
    if t.shape[0] != ctx.size:
        raise ValueError(
            f"win_create expects rank-major tensor with leading dim {ctx.size}"
        )
    if name in ctx.windows:
        return False
    ctx.windows[name] = _Window(name, t, ctx.plan, zero_init)
    if meta is not None:
        ctx.win_fusion[name] = meta
    return True


def win_free(name: Optional[str] = None) -> bool:
    """Free one window, or all when name is None (reference ``bf.win_free`` [U])."""
    _log_op("win_free", name)
    ctx = _ctx()
    if name is None:
        ctx.windows.clear()
        ctx.win_fusion.clear()
        return True
    ctx.win_fusion.pop(name, None)
    return ctx.windows.pop(name, None) is not None


def _cast_to_window_dtype(win, name, tensor, op):
    """Eager cast with a CLEAR multi-process error; a cast that converts is
    a program launch and gets the span ``<op>/cast``.

    In the multi-process non-fused path the input is a global
    non-fully-addressable array; an eager ``convert_element_type`` on it
    raises an opaque JAX error, so detect the case and name the fix
    (the fused path avoids this by casting inside the compiled program).
    """
    t = jnp.asarray(tensor) if not isinstance(tensor, jax.Array) else tensor
    if t.dtype == win.dtype:
        return t
    if not getattr(t, "is_fully_addressable", True):
        raise ValueError(
            f"window '{name}' holds {win.dtype} but the input is {t.dtype}: "
            "eager dtype casts on non-fully-addressable (multi-process "
            "global) arrays are not supported — cast the input to the "
            "window dtype before the call, or use a fused (pytree) window"
        )
    with timeline_context(f"{op}/cast"):
        return jnp.asarray(t, dtype=win.dtype)


def win_put(tensor, name: str, dst_weights: WeightsArg = None) -> bool:
    """Deposit (optionally dst-scaled) values into this rank's slot at each
    out-neighbor — only the ranks listed in ``dst_weights`` when given
    (reference ``bf.win_put`` — MPI_Put path [U]).

    Also refreshes the window's exposed tensor: upstream the window aliases
    the tensor's memory, so the put value *is* the current exposure.
    """
    with timeline_context("win_put") as span:
        _log_op("win_put", name)
        win = _win(name)
        tensor = basics.to_rank_major_global(tensor)
        _note_nbytes(span, tensor)
        scales, active = _class_scales(win.plan, dst_weights, side="send")
        meta = _ctx().win_fusion.get(name)
        if meta is not None:
            _fused_exchange(win, name, meta, tensor, scales, active,
                            accumulate=False, op="win_put")
        else:
            win.self_tensor = _cast_to_window_dtype(win, name, tensor, "win_put")
            _exchange(win, win.self_tensor, scales, active, accumulate=False,
                      op="win_put")
    return True


@jax.jit
def _completion_probe(mail):
    """A tiny array data-dependent on ``mail``'s producing op — what a
    nonblocking Handle holds.  The mailbox buffers themselves are DONATED
    by the next window op on the same window, which would leave a Handle
    holding a deleted array; the probe is a separate 1-element buffer that
    becomes ready exactly when the exchange completes and is never
    donated."""
    return jnp.ravel(mail)[:1]


def win_put_nonblocking(tensor, name: str, dst_weights: WeightsArg = None):
    from bluefog_tpu.ops import Handle

    win_put(tensor, name, dst_weights)
    return Handle(_completion_probe(_win(name).mail))


def win_accumulate(tensor, name: str, dst_weights: WeightsArg = None) -> bool:
    """Like win_put but adds into the destination slot (reference
    ``bf.win_accumulate`` — MPI_Accumulate path [U])."""
    with timeline_context("win_accumulate") as span:
        _log_op("win_accumulate", name)
        win = _win(name)
        tensor = basics.to_rank_major_global(tensor)
        _note_nbytes(span, tensor)
        scales, active = _class_scales(win.plan, dst_weights, side="send")
        meta = _ctx().win_fusion.get(name)
        if meta is not None:
            _fused_exchange(win, name, meta, tensor, scales, active,
                            accumulate=True, op="win_accumulate")
        else:
            win.self_tensor = _cast_to_window_dtype(
                win, name, tensor, "win_accumulate")
            _exchange(win, win.self_tensor, scales, active, accumulate=True,
                      op="win_accumulate")
    return True


def win_accumulate_nonblocking(tensor, name: str, dst_weights: WeightsArg = None):
    from bluefog_tpu.ops import Handle

    win_accumulate(tensor, name, dst_weights)
    return Handle(_completion_probe(_win(name).mail))


def win_put_async(tensor, name: str, dst_weights: WeightsArg = None):
    """API parity with :func:`bluefog_tpu.islands.win_put_async`: the
    bulk-synchronous emulation has no background wire, so the op executes
    at the call site and the returned
    :class:`~bluefog_tpu.progress.handles.WinHandle` is already resolved
    — programs written against the async surface run unchanged here."""
    from bluefog_tpu import progress as _progress

    t = tensor() if callable(tensor) else tensor
    return _progress.completed(win_put(t, name, dst_weights))


def win_accumulate_async(tensor, name: str, dst_weights: WeightsArg = None):
    """See :func:`win_put_async` — completed-handle parity wrapper."""
    from bluefog_tpu import progress as _progress

    t = tensor() if callable(tensor) else tensor
    return _progress.completed(win_accumulate(t, name, dst_weights))


def win_update_async(name: str,
                     self_weight=None,
                     neighbor_weights: WeightsArg = None,
                     reset: bool = False):
    """See :func:`win_put_async`; the handle's ``result()`` is the
    combined tensor (``clone`` semantics, matching the island engine)."""
    from bluefog_tpu import progress as _progress

    return _progress.completed(win_update(
        name, self_weight=self_weight, neighbor_weights=neighbor_weights,
        reset=reset, clone=True))


def win_get(name: str, src_weights: WeightsArg = None) -> bool:
    """Pull in-neighbors' exposed tensors into my mailbox slots, optionally
    receiver-scaled (reference ``bf.win_get`` — MPI_Get path [U])."""
    with timeline_context("win_get"):
        _log_op("win_get", name)
        win = _win(name)
        # A get of s's exposed tensor by d == a put of s's tensor to d with
        # receiver-side scaling, under the lockstep schedule.
        send, _ = _class_scales(win.plan, None, side="send")
        recv, active = _class_scales(win.plan, src_weights, side="recv")
        # apply receiver scale post-transfer by folding into sender scale:
        # within a class each (s,d) is unique, so scale at sender by the
        # destination's recv weight.
        for c, cls in enumerate(win.plan.classes):
            for s, d in cls.perm:
                send[c, s] = recv[c, d]
        _exchange(win, win.self_tensor, send, active, accumulate=False,
                  op="win_get")
    return True


def win_get_nonblocking(name: str, src_weights: WeightsArg = None):
    from bluefog_tpu.ops import Handle

    win_get(name, src_weights)
    return Handle(_completion_probe(_win(name).mail))


def _reset_mailbox(win: _Window, op: str) -> None:
    """One span, ``<op>/reset``, over the two programs (a ``zeros_like``
    each) that clear the mailbox and its associated p."""
    with timeline_context(f"{op}/reset"):
        win.mail = jnp.zeros_like(win.mail)
        win.p_mail = jnp.zeros_like(win.p_mail)


def _update_weights(win: _Window, self_weight, neighbor_weights):
    """Host-side combine weights: matrix [size, maxd] + self vector [size]
    (the reference ``win_update`` weight convention: default uniform
    1/(in_degree+1); explicit neighbor weights imply self = 1 - sum)."""
    plan = win.plan
    size = plan.size
    maxd = max(plan.max_in_degree, 1)
    wmat = np.zeros((size, maxd), dtype=np.float32)
    swvec = np.zeros((size,), dtype=np.float32)
    for d in range(size):
        nbrs = plan.in_neighbors[d]
        if neighbor_weights is not None:
            for k, s in enumerate(nbrs):
                wmat[d, k] = float(neighbor_weights[d].get(s, 0.0))
        else:
            for k in range(len(nbrs)):
                wmat[d, k] = 1.0 / (len(nbrs) + 1)
        if self_weight is None:
            swvec[d] = (
                1.0 - wmat[d].sum()
                if neighbor_weights is not None
                else 1.0 / (len(nbrs) + 1)
            )
        elif np.isscalar(self_weight):
            swvec[d] = float(self_weight)
        else:
            swvec[d] = float(self_weight[d])
    return wmat, swvec


def degraded_update_weights(plan: CommPlan, dead):
    """Per-rank ``(self_weights, neighbor_weights)`` for :func:`win_update`
    with the ranks in ``dead`` excised from the combine.

    Each survivor drops its dead in-neighbors and ABSORBS their compiled
    plan weight into its own self weight, so every row total is preserved
    exactly: convex rows stay convex and push-sum collect rows stay
    mass-conserving — the island runtime's degraded-combine rule
    (resilience/degraded.py), made available to the SPMD emulation for
    fault-injected gossip.  Dead ranks' own rows are left untouched
    (their state no longer participates)."""
    dead = set(int(r) for r in dead)
    W = plan.mixing_matrix()
    self_w: List[float] = []
    neighbor_w: List[Dict[int, float]] = []
    for d in range(plan.size):
        sw = float(W[d, d])
        nw = {}
        for s in plan.in_neighbors[d]:
            if d not in dead and s in dead:
                sw += float(W[d, s])
            else:
                nw[s] = float(W[d, s])
        self_w.append(sw)
        neighbor_w.append(nw)
    return self_w, neighbor_w


def _combine(self_tensor, mail, p_self, p_mail, wmat, swvec, *, wdt, with_p):
    """Fused local weighted combine (jitted via the context cache)."""
    size, maxd = wmat.shape
    extra = (1,) * (self_tensor.ndim - 1)
    w = wmat.astype(wdt).reshape((size, maxd) + extra)
    sw = swvec.astype(wdt).reshape((size,) + extra)
    combined = sw * self_tensor.astype(wdt) + (w * mail.astype(wdt)).sum(axis=1)
    new_p = swvec * p_self + (wmat * p_mail).sum(axis=1) if with_p else p_self
    return combined.astype(self_tensor.dtype), new_p


def win_update(
    name: str,
    self_weight: Optional[Union[float, Sequence[float]]] = None,
    neighbor_weights: WeightsArg = None,
    reset: bool = False,
    clone: bool = False,
):
    """Local weighted combine of the exposed tensor with mailbox slots,
    storing the result back as the exposed tensor (reference
    ``bf.win_update(name, self_weight, neighbor_weights, reset, clone)``
    [U]).  Default weights: uniform 1/(in_degree+1).  ``reset`` zeroes the
    mailbox (and associated p) after reading — the accumulate idiom.
    """
    with timeline_context("win_update"):
        _log_op("win_update", name)
        ctx = _ctx()
        win = _win(name)
        maxd = max(win.plan.max_in_degree, 1)
        wmat, swvec = _update_weights(win, self_weight, neighbor_weights)
        wdt = win.dtype if jnp.issubdtype(win.dtype, jnp.inexact) else jnp.float32
        with_p = ctx.win_associated_p_enabled
        meta = ctx.win_fusion.get(name)
        # one fused kernel per (shape, dtype, with_p); weights are traced
        # args so every weight value shares the compile.  Fused (pytree)
        # windows get the unpack INSIDE the same program — a separate eager
        # unpack would cost an extra dispatch per round.
        if meta is None:
            key = ("win_update", with_p, win.dtype, win.shape[1:], maxd)
            f = ctx.jit_cache(
                key,
                lambda: jax.jit(_combine, static_argnames=("wdt", "with_p")),
            )
        else:
            key = ("win_update_fused", with_p, win.dtype, win.shape[1:],
                   maxd, meta.treedef, tuple(meta.shapes))

            def build():
                n = ctx.size

                def f(self_t, mail, p_self, p_mail, wmat, swvec):
                    combined, p_new = _combine(
                        self_t, mail, p_self, p_mail, wmat, swvec,
                        wdt=wdt, with_p=with_p,
                    )
                    return combined, p_new, _unpack_leaves(meta, combined, n)

                return jax.jit(f)

            f = ctx.jit_cache(key, build)
        wmat, swvec = jnp.asarray(wmat), jnp.asarray(swvec)
        with timeline_context("win_update/combine"):
            if meta is None:
                combined, p_self = f(
                    win.self_tensor, win.mail, win.p_self, win.p_mail,
                    wmat, swvec, wdt=wdt, with_p=with_p,
                )
                leaves = None
            else:
                combined, p_self, leaves = f(
                    win.self_tensor, win.mail, win.p_self, win.p_mail,
                    wmat, swvec,
                )
        win.self_tensor = combined
        if with_p:
            win.p_self = p_self
        if reset:
            _reset_mailbox(win, "win_update")
        if meta is not None:
            tree = jax.tree_util.tree_unflatten(meta.treedef, leaves)
            if clone:
                tree = jax.tree_util.tree_map(jnp.array, tree)
            return tree
        out = win.self_tensor
        return jnp.array(out) if clone else out


def win_put_update(
    tensor,
    name: str,
    dst_weights: WeightsArg = None,
    *,
    self_weight: Optional[Union[float, Sequence[float]]] = None,
    neighbor_weights: WeightsArg = None,
    accumulate: bool = False,
    reset: bool = False,
):
    """Fused ``win_put`` (or ``win_accumulate``) + ``win_update`` in ONE
    compiled program — the hot path of :class:`DistributedWinPutOptimizer`
    and the gossip benchmark.  Semantically identical to the two calls in
    sequence; returns the combined tensor.  Not a reference API (upstream's
    put and update run on different sides of an RMA epoch); provided
    because under the mailbox emulation the pair always executes back to
    back, and one dispatch lets XLA schedule the exchange with the combine.
    """
    with timeline_context("win_put_update") as span:
        _log_op("win_put_update", name)
        ctx = _ctx()
        win = _win(name)
        tensor = basics.to_rank_major_global(tensor)
        _note_nbytes(span, tensor)
        meta = ctx.win_fusion.get(name)
        if meta is not None:
            leaves, treedef = jax.tree_util.tree_flatten(tensor)
            if treedef != meta.treedef:
                raise ValueError(
                    f"pytree structure does not match the window's: "
                    f"{treedef} vs {meta.treedef}"
                )
            _check_fused_leaves(meta, leaves, ctx.size)
            t = leaves  # packed inside the compiled program below
        else:
            t = _cast_to_window_dtype(win, name, tensor, "win_put_update")
        if dst_weights is None and self_weight is None and neighbor_weights is None:
            # the optimizer hot path: the four weight arrays are constant
            # per window, so build + upload them once
            if win.default_consts is None:
                scales, active = _class_scales(win.plan, None, side="send")
                wmat, swvec = _update_weights(win, None, None)
                win.default_consts = tuple(
                    jnp.asarray(a) for a in (scales, active, wmat, swvec)
                )
            scales_d, active_d, wmat_d, swvec_d = win.default_consts
        else:
            scales, active = _class_scales(win.plan, dst_weights, side="send")
            wmat, swvec = _update_weights(win, self_weight, neighbor_weights)
            scales_d, active_d, wmat_d, swvec_d = (
                jnp.asarray(scales), jnp.asarray(active),
                jnp.asarray(wmat), jnp.asarray(swvec),
            )
        with_p = ctx.win_associated_p_enabled
        wdt = win.dtype if jnp.issubdtype(win.dtype, jnp.inexact) else jnp.float32
        key = ("win_put_update", win.plan, accumulate, with_p, win.dtype,
               win.shape[1:],
               None if meta is None else (meta.treedef, tuple(meta.shapes)))

        def build():
            if meta is None:
                return _build_put_update(win.plan, accumulate, with_p, wdt)
            inner = _build_put_update(win.plan, accumulate, with_p, wdt,
                                      donate=False)
            n = ctx.size

            def f(ls, mail, versions, p_self, p_mail, sc, ac, wm, sw):
                x = _pack_leaves(meta, ls, n, dtype=win.dtype)
                combined, mail, versions, p_mail, p_self = inner(
                    x, mail, versions, p_self, p_mail, sc, ac, wm, sw
                )
                return (combined, mail, versions, p_mail, p_self,
                        _unpack_leaves(meta, combined, n))

            # donate at the outermost jit (nested donation is ignored)
            return jax.jit(f, donate_argnums=(1, 2, 3, 4))

        f = ctx.jit_cache(key, build)
        with timeline_context("win_put_update/put_update"):
            out = f(
                t, win.mail, win.versions, win.p_self, win.p_mail,
                scales_d, active_d, wmat_d, swvec_d,
            )
        combined, mail, versions, p_mail, p_self = out[:5]
        win.self_tensor = combined
        win.mail, win.versions = mail, versions
        # always reassign: the jit donates the old p buffers, so the
        # previous win.p_mail/p_self are invalid even with with_p off
        # (the returned values are passthroughs in that case)
        win.p_mail, win.p_self = p_mail, p_self
        if reset:
            _reset_mailbox(win, "win_put_update")
        if meta is not None:
            return jax.tree_util.tree_unflatten(meta.treedef, out[5])
        return combined


def win_update_then_collect(name: str, require_mutex: bool = False):
    """Collect-style update: self weight 1, every neighbor slot weight 1,
    then reset — the push-sum accumulate-and-drain idiom (reference
    ``bf.win_update_then_collect`` [U]).

    ``require_mutex`` is accepted for parity but has no effect HERE: under
    the bulk-synchronous SPMD emulation the combine and drain execute in
    one compiled program, so no concurrent writer can interleave
    (staleness-0 — the mutex the reference takes is provably redundant).
    The islands runtime, whose writers ARE concurrent, honors the flag
    with a real cross-process mutex (``islands.win_update_then_collect``).
    """
    if require_mutex:
        logger.debug(
            "win_update_then_collect(require_mutex=True): no-op under the "
            "bulk-synchronous emulation (atomic by construction); the "
            "islands runtime takes a real mutex"
        )
    _log_op("win_update_then_collect", name)
    ctx = _ctx()
    win = _win(name)
    ones = [
        {s: 1.0 for s in win.plan.in_neighbors[d]} for d in range(ctx.size)
    ]
    return win_update(name, self_weight=1.0, neighbor_weights=ones, reset=True)


def win_wait(handle) -> bool:
    handle.wait()
    return True


def win_poll(handle) -> bool:
    """Reference ``bf.win_poll`` [U].  May block where the platform has no
    async readiness query (see :meth:`bluefog_tpu.ops.Handle.poll`)."""
    return handle.poll()


@contextlib.contextmanager
def win_mutex(name: str, for_self: bool = False, ranks: Optional[List[int]] = None):
    """No-op shim kept for API parity (reference ``bf.win_mutex`` [U]): the
    mailbox emulation is bulk-synchronous, so slot access is never
    concurrent (SURVEY.md §5.2)."""
    del name, for_self, ranks
    yield


def get_win_version(name: str) -> List[Dict[int, int]]:
    """Per-rank {in_neighbor: deposit_count} (reference
    ``bf.get_win_version`` [U])."""
    win = _win(name)
    ver = np.asarray(win.versions)
    return [
        {s: int(ver[d, k]) for k, s in enumerate(win.plan.in_neighbors[d])}
        for d in range(win.plan.size)
    ]


def win_associated_p(name: str) -> jnp.ndarray:
    """The push-sum associated scalar p per rank (reference
    ``bf.win_associated_p`` [U]).

    Returns a COPY: the window's own p buffer is donated by the next
    window op, so handing out the live reference would leave the caller
    holding a deleted array."""
    with timeline_context("win_associated_p"):
        return jnp.array(_win(name).p_self)


def win_set_exposed(name: str, tensor, associated_p=None) -> None:
    """Overwrite the window's exposed tensor (and optionally its associated
    p) without a put — the debias-and-restart idiom of push-sum loops, where
    the caller stores x/p back as the new x and resets p to 1.  The reference
    gets this for free because its windows alias the torch tensor [U]; the
    mailbox emulation needs an explicit setter."""
    with timeline_context("win_set_exposed") as span:
        _log_op("win_set_exposed", name)
        win = _win(name)
        tensor = basics.to_rank_major_global(tensor)
        _note_nbytes(span, tensor)
        t = jnp.asarray(_pack_input(name, tensor), dtype=win.dtype)
        if t.shape != win.shape:
            raise ValueError(f"shape {t.shape} != window shape {win.shape}")
        win.self_tensor = t
        if associated_p is not None:
            win.p_self = jnp.broadcast_to(
                jnp.asarray(associated_p, jnp.float32), win.p_self.shape
            )


def turn_on_win_ops_with_associated_p() -> None:
    _ctx().win_associated_p_enabled = True


def turn_off_win_ops_with_associated_p() -> None:
    _ctx().win_associated_p_enabled = False

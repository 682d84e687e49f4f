"""Ring attention: sequence-parallel exact attention over a mesh axis.

No sibling in the reference (it predates long-context work — SURVEY.md
§5.7); this is the long-context capability the rebuild adds so the gossip
data parallelism composes with sequence sharding on TPU.  The algorithm is
the public blockwise ring attention (Liu et al., arXiv:2310.01889): each
device holds one sequence block of Q, K, V; K/V blocks rotate around the
ring one ``lax.ppermute`` hop per step (riding exactly the wraparound ICI
links, see ``parallel/ici_map``) while each device accumulates its queries'
attention with the online-softmax recurrence — compute overlaps the
neighbor transfer, and no device ever materializes the full sequence.

Layout: per-device ``q, k, v: [B, T_local, H, D]``; the global sequence is
``axis_size * T_local`` in rank order along ``axis_name``.  Exactness (vs a
single-device softmax over the full sequence) is tested to fp32 tolerance.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.parallel._util import resolve_axis_size, vma_full

__all__ = [
    "ring_attention",
    "ring_flash_attention",
    "make_ring_attention_fn",
    "stripe_blocks",
    "unstripe_blocks",
    "striped_positions",
]


def stripe_blocks(x, n: int, axis: int = 1):
    """Permute a global sequence so contiguous shard ``r`` of the result
    holds global positions ``r, r+n, r+2n, ...`` — the *striped* layout.

    Striping balances causal ring attention: with contiguous blocks, hop
    ``s`` is fully masked on devices ``idx < s`` but SPMD lock-step still
    waits for the devices computing full hops, so block-level skipping
    saves no wall-clock; striped, every hop is a near-triangular half-load
    on every device (~2x wall-clock for long causal sequences; same idea
    as striped attention, arXiv:2311.09431).  Apply before sharding; undo
    with :func:`unstripe_blocks`.
    """
    t = x.shape[axis]
    if t % n:
        raise ValueError(f"sequence length {t} not divisible by {n}")
    x = jnp.moveaxis(x, axis, 0)
    x = x.reshape((t // n, n) + x.shape[1:])  # [L, n, ...]: in[i*n + r]
    x = jnp.swapaxes(x, 0, 1).reshape((t,) + x.shape[2:])  # out[r*L + i]
    return jnp.moveaxis(x, 0, axis)


def unstripe_blocks(x, n: int, axis: int = 1):
    """Inverse of :func:`stripe_blocks`."""
    t = x.shape[axis]
    if t % n:
        raise ValueError(f"sequence length {t} not divisible by {n}")
    x = jnp.moveaxis(x, axis, 0)
    x = x.reshape((n, t // n) + x.shape[1:])  # [n, L, ...]: in[r*L + i]
    x = jnp.swapaxes(x, 0, 1).reshape((t,) + x.shape[2:])  # out[i*n + r]
    return jnp.moveaxis(x, 0, axis)


def striped_positions(t_local: int, axis_name: str):
    """Global positions of this device's striped shard (``i*n + idx``) —
    feed to rotary/positional encodings when training striped."""
    n = resolve_axis_size(axis_name, None)
    return jnp.arange(t_local) * n + lax.axis_index(axis_name)


def _causal_hop_dispatch(step, idx, diag_fn, visible_fn, masked_fn, ops):
    """Hop-level causal dispatch, shared by both ring variants: with square
    blocks, the block held at ring step ``s`` has global index ``j = (idx -
    s) mod n``, so ``j == idx`` iff ``s == 0`` (the diagonal, needs element
    masking) and ``j > idx`` iff ``s > idx`` (fully masked — skip the
    compute); every other hop is fully visible (mask-free).  The classic
    halve-the-work fix for causal ring attention."""
    if step == 0:
        return diag_fn(ops)
    return lax.cond(step > idx, masked_fn, visible_fn, ops)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    axis_size: int,
    *,
    causal: bool = True,
    striped: bool = False,
) -> jnp.ndarray:
    """Exact blockwise attention across sequence shards on ``axis_name``.

    q, k, v: [B, T_local, H, D] (this device's sequence block; the
    :func:`stripe_blocks` layout when ``striped=True`` — see its docstring
    for why striping balances the causal load).
    Returns [B, T_local, H, D] in q's dtype.
    """
    n = resolve_axis_size(axis_name, axis_size)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    idx = lax.axis_index(axis_name)

    if striped and causal and Tq != Tk:
        raise ValueError(
            f"striped causal ring attention needs equal q/k shard lengths "
            f"(got {Tq} vs {Tk}); the striped layout has no contiguous-"
            f"block fallback"
        )
    qf = q.astype(jnp.float32)
    m = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, H, Tq), jnp.float32)
    o = jnp.zeros((B, Tq, H, D), jnp.float32)
    perm = tuple((i, (i + 1) % n) for i in range(n))

    def fold_block(m, l, o, kb, vb, valid):
        """Online-softmax update of (m, l, o) with one key block."""
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kb) * scale
        m_new = jnp.maximum(
            m, jnp.max(jnp.where(valid, scores, -jnp.inf), axis=-1)
        )
        # keep m finite where nothing has been seen yet (fully masked rows)
        m_new = jnp.where(jnp.isfinite(m_new), m_new, m)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)  # [B,H,Tq]
        p = jnp.where(valid, jnp.exp(scores - m_new[..., None]), 0.0)
        p = jnp.where(jnp.isfinite(m_new)[..., None], p, 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, vb
        )
        return m_new, l, o

    all_valid = jnp.ones((1, 1, Tq, Tk), bool)
    tri = (jnp.arange(Tk)[None, :] <= jnp.arange(Tq)[:, None])[None, None]
    tri_strict = (jnp.arange(Tk)[None, :] < jnp.arange(Tq)[:, None])[None, None]
    kv = (k.astype(jnp.float32), v.astype(jnp.float32))
    for step in range(n):
        kb, vb = kv
        j = (idx - step) % n  # which global block this device holds now
        if striped and causal and Tq == Tk:
            # striped layout: key stripe j visible up to/including the
            # diagonal iff j <= our stripe index (see stripe_blocks); a
            # mask select beats lax.cond here — both "branches" would run
            # the identical fold, differing only in a constant mask
            valid = tri if step == 0 else jnp.where(j <= idx, tri, tri_strict)
            m, l, o = fold_block(m, l, o, kb, vb, valid)
        elif causal and Tq == Tk:
            m, l, o = _causal_hop_dispatch(
                step, idx,
                lambda ops: fold_block(*ops, tri),
                lambda ops: fold_block(*ops, all_valid),
                lambda ops: ops[:3],
                (m, l, o, kb, vb),
            )
        else:
            if causal:
                gq = idx * Tq + jnp.arange(Tq)  # global query positions
                gk = j * Tk + jnp.arange(Tk)  # global key positions
                valid = (gk[None, :] <= gq[:, None])[None, None]
            else:
                valid = all_valid
            m, l, o = fold_block(m, l, o, kb, vb, valid)
        if step != n - 1:
            kv = lax.ppermute(kv, axis_name, perm)

    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def ring_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    axis_size: int,
    *,
    causal: bool = True,
    striped: bool = False,
    block_q: Optional[int] = None,  # None: per-shard sequence-adaptive
    block_k: Optional[int] = None,  # (kernels._default_blocks)
    interpret: bool = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Ring attention with blockwise flash attention as the per-hop compute.

    ``striped=True`` assumes the :func:`stripe_blocks` layout (shard ``r``
    holds global positions ``i*n + r``): every causal hop then reduces to a
    (near-)triangular mask with static offsets — delta 0 when the key
    shard's stripe index is <= ours, else delta 1 — so the work is balanced
    across devices instead of diagonal-heavy (see :func:`stripe_blocks`).

    Same semantics/layout as :func:`ring_attention`, but each hop runs
    :func:`bluefog_tpu.kernels.flash_attention_with_lse` — MXU-blocked,
    O(T_local·block) memory instead of materializing the [Tq, Tk] score
    matrix — and hops merge by the logsumexp rule.  ``impl`` selects the
    per-hop implementation (default "auto" = the Pallas kernel; "xla"
    selects the blockwise-XLA forward, measured 13x slower in end-to-end
    training — see the flash_attention module docstring).  Differentiable
    end to end (the kernel's VJP carries the lse cotangent the merge
    needs).

    Note: when running the kernel in *interpret mode* (CPU testing), the
    Pallas HLO interpreter is not vma-aware, so the enclosing
    ``jax.shard_map`` needs ``check_vma=False``; compiled TPU execution has
    no such restriction.
    """
    from bluefog_tpu.kernels import flash_attention_with_lse

    n = resolve_axis_size(axis_name, axis_size)
    tq, tk = q.shape[1], k.shape[1]
    if striped and causal and tq != tk:
        raise ValueError(
            f"striped causal ring attention needs equal q/k shard lengths "
            f"(got {tq} vs {tk}); the striped layout has no contiguous-"
            f"block fallback"
        )
    idx = lax.axis_index(axis_name)
    perm = tuple((i, (i + 1) % n) for i in range(n))

    def flash(q_, kb_, vb_, *, q_start, k_start, causal_):
        q_start = jnp.asarray(q_start, jnp.float32).reshape(1)
        k_start = jnp.asarray(k_start, jnp.float32).reshape(1)
        return flash_attention_with_lse(
            q_, kb_, vb_, q_start=q_start, k_start=k_start, causal=causal_,
            block_q=block_q, block_k=block_k, interpret=interpret, impl=impl,
        )

    def masked_hop(ops):
        # sentinels vma-typed like the compute branches' outputs
        q_, _, _ = ops
        b, t, h, _ = q_.shape
        return (vma_full(q_, q_.shape, q_.dtype),
                vma_full(q_, (b, h, t), jnp.float32, -1e30))

    def diag_hop(ops):
        # q_start == k_start: relative masking suffices, and static zero
        # offsets unlock the aligned triangular fast paths
        return flash(*ops, q_start=0, k_start=0, causal_=True)

    def visible_hop(ops):
        return flash(*ops, q_start=0, k_start=0, causal_=False)

    o = None
    lse = None
    kv = (k, v)
    for step in range(n):
        kb, vb = kv
        j = (idx - step) % n  # global index of the key block held this step
        if striped and causal and tq == tk:
            # striped layout: token (i, stripe j) has global pos i*n + j,
            # so visibility vs our stripe idx depends only on j <= idx.
            # One flash call with a traced 0/1 key offset instead of a
            # lax.cond between two static-offset calls: the cond's
            # transpose hoists the branches' scalar offset constants to
            # the shard_map boundary, where their (zero) cotangents fail
            # shard_map's replication checking — the same class of failure
            # the tp/pipeline blocks hit
            delta = 0 if step == 0 else jnp.where(j <= idx, 0, 1)
            o_s, lse_s = flash(q, kb, vb, q_start=0, k_start=delta,
                               causal_=True)
        elif causal and tq == tk:
            o_s, lse_s = _causal_hop_dispatch(
                step, idx, diag_hop, visible_hop, masked_hop, (q, kb, vb)
            )
        else:
            o_s, lse_s = flash(
                q, kb, vb, q_start=idx * tq, k_start=j * tk, causal_=causal
            )
        o_s = o_s.astype(jnp.float32)
        if o is None:
            o, lse = o_s, lse_s
        else:
            m = jnp.maximum(lse, lse_s)
            w_old = jnp.exp(lse - m)  # [B, H, T]
            w_new = jnp.exp(lse_s - m)
            denom = w_old + w_new  # >= 1 (or 2 for all-masked rows)
            align = lambda w: w.transpose(0, 2, 1)[..., None]  # -> [B,T,H,1]
            o = (align(w_old) * o + align(w_new) * o_s) / align(denom)
            lse = m + jnp.log(denom)
        if step != n - 1:
            kv = lax.ppermute(kv, axis_name, perm)
    return o.astype(q.dtype)


def make_ring_attention_fn(axis_name: str, axis_size: int, causal: bool = True,
                           *, flash: bool = False, striped: bool = False,
                           **flash_kwargs) -> Callable:
    """attention_fn for ``models.transformer.LlamaLM``: plugs sequence-
    parallel ring attention into the decoder blocks (``flash=True`` selects
    the blockwise flash hop compute; ``striped=True`` the load-balanced
    :func:`stripe_blocks` layout — pair with :func:`striped_positions`)."""
    if flash:
        return partial(
            ring_flash_attention, axis_name=axis_name, axis_size=axis_size,
            causal=causal, striped=striped, **flash_kwargs
        )
    return partial(
        ring_attention, axis_name=axis_name, axis_size=axis_size,
        causal=causal, striped=striped,
    )

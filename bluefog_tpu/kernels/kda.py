"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692 section 3): the delta
rule with a decay a channel, in its chunked form, forward and backward.

For every head (keys and queries of ``K`` channels, values of ``V``, a state
``S`` of ``K x V``) and token ``t``, with a log-decay a channel ``g[t] <= 0``
and a step ``b[t]`` in (0, 1)::

    S[t] = (I - b[t] k[t] k[t]^T) Diag(exp g[t]) S[t-1] + b[t] k[t] v[t]^T    S[-1] = 0
    o[t] = S[t]^T q[t]

which is the token-by-token ``lax.scan`` of chipbench's plain reference; the
program never runs that.  The chunked form (the paper's WY form) walks the
sequence ``chunk`` tokens at a time.  With ``G[r]`` the running sum of ``g``
from the chunk's first token to token ``r`` (a channel), ``u[r] = b[r] (v[r] -
(Diag(exp g[r]) S[r-1])^T k[r])`` the value the delta rule writes, and ``S``
the state that enters the chunk::

    A[r, i] = b[r] sum_c k[r, c] k[i, c] exp(G[r, c] - G[i, c])      i < r
    P[r, i] =      sum_c q[r, c] k[i, c] exp(G[r, c] - G[i, c])      i <= r
    T  = (I + A)^-1 Diag(b)          W = T (k * exp G)          U0 = T v
    U  = U0 - W S
    O  = (q * exp G) S + P U
    S' = Diag(exp G[last]) S + (k * exp(G[last] - G))^T U

**Two stages.**  What needs no state (``A``, ``P``, the triangular inverse,
``W``, ``U0``) is an XLA expression under the scope ``kda_intra``, in
float32, every product at ``Precision.HIGHEST``, differentiated by JAX
(:func:`_intra`).  ``exp(-G)`` is not finite over a chunk (``g >= -5`` a token:
e^320 at 64 tokens), so the pairs are formed on sub-blocks of ``SUB`` = 16
tokens, relative to the running sum at each sub-block's **middle** token
``m``: rows and keys of sub-block ``I`` carry ``exp(+-(G - G[m]))``, between
e^-40 and e^40, and keys of an earlier sub-block ``exp(G[m] - G[i])``, at most
1.  No factor is formed that the mask then has to hide an infinity of, and
none so small that a cotangent times it leaves float32: relative to the
sub-block's first token the factors run from e^-80 to e^80, the values come
out right and the gradients of ``k`` and ``g`` do not (a cotangent of 1e-3
times e^-80 is flushed to zero before the e^80 beside it brings it back; 0.29
of the largest entry of ``g``'s gradient, read on the CPU at ``g`` = -5).
The inverse is exact block substitution: each 16 x 16 diagonal
block row by row, then ``X - X A_off X`` to merge blocks of 16 into 32 and 32
into ``chunk``.  These are batched 64 x 64 products, a few thousand a layer,
that XLA runs at the product units' throughput and that one grid step a
chunk and a head would run at their latency (not measured in a kernel).

What walks the state is the pair of Pallas kernels ``kda_chunk_fwd`` and
``kda_chunk_bwd`` (the names the device trace shows), grid ``(batch, heads,
chunks)`` with the chunks innermost and in order (the backward kernel last to
first).  The state is carried transposed, ``[V, K]`` float32 in VMEM scratch,
so that the decay a key channel multiplies along the lanes; the products take
operands in the values' type (bfloat16 in training) and accumulate in float32.
The differentiated forward writes the state that enters each chunk
(``[batch, heads, chunks, V, K]`` float32) for the hand-written backward
(``jax.custom_vjp``), which makes ``U`` again from it and carries the state's
cotangent the same way.

On a platform that is not a TPU the same kernels run in Pallas' interpret
mode, as the flash and the scan kernels do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.kernels.flash_attention import (
    _block_spec, _default_interpret, _out_struct)
from bluefog_tpu.kernels.ssd import _NT, _TN, _dot, _program
from bluefog_tpu.parallel._util import vma_full

__all__ = ["kda_chunked", "SUB"]

SUB = 16  # tokens of a sub-block: exp(5 x 16) is finite in float32
_HIGH = lax.Precision.HIGHEST


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGH,
                      preferred_element_type=jnp.float32)


def _same_block(c, size):
    """[c, c] bool: row and column in one diagonal block of ``size``."""
    at = jnp.arange(c) // size
    return at[:, None] == at[None, :]


def _in_blocks(parts, c):
    """Sub-block rows ``parts[I]`` ``[..., SUB, (I + 1) SUB]`` (what row block
    ``I`` holds up to and with its diagonal block) -> ``[..., c, c]``, zeros
    right of the diagonal blocks."""
    rows = [jnp.pad(p, ((0, 0),) * (p.ndim - 1) + ((0, c - p.shape[-1]),))
            for p in parts]
    return jnp.concatenate(rows, axis=-2)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` ``[..., c, c]`` strictly lower triangular,
    ``c`` = ``SUB`` times a power of two: substitution, never a power of
    ``a``."""
    c = a.shape[-1]
    nb = c // SUB
    # the diagonal blocks together, [..., nb, SUB, SUB], one row at a time: the
    # rows before r are final and row r is still e_r, so row r becomes e_r -
    # d[r] x.  A scan, not sixty unrolled updates: each of those is a fusion
    # of its own in every one of a step's eighteen copies of this (the cell's
    # compiled step carried 155 MB of generated code with them, 132 without)
    d = jnp.stack([a[..., i * SUB:(i + 1) * SUB, i * SUB:(i + 1) * SUB]
                   for i in range(nb)], axis=-3)

    def row(x, r):
        new = _mm("...j,...jk->...k", lax.dynamic_index_in_dim(d, r, -2, False), x)
        return x - jnp.where(jnp.arange(SUB)[:, None] == r, new[..., None, :], 0.0), None

    x, _ = lax.scan(row, vma_full(d, d.shape, a.dtype) + jnp.eye(SUB, dtype=a.dtype),
                    jnp.arange(1, SUB))
    x = _in_blocks([jnp.pad(x[..., i, :, :], ((0, 0),) * (x.ndim - 2) + ((i * SUB, 0),))
                    for i in range(nb)], c)
    size = SUB
    while size < c:  # [[X1, 0], [-X2 A21 X1, X2]], every pair of blocks at once
        off = jnp.where(_same_block(c, 2 * size) & ~_same_block(c, size), a, 0.0)
        x = x - _mm("...ij,...jk->...ik", _mm("...ij,...jk->...ik", x, off), x)
        size *= 2
    return x


def _intra(q, k, v, g, beta, dtype):
    """The chunk's stateless part.  ``q, k, g`` ``[..., c, K]``, ``v`` ``[...,
    c, V]``, ``beta`` ``[..., c]``, float32, the leading axes batch, head and
    chunk.  Returns ``(q exp G, P, W, U0, k exp(G[last] - G), exp G[last])``
    of the module's docstring; what the kernels multiply in ``dtype``, ``P``,
    ``U0`` and the chunk's decay in float32."""
    c, kd = q.shape[-2:]
    nb = c // SUB
    lead = q.shape[:-2]
    gsum = jnp.cumsum(g, axis=-2)
    by_sub = lambda a: a.reshape(lead + (nb, SUB) + a.shape[-1:])
    gs = by_sub(gsum)
    # relative to the running sum at each sub-block's middle token
    mid = gs[..., SUB // 2 - 1, :]                       # [..., nb, K]
    rel = gs - mid[..., None, :]                         # within +-8 x 5
    rows = jnp.stack([by_sub(q), by_sub(k)]) * jnp.exp(rel)    # [2, ..., nb, SUB, K]
    own = by_sub(k) * jnp.exp(-rel)
    parts = []
    for i in range(nb):
        diag = _mm("x...sd,...jd->x...sj", rows[..., i, :, :], own[..., i, :, :])
        if i:
            before = k[..., :i * SUB, :] * jnp.exp(
                mid[..., i, None, :] - gsum[..., :i * SUB, :])     # at most k
            diag = jnp.concatenate(
                [_mm("x...sd,...jd->x...sj", rows[..., i, :, :], before), diag], -1)
        parts.append(diag)
    pairs = _in_blocks(parts, c)                         # [2, ..., c, c]
    r, i = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    p = jnp.where(r >= i, pairs[0], 0.0)
    a = jnp.where(r > i, pairs[1] * beta[..., :, None], 0.0)
    t = _unit_lower_inverse(a) * beta[..., None, :]
    decay = jnp.exp(gsum)
    w = _mm("...ij,...jd->...id", t, k * decay)
    u0 = _mm("...ij,...jd->...id", t, v)
    last = gsum[..., -1:, :]
    return ((q * decay).astype(dtype), p, w.astype(dtype), u0,
            (k * jnp.exp(last - gsum)).astype(dtype), jnp.exp(last))


def _fwd_kernel(h, n, qg_ref, p_ref, w_ref, u0_ref, kd_ref, gam_ref, o_ref, *rest,
                save):
    if save:
        s_ref, st_scr = rest
    else:
        st_scr, = rest

    @pl.when(n == 0)
    def _start():
        st_scr[...] = jnp.zeros_like(st_scr)

    dtype = qg_ref.dtype
    st = st_scr[...]                                     # [V, K], S transposed
    if save:
        s_ref[0, 0, 0] = st
    stc = st.astype(dtype)
    u = (u0_ref[0, 0, 0] - _dot(w_ref[0, 0, 0], stc, _NT)).astype(dtype)   # [c, V]
    o = _dot(qg_ref[0, 0, 0], stc, _NT) + _dot(p_ref[0, 0, 0].astype(dtype), u)
    o_ref[0, 0, 0] = o.astype(o_ref.dtype)
    st_scr[...] = st * gam_ref[0, 0, 0] + _dot(u, kd_ref[0, 0, 0], _TN)


def _bwd_kernel(h, n, qg_ref, p_ref, w_ref, u0_ref, kd_ref, gam_ref, s_ref, do_ref,
                dqg_ref, dp_ref, dw_ref, du0_ref, dkd_ref, dgam_ref, dst_scr):
    @pl.when(n == 0)
    def _start():
        dst_scr[...] = jnp.zeros_like(dst_scr)

    dtype = qg_ref.dtype
    qg, w, kd, do = qg_ref[0, 0, 0], w_ref[0, 0, 0], kd_ref[0, 0, 0], do_ref[0, 0, 0]
    st, dst = s_ref[0, 0, 0], dst_scr[...]               # [V, K]: S, and S' 's cotangent
    stc, dstc = st.astype(dtype), dst.astype(dtype)
    u = (u0_ref[0, 0, 0] - _dot(w, stc, _NT)).astype(dtype)
    du = _dot(p_ref[0, 0, 0].astype(dtype), do, _TN) + _dot(kd, dstc, _NT)  # [c, V]
    duc = du.astype(dtype)
    dqg_ref[0, 0, 0] = _dot(do, stc).astype(dqg_ref.dtype)
    dp_ref[0, 0, 0] = _dot(do, u, _NT)
    du0_ref[0, 0, 0] = du
    dw_ref[0, 0, 0] = (-_dot(duc, stc)).astype(dw_ref.dtype)
    dkd_ref[0, 0, 0] = _dot(u, dstc).astype(dkd_ref.dtype)
    dgam_ref[0, 0, 0] = jnp.sum(st * dst, axis=0, keepdims=True)
    dst_scr[...] = (_dot(do, qg, _TN) + dst * gam_ref[0, 0, 0] - _dot(duc, w, _TN))


def _specs(c, kd, vd, chunk_of):
    at = lambda i, h, n: (i, h, chunk_of(n), 0, 0)
    return dict(k=_block_spec((1, 1, 1, c, kd), at), v=_block_spec((1, 1, 1, c, vd), at),
                p=_block_spec((1, 1, 1, c, c), at), gam=_block_spec((1, 1, 1, 1, kd), at),
                s=_block_spec((1, 1, 1, vd, kd), at))


def _fwd(qg, p, w, u0, kd, gam, interpret, save):
    bsz, heads, chunks, c, k_dim = qg.shape
    v_dim = u0.shape[-1]
    spec = _specs(c, k_dim, v_dim, lambda n: n)
    operands = (qg, p, w, u0, kd, gam)
    o_shape = _out_struct(u0.shape, qg.dtype, operands)
    s_shape = _out_struct((bsz, heads, chunks, v_dim, k_dim), jnp.float32, operands)
    out = pl.pallas_call(
        _program(functools.partial(_fwd_kernel, save=save), interpret),
        grid=(bsz, heads, chunks),
        in_specs=[spec["k"], spec["p"], spec["k"], spec["v"], spec["k"], spec["gam"]],
        out_specs=[spec["v"], spec["s"]] if save else [spec["v"]],
        out_shape=[o_shape, s_shape] if save else [o_shape],
        scratch_shapes=[pltpu.VMEM((v_dim, k_dim), jnp.float32)],
        interpret=interpret, name="kda_chunk_fwd",
    )(*operands)
    return out[0], (out[1] if save else None)


def _bwd(qg, p, w, u0, kd, gam, states, do, interpret):
    bsz, heads, chunks, c, k_dim = qg.shape
    v_dim = u0.shape[-1]
    spec = _specs(c, k_dim, v_dim, lambda n: chunks - 1 - n)
    operands = (qg, p, w, u0, kd, gam, states, do)
    like = lambda a: _out_struct(a.shape, a.dtype, operands)
    return pl.pallas_call(
        _program(_bwd_kernel, interpret),
        grid=(bsz, heads, chunks),
        in_specs=[spec["k"], spec["p"], spec["k"], spec["v"], spec["k"], spec["gam"],
                  spec["s"], spec["v"]],
        out_specs=[spec["k"], spec["p"], spec["k"], spec["v"], spec["k"], spec["gam"]],
        out_shape=[like(a) for a in (qg, p, w, u0, kd, gam)],
        scratch_shapes=[pltpu.VMEM((v_dim, k_dim), jnp.float32)],
        interpret=interpret, name="kda_chunk_bwd",
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _inter(qg, p, w, u0, kd, gam, interpret):
    """``O`` of the module's docstring from the chunks' stateless parts, the
    state carried from chunk to chunk."""
    return _fwd(qg, p, w, u0, kd, gam, interpret, save=False)[0]


def _inter_fwd(qg, p, w, u0, kd, gam, interpret):
    o, states = _fwd(qg, p, w, u0, kd, gam, interpret, save=True)
    return o, (qg, p, w, u0, kd, gam, states)


def _inter_bwd(interpret, res, do):
    return tuple(_bwd(*res, do, interpret))


_inter.defvjp(_inter_fwd, _inter_bwd)


def _heads(q, k, v, g, beta, chunk, interpret):
    """:func:`kda_chunked` for the heads it is handed, ``T`` whole chunks."""
    bsz, t, heads, _ = q.shape

    def by_chunk(a):  # [batch, T, H, ...] -> [batch, H, chunks, chunk, ...], float32
        a = a.astype(jnp.float32).reshape((bsz, t // chunk, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    with jax.named_scope("kda_intra"):
        parts = _intra(by_chunk(q), by_chunk(k), by_chunk(v), by_chunk(g),
                       by_chunk(beta), v.dtype)
    o = _inter(*parts, interpret)                        # [batch, H, chunks, chunk, V]
    return jnp.moveaxis(o, 1, 3).reshape(bsz, t, heads, -1)


def kda_chunked(q, k, v, g, beta, *, chunk=64, heads_at_once=4, interpret=None):
    """``o[t] = S[t]^T q[t]`` of the recurrence in the module's docstring,
    differentiable in all five arguments.

    ``q``, ``k``: ``[batch, T, H, K]`` as the recurrence takes them (after the
    norm and the query's scale); ``v``: ``[batch, T, H, V]``; ``g``: ``[batch,
    T, H, K]``, the log-decay, at most 0 and not under -5.5 a token (what
    keeps a sub-block's ``exp`` finite); ``beta``: ``[batch, T, H]``.
    ``chunk`` is ``SUB`` times a power of two; a ``T`` that it does not
    divide is padded with tokens that leave the state as it is.  The heads are
    walked ``heads_at_once`` at a time (where that divides them), each group
    under a ``jax.checkpoint``: the stateless stage's intermediates, some
    forty float32 arrays of ``[T, K]`` a head that its backward pass wants,
    are alive for one group and not for the layer.  Returns ``[batch, T, H,
    V]`` in ``v``'s type."""
    if interpret is None:
        interpret = _default_interpret()
    if chunk % SUB or (chunk // SUB) & (chunk // SUB - 1):
        raise ValueError(f"chunk {chunk}: {SUB} times a power of two")
    t, heads = q.shape[1:3]
    pad = -t % chunk
    args = (q, k, v, g, beta)
    if pad:  # k = 0, beta = 0, g = 0: the state passes
        args = tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                     for a in args)
    one = functools.partial(_heads, chunk=chunk, interpret=interpret)
    if heads % heads_at_once or heads == heads_at_once:
        o = one(*args)
    else:
        groups = heads // heads_at_once

        def split(a):  # [batch, T, H, ...] -> [groups, batch, T, heads_at_once, ...]
            a = a.reshape(a.shape[:2] + (groups, heads_at_once) + a.shape[3:])
            return jnp.moveaxis(a, 2, 0)

        o = lax.map(lambda group: jax.checkpoint(one)(*group), tuple(map(split, args)))
        o = jnp.moveaxis(o, 0, 2).reshape(o.shape[1:3] + (heads, -1))
    return o[:, :t] if pad else o

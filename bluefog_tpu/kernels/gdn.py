"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464) in its
chunked form, forward and backward: the delta rule of :mod:`.kda` with **one
log-decay a head**, of any size, and value heads that share key heads.

For every value head ``h`` (a state ``S`` of ``K x V``, its query and key those
of key head ``h // share``, ``share`` value heads on a key head) and token
``t``, with a log-decay ``g[t] <= 0`` that is one number, a step ``b[t]`` in
(0, 1), ``k[t]`` the key over its length and ``q[t]`` the query over its length
and ``sqrt(K)``::

    S[t] = (I - b[t] k[t] k[t]^T) exp(g[t]) S[t-1] + b[t] k[t] v[t]^T    S[-1] = 0
    o[t] = S[t]^T q[t]

which is the token-by-token ``lax.scan`` of chipbench's plain reference.  The
chunked form is :mod:`.kda`'s with the decay a number: with ``G[r]`` the running
sum of ``g`` from the chunk's first token to token ``r``::

    D[r, i] = exp(G[r] - G[i])                                       i <= r
    A = Diag(b) strict_lower((k k^T) * D)        P = lower((q k^T) * D)
    T = (I + A)^-1 Diag(b)        W = T (k exp G)        U0 = T v
    U = U0 - W S        O = (q exp G) S + P U
    S' = exp(G[last]) S + (k exp(G[last] - G))^T U

**No factor is above 1.**  :mod:`.kda` has a decay a channel, so its pairs are
sums over channels of ``exp(G[r, c] - G[i, c])`` and have to be formed as
``(k exp(G - G[m])) (k exp(G[m] - G))^T`` on sub-blocks of 16 tokens, sound
while ``g >= -5`` a token.  Here ``g = -exp(A_log) softplus(a + dt_bias)`` has
no lower bound (-20 a token is ordinary: eight tokens reach e^160, past
float32), and needs none: ``D`` is a ``[chunk, chunk]`` matrix of numbers in
[0, 1] that multiplies the **one** ``K``-deep product ``q k^T`` (``k k^T``) of
the key head, element by element, and ``exp G``, ``exp(G[last] - G)`` and
``exp G[last]`` are at most 1 too.  What underflows is a term the recurrence
itself has lost.

**The stateless stage** is the pair of Pallas kernels ``gdn_intra_fwd`` and
``gdn_intra_bwd`` under the scope ``gdn_intra`` (:func:`_intra`, a
``jax.custom_vjp`` whose residuals are its five arguments): grid ``(batch,
value heads / 4, chunks)``, nothing carried.  ``q`` and ``k`` are read where
the mixer left them, ``[batch, T, key heads x K]``, a step's ``4 / share`` key
heads at block index ``h`` (key head ``j`` serves value heads ``share j ..
share j + share - 1``; nothing is repeated in HBM), ``v`` ``[batch, T, value
heads x V]``, ``g`` and ``beta`` ``[batch, T, value heads]`` (never broadcast to
channels); the unit vectors of ``q`` and ``k`` and the two raw products are
taken once a key head on the widened block, as :mod:`.kda` has them since PR
47.  The running sum of ``g`` is a masked sum down the sublanes (float32
adds, the tokens in their order), a column turned into a row by a sum against
the diagonal.  The triangular inverse is :mod:`.kda`'s exact block
substitution, imported.  Float32 inside, every product at
``Precision.HIGHEST``.  The backward kernel makes ``G``, ``D``, the pairs and
the inverse again and writes the adjoint out (``M = dP * P + dKp * Kp``, the
cotangent of ``D`` times ``D``)::

    dX  = dW (b k exp G)^T + dU0 (b v)^T     d(b k exp G) = X^T dW    d(b v) = X^T dU0
    dA  = -strict_lower(X^T dX X^T)          dKp = Diag(b) dA
    db  = rows of d(b K+) K+ + d(b v) v + dA Kp, summed
    dq  = (lower(dP) * D) k + dqg exp G
    dk  = (lower(dP) * D)^T q + (dKp * D + (dKp * D)^T) k + d(k exp G) exp G + dkd exp(G[last] - G)
    dG  = rows of M less columns of M, and what exp G, exp(G[last] - G) and
          exp G[last] took;  dg = the running sum of dG from the last token back

and sums a key head's two cotangents over the value heads it serves before it
hands them through the unit vectors.

**The walk over the chunks is :mod:`.kda`'s**, called: ``kda_chunk_fwd`` /
``kda_chunk_bwd`` take ``(q exp G, P, W, U0, k exp(G[last] - G), exp G[last])``
and do not know where the decay came from; the chunk's decay is handed them in
the ``[1, K]`` row they multiply the state by, the one number in every lane.

Where the shapes are not whole 128-lane blocks a head (the CPU tests' small
sizes) the stage is the same definition as an XLA expression
(:func:`stage_expression`: ``jnp.cumsum``, two einsums, a triangular solve),
which is also what the kernels are tested against, and the walk is the same
kernels in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from bluefog_tpu.kernels.flash_attention import (
    _block_spec, _default_interpret, _out_struct)
from bluefog_tpu.kernels.kda import (
    _EPS, _HEADS_A_STEP, SUB, _head, _inter, _iota, _mm, _step_size, _unit,
    _unit_lower_inverses, _unit_pull)
from bluefog_tpu.kernels.ssd import _LANES, _NEG, _NT, _TN, _program

__all__ = ["HEADS_A_CALL", "gdn_chunked", "kernels_take", "stage_expression"]


# value heads a call of the four kernels walks, a group under a
# ``jax.checkpoint``; chipbench's FLOPs module and roofline readers count a
# call's operations and a loop's trips by it
HEADS_A_CALL = 4


def _heads_a_step(heads):
    return max(d for d in range(1, _HEADS_A_STEP + 1) if heads % d == 0)


def _a_call(heads, share):
    """The value heads a call of the kernels takes: ``HEADS_A_CALL`` where
    that divides a layer's ``heads`` and is whole key heads, else the layer's."""
    return HEADS_A_CALL if heads % HEADS_A_CALL == 0 and HEADS_A_CALL % share == 0 else heads


def kernels_take(key_dim, value_dim, heads, share):
    """Whether a layer's stage goes through its Pallas kernels or through
    :func:`stage_expression`: by the shapes alone.  A head's channels whole
    128-lane blocks, and the value heads of a grid step (of the heads of a
    call) whole key heads."""
    return (key_dim % _LANES == 0 and value_dim % _LANES == 0
            and _heads_a_step(_a_call(heads, share)) % share == 0)


def _to_row(col, r, i):
    """``[c, 1]`` -> ``[1, c]``: a sum against the diagonal, exact."""
    return jnp.sum(jnp.where(r == i, col, 0.0), axis=0, keepdims=True)


def _to_col(row, r, i):
    return jnp.sum(jnp.where(r == i, row, 0.0), axis=1, keepdims=True)


def _decays(g_ref, head, r, i):
    """Of one head's ``g`` down the rows of the ``[1, c, H]`` block: ``G``
    ``[c, 1]`` and ``D`` ``[c, c]`` of the module's docstring, ``D`` 0 above the
    diagonal."""
    g = _step_size(g_ref, head)                                          # [c, 1]
    across = jnp.sum(jnp.where(r <= i, g, 0.0), axis=0, keepdims=True)   # G[i], [1, c]
    gsum = _to_col(across, r, i)
    return gsum, jnp.exp(jnp.where(r >= i, gsum - across, _NEG))


def _key_head(q_ref, k_ref, j, kd):
    """Key head ``j`` of the step's block: the unit vectors with their ``rho``
    and the two raw products."""
    (q, rq), (k, rk) = _unit(_head(q_ref, j, kd), kd ** -0.5), _unit(_head(k_ref, j, kd))
    return q, k, rq, rk, _mm(q, k, _NT), _mm(k, k, _NT)


def _intra_fwd_kernel(h, n, q_ref, k_ref, v_ref, g_ref, beta_ref,
                      qg_ref, p_ref, w_ref, u0_ref, kd_ref, gam_ref, *, share):
    hb, kd, vd = qg_ref.shape[1], qg_ref.shape[-1], u0_ref.shape[-1]
    c = q_ref.shape[1]
    r, i = _iota((c, c), 0), _iota((c, c), 1)
    heads = []
    for j in range(hb):
        if j % share == 0:
            q, k, _, _, qk, kk = _key_head(q_ref, k_ref, j // share, kd)
        b = _step_size(beta_ref, h * hb + j)
        gsum, decay = _decays(g_ref, h * hb + j, r, i)
        grow, last = jnp.exp(gsum), gsum[-1:]
        qg_ref[0, j, 0] = (q * grow).astype(qg_ref.dtype)
        p_ref[0, j, 0] = qk * decay
        kd_ref[0, j, 0] = (k * jnp.exp(last - gsum)).astype(kd_ref.dtype)
        gam_ref[0, j, 0] = jnp.broadcast_to(jnp.exp(last), (1, kd))
        heads.append((b * jnp.where(r > i, kk * decay, 0.0), b * (k * grow),
                      b * _head(v_ref, j, vd)))
    xs = _unit_lower_inverses([a for a, _, _ in heads])
    for j, (x, (_, kb, vb)) in enumerate(zip(xs, heads)):
        w_ref[0, j, 0] = _mm(x, kb).astype(w_ref.dtype)
        u0_ref[0, j, 0] = _mm(x, vb)


def _intra_bwd_kernel(h, n, q_ref, k_ref, v_ref, g_ref, beta_ref,
                      dqg_ref, dp_ref, dw_ref, du0_ref, dkd_ref, dgam_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *, share):
    hb, kd, vd = dqg_ref.shape[1], dqg_ref.shape[-1], du0_ref.shape[-1]
    c = q_ref.shape[1]
    r, i = _iota((c, c), 0), _iota((c, c), 1)
    keys = [_key_head(q_ref, k_ref, j, kd) for j in range(hb // share)]
    heads = []
    for j in range(hb):  # the forward's decays and pairs again
        _, _, _, _, qk, kk = keys[j // share]
        gsum, decay = _decays(g_ref, h * hb + j, r, i)
        heads.append((_head(v_ref, j, vd), _step_size(beta_ref, h * hb + j), gsum, decay,
                      qk * decay, jnp.where(r > i, kk * decay, 0.0)))
    xs = _unit_lower_inverses([b * kp for _, b, _, _, _, kp in heads])
    # W = X (b K+), U0 = X (b v), X = (I + Diag(b) Kp)^-1: a stage at a time
    # over the step's heads, as the inverses
    grows = [jnp.exp(gsum) for _, _, gsum, _, _, _ in heads]
    dws = [dw_ref[0, j, 0].astype(jnp.float32) for j in range(hb)]
    dxs = [_mm(dw, b * (keys[j // share][1] * grow), _NT) + _mm(du0_ref[0, j, 0], b * v, _NT)
           for j, (dw, grow, (v, b, *_)) in enumerate(zip(dws, grows, heads))]
    through = [_mm(dx, x, _NT) for dx, x in zip(dxs, xs)]
    das = [jnp.where(r > i, -_mm(x, t, _TN), 0.0) for x, t in zip(xs, through)]
    dq = dk = None
    for j, (x, da, dw, grow, head) in enumerate(zip(xs, das, dws, grows, heads)):
        q, k, rq, rk, _, _ = keys[j // share]
        v, b, gsum, decay, p, kp = head
        if j % share == 0:
            dq, dk = jnp.zeros_like(q), jnp.zeros_like(k)
        kplus, last = k * grow, gsum[-1:]
        dqg, dkd = dqg_ref[0, j, 0].astype(jnp.float32), dkd_ref[0, j, 0].astype(jnp.float32)
        dkb, dvb = _mm(x, dw, _TN), _mm(x, du0_ref[0, j, 0], _TN)
        db = jnp.sum(dkb * kplus, axis=1, keepdims=True) + jnp.sum(
            dvb * v, axis=1, keepdims=True) + jnp.sum(da * kp, axis=1, keepdims=True)
        dkplus, dkp = b * dkb, b * da
        dpm = jnp.where(r >= i, dp_ref[0, j, 0], 0.0)
        dqk, dkk = dpm * decay, dkp * decay
        fade = jnp.exp(last - gsum)
        faded = jnp.sum(dkd * (k * fade), axis=1, keepdims=True)
        dq = dq + dqg * grow + _mm(dqk, k)
        dk = dk + dkplus * grow + dkd * fade + _mm(dqk, q, _TN) + _mm(dkk, k) + _mm(dkk, k, _TN)
        # D's cotangent times D: a row's exponent took it, a column's gave it back
        m = dpm * p + dkp * kp
        dlast = jnp.sum(faded, axis=0, keepdims=True) + jnp.sum(
            dgam_ref[0, j, 0], axis=1, keepdims=True) * jnp.exp(last)
        dgsum = (jnp.sum(m, axis=1, keepdims=True)
                 - _to_col(jnp.sum(m, axis=0, keepdims=True), r, i)
                 + jnp.sum(dqg * (q * grow) + dkplus * kplus, axis=1, keepdims=True)
                 - faded + jnp.where(_iota((c, 1), 0) == c - 1, dlast, 0.0))
        dv_ref[0, :, j * vd:(j + 1) * vd] = (b * dvb).astype(dv_ref.dtype)
        # the running sum from the last token back, as a row
        dg_ref[0, j, 0] = jnp.sum(jnp.where(r >= i, dgsum, 0.0), axis=0, keepdims=True)
        dbeta_ref[0, j, 0] = _to_row(db, r, i)
        if j % share == share - 1:  # of the blocks as they were read
            at = j // share
            dq_ref[0, :, at * kd:(at + 1) * kd] = _unit_pull(
                dq, q, rq, kd ** -0.5).astype(dq_ref.dtype)
            dk_ref[0, :, at * kd:(at + 1) * kd] = _unit_pull(dk, k, rk).astype(dk_ref.dtype)


def _stage_specs(q, v, beta, share, chunk):
    """The block specs and shapes of what the stage's kernels read in place
    (``q``'s kind: ``[batch, T, key heads x K]``; ``v``'s; ``beta``'s, which is
    ``g``'s) and of what :func:`bluefog_tpu.kernels.kda._inter` takes, ``hb``
    value heads a step."""
    bsz, t, heads = beta.shape
    kd, vd, chunks = q.shape[-1] // (heads // share), v.shape[-1] // heads, t // chunk
    hb = _heads_a_step(heads)
    flat = lambda i, h, n: (i, n, h)
    split = lambda i, h, n: (i, h, n, 0, 0)
    spec = dict(k=_block_spec((1, chunk, hb // share * kd), flat),
                v=_block_spec((1, chunk, hb * vd), flat),
                beta=_block_spec((1, chunk, heads), lambda i, h, n: (i, n, 0)),
                qg=_block_spec((1, hb, 1, chunk, kd), split),
                p=_block_spec((1, hb, 1, chunk, chunk), split),
                u0=_block_spec((1, hb, 1, chunk, vd), split),
                gam=_block_spec((1, hb, 1, 1, kd), split),
                row=_block_spec((1, hb, 1, 1, chunk), split))
    lead = (bsz, heads, chunks)
    shape = dict(qg=lead + (chunk, kd), p=lead + (chunk, chunk), u0=lead + (chunk, vd),
                 gam=lead + (1, kd), row=lead + (1, chunk))
    return (bsz, heads // hb, chunks), spec, shape


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _intra_fwd(q, k, v, g, beta, share, chunk, interpret):
    grid, spec, shape = _stage_specs(q, v, beta, share, chunk)
    operands = (q, k, v, g, beta)
    out = lambda name, dtype: _out_struct(shape[name], dtype, operands)
    return tuple(pl.pallas_call(
        _program(functools.partial(_intra_fwd_kernel, share=share), interpret),
        grid=grid,
        in_specs=[spec["k"], spec["k"], spec["v"], spec["beta"], spec["beta"]],
        out_specs=[spec["qg"], spec["p"], spec["qg"], spec["u0"], spec["qg"], spec["gam"]],
        out_shape=[out("qg", v.dtype), out("p", jnp.float32), out("qg", v.dtype),
                   out("u0", jnp.float32), out("qg", v.dtype), out("gam", jnp.float32)],
        interpret=interpret, name="gdn_intra_fwd",
    )(*operands))


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _intra_bwd(q, k, v, g, beta, cotangents, share, chunk, interpret):
    grid, spec, shape = _stage_specs(q, v, beta, share, chunk)
    operands = (q, k, v, g, beta) + tuple(cotangents)
    row = _out_struct(shape["row"], jnp.float32, operands)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        _program(functools.partial(_intra_bwd_kernel, share=share), interpret),
        grid=grid,
        in_specs=[spec["k"], spec["k"], spec["v"], spec["beta"], spec["beta"],
                  spec["qg"], spec["p"], spec["qg"], spec["u0"], spec["qg"], spec["gam"]],
        out_specs=[spec["k"], spec["k"], spec["v"], spec["row"], spec["row"]],
        out_shape=[_out_struct(o.shape, o.dtype, operands) for o in operands[:3]] + [row, row],
        interpret=interpret, name="gdn_intra_bwd",
    )(*operands)
    # [batch, H, chunks, 1, chunk] -> [batch, T, H]
    tokens = lambda a, like: jnp.moveaxis(
        a.reshape(a.shape[:2] + (-1,)), 1, 2).astype(like.dtype)
    return dq, dk, dv, tokens(dg, g), tokens(dbeta, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _intra(q, k, v, g, beta, share, chunk, interpret):
    """The chunk's stateless part.  ``q, k`` ``[batch, T, key heads x K]``, the
    heads side by side on the lanes, ``v`` ``[batch, T, H x V]``, ``g`` and
    ``beta`` ``[batch, T, H]``, ``T`` whole chunks, read where they are.
    Returns ``(q exp G, P, W, U0, k exp(G[last] - G), exp G[last])`` of the
    module's docstring, ``[batch, H, chunks, chunk, .]``: what the walk's
    kernels multiply in ``v``'s type, ``P``, ``U0`` and the chunk's decay in
    float32."""
    return _intra_fwd(q, k, v, g, beta, share, chunk, interpret)


def _intra_residuals(q, k, v, g, beta, share, chunk, interpret):
    return _intra_fwd(q, k, v, g, beta, share, chunk, interpret), (q, k, v, g, beta)


def _intra_cotangents(share, chunk, interpret, res, cotangents):
    return _intra_bwd(*res, cotangents, share, chunk, interpret)


_intra.defvjp(_intra_residuals, _intra_cotangents)


def stage_expression(q, k, v, g, beta, share, chunk):
    """:func:`_intra` as an XLA expression, differentiated by JAX: the
    definition, and the path of the shapes the kernels do not tile."""
    bsz, t, heads = beta.shape
    hk, n = heads // share, t // chunk
    kd, vd = q.shape[-1] // hk, v.shape[-1] // heads
    f32 = jnp.float32
    # [batch, chunks, key heads, (value heads of it,) chunk, .]
    keyed = lambda a: jnp.moveaxis(a.astype(f32).reshape(bsz, n, chunk, hk, kd), 2, 3)
    valued = lambda a, *tail: jnp.moveaxis(
        a.astype(f32).reshape(bsz, n, chunk, hk, share, *tail), 2, 4)
    unit = lambda x: x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _EPS)
    q, k = unit(keyed(q)) * kd ** -0.5, unit(keyed(k))
    v, b = valued(v, vd), valued(beta)[..., None]                  # [.., c, V], [.., c, 1]
    gsum = jnp.cumsum(valued(g), axis=-1)                          # [b, n, hk, share, c]
    r, i = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(r >= i, gsum[..., :, None] - gsum[..., None, :], _NEG))
    pairs = lambda x: jnp.einsum("bnhrk,bnhik->bnhri", x, k, precision=lax.Precision.HIGHEST)
    p = pairs(q)[:, :, :, None] * decay
    a = b * jnp.where(r > i, pairs(k)[:, :, :, None] * decay, 0.0)
    grow, fade = jnp.exp(gsum)[..., None], jnp.exp(gsum[..., -1:] - gsum)[..., None]
    q, k = q[:, :, :, None], k[:, :, :, None]                      # every value head's
    solve = functools.partial(jax.scipy.linalg.solve_triangular,
                              a + jnp.eye(chunk, dtype=f32), lower=True, unit_diagonal=True)
    w, u0 = solve(b * (k * grow)), solve(b * v)
    gam = jnp.broadcast_to(grow[..., -1:, :], grow.shape[:-2] + (1, kd))
    walk = lambda x: jnp.moveaxis(                                 # [batch, H, chunks, ., .]
        x.reshape((bsz, n, heads) + x.shape[-2:]), 1, 2)
    return tuple(map(walk, (q * grow, p, w, u0, k * fade, gam)))


def _heads(q, k, v, g, beta, share, chunk, interpret, kernels):
    """:func:`gdn_chunked` for the heads it is handed side by side on the
    lanes, ``T`` whole chunks.  Returns ``[batch, T, H x V]``."""
    with jax.named_scope("gdn_intra"):
        if kernels:
            parts = _intra(q, k, v, g, beta, share, chunk, interpret)
        else:
            qg, p, w, u0, kd, gam = stage_expression(q, k, v, g, beta, share, chunk)
            parts = (qg.astype(v.dtype), p, w.astype(v.dtype), u0, kd.astype(v.dtype), gam)
    o = _inter(*parts, interpret)                        # [batch, H, chunks, chunk, V]
    return jnp.moveaxis(o, 1, 3).reshape(v.shape)


def gdn_chunked(q, k, v, g, beta, *, chunk=64, interpret=None):
    """``o[t] = S[t]^T q[t]`` of the recurrence in the module's docstring,
    differentiable in all five arguments.

    ``q``, ``k``: ``[batch, T, key heads, K]`` as projected and convolved: the
    stage takes a head's vector over its length, ``x / sqrt(sum(x x) + 1e-6)``,
    and the query over ``sqrt(K)`` besides; the gradients are the raw arrays'.
    ``v``: ``[batch, T, H, V]``, ``H`` a multiple of the key heads; ``g``:
    ``[batch, T, H]``, the log-decay, at most 0, of any size; ``beta``:
    ``[batch, T, H]``.  ``chunk`` is ``SUB`` times a power of two; a ``T`` that
    it does not divide is padded with tokens that leave the state as it is.
    The value heads are walked ``HEADS_A_CALL`` at a time (where that divides
    them and is whole key heads), each group under a ``jax.checkpoint``, as
    :func:`bluefog_tpu.kernels.kda.kda_chunked` walks its own: what the stage
    hands the walk is alive for one group and not for the layer.  Returns
    ``[batch, T, H, V]`` in ``v``'s type."""
    if interpret is None:
        interpret = _default_interpret()
    if chunk % SUB or (chunk // SUB) & (chunk // SUB - 1):
        raise ValueError(f"chunk {chunk}: {SUB} times a power of two")
    t, heads = v.shape[1:3]
    share, kd = heads // q.shape[2], q.shape[3]
    if heads != share * q.shape[2] or k.shape != q.shape:
        raise ValueError(f"{heads} value heads on key heads of {q.shape[2:]}, {k.shape[2:]}")
    pad = -t % chunk
    # a head's channels beside the next head's, [batch, T, H x .]: a group's
    # block is whole 128-lane tiles and the kernels read it where it is
    args = tuple(a.reshape(a.shape[:2] + (-1,)) for a in (q, k, v)) + (g, beta)
    if pad:  # k = 0 (its unit vector too), beta = 0, g = 0: the state passes
        args = tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in args)
    one = functools.partial(
        _heads, share=share, chunk=chunk, interpret=interpret,
        kernels=kernels_take(kd, v.shape[3], heads, share))
    groups = heads // _a_call(heads, share)
    if groups == 1:
        o = one(*args)
    else:
        def split(a):  # [batch, T, H x .] -> [groups, batch, T, HEADS_A_CALL x .]
            return jnp.moveaxis(a.reshape(a.shape[:2] + (groups, -1)), 2, 0)

        o = lax.map(lambda group: jax.checkpoint(one)(*group), tuple(map(split, args)))
        o = jnp.moveaxis(o, 0, 2)
    o = o.reshape(o.shape[:2] + (heads, -1))
    return o[:, :t] if pad else o

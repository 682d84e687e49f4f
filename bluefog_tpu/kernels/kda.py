"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692 section 3): the delta
rule with a decay a channel, in its chunked form, forward and backward.

For every head (keys and queries of ``K`` channels, values of ``V``, a state
``S`` of ``K x V``) and token ``t``, with a log-decay a channel ``g[t] <= 0``,
a step ``b[t]`` in (0, 1), and ``k[t]`` the head's projected key over its
length, ``q[t]`` its query over its length and ``sqrt(K)``::

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

**Two stages, four kernels.**  What needs no state (``A``, ``P``, the
triangular inverse, ``W``, ``U0``) is the pair of Pallas kernels
``kda_intra_fwd`` and ``kda_intra_bwd`` under the scope ``kda_intra``
(:func:`_intra`, a ``jax.custom_vjp`` whose residuals are its five arguments):
grid ``(batch, heads / 4, chunks)``, nothing carried from step to step.  They
read ``q``, ``k``, ``v`` in the type the mixer hands over, ``g`` and ``beta``
where the model holds them, a ``[chunk, 4 K]`` block of ``[batch, T, H x K]``
at the step's four heads, and widen it in VMEM; the forward writes
what the walk takes in the layout it takes it, the backward the five
gradients in the arguments' layout and types.  **The unit vectors are taken
there** (PR 47): ``q`` and ``k`` come in as projected and convolved, and on
the widened block, a head's ``K`` channels on the lanes, ``x rsqrt(sum(x x) +
1e-6)`` is a multiply, a lane reduction, an ``rsqrt`` and a multiply (the
query's also over ``sqrt(K)``; the constants of chipbench's reference).  The
unit vectors stay in float32 from the raw block on, and the backward kernel,
which makes them again with the pairs, hands back the raw blocks' cotangents:
with ``y = s x / |x|``, ``dx = (s / |x|) (dy - y sum(dy y) / s^2)``, the part
of ``dy`` across ``x``.  (As an XLA expression in the mixer the same norms
were three float32 passes over ``[batch, T, H, K]`` a layer, 60 ms of the
Ling cell's step.)  Float32 throughout, every product at
``Precision.HIGHEST``, the running sum one of them (with a triangle of
ones).  ``exp(-G)`` is not finite over a chunk (``g >= -5`` a token:
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
The inverse is exact block substitution: each 16 x 16 diagonal block a row at
a time on the vector units (once a row is final every later row takes its
share of it), then ``X - X A_off X`` to merge blocks of 16 into 32 and 32
into ``chunk``.  The backward kernel makes ``G``, the pairs and the inverse
again and writes the adjoint out (with ``K+ = k exp G``, ``Kp = A / b``)::

    dX  = dW (b K+)^T + dU0 (b v)^T      d(b K+) = X^T dW      d(b v) = X^T dU0
    dA  = -strict_lower(X^T dX X^T)      dKp = Diag(b) dA
    db  = rows of d(b K+) K+ + d(b v) v + dA Kp, summed
    a row block at a time, with its rows R = [q; k] exp(G - G[m]) and its keys
    F = k exp(G[m] - G[i]):   dR = [lower(dP); dKp] F    dF = [lower(dP); dKp]^T R
    dG  = what each factor's exponent took, the middle tokens' and the last
          token's shares at their rows;  dg = the running sum of dG from the
          chunk's last token back

A substitution and a merge are chains of dependent steps, so a grid step
takes its four heads through each of them together.  At the Ling cell's
shapes (chunks of 64, ``K`` = ``V`` = 128) a grid step of four heads reads
3.75 us forward and 7.0 us backward with the forward's part made again: 0.94
and 1.75 us a chunk and head, where two heads a step read 1.12 and 1.84 and
one head 1.64 and 2.51 (my chip runs, PR 44; the XLA expression these replace
read 1.55 forward, 1.83 recomputed and 4.1 backward in the step).  The
inverse is half of the forward (0.47 us without it), its merges 0.35 of that.
With the unit vectors taken inside, a layer's 1,024 grid steps in one call
read 4.01 us a step forward and 7.72 backward by the host's clock, where the
kernels before, handed unit vectors, read 4.09 and 7.49 the same way (my chip
runs, PR 47): the norms are within the forward's noise and 3 % of the
backward.

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

import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.kernels.flash_attention import (
    _block_spec, _default_interpret, _out_struct)
from bluefog_tpu.kernels.ssd import _NEG, _NT, _TN, _dot, _program

__all__ = ["kda_chunked", "SUB"]

SUB = 16  # tokens of a sub-block: exp(5 x 16) is finite in float32
_HEADS_A_STEP = 4  # of the stateless stage's kernels, at most
_EPS = 1e-6  # beside a head's squared length, as the reference's `_unit`
_HIGH = lax.Precision.HIGHEST


def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, precision=_HIGH,
                           preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _sub_blocks(a):
    return [a[i:i + SUB] for i in range(0, a.shape[0], SUB)]


def _sum_back(a):
    """The running sum of ``a`` ``[c, K]`` from its last row up, the
    transpose of the running sum down the rows: a log-step scan."""
    c = a.shape[0]
    token, step = _iota(a.shape, 0), 1
    while step < c:
        a = a + jnp.where(token < c - step, pltpu.roll(a, c - step, 0), 0.0)
        step *= 2
    return a


_Pairs = collections.namedtuple("_Pairs", "gsum grow rows factors p kp")


def _pairs(q, k, g):
    """A chunk's pairs for one head, ``q, k, g`` ``[c, K]`` in float32:
    ``gsum``, ``G`` (the running sum of ``g``); ``grow``, ``exp(G - G[m])``
    down the rows, ``m`` the middle token of a row's sub-block; a row block at
    a time, ``rows``, its rows of ``q`` over its rows of ``k`` times that
    (``[2 SUB, K]``), and ``factors``, its keys' ``exp(G[m] - G[i])`` (``i`` up
    to the block's last token; 0 after it); ``p`` and ``kp``, ``P`` and ``A /
    b`` of the module's docstring."""
    c, kd = q.shape
    r, i = _iota((c, c), 0), _iota((c, c), 1)
    # a product with a triangle of ones: the tokens in their order, as
    # `jnp.cumsum` on the CPU (a log-step scan reads 0.96 us a chunk and head
    # where this reads 0.94 on the chip, and rounds in another order: 2.5e-6
    # between chunks of 16 and 128 where `tests/test_ling_hybrid.py` holds 2e-6
    # and this gives 1.8e-6)
    gsum = _mm(jnp.where(r >= i, 1.0, 0.0), g)
    mids = [s[SUB // 2 - 1:SUB // 2] for s in _sub_blocks(gsum)]         # [1, K] each
    grow = jnp.exp(gsum - jnp.concatenate(
        [jnp.broadcast_to(m, (SUB, kd)) for m in mids], axis=0))         # within +-8 x 5
    rows = [jnp.concatenate(pair, axis=0)
            for pair in zip(_sub_blocks(q * grow), _sub_blocks(k * grow))]
    token = _iota((c, kd), 0)
    # at most 1 before the row block, within e^-40..e^40 inside it
    factors = [jnp.exp(jnp.where(token < (n + 1) * SUB, m - gsum, _NEG))
               for n, m in enumerate(mids)]
    pairs = [_mm(row, k * f, _NT) for row, f in zip(rows, factors)]      # [2 SUB, c] each
    p = jnp.where(r >= i, jnp.concatenate([x[:SUB] for x in pairs], axis=0), 0.0)
    kp = jnp.where(r > i, jnp.concatenate([x[SUB:] for x in pairs], axis=0), 0.0)
    return _Pairs(gsum, grow, rows, factors, p, kp)


def _unit_lower_inverses(heads):
    """``(I + a)^-1`` for each ``a`` ``[c, c]`` of ``heads``, strictly lower
    triangular, ``c`` = ``SUB`` times a power of two: substitution, never a
    power of ``a``.  The heads of a grid step go through each stage together:
    a stage is a chain of dependent steps whose latency one head alone waits
    out (1.64 us a chunk and head at one head a step, 0.94 at four; PERF.md
    section 6, PR 44)."""
    c = heads[0].shape[0]
    nb = c // SUB
    r, i = _iota((c, c), 0), _iota((c, c), 1)
    # every head's diagonal blocks together, [heads x nb, SUB, SUB]: once row
    # j is final, every row after it takes its share of it; column j of a
    # strictly lower block is zero down to row j, so the rows before are left
    # as they are
    d = jnp.stack([s[:, n * SUB:(n + 1) * SUB]
                   for a in heads for n, s in enumerate(_sub_blocks(a))])
    x = jnp.where(_iota(d.shape, 1) == _iota(d.shape, 2), 1.0, 0.0)
    for j in range(SUB - 1):
        x = x - d[:, :, j:j + 1] * x[:, j:j + 1, :]
    xs = [jnp.where(r // SUB == i // SUB, jnp.concatenate(
        [x[h * nb:(h + 1) * nb].reshape(c, SUB)] * nb, axis=1), 0.0)
        for h in range(len(heads))]
    size = SUB
    while size < c:  # [[X1, 0], [-X2 A21 X1, X2]], every pair of blocks at once
        off = (r // (2 * size) == i // (2 * size)) & (r // size != i // size)
        through = [_mm(x, jnp.where(off, a, 0.0)) for x, a in zip(xs, heads)]
        xs = [x - _mm(t, x) for t, x in zip(through, xs)]
        size *= 2
    return xs


def _head(ref, j, width):
    """Head ``j`` of a step's ``[1, c, heads x width]`` block, in float32."""
    return ref[0, :, j * width:(j + 1) * width].astype(jnp.float32)


def _unit(x, scale=1.0):
    """``y = scale x / |x|`` down the rows of ``x`` ``[c, K]`` (a head's
    channels on the lanes: one lane reduction a row) and ``rho = scale /
    |x|`` ``[c, 1]``.  A row of zeros stays zeros."""
    rho = scale * lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + _EPS)
    return x * rho, rho


def _unit_pull(dy, y, rho, scale=1.0):
    """``x``'s cotangent from ``dy``, that of :func:`_unit`'s ``y``: ``dy``
    less its share along ``y``, over ``x``'s length."""
    along = jnp.sum(dy * y, axis=1, keepdims=True) * scale ** -2
    return rho * (dy - y * along)


def _step_size(beta_ref, head):
    """``beta`` of head ``head`` down the rows, ``[c, 1]``, from the ``[1, c,
    H]`` block of all the heads'."""
    b = beta_ref[0].astype(jnp.float32)
    return jnp.sum(jnp.where(_iota(b.shape, 1) == head, b, 0.0), axis=1, keepdims=True)


def _intra_fwd_kernel(h, n, q_ref, k_ref, v_ref, g_ref, beta_ref,
                      qg_ref, p_ref, w_ref, u0_ref, kd_ref, gam_ref):
    hb, kd, vd = qg_ref.shape[1], qg_ref.shape[-1], u0_ref.shape[-1]
    heads = []
    for j in range(hb):
        q, k, g = (_head(ref, j, kd) for ref in (q_ref, k_ref, g_ref))
        q, k = _unit(q, kd ** -0.5)[0], _unit(k)[0]
        b = _step_size(beta_ref, h * hb + j)
        made = _pairs(q, k, g)
        decay, last = jnp.exp(made.gsum), made.gsum[-1:]
        qg_ref[0, j, 0] = (q * decay).astype(qg_ref.dtype)
        p_ref[0, j, 0] = made.p
        kd_ref[0, j, 0] = (k * jnp.exp(last - made.gsum)).astype(kd_ref.dtype)
        gam_ref[0, j, 0] = jnp.exp(last)
        heads.append((b * made.kp, b * (k * decay), b * _head(v_ref, j, vd)))
    xs = _unit_lower_inverses([a for a, _, _ in heads])
    for j, (x, (_, kb, vb)) in enumerate(zip(xs, heads)):
        w_ref[0, j, 0] = _mm(x, kb).astype(w_ref.dtype)
        u0_ref[0, j, 0] = _mm(x, vb)


def _intra_bwd_kernel(h, n, q_ref, k_ref, v_ref, g_ref, beta_ref,
                      dqg_ref, dp_ref, dw_ref, du0_ref, dkd_ref, dgam_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    hb, kd, vd = dqg_ref.shape[1], dqg_ref.shape[-1], du0_ref.shape[-1]
    c = q_ref.shape[1]
    r, i = _iota((c, c), 0), _iota((c, c), 1)
    token = _iota((c, kd), 0)
    heads = []
    norms = []
    for j in range(hb):  # the forward's unit vectors and pairs again
        q, k, g = (_head(ref, j, kd) for ref in (q_ref, k_ref, g_ref))
        (q, rq), (k, rk) = _unit(q, kd ** -0.5), _unit(k)
        norms.append((rq, rk))
        heads.append((q, k, _head(v_ref, j, vd), _step_size(beta_ref, h * hb + j),
                      _pairs(q, k, g)))
    xs = _unit_lower_inverses([b * made.kp for _, _, _, b, made in heads])
    # W = X (b K+), U0 = X (b v), X = (I + Diag(b) Kp)^-1: a stage at a time
    # over the step's heads, as the inverses
    decays = [jnp.exp(made.gsum) for *_, made in heads]
    dws = [dw_ref[0, j, 0].astype(jnp.float32) for j in range(hb)]
    dxs = [_mm(dw, b * (k * decay), _NT) + _mm(du0_ref[0, j, 0], b * v, _NT)
           for j, (dw, decay, (_, k, v, b, _)) in enumerate(zip(dws, decays, heads))]
    through = [_mm(dx, x, _NT) for dx, x in zip(dxs, xs)]
    das = [jnp.where(r > i, -_mm(x, t, _TN), 0.0) for x, t in zip(xs, through)]
    for j, (x, da, dw, decay, head) in enumerate(zip(xs, das, dws, decays, heads)):
        q, k, v, b, (gsum, grow, rows, factors, _, kp) = head
        kplus, last = k * decay, gsum[-1:]
        dqg, dkd = dqg_ref[0, j, 0].astype(jnp.float32), dkd_ref[0, j, 0].astype(jnp.float32)
        dkb, dvb = _mm(x, dw, _TN), _mm(x, du0_ref[0, j, 0], _TN)
        db = jnp.sum(dkb * kplus, axis=1, keepdims=True) + jnp.sum(
            dvb * v, axis=1, keepdims=True) + jnp.sum(da * kp, axis=1, keepdims=True)
        dkplus = b * dkb
        # q exp G, k exp G, k exp(G[last] - G), exp G[last]
        fade = jnp.exp(last - gsum)
        through = dkd * (k * fade)
        dq = dqg * decay
        dk = dkplus * decay + dkd * fade
        dgsum = dqg * (q * decay) + dkplus * kplus - through
        dlast = jnp.sum(through, axis=0, keepdims=True) + dgam_ref[0, j, 0] * jnp.exp(last)
        # the pairs, a row block at a time as the forward made them: a block's
        # rows times exp(G - G[m]) against the keys times exp(G[m] - G[i])
        dpairs = zip(_sub_blocks(jnp.where(r >= i, dp_ref[0, j, 0], 0.0)),
                     _sub_blocks(b * da))
        drows, dmids = [], []
        for dpair, row, f in zip(dpairs, rows, factors):
            dpair, key = jnp.concatenate(dpair, axis=0), k * f          # [2 SUB, c], [c, K]
            drows.append(_mm(dpair, key))                                # [2 SUB, K]
            dkey = _mm(dpair, row, _TN)                                  # [c, K]
            dk = dk + dkey * f
            through = dkey * key
            dgsum = dgsum - through
            dmids.append(jnp.sum(through, axis=0, keepdims=True))
        drq = jnp.concatenate([d[:SUB] for d in drows], axis=0)
        drk = jnp.concatenate([d[SUB:] for d in drows], axis=0)
        dq = dq + drq * grow
        dk = dk + drk * grow
        through = drq * (q * grow) + drk * (k * grow)
        dgsum = dgsum + through
        for m, (dmid, part) in enumerate(zip(dmids, _sub_blocks(through))):
            dmid = dmid - jnp.sum(part, axis=0, keepdims=True)
            dgsum = dgsum + jnp.where(token == m * SUB + SUB // 2 - 1, dmid, 0.0)
        dgsum = dgsum + jnp.where(token == c - 1, dlast, 0.0)
        # of the blocks as they were read, through the unit vectors
        rq, rk = norms[j]
        dq_ref[0, :, j * kd:(j + 1) * kd] = _unit_pull(
            dq, q, rq, kd ** -0.5).astype(dq_ref.dtype)
        dk_ref[0, :, j * kd:(j + 1) * kd] = _unit_pull(dk, k, rk).astype(dk_ref.dtype)
        dv_ref[0, :, j * vd:(j + 1) * vd] = (b * dvb).astype(dv_ref.dtype)
        dg_ref[0, :, j * kd:(j + 1) * kd] = _sum_back(dgsum).astype(dg_ref.dtype)
        dbeta_ref[0, j, 0] = jnp.sum(jnp.where(r == i, db, 0.0), axis=0, keepdims=True)


def _stage_specs(q, v, beta, chunk):
    """The block specs and shapes of what the stage's kernels read in place
    (``q``'s kind: ``[batch, T, H x K]``; ``v``'s; ``beta``) and of what
    :func:`_inter` takes, ``hb`` heads a step."""
    bsz, t, heads = beta.shape
    kd, vd, chunks = q.shape[-1] // heads, v.shape[-1] // heads, t // chunk
    hb = max(d for d in range(1, _HEADS_A_STEP + 1) if heads % d == 0)
    flat = lambda i, h, n: (i, n, h)
    split = lambda i, h, n: (i, h, n, 0, 0)
    spec = dict(k=_block_spec((1, chunk, hb * kd), flat),
                v=_block_spec((1, chunk, hb * vd), flat),
                beta=_block_spec((1, chunk, heads), lambda i, h, n: (i, n, 0)),
                qg=_block_spec((1, hb, 1, chunk, kd), split),
                p=_block_spec((1, hb, 1, chunk, chunk), split),
                u0=_block_spec((1, hb, 1, chunk, vd), split),
                gam=_block_spec((1, hb, 1, 1, kd), split),
                dbeta=_block_spec((1, hb, 1, 1, chunk), split))
    lead = (bsz, heads, chunks)
    shape = dict(qg=lead + (chunk, kd), p=lead + (chunk, chunk), u0=lead + (chunk, vd),
                 gam=lead + (1, kd), dbeta=lead + (1, chunk))
    return (bsz, heads // hb, chunks), spec, shape


@functools.partial(jax.jit, static_argnums=(5, 6))
def _intra_fwd(q, k, v, g, beta, chunk, interpret):
    grid, spec, shape = _stage_specs(q, v, beta, chunk)
    operands = (q, k, v, g, beta)
    out = lambda name, dtype: _out_struct(shape[name], dtype, operands)
    return tuple(pl.pallas_call(
        _program(_intra_fwd_kernel, interpret),
        grid=grid,
        in_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"]],
        out_specs=[spec["qg"], spec["p"], spec["qg"], spec["u0"], spec["qg"], spec["gam"]],
        out_shape=[out("qg", v.dtype), out("p", jnp.float32), out("qg", v.dtype),
                   out("u0", jnp.float32), out("qg", v.dtype), out("gam", jnp.float32)],
        interpret=interpret, name="kda_intra_fwd",
    )(*operands))


@functools.partial(jax.jit, static_argnums=(6, 7))
def _intra_bwd(q, k, v, g, beta, cotangents, chunk, interpret):
    grid, spec, shape = _stage_specs(q, v, beta, chunk)
    operands = (q, k, v, g, beta) + tuple(cotangents)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        _program(_intra_bwd_kernel, interpret),
        grid=grid,
        in_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["beta"],
                  spec["qg"], spec["p"], spec["qg"], spec["u0"], spec["qg"], spec["gam"]],
        out_specs=[spec["k"], spec["k"], spec["v"], spec["k"], spec["dbeta"]],
        out_shape=[_out_struct(o.shape, o.dtype, operands) for o in operands[:4]] + [
            _out_struct(shape["dbeta"], jnp.float32, operands)],
        interpret=interpret, name="kda_intra_bwd",
    )(*operands)
    # [batch, H, chunks, 1, chunk] -> [batch, T, H]
    dbeta = jnp.moveaxis(dbeta.reshape(dbeta.shape[:2] + (-1,)), 1, 2).astype(beta.dtype)
    return dq, dk, dv, dg, dbeta


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _intra(q, k, v, g, beta, chunk, interpret):
    """The chunk's stateless part.  ``q, k, g`` ``[batch, T, H x K]``, the
    heads side by side on the lanes, ``v`` ``[batch, T, H x V]``, ``beta``
    ``[batch, T, H]``, ``T`` whole chunks, read where they are.  Returns ``(q
    exp G, P, W, U0, k exp(G[last] - G), exp G[last])`` of the module's
    docstring, ``[batch, H, chunks, chunk, .]``: what the walk's kernels
    multiply in ``v``'s type, ``P``, ``U0`` and the chunk's decay in float32."""
    return _intra_fwd(q, k, v, g, beta, chunk, interpret)


def _intra_residuals(q, k, v, g, beta, chunk, interpret):
    return _intra_fwd(q, k, v, g, beta, chunk, interpret), (q, k, v, g, beta)


def _intra_cotangents(chunk, interpret, res, cotangents):
    return _intra_bwd(*res, cotangents, chunk, interpret)


_intra.defvjp(_intra_residuals, _intra_cotangents)


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
    """:func:`kda_chunked` for the heads it is handed side by side on the
    lanes (``[batch, T, H x K]``; ``beta`` ``[batch, T, H]``), ``T`` whole
    chunks.  Returns ``[batch, T, H x V]``."""
    with jax.named_scope("kda_intra"):
        parts = _intra(q, k, v, g, beta, chunk, interpret)
    o = _inter(*parts, interpret)                        # [batch, H, chunks, chunk, V]
    return jnp.moveaxis(o, 1, 3).reshape(v.shape)


def kda_chunked(q, k, v, g, beta, *, chunk=64, heads_at_once=4, interpret=None):
    """``o[t] = S[t]^T q[t]`` of the recurrence in the module's docstring,
    differentiable in all five arguments.

    ``q``, ``k``: ``[batch, T, H, K]`` as projected and convolved: the
    kernels take a head's vector over its length, ``x / sqrt(sum(x x) +
    1e-6)``, and the query over ``sqrt(K)`` besides, and the recurrence runs on
    those; the gradients are the raw arrays'.  ``v``: ``[batch, T, H, V]``;
    ``g``: ``[batch, T, H, K]``, the log-decay, at most 0 and not under -5.5 a
    token (what keeps a sub-block's ``exp`` finite); ``beta``: ``[batch, T,
    H]``.
    ``chunk`` is ``SUB`` times a power of two; a ``T`` that it does not
    divide is padded with tokens that leave the state as it is.  The heads are
    walked ``heads_at_once`` at a time (where that divides them), each group
    under a ``jax.checkpoint``: what the stateless stage hands the walk, six
    arrays a head and their cotangents, is alive for one group and not for
    the layer.  Returns ``[batch, T, H, V]`` in ``v``'s type."""
    if interpret is None:
        interpret = _default_interpret()
    if chunk % SUB or (chunk // SUB) & (chunk // SUB - 1):
        raise ValueError(f"chunk {chunk}: {SUB} times a power of two")
    t, heads = q.shape[1:3]
    pad = -t % chunk
    # a head's channels beside the next head's, [batch, T, H x K]: a group's
    # block is whole 128-lane tiles and the kernels read it where it is ([T,
    # 4, K] is tiled four rows at a time and would be copied a call)
    args = tuple(a.reshape(a.shape[:2] + (-1,)) for a in (q, k, v, g)) + (beta,)
    if pad:  # k = 0 (its unit vector too), beta = 0, g = 0: the state passes
        args = tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in args)
    one = functools.partial(_heads, chunk=chunk, interpret=interpret)
    if heads % heads_at_once or heads == heads_at_once:
        o = one(*args)
    else:
        groups = heads // heads_at_once

        def split(a):  # [batch, T, H x .] -> [groups, batch, T, heads_at_once x .]
            return jnp.moveaxis(a.reshape(a.shape[:2] + (groups, -1)), 2, 0)

        o = lax.map(lambda group: jax.checkpoint(one)(*group), tuple(map(split, args)))
        o = jnp.moveaxis(o, 0, 2)
    o = o.reshape(o.shape[:2] + (heads, -1))
    return o[:, :t] if pad else o

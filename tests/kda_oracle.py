"""The chunk's stateless stage of the delta rule as the XLA expression that
`bluefog_tpu/kernels/kda.py` ran until PR 44, differentiated by JAX: the
oracle that `tests/test_ling_hybrid.py` holds the stage's two Pallas kernels
(`kda_intra_fwd`, `kda_intra_bwd`) to.  Float32, every product at
``Precision.HIGHEST``, the pairs on sub-blocks of ``SUB`` tokens relative to
the running sum at each sub-block's middle token, the inverse by substitution
and the merges ``X - X A_off X``.  Since PR 47 the kernels take a head's unit
vector themselves: :func:`on_units` puts the same norm, as a plain expression
that JAX differentiates, in front of this oracle or of the token recurrence."""

import jax.numpy as jnp
from jax import lax

from bluefog_tpu.kernels.kda import SUB

_HIGH = lax.Precision.HIGHEST


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGH,
                      preferred_element_type=jnp.float32)


def unit(x, scale=1.0):
    """``scale x / |x|`` over the last axis, as chipbench's reference takes a
    head's vector over its length (`reference/ling-3.0-flash-vl.py: _unit`)."""
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def on_units(fn):
    """``fn(q, k, ...)`` handed q and k raw, ``[..., K]``: the key over its
    length and the query over its length and ``sqrt(K)``, in float32, first."""
    def of_raw(q, k, *rest):
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        return fn(unit(q, q.shape[-1] ** -0.5), unit(k), *rest)
    return of_raw


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

    x, _ = lax.scan(row, jnp.zeros(d.shape, a.dtype) + jnp.eye(SUB, dtype=a.dtype),
                    jnp.arange(1, SUB))
    x = _in_blocks([jnp.pad(x[..., i, :, :], ((0, 0),) * (x.ndim - 2) + ((i * SUB, 0),))
                    for i in range(nb)], c)
    size = SUB
    while size < c:  # [[X1, 0], [-X2 A21 X1, X2]], every pair of blocks at once
        off = jnp.where(_same_block(c, 2 * size) & ~_same_block(c, size), a, 0.0)
        x = x - _mm("...ij,...jk->...ik", _mm("...ij,...jk->...ik", x, off), x)
        size *= 2
    return x


def intra(q, k, v, g, beta, dtype):
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


def by_chunk(a, chunk):
    """``[batch, T, H, ...]`` -> ``[batch, H, chunks, chunk, ...]`` in
    float32: the layout :func:`intra` takes and the kernels write."""
    a = a.astype(jnp.float32).reshape((a.shape[0], a.shape[1] // chunk, chunk) + a.shape[2:])
    return jnp.moveaxis(a, 3, 1)

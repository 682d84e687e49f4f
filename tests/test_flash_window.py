"""The causal band in the flash-attention kernels: ``window=`` in the forward
kernel and both backward kernels (Pallas, interpret mode at tiny blocks) and
in the XLA fall-back against the masked dense attention, at a window smaller
than, equal to and larger than the sequence; ``window=None`` against the call
that never heard of a window, bit for bit."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.kernels.flash_attention import (
    flash_attention, flash_attention_with_lse, make_flash_attention_fn)

B, T, H, D = 1, 64, 2, 16


def masked_dense(q, k, v, window, q_start=0, k_start=0):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    i = q_start + jnp.arange(q.shape[1])[:, None]
    j = k_start + jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", jnp.where(seen, p, 0.0), v)


@pytest.fixture(scope="module")
def qkvg():
    return tuple(jax.random.normal(k, (B, T, H, D))
                 for k in jax.random.split(jax.random.PRNGKey(0), 4))


# smaller than a block, across blocks, the sequence itself, past it
WINDOWS = [1, 5, 24, T, T + 36]


@pytest.mark.parametrize("impl,blocks", [
    ("pallas", (8, 8)), ("pallas", (16, 8)), ("pallas", (8, 16)), ("xla", (8, 8))])
@pytest.mark.parametrize("window", WINDOWS)
def test_band_forward_and_both_backward_kernels_match_masked_dense(
        qkvg, impl, blocks, window):
    q, k, v, g = qkvg
    kw = dict(window=window, block_q=blocks[0], block_k=blocks[1], impl=impl)
    out = flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(out, masked_dense(q, k, v, window), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(flash_attention(*a, **kw) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(masked_dense(*a, window) * g), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):  # dQ, then dK and dV (the other kernel)
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_the_band_grid_visits_only_the_blocks_the_band_touches():
    from bluefog_tpu.kernels.flash_attention import _Band

    band = _Band(1024, 1024, 4096, 8, 8)  # the benchmark's window layer
    assert (band.k_steps, band.q_steps) == (5, 5)
    tiles = sum(band.k_hi(i) - band.k_lo(i) + 1 for i in range(8))
    assert tiles == 30  # a causal kernel runs 36, a masking one visits 64
    assert [band.k_lo(i) for i in range(8)] == [0, 0, 0, 0, 0, 1, 2, 3]
    assert [band.q_hi(j) for j in range(8)] == [4, 5, 6, 7, 7, 7, 7, 7]
    wide = _Band(1024, 1024, 8192, 8, 8)  # a window as long as the sequence
    assert sum(wide.k_hi(i) - wide.k_lo(i) + 1 for i in range(8)) == 36


def test_band_with_traced_offsets_masks_on_global_positions(qkvg):
    """One hop of a ring with a window: the offsets are traced, so the
    dynamic-offset kernels mask (and skip) on global positions."""
    q, k, v, g = qkvg

    def hop(q, k, v, qs, ks):
        o, lse = flash_attention_with_lse(q, k, v, q_start=qs, k_start=ks,
                                          window=20, block_q=8, block_k=8)
        return jnp.sum(o * g)

    qs, ks = jnp.int32(64), jnp.int32(48)
    got = jax.grad(hop, (0, 1, 2))(q, k, v, qs, ks)
    want = jax.grad(lambda *a: jnp.sum(masked_dense(*a, 20, 64, 48) * g),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_window_none_is_the_call_that_never_heard_of_a_window(qkvg):
    q, k, v, g = qkvg

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * g)

    old = loss(lambda *a: flash_attention(*a, causal=True, block_q=16, block_k=16))
    new = loss(lambda *a: flash_attention(*a, causal=True, block_q=16, block_k=16,
                                          window=None))
    made = loss(make_flash_attention_fn(block_q=16, block_k=16))
    texts = {hashlib.sha256(jax.jit(jax.grad(f, (0, 1, 2))).lower(q, k, v)
                            .as_text().encode()).hexdigest()
             for f in (old, new, made)}
    assert len(texts) == 1
    for a, b in zip(jax.grad(old, (0, 1, 2))(q, k, v), jax.grad(new, (0, 1, 2))(q, k, v)):
        assert np.array_equal(np.asarray(a), np.asarray(b))  # bit for bit


def test_a_window_needs_a_causal_mask():
    x = jnp.zeros((1, 8, 1, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        flash_attention(x, x, x, window=0)

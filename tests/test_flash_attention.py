"""Pallas flash-attention kernel vs the dense reference (interpret mode on
the CPU mesh — the kernel logic itself runs, per SURVEY.md §4's fake-backend
strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.kernels import flash_attention
from bluefog_tpu.models.transformer import dense_attention


def _rand_qkv(key, b, t, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, t, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t,block", [(32, 16), (64, 64), (48, 16)])
def test_flash_matches_dense(causal, t, block):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, t, 3, 16)
    out = flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block, interpret=True
    )
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("delta", [0, 1, 4])
def test_pallas_static_key_ahead_delta_matches_dense(delta):
    """Static equal-ish offsets with key-ahead delta: 0 and 1 take the
    Pallas ALIGNED fast path (interior tiles unmasked); delta >= 2 MUST
    fall back to the general masked path — the aligned path's unmasked
    interior tiles would attend to future keys there (r4 review finding)."""
    from bluefog_tpu.kernels.flash_attention import (
        _aligned_or_none,
        flash_attention_with_lse,
    )

    assert _aligned_or_none(delta, True, 32, 32, 16, 16) == (
        delta if delta <= 1 else None)

    t = 32
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), 2, t, 3, 16)
    out, _ = flash_attention_with_lse(
        q, k, v, q_start=0, k_start=delta, causal=True,
        block_q=16, block_k=16, impl="pallas", interpret=True,
    )
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(16)
    qpos = jnp.arange(t)
    kpos = delta + jnp.arange(t)
    scores = jnp.where(kpos[None, :] <= qpos[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_uneven_q_k_blocks():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 64, 2, 8)
    out = flash_attention(
        q, k, v, causal=False, block_q=32, block_k=16, interpret=True
    )
    ref = dense_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("offsets", [(0, 0), (8, 16)])
def test_xla_impl_matches_dense(causal, offsets):
    """The XLA blockwise forward (the default compiled path) matches dense
    on both the aligned-triangular and general fori_loop branches."""
    t = 32
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 2, t, 3, 16)
    qs, ks = offsets
    from bluefog_tpu.kernels.flash_attention import flash_attention_with_lse

    out, lse = flash_attention_with_lse(
        q, k, v, q_start=qs, k_start=ks, causal=causal,
        block_q=16, block_k=16, impl="xla",
    )
    # dense reference with the same global-offset mask
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(16)
    if causal:
        qpos = qs + jnp.arange(t)
        kpos = ks + jnp.arange(t)
        scores = jnp.where(kpos[None, :] <= qpos[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_xla_impl_gradients_match_dense():
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 1, 32, 2, 8)

    def loss_xla(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                            impl="xla")
        return jnp.sum(jnp.sin(o))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal=True)))

    g_x = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gx, gd in zip(g_x, g_d):
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gd), atol=3e-5)


def test_flash_gradients_indivisible_length():
    """T=40 with requested block 16: _fit_block shrinks both forward AND
    backward blocking; the backward must cover the tail keys (regression:
    an unfitted backward block silently zeroed tail dK/dV)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 40, 2, 8)

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16, interpret=True
        )
        return jnp.sum(jnp.sin(o))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal=True)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=3e-5)
        assert float(jnp.abs(gf[:, -8:]).max()) > 0  # tail keys got gradient


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_dense(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 32, 2, 8)

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=16, interpret=True
        )
        return jnp.sum(jnp.sin(o))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal=causal)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=3e-5)


def test_flash_bf16_inputs():
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 32, 2, 8, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2
    )


def test_flash_in_llama_model():
    """flash attention_fn plugs into the decoder family end to end."""
    from bluefog_tpu.kernels import make_flash_attention_fn
    from bluefog_tpu.models.transformer import LlamaLM

    model = LlamaLM(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2, dff=64,
        dtype=jnp.float32,
        attention_fn=make_flash_attention_fn(block_q=16, block_k=16,
                                             interpret=True),
    )
    ids = jnp.zeros((1, 32), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    logits = model.apply(params, ids)
    assert logits.shape == (1, 32, 64)
    assert bool(jnp.all(jnp.isfinite(logits)))

"""A decoder whose layers mix their tokens by a state-space scan or by
attention, a kind a layer: the hybrid of IBM's Granite 4.0-H (`model_type`
granitemoehybrid), whose state-space layer is Mamba-2 (Dao and Gu,
arXiv:2405.21060).

Every layer is ``h + r Mixer(RMSNorm(h))`` then ``h + r MLP(RMSNorm(h))``
with one residual multiplier ``r`` and the dense gated MLP of
:class:`bluefog_tpu.models.transformer._GatedMLP`.  An ``"attention"`` layer
is grouped-query causal attention over the whole sequence with **no position
signal**, its scores scaled by ``attention_multiplier`` in place of
``1 / sqrt(head_dim)``; a ``"mamba"`` layer is :class:`Mamba2Mixer`.  The
input is ``embedding_multiplier`` times the embedding, the logits are the
final norm's output times the **same** tensor over ``logits_scaling``; with
``labels`` the model returns the chunked next-token loss
(:func:`bluefog_tpu.models.transformer.chunked_softmax_cross_entropy`), so
:func:`bluefog_tpu.training.make_lm_loss_fns`' identity loss serves it.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bluefog_tpu.models.transformer import (
    RMSNorm,
    _GatedMLP,
    _head_matmul,
    _HeadKernel,
    chunked_softmax_cross_entropy,
)

__all__ = ["HybridMambaLM", "Mamba2Mixer", "causal_conv", "conv_silu"]

# What the backward pass of a recomputed block is handed beside the block's
# input, each a `checkpoint_name` where the value is made: the flash forward's
# output and logsumexp (`kernels.flash_attention._flash_core_fwd`; with both
# its kernel does not run again), a mixer's output and the MLP's gate-and-up
# product (`models.transformer._GatedMLP`).  Made again: the norms, the
# state-space input projection, the convolution and its SiLU, the softplus,
# the scan's forward, the gate and its norm, `silu(gate) * up` and the
# attention layer's q, k and v.  In the order a larger share drops them from
# the tail: bytes kept over the recomputation spared rise along it.  The next
# name, "ssm_in_proj", is on its value too and is not kept: with it the
# granite-4.0-h-micro cell's compiled step asks for 15.13 GB, with these for
# 13.81 (PERF.md section 6, PR 40: the table, and both read on the chip).
REMAT_KEEPS = ("attn_out", "attn_lse", "mixer_out", "mlp_gate_up")


def causal_conv(x, kernel, bias):
    """Depth-wise causal convolution: ``out[:, t] = bias + sum_k kernel[k] *
    x[:, t - (W - 1) + k]``, zeros before the sequence.  x ``[B, T, C]``,
    kernel ``[W, C]``; float32 out.  The definition, and the path of the
    shapes that :mod:`bluefog_tpu.kernels.causal_conv` does not tile: ``W``
    shifted multiply-adds over a padded float32 copy, which the TPU's compiler
    does not make one pass of (every slice starts off the 8-row tile: 1.36 ms
    a layer forward at 8,192 x 4,352 where the bytes ask for 0.17; PERF.md
    section 6, PR 42)."""
    w, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (w - 1, 0), (0, 0)))
    return bias + sum(kernel[k] * padded[:, k:k + t] for k in range(w))


def conv_kernels_take(tokens, inner, group_states, width):
    """Whether a state-space layer's convolution goes through the kernels of
    :mod:`bluefog_tpu.kernels.causal_conv` or through :func:`causal_conv`: by
    the shapes alone (the scan's ``inner`` channels and its ``2 *
    group_states`` of B and C each in whole 128-lane blocks, the tokens in
    whole 8-row tiles)."""
    from bluefog_tpu.kernels.causal_conv import tiles

    return (tiles(tokens, inner, width, inner)
            and tiles(tokens, 2 * group_states, width, 2 * inner))


def conv_silu(zxbcdt, taps, bias, inner):
    """``silu(causal_conv(xBC))`` of a Mamba-2 layer in ``zxbcdt``'s type,
    ``xBC`` the channels ``inner .. inner + C`` of the input projection's
    output ``zxbcdt``: the scan's ``x`` (``inner`` channels) and its ``[B,
    C]``.  Where the kernels take the shapes, two calls, a channel's
    convolution being its own: each reads its channels where the product left
    them and writes what the scan reads, so that no slice of ``[T, C]`` is
    made before or after."""
    from bluefog_tpu.kernels.causal_conv import causal_conv_silu

    width, conv = taps.shape
    if conv_kernels_take(zxbcdt.shape[1], inner, (conv - inner) // 2, width):
        return (causal_conv_silu(zxbcdt, taps[:, :inner], bias[:inner], offset=inner),
                causal_conv_silu(zxbcdt, taps[:, inner:], bias[inner:],
                                 offset=2 * inner))
    xbc = jax.nn.silu(causal_conv(
        zxbcdt[..., inner:inner + conv], taps, bias)).astype(zxbcdt.dtype)
    return xbc[..., :inner], xbc[..., inner:]


class Mamba2Mixer(nn.Module):
    """``[z, xBC, dt] = u W_in``; ``xBC`` through a causal depth-wise
    convolution and SiLU (:func:`conv_silu`); ``[x, B, C] = xBC``; the scan of
    :func:`bluefog_tpu.kernels.ssd.ssd_scan` with step sizes ``softplus(dt +
    dt_bias)``; ``w * RMSNorm(y * silu(z))`` over all channels; ``W_out``.
    No bias but the convolution's.  Step sizes, the decay rates and the two
    norms in float32, the products in ``dtype``."""

    num_heads: int
    head_dim: int
    state_size: int
    groups: int = 1
    conv_width: int = 4
    chunk: int = 256
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        from bluefog_tpu.kernels.ssd import ssd_scan

        B, T, d = u.shape
        h, p, n, g = self.num_heads, self.head_dim, self.state_size, self.groups
        inner, conv = h * p, h * p + 2 * g * n
        init = nn.initializers.normal(0.02)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype, kernel_init=init)
        # each scope holds the slices of what it made: a slice outside would
        # be the mixer's by its path and no scope's
        with jax.named_scope("ssm_in_proj"):
            zxbcdt = checkpoint_name(dense(inner + conv + h, name="in_proj")(u),
                                     "ssm_in_proj")
            z, dt = zxbcdt[..., :inner], zxbcdt[..., inner + conv:]
        with jax.named_scope("ssm_conv"):
            x, bc = conv_silu(
                zxbcdt,
                self.param("conv_kernel", init, (self.conv_width, conv), jnp.float32),
                self.param("conv_bias", nn.initializers.zeros_init(), (conv,),
                           jnp.float32), inner)
            bm, cm = bc[..., :g * n], bc[..., g * n:]
        ones = nn.initializers.ones_init()
        dt_bias = self.param("dt_bias", ones, (h,), jnp.float32)
        a_log = self.param("A_log", ones, (h,), jnp.float32)
        skip = self.param("D", ones, (h,), jnp.float32)
        with jax.named_scope("ssm_scan"):
            y = ssd_scan(x.reshape(B, T, h, p),
                         jax.nn.softplus(dt.astype(jnp.float32) + dt_bias), a_log,
                         bm.reshape(B, T, g, n), cm.reshape(B, T, g, n), skip,
                         chunk=self.chunk)
        with jax.named_scope("ssm_gate_norm"):
            gated = y.reshape(B, T, inner).astype(jnp.float32) * jax.nn.silu(
                z.astype(jnp.float32))
            gated = RMSNorm(dtype=self.dtype, eps=self.eps, name="norm")(gated)
        with jax.named_scope("ssm_out_proj"):
            return dense(d, name="out_proj")(gated)


class _AttentionMixer(nn.Module):
    """Grouped-query causal attention over the whole sequence, no position
    signal; the kernels read the shared key-value heads in place.  They scale
    scores by ``1 / sqrt(head_dim)``, so ``q`` carries the rest of ``scale``
    (exact in bfloat16 where that is a power of two, as Granite's 1/64 on
    heads of 64)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    scale: float
    dtype: Any
    attention_fn: Callable  # (q, k, v) -> out, causal

    @nn.compact
    def __call__(self, u):
        B, T, d = u.shape
        H, kvh, hd = self.num_heads, self.num_kv_heads, self.head_dim
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=self.dtype)
        q = dense((H, hd), name="q")(u) * (self.scale * math.sqrt(hd))
        k, v = dense((kvh, hd), name="k")(u), dense((kvh, hd), name="v")(u)
        with jax.named_scope("attention_global"):
            att = self.attention_fn(q, k, v)
        return dense(d, name="o")(att.reshape(B, T, H * hd))


class _HybridBlock(nn.Module):
    mixer: Callable[[], nn.Module]
    dff: int
    residual_multiplier: float
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, h):
        norm = partial(RMSNorm, dtype=self.dtype, eps=self.eps)
        r = self.residual_multiplier
        mixed = checkpoint_name(self.mixer()(norm(name="mixer_norm")(h)), "mixer_out")
        h = h + (r * mixed).astype(h.dtype)
        with jax.named_scope("mlp_dense"):
            return h + (r * _GatedMLP(self.dff, self.dtype, name="mlp")(
                norm(name="mlp_norm")(h))).astype(h.dtype)


class HybridMambaLM(nn.Module):
    """The decoder of the module's docstring.  ``layer_kinds`` names each
    layer's mixer, ``"mamba"`` or ``"attention"``.  ``remat`` recomputes each
    block in the backward pass (``nn.remat``: a block's input and the values
    ``REMAT_KEEPS`` names are all that is kept of it).
    ``tie_embeddings=False`` gives the head a tensor of its own
    (``head/kernel``)."""

    vocab_size: int
    hidden_size: int
    layer_kinds: Tuple[str, ...]
    dff: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int = 1
    conv_width: int = 4
    chunk: int = 256
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None  # None: 1 / sqrt(head_dim)
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    eps: float = 1e-5
    tie_embeddings: bool = True
    remat: bool = True
    head_chunks: int = 1
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None  # None: the flash kernels

    @nn.compact
    def __call__(self, input_ids, labels=None):
        from bluefog_tpu.kernels.flash_attention import flash_attention
        from bluefog_tpu.telemetry import registry as _telemetry

        kinds = tuple(self.layer_kinds)
        if set(kinds) - {"mamba", "attention"}:
            raise ValueError(f"layer kinds {sorted(set(kinds))}: 'mamba' or 'attention'")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads {self.num_heads} not divisible by "
                             f"num_kv_heads {self.num_kv_heads}")
        scale = (self.head_dim ** -0.5 if self.attention_multiplier is None
                 else self.attention_multiplier)
        keeps = REMAT_KEEPS if self.remat else ()
        reg = _telemetry.get_registry()
        if reg.enabled:
            tokens, width = input_ids.size, jnp.dtype(self.dtype).itemsize
            n_att, n_ssm = kinds.count("attention"), kinds.count("mamba")
            kept = {  # bytes a step under each name, from the shapes where it is made
                "attn_out": n_att * tokens * self.num_heads * self.head_dim * width,
                "attn_lse": n_att * tokens * self.num_heads * 4,
                "mixer_out": len(kinds) * tokens * self.hidden_size * width,
                "mlp_gate_up": len(kinds) * tokens * 2 * self.dff * width,
                "ssm_in_proj": n_ssm * tokens * width * (
                    2 * self.ssm_heads * self.ssm_head_dim
                    + 2 * self.ssm_groups * self.ssm_state + self.ssm_heads),
            }
            for name, value in (
                    ("ssm.layers", n_ssm), ("ssm.heads", self.ssm_heads),
                    ("ssm.head_dim", self.ssm_head_dim), ("ssm.state", self.ssm_state),
                    ("ssm.groups", self.ssm_groups), ("ssm.chunk", self.chunk),
                    ("ssm.conv_width", self.conv_width),
                    ("ssm.conv_kernel_layers", n_ssm * conv_kernels_take(
                        input_ids.shape[1], self.ssm_heads * self.ssm_head_dim,
                        self.ssm_groups * self.ssm_state, self.conv_width)),
                    ("attention.layers_global", n_att),
                    ("attention.heads_global", self.num_heads),
                    ("attention.kv_heads", self.num_kv_heads),
                    ("attention.scale", scale),
                    ("lm.tied_head", int(self.tie_embeddings)),
                    ("lm.remat_blocks", len(kinds) if self.remat else 0),
                    ("lm.remat_kept_names", len(keeps)),
                    ("lm.remat_kept_mb", sum(kept[k] for k in keeps) / 1e6)):
                reg.gauge(name).set(value)
        mixers = {
            "mamba": partial(
                Mamba2Mixer, self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                self.ssm_groups, self.conv_width, self.chunk, self.eps, self.dtype,
                name="mixer"),
            "attention": partial(
                _AttentionMixer, self.num_heads, self.num_kv_heads, self.head_dim,
                scale, self.dtype,
                self.attention_fn or partial(flash_attention, causal=True),
                name="mixer"),
        }
        embed = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(0.02), name="embed")
        h = (jnp.take(embed.embedding, input_ids, axis=0)
             * self.embedding_multiplier).astype(self.dtype)
        block_cls = _HybridBlock
        if self.remat:
            block_cls = nn.remat(_HybridBlock, policy=jax.checkpoint_policies
                                 .save_only_these_names(*keeps))
        for i, kind in enumerate(kinds):
            h = block_cls(mixers[kind], self.dff, self.residual_multiplier, self.eps,
                          self.dtype, name=f"layer_{i}")(h)
        h = RMSNorm(dtype=jnp.float32, eps=self.eps, name="final_norm")(h)
        h = h / self.logits_scaling
        if self.tie_embeddings:
            kernel = embed.embedding.T  # one tensor: its gradient sums both uses
        else:
            kernel = _HeadKernel(self.vocab_size, name="head")(self.hidden_size)
        if labels is None:
            return _head_matmul(h, kernel, jnp.float32)
        return chunked_softmax_cross_entropy(h, kernel, labels, max(self.head_chunks, 1))

"""Decoders whose layers mix their tokens by a recurrence or by attention, a
kind a layer.  :class:`HybridMambaLM`: the hybrid of IBM's Granite 4.0-H
(`model_type` granitemoehybrid), whose state-space layer is Mamba-2 (Dao and
Gu, arXiv:2405.21060).  :class:`DeltaLatentMoELM`: inclusionAI's Ling 3.0
flash, whose linear layer is Kimi Delta Attention (:class:`KDAMixer`,
arXiv:2510.26692), whose attention layer is DeepSeek-V2's latent attention
(:class:`LatentAttentionMixer`, arXiv:2405.04434) and whose feed-forward part
is, after a leading dense layer, DeepSeek-V3's expert layer; with latent
attention in every layer, no gate on its heads and the rotary over interleaved
pairs, the same class is a DeepSeek-V3-style decoder (kakaocorp's kanana-2).
:class:`ShortConvMoELM`: LiquidAI's LFM2 expert decoder (`model_type`
lfm2_moe), whose mixer is a gated short convolution (:class:`ShortConvMixer`)
in three layers of four and grouped-query attention with a norm a head on q
and k in the fourth.  :class:`GatedDeltaMoELM`: Qwen3-Next's (`model_type`
qwen3_next), whose mixer is the gated delta rule with one decay a head
(:class:`GatedDeltaNetMixer`) in three layers of four and attention with an
output gate a channel in the fourth, every norm zero-centred.  All are stacks
of :class:`_HybridBlock`, which takes its mixer and its feed-forward part as
it is handed them.

Of :class:`HybridMambaLM`:

Every layer is ``h + r Mixer(RMSNorm(h))`` then ``h + r MLP(RMSNorm(h))``
with one residual multiplier ``r`` and the dense gated MLP of
:class:`bluefog_tpu.models.transformer._GatedMLP`.  An ``"attention"`` layer
is grouped-query causal attention over the whole sequence with **no position
signal**, its scores scaled by ``attention_multiplier`` in place of
``1 / sqrt(head_dim)``; a ``"mamba"`` layer is :class:`Mamba2Mixer`.  The
input is ``embedding_multiplier`` times the embedding, the logits are the
final norm's output times the **same** tensor over ``logits_scaling``; with
``labels`` the model returns the chunked next-token loss
(:func:`bluefog_tpu.models.transformer.chunked_softmax_cross_entropy`: one
loop over the chunks that takes the head's gradient with the loss), so
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
    Rotary,
    _GatedMLP,
    _head_matmul,
    _HeadKernel,
    _rotary,
    chunked_softmax_cross_entropy,
    expert_feed_forward,
    rotary_frequencies,
)

__all__ = ["DeltaLatentMoELM", "GatedDeltaMoELM", "GatedDeltaNetMixer", "HybridMambaLM",
           "KDAMixer", "LatentAttentionMixer", "Mamba2Mixer", "ShortConvMixer",
           "ShortConvMoELM", "causal_conv", "conv_silu", "gated_short_conv"]

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
# What :class:`DeltaLatentMoELM` keeps: the latent-attention layer's flash
# residuals and the delta rule's output, 67 MB a layer at 8,192 tokens x 32
# heads of 128.  `kda_chunked` walks its heads a group at a time, each group
# under a checkpoint of its own so that one group's intermediates are alive
# and not a layer's (1.28 GB a layer at once, compiled for the v5e, 3.57 while
# the chunk's stateless stage was an XLA expression; PERF.md section 6, PR 44
# and PR 43); with its output kept, the recomputed block does not run
# the group's forward a second time before the group's backward runs it a
# third.  A recomputed delta-rule block makes its projections, convolution,
# gates and gated norm again; the unit vectors of q and k are the kernels' own
# (PR 47), so it makes neither them nor their float32 copies.
DELTA_KEEPS = ("attn_out", "attn_lse", "kda_out")
# What :class:`GatedDeltaMoELM` keeps: the attention layer's flash residuals,
# the gated delta rule's output (its walk under `gdn_chunked`'s checkpoint a
# group of heads does not run a second time before its backward runs it a
# third) and every mixer's output, 34 MB a layer at 8,192 tokens x 2,048.
GATED_DELTA_KEEPS = ("attn_out", "attn_lse", "gdn_out", "mixer_out")


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
    """Grouped-query causal attention over the whole sequence; the kernels
    read the shared key-value heads in place.  They scale scores by ``1 /
    sqrt(head_dim)``, so ``q`` carries the rest of ``scale`` (exact in
    bfloat16 where that is a power of two, as Granite's 1/64 on heads of 64).
    As Granite calls it, **no position signal** and no norm.  With
    ``qk_norm_eps`` every head of ``q`` and of ``k`` goes through an RMS norm
    over its channels first, one learned scale a channel that the heads share
    (``q_norm``, ``k_norm``; float32; ``zero_centered``: ``1 + w``, ``w`` from
    zeros); with ``rotary`` both are then turned, half-split (LFM2's attention
    layer has both), over the ``2 len(rotary.inv_freq)`` first channels of a
    head (Qwen3-Next's quarter).  With ``gate`` (Qwen3-Next's) ``q``'s product
    is twice as wide, ``[q, gate]`` a head, and the kernels' output is times
    ``sigmoid(gate)`` a channel before ``o``; without it no wider leaf and no
    ``attention_gate`` scope."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    scale: float
    dtype: Any
    attention_fn: Callable  # (q, k, v) -> out, causal
    rotary: Optional[Rotary] = None
    qk_norm_eps: Optional[float] = None
    gate: bool = False
    zero_centered: bool = False

    @nn.compact
    def __call__(self, u):
        B, T, d = u.shape
        H, kvh, hd = self.num_heads, self.num_kv_heads, self.head_dim
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=self.dtype)
        rest = self.scale * math.sqrt(hd)  # of the scale, beside the kernels' own
        if self.gate:
            q = dense((H, 2 * hd), name="q")(u)
            q, gate = q[..., :hd], q[..., hd:]
        else:
            q = dense((H, hd), name="q")(u)
        if self.qk_norm_eps is None:
            q = q * rest
        k, v = dense((kvh, hd), name="k")(u), dense((kvh, hd), name="v")(u)
        if self.qk_norm_eps is not None:  # a norm would take a scale put on before it
            with jax.named_scope("attention_qk_norm"):
                norm = partial(RMSNorm, dtype=self.dtype, eps=self.qk_norm_eps,
                               zero_centered=self.zero_centered)
                q, k = norm(name="q_norm")(q) * rest, norm(name="k_norm")(k)
        if self.rotary is not None:
            positions = jnp.arange(T)
            q = _rotary(q, positions, rotary=self.rotary)
            k = _rotary(k, positions, rotary=self.rotary)
        with jax.named_scope("attention_global"):
            att = self.attention_fn(q, k, v)
        if self.gate:
            with jax.named_scope("attention_gate"):
                att = (att * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)
        return dense(d, name="o")(att.reshape(B, T, H * hd))


def dense_ffn(dff, dtype):
    """A block's feed-forward part: the dense gated MLP, leaves under
    ``mlp``."""
    def ffn(block, normed):
        return _GatedMLP(dff, dtype, name="mlp")(normed)
    return "mlp_dense", ffn


def expert_ffn(*args, **routing):
    """A block's feed-forward part: this share of an expert layer beside the
    shared expert (:func:`bluefog_tpu.models.transformer.expert_feed_forward`,
    whose arguments after the stream these are), the leaves the block's own."""
    def ffn(block, normed):
        return expert_feed_forward(block, normed, *args, **routing)
    return "moe_layer", ffn


class _HybridBlock(nn.Module):
    """``h + r Mixer(norm(h))``, then ``h + r FFN(norm(h))``: ``mixer`` makes
    the mixer's module, ``ffn`` is ``(scope, (block, normed) -> out)`` of
    :func:`dense_ffn` or :func:`expert_ffn`."""

    mixer: Callable[[], nn.Module]
    ffn: Tuple[str, Callable]
    residual_multiplier: float
    eps: float
    dtype: Any
    zero_centered: bool = False  # the two norms' scales (:class:`RMSNorm`)

    @nn.compact
    def __call__(self, h):
        norm = partial(RMSNorm, dtype=self.dtype, eps=self.eps,
                       zero_centered=self.zero_centered)
        r = self.residual_multiplier
        mixed = checkpoint_name(self.mixer()(norm(name="mixer_norm")(h)), "mixer_out")
        h = h + (r * mixed).astype(h.dtype)
        scope, ffn = self.ffn
        with jax.named_scope(scope):
            return h + (r * ffn(self, norm(name="mlp_norm")(h))).astype(h.dtype)


def _remat(keeps):
    return nn.remat(_HybridBlock, policy=jax.checkpoint_policies
                    .save_only_these_names(*keeps))


def _head(h, embed, head_of, labels, head_chunks):
    """Logits of the normed stream ``h``, or with ``labels`` the chunked
    next-token loss: through the embedding's own tensor (its gradient sums
    both uses), or where ``head_of`` makes one, through the head's."""
    kernel = embed.embedding.T if head_of is None else head_of()
    if labels is None:
        return _head_matmul(h, kernel, jnp.float32)
    return chunked_softmax_cross_entropy(h, kernel, labels, max(head_chunks, 1))


def _decoder(model, input_ids, labels, blocks, keeps, zero_centered=False):
    """What :class:`ShortConvMoELM` and :class:`GatedDeltaMoELM` share, in
    ``model``'s ``__call__``: the embedding's rows, ``blocks`` (``(mixer,
    ffn)`` a layer) as ``layer_<i>`` under ``keeps``, the final norm and the
    head, tied or its own (``model.tie_embeddings``)."""
    embed = nn.Embed(model.vocab_size, model.hidden_size, dtype=model.dtype,
                     embedding_init=nn.initializers.normal(0.02), name="embed")
    h = jnp.take(embed.embedding, input_ids, axis=0).astype(model.dtype)
    block_cls = _remat(keeps) if model.remat else _HybridBlock
    for i, (mixer, ffn) in enumerate(blocks):
        h = block_cls(mixer, ffn, 1.0, model.eps, model.dtype, zero_centered,
                      name=f"layer_{i}")(h)
    h = RMSNorm(dtype=jnp.float32, eps=model.eps, zero_centered=zero_centered,
                name="final_norm")(h)
    untied = lambda: _HeadKernel(model.vocab_size, name="head")(model.hidden_size)
    return _head(h, embed, None if model.tie_embeddings else untied, labels,
                 model.head_chunks)


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
        block_cls = _remat(keeps) if self.remat else _HybridBlock
        ffn = dense_ffn(self.dff, self.dtype)
        for i, kind in enumerate(kinds):
            h = block_cls(mixers[kind], ffn, self.residual_multiplier, self.eps,
                          self.dtype, name=f"layer_{i}")(h)
        h = RMSNorm(dtype=jnp.float32, eps=self.eps, name="final_norm")(h)
        h = h / self.logits_scaling
        untied = lambda: _HeadKernel(self.vocab_size, name="head")(self.hidden_size)
        return _head(h, embed, None if self.tie_embeddings else untied, labels,
                     self.head_chunks)


def gated_short_conv(bcx, kernel):
    """``C * causal_conv(B * x, kernel, 0)`` with ``[B, C, x] = bcx`` in thirds
    of its channels: LFM2's gated short convolution.  Both gates and the taps'
    sum in float32, one rounding to ``bcx``'s type.  The definition, and the
    path of the shapes that :mod:`bluefog_tpu.kernels.causal_conv` does not
    tile."""
    d = kernel.shape[1]
    gate_b, gate_c, x = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32)
                         for i in range(3))
    return (gate_c * causal_conv(gate_b * x, kernel, 0.0)).astype(bcx.dtype)


def short_conv_kernels_take(tokens, channels, width):
    """Whether a short-convolution layer goes through the kernels of
    :mod:`bluefog_tpu.kernels.causal_conv` or through
    :func:`gated_short_conv`: by the shapes alone, as
    :func:`conv_kernels_take`."""
    from bluefog_tpu.kernels.causal_conv import tiles

    return tiles(tokens, channels, width)


class ShortConvMixer(nn.Module):
    """LFM2's gated short convolution: ``[B, C, x] = u W_in`` (three chunks of
    the hidden size, in that order); ``z = B * x``; a causal depth-wise
    convolution of ``conv_width`` taps over ``z``, no bias, no activation; ``y
    = (C * conv) W_out``.  No state, no heads, no position signal: the taps
    see ``conv_width - 1`` tokens back.  Where the kernels take the shapes,
    one kernel each way reads the three chunks where ``W_in`` left them
    (:func:`bluefog_tpu.kernels.causal_conv.short_conv`); the gates and the
    taps' sum in float32, the products in ``dtype``."""

    conv_width: int = 3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        from bluefog_tpu.kernels.causal_conv import short_conv

        d = u.shape[-1]
        init = nn.initializers.normal(0.02)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype, kernel_init=init)
        with jax.named_scope("short_conv_in_proj"):
            bcx = dense(3 * d, name="in_proj")(u)
        with jax.named_scope("short_conv_gate"):
            taps = self.param("conv_kernel", init, (self.conv_width, d), jnp.float32)
            if short_conv_kernels_take(u.shape[1], d, self.conv_width):
                y = short_conv(bcx, taps)
            else:
                y = gated_short_conv(bcx, taps)
        with jax.named_scope("short_conv_out_proj"):
            return dense(d, name="out_proj")(y)


def kda_conv_kernels_take(tokens, inner, width):
    """Whether a delta-rule layer's three convolutions (q, k and v, ``3 *
    inner`` channels of one product) go through the kernels of
    :mod:`bluefog_tpu.kernels.causal_conv` or through :func:`causal_conv`: by
    the shapes alone, as :func:`conv_kernels_take`."""
    from bluefog_tpu.kernels.causal_conv import tiles

    return tiles(tokens, 3 * inner, width)


class KDAMixer(nn.Module):
    """Kimi Delta Attention (arXiv:2510.26692 section 3).  ``[q, k, v] = u
    W_qkv`` through a causal depth-wise convolution and SiLU, no bias (the
    kernels of :mod:`bluefog_tpu.kernels.causal_conv` where they take the
    shapes); a head at a time ``q / |q| / sqrt(K)`` and ``k / |k|``, taken by
    the delta rule's kernels on the blocks they read (this module hands them q
    and k as convolved and makes no float32 copy of either); the log-decay a
    channel ``lower_bound * sigmoid(exp(A_log[head]) * (u W_f +
    dt_bias))``, in ``(lower_bound, 0)``; the step ``sigmoid(u W_b)`` a head;
    the delta rule of :func:`bluefog_tpu.kernels.kda.kda_chunked`; each head's
    output through an RMS norm with one learned weight a channel, times
    ``sigmoid(u W_g)``; ``W_o``.  No position signal: the decay carries it.
    The norms, the decay, the step, the gate and the state in float32, the
    products in ``dtype``."""

    num_heads: int
    head_dim: int
    conv_width: int = 4
    chunk: int = 64
    lower_bound: float = -5.0
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        from bluefog_tpu.kernels.causal_conv import causal_conv_silu
        from bluefog_tpu.kernels.kda import kda_chunked
        from bluefog_tpu.parallel._util import vma_full

        B, T, d = u.shape
        H, hd = self.num_heads, self.head_dim
        inner = H * hd
        init = nn.initializers.normal(0.02)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype, kernel_init=init)
        with jax.named_scope("kda_in_proj"):
            qkv = dense(3 * inner, name="kda_qkv")(u)
        with jax.named_scope("kda_conv"):
            taps = self.param("conv_kernel", init, (self.conv_width, 3 * inner),
                              jnp.float32)
            if kda_conv_kernels_take(T, inner, self.conv_width):
                # no bias: zeros that vary over the mesh as the taps do
                qkv = causal_conv_silu(qkv, taps, vma_full(taps, (3 * inner,), jnp.float32))
            else:
                qkv = jax.nn.silu(causal_conv(qkv, taps, 0.0)).astype(self.dtype)
            q, k, v = (qkv[..., i * inner:(i + 1) * inner].reshape(B, T, H, hd)
                       for i in range(3))
        with jax.named_scope("kda_gates"):
            a_log = self.param("A_log", nn.initializers.zeros_init(), (H,), jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.zeros_init(), (inner,),
                                 jnp.float32)
            f = dense(inner, name="kda_f")(u).astype(jnp.float32) + dt_bias
            g = self.lower_bound * jax.nn.sigmoid(
                jnp.exp(a_log)[:, None] * f.reshape(B, T, H, hd))
            beta = jax.nn.sigmoid(dense(H, name="kda_b")(u).astype(jnp.float32))
        with jax.named_scope("kda_chunk"):
            # q and k as convolved: the stage's kernels take the unit vectors
            o = kda_chunked(q, k, v, g, beta, chunk=self.chunk)
            o = checkpoint_name(o, "kda_out")
        with jax.named_scope("kda_gate_norm"):
            gate = jax.nn.sigmoid(dense(inner, name="kda_g")(u).astype(jnp.float32))
            o = RMSNorm(dtype=jnp.float32, eps=self.eps, name="kda_norm")(o)
            o = (o.reshape(B, T, inner) * gate).astype(self.dtype)
        with jax.named_scope("kda_out_proj"):
            return dense(d, name="kda_o")(o)


class GatedDeltaNetMixer(nn.Module):
    """Qwen3-Next's linear layer, the gated delta rule (Gated DeltaNet,
    arXiv:2412.06464).  ``[q, k, v, z] = u W_qkvz``, ``key_heads`` heads of
    ``key_dim`` for q and for k, ``num_heads`` of ``value_dim`` for v and for
    z, in that order on the lanes (the checkpoint groups the columns by key
    head: a permutation of them and of the convolution's taps); ``[b, a] = u
    W_ba``, a number a value head each; ``[q, k, v]`` through a causal
    depth-wise convolution and SiLU, no bias, read where the product left them
    (z is not convolved; the kernels of :mod:`bluefog_tpu.kernels.causal_conv`
    where their ``tiles`` takes the shapes, :func:`causal_conv` elsewhere); a head at a time ``q / |q| / sqrt(K)`` and ``k / |k|``,
    taken by the delta rule's kernels on the blocks they read; the step
    ``sigmoid(b)`` and the log-decay ``-exp(A_log) softplus(a + dt_bias)``, **one
    number a value head and token, of any size**, in float32; the recurrence of
    :func:`bluefog_tpu.kernels.gdn.gdn_chunked`, key head ``j`` serving value
    heads ``share j ..``; each head's output through an RMS norm with one
    learned weight a channel that the heads share (a plain scale from ones),
    times ``silu(z)``; ``W_o``.  No position signal: the decay carries it."""

    num_heads: int      # value heads
    key_heads: int
    key_dim: int
    value_dim: int
    conv_width: int = 4
    chunk: int = 64
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        from bluefog_tpu.kernels.causal_conv import causal_conv_silu, tiles
        from bluefog_tpu.kernels.gdn import gdn_chunked
        from bluefog_tpu.parallel._util import vma_full

        B, T, d = u.shape
        H, hk, kd, vd = self.num_heads, self.key_heads, self.key_dim, self.value_dim
        keys, values = hk * kd, H * vd
        conv = 2 * keys + values
        init = nn.initializers.normal(0.02)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype, kernel_init=init)
        with jax.named_scope("gdn_in_proj"):
            qkvz = dense(conv + values, name="gdn_qkvz")(u)
            ba = dense(2 * H, name="gdn_ba")(u)
        with jax.named_scope("gdn_conv"):
            taps = self.param("conv_kernel", init, (self.conv_width, conv), jnp.float32)
            if tiles(T, conv, self.conv_width):
                # no bias: zeros that vary over the mesh as the taps do
                qkv = causal_conv_silu(qkvz, taps, vma_full(taps, (conv,), jnp.float32))
            else:
                qkv = jax.nn.silu(causal_conv(qkvz[..., :conv], taps, 0.0)).astype(self.dtype)
            q = qkv[..., :keys].reshape(B, T, hk, kd)
            k = qkv[..., keys:2 * keys].reshape(B, T, hk, kd)
            v = qkv[..., 2 * keys:].reshape(B, T, H, vd)
        with jax.named_scope("gdn_gates"):
            # the checkpoint's rule: A uniform in (0, 16), dt_bias ones
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                    key, shape, jnp.float32, 1e-3, 16.0)), (H,))
            dt_bias = self.param("dt_bias", nn.initializers.ones_init(), (H,), jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :H].astype(jnp.float32))
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., H:].astype(jnp.float32) + dt_bias)
        with jax.named_scope("gdn_chunk"):
            # q and k as convolved: the stage's kernels take the unit vectors
            o = checkpoint_name(gdn_chunked(q, k, v, g, beta, chunk=self.chunk), "gdn_out")
        with jax.named_scope("gdn_gate_norm"):
            z = qkvz[..., conv:].astype(jnp.float32)
            o = RMSNorm(dtype=jnp.float32, eps=self.eps, name="gdn_norm")(o)
            o = (o.reshape(B, T, values) * jax.nn.silu(z)).astype(self.dtype)
        with jax.named_scope("gdn_out_proj"):
            return dense(d, name="gdn_o")(o)


class LatentAttentionMixer(nn.Module):
    """DeepSeek-V2's latent attention (arXiv:2405.04434 section 2.1) with no
    query compression, in its plain (not absorbed) form: ``q = u W_q`` in
    heads of ``nope + rope``; ``[c, k_r] = u W_kva``, ``c`` of ``kv_rank``
    through an RMS norm, ``k_r`` **one** rotary key head of ``rope`` that every
    head reads; ``[k_n, v] = c W_kvb`` in heads of ``nope + v_dim``; rotary on
    the queries' last ``rope`` channels and on ``k_r``; causal softmax of ``(q_n
    . k_n + q_r . k_r) / sqrt(nope + rope)`` over the whole sequence through
    the flash kernels, which take the values' head size beside the query-key
    one; with ``head_gate`` (Ling's) every head's output times a sigmoid gate
    of the normed input, without it (DeepSeek-V2's own) no ``gate`` leaf;
    ``W_o``.  ``rotary_interleaved``: the rotary pairs channel ``2i`` with
    ``2i + 1`` (:func:`bluefog_tpu.models.transformer._rotary`)."""

    num_heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    rotary: Rotary
    eps: float
    dtype: Any
    attention_fn: Callable  # (q, k, v) -> out, causal
    head_gate: bool = True
    rotary_interleaved: bool = False

    @nn.compact
    def __call__(self, u):
        B, T, d = u.shape
        H = self.num_heads
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=self.dtype)
        positions = jnp.arange(T)
        q = dense((H, self.nope + self.rope), name="mla_q")(u)
        down = dense(self.kv_rank + self.rope, name="mla_kv_down")(u)
        with jax.named_scope("mla_kv_norm"):
            c = RMSNorm(dtype=self.dtype, eps=self.eps, name="mla_kv_norm")(
                down[..., :self.kv_rank])
        kv = dense((H, self.nope + self.v_dim), name="mla_kv_up")(c)
        turn = partial(_rotary, positions=positions, rotary=self.rotary,
                       interleaved=self.rotary_interleaved)
        q = jnp.concatenate([q[..., :self.nope], turn(q[..., self.nope:])], axis=-1)
        k_r = turn(down[..., None, self.kv_rank:])
        with jax.named_scope("mla_kv_up"):  # the one rotary head beside every head's own
            k = jnp.concatenate([kv[..., :self.nope], jnp.broadcast_to(
                k_r, (B, T, H, self.rope))], axis=-1)
        with jax.named_scope("attention_global"):
            att = self.attention_fn(q, k, kv[..., self.nope:])
        if self.head_gate:
            with jax.named_scope("attention_gate"):
                gate = jax.nn.sigmoid(dense(H, name="gate")(u).astype(jnp.float32))
                att = (att * gate[..., None]).astype(self.dtype)
        return dense(d, name="o")(att.reshape(B, T, H * self.v_dim))


class DeltaLatentMoELM(nn.Module):
    """The decoder of Ling 3.0 flash, and with ``"mla"`` in every layer a
    DeepSeek-V3-style one: ``layer_kinds`` names each layer's mixer,
    ``"kda"`` (:class:`KDAMixer`, heads of ``head_dim``) or ``"mla"``
    (:class:`LatentAttentionMixer`, with ``head_gate`` and
    ``rotary_interleaved`` as it takes them), ``layer_dense`` says where the
    feed-forward part is the dense gated MLP of ``dff`` and where this share
    of the expert layer: sigmoid scores over ``num_experts``, the choice on
    the score plus a bias that takes no gradient, ``top_k`` among the
    ``groups_kept`` best of ``groups`` groups (one group: among all), weights
    renormalised times ``routed_scale``, the ``experts_held`` computed
    dropless beside a shared expert of ``shared_dff``.  Pre-norm residual
    blocks, embedding and head untied, every block recomputed in the backward
    pass but for :data:`DELTA_KEEPS`.  With ``labels`` the chunked next-token loss."""

    vocab_size: int
    hidden_size: int
    layer_kinds: Tuple[str, ...]
    layer_dense: Tuple[bool, ...]
    dff: int
    num_heads: int
    kv_rank: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    rope_theta: float
    num_experts: int
    top_k: int
    experts_held: Tuple[int, ...]
    expert_dff: int
    shared_dff: int
    routed_scale: float
    groups: int
    groups_kept: int
    head_dim: Optional[int] = None  # a delta-rule head's channels: "kda" layers only
    head_gate: bool = True
    rotary_interleaved: bool = False
    conv_width: int = 4
    chunk: int = 64
    lower_bound: float = -5.0
    eps: float = 1e-6
    remat: bool = True
    head_chunks: int = 1
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None  # None: the flash kernels

    @nn.compact
    def __call__(self, input_ids, labels=None):
        from bluefog_tpu.kernels.flash_attention import flash_attention
        from bluefog_tpu.telemetry import registry as _telemetry

        kinds, is_dense = tuple(self.layer_kinds), tuple(self.layer_dense)
        if set(kinds) - {"kda", "mla"} or len(is_dense) != len(kinds):
            raise ValueError(f"layer kinds {sorted(set(kinds))}: 'kda' or 'mla', and "
                             f"{len(is_dense)} feed-forward kinds for {len(kinds)}")
        n_kda, n_mla = kinds.count("kda"), kinds.count("mla")
        if n_kda and not self.head_dim:
            raise ValueError(f"{n_kda} 'kda' layers and no head_dim")
        keeps = DELTA_KEEPS if self.remat else ()
        reg = _telemetry.get_registry()
        if reg.enabled:
            tokens, width = input_ids.size, jnp.dtype(self.dtype).itemsize
            inner = self.num_heads * (self.head_dim or 0)
            kept = {
                "attn_out": n_mla * tokens * self.num_heads * self.v_dim * width,
                "attn_lse": n_mla * tokens * self.num_heads * 4,
                "kda_out": n_kda * tokens * inner * width,
            }
            delta = (
                ("kda.layers", n_kda), ("kda.heads", self.num_heads),
                ("kda.head_dim", self.head_dim), ("kda.chunk", self.chunk),
                ("kda.lower_bound", self.lower_bound),
                ("kda.kernel_layers", n_kda * kda_conv_kernels_take(
                    input_ids.shape[1], inner, self.conv_width)),
                # whose chunks' stateless stage the kernels take: one path,
                # every shape (`kernels/kda.py`)
                ("kda.intra_kernel_layers", n_kda)) if n_kda else ()
            for name, value in delta + (
                    ("mla.layers", n_mla), ("mla.kv_rank", self.kv_rank),
                    ("mla.qk_dims", self.qk_nope + self.qk_rope),
                    ("mla.v_dims", self.v_dim),
                    ("mla.head_gate", int(self.head_gate)),
                    ("mla.rotary_interleaved", int(self.rotary_interleaved)),
                    ("attention.layers_global", n_mla),
                    ("attention.heads_global", self.num_heads),
                    ("moe.score", 1),  # 1: sigmoid scores (0: a softmax's)
                    ("moe.groups", self.groups), ("moe.groups_kept", self.groups_kept),
                    ("moe.shared_width", self.shared_dff),
                    ("moe.routed_scale", self.routed_scale),
                    ("moe.dense_layers", sum(is_dense)),
                    ("lm.tied_head", 0),
                    ("lm.remat_blocks", len(kinds) if self.remat else 0),
                    ("lm.remat_kept_names", len(keeps)),
                    ("lm.remat_kept_mb", sum(kept[k] for k in keeps) / 1e6)):
                reg.gauge(name).set(value)
        mixers = {
            "kda": partial(KDAMixer, self.num_heads, self.head_dim, self.conv_width,
                           self.chunk, self.lower_bound, self.eps, self.dtype,
                           name="mixer"),
            "mla": partial(LatentAttentionMixer, self.num_heads, self.kv_rank,
                           self.qk_nope, self.qk_rope, self.v_dim,
                           rotary_frequencies(self.qk_rope, self.rope_theta), self.eps,
                           self.dtype,
                           self.attention_fn or partial(flash_attention, causal=True),
                           self.head_gate, self.rotary_interleaved, name="mixer"),
        }
        ffns = {
            True: dense_ffn(self.dff, self.dtype),
            False: expert_ffn(
                self.num_experts, self.top_k, tuple(self.experts_held), self.expert_dff,
                self.shared_dff, self.routed_scale, self.dtype, score="sigmoid",
                bias=True, groups=self.groups, groups_kept=self.groups_kept),
        }
        embed = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(0.02), name="embed")
        h = jnp.take(embed.embedding, input_ids, axis=0).astype(self.dtype)
        block_cls = _remat(keeps) if self.remat else _HybridBlock
        for i, kind in enumerate(kinds):
            h = block_cls(mixers[kind], ffns[is_dense[i]], 1.0, self.eps, self.dtype,
                          name=f"layer_{i}")(h)
        h = RMSNorm(dtype=jnp.float32, eps=self.eps, name="final_norm")(h)
        return _head(h, embed,
                     lambda: _HeadKernel(self.vocab_size, name="head")(self.hidden_size),
                     labels, self.head_chunks)


class ShortConvMoELM(nn.Module):
    """LiquidAI's LFM2 expert decoder: ``layer_kinds`` names each layer's
    mixer, ``"conv"`` (:class:`ShortConvMixer`) or ``"attention"``
    (:class:`_AttentionMixer` with a norm a head on q and k and a half-split
    rotary over the whole head at ``rope_theta``, scores over
    ``sqrt(head_dim)``); ``layer_dense`` says where the feed-forward part is
    the dense gated MLP of ``dff`` and where this share of the expert layer:
    sigmoid scores over ``num_experts``, the choice on the score plus a bias
    that takes no gradient, weights the chosen scores over their sum plus
    ``route_eps``, times ``routed_scale``, the ``experts_held`` computed
    dropless, **no shared expert** unless ``shared_dff`` says one.  Pre-norm
    residual blocks, the head tied to the embedding, every block recomputed in
    the backward pass but for :data:`REMAT_KEEPS`.  With ``labels`` the
    chunked next-token loss.

    A class of its own beside :class:`HybridMambaLM` and
    :class:`DeltaLatentMoELM`: the first has no feed-forward kind a layer and
    requires the scan's sizes, the second requires latent attention's and
    unties its head; this one shares their block, their feed-forward parts,
    their head and Granite's attention mixer, and adds the layer table."""

    vocab_size: int
    hidden_size: int
    layer_kinds: Tuple[str, ...]
    layer_dense: Tuple[bool, ...]
    dff: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    num_experts: int
    top_k: int
    experts_held: Tuple[int, ...]
    expert_dff: int
    routed_scale: float = 1.0
    route_eps: float = 1e-6
    shared_dff: int = 0
    conv_width: int = 3
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

        kinds, is_dense = tuple(self.layer_kinds), tuple(self.layer_dense)
        if set(kinds) - {"conv", "attention"} or len(is_dense) != len(kinds):
            raise ValueError(f"layer kinds {sorted(set(kinds))}: 'conv' or 'attention', "
                             f"and {len(is_dense)} feed-forward kinds for {len(kinds)}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads {self.num_heads} not divisible by "
                             f"num_kv_heads {self.num_kv_heads}")
        n_conv, n_att = kinds.count("conv"), kinds.count("attention")
        keeps = REMAT_KEEPS if self.remat else ()
        reg = _telemetry.get_registry()
        if reg.enabled:
            tokens, width = input_ids.size, jnp.dtype(self.dtype).itemsize
            kept = {
                "attn_out": n_att * tokens * self.num_heads * self.head_dim * width,
                "attn_lse": n_att * tokens * self.num_heads * 4,
                "mixer_out": len(kinds) * tokens * self.hidden_size * width,
                "mlp_gate_up": sum(is_dense) * tokens * 2 * self.dff * width,
            }
            for name, value in (
                    ("mixer.layers_short_conv", n_conv),
                    ("mixer.layers_attention", n_att),
                    ("short_conv.taps", self.conv_width),
                    ("short_conv.kernel_layers", n_conv * short_conv_kernels_take(
                        input_ids.shape[1], self.hidden_size, self.conv_width)),
                    ("attention.qk_norm", 1),
                    ("attention.layers_global", n_att),
                    ("attention.heads_global", self.num_heads),
                    ("attention.kv_heads", self.num_kv_heads),
                    ("attention.scale", self.head_dim ** -0.5),
                    ("moe.score", 1),  # 1: sigmoid scores (0: a softmax's)
                    ("moe.groups", 1), ("moe.groups_kept", 1),
                    ("moe.shared_width", self.shared_dff),
                    ("moe.routed_scale", self.routed_scale),
                    ("moe.dense_layers", sum(is_dense)),
                    ("lm.tied_head", int(self.tie_embeddings)),
                    ("lm.remat_blocks", len(kinds) if self.remat else 0),
                    ("lm.remat_kept_names", len(keeps)),
                    ("lm.remat_kept_mb", sum(kept[k] for k in keeps) / 1e6)):
                reg.gauge(name).set(value)
        mixers = {
            "conv": partial(ShortConvMixer, self.conv_width, self.dtype, name="mixer"),
            "attention": partial(
                _AttentionMixer, self.num_heads, self.num_kv_heads, self.head_dim,
                self.head_dim ** -0.5, self.dtype,
                self.attention_fn or partial(flash_attention, causal=True),
                rotary_frequencies(self.head_dim, self.rope_theta), self.eps,
                name="mixer"),
        }
        ffns = {
            True: dense_ffn(self.dff, self.dtype),
            False: expert_ffn(
                self.num_experts, self.top_k, tuple(self.experts_held), self.expert_dff,
                self.shared_dff, self.routed_scale, self.dtype, score="sigmoid",
                bias=True, eps=self.route_eps),
        }
        return _decoder(self, input_ids, labels,
                        [(mixers[kind], ffns[dense]) for kind, dense in zip(kinds, is_dense)],
                        keeps)


class GatedDeltaMoELM(nn.Module):
    """Qwen3-Next's decoder: ``layer_kinds`` names each layer's mixer,
    ``"gdn"`` (:class:`GatedDeltaNetMixer`: ``gdn_heads`` value heads of
    ``gdn_value_dim`` on ``gdn_key_heads`` key heads of ``gdn_key_dim``) or
    ``"attention"`` (:class:`_AttentionMixer` with an output gate a channel, a
    zero-centred norm a head on q and k and a half-split rotary over the first
    ``rotary_dims`` channels of a head at ``rope_theta``, scores over
    ``sqrt(head_dim)``); every layer's feed-forward part is this share of the
    expert layer: a softmax over ``num_experts``, the ``top_k`` largest, their
    weights over their sum, the ``experts_held`` computed dropless beside a
    shared expert of ``shared_dff`` times its own sigmoid gate a token.  Every
    block norm and the final norm are zero-centred (``1 + w``).  Pre-norm
    residual blocks, embedding and head untied, every block recomputed in the
    backward pass but for :data:`GATED_DELTA_KEEPS`.  With ``labels`` the
    chunked next-token loss."""

    vocab_size: int
    hidden_size: int
    layer_kinds: Tuple[str, ...]
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dims: int
    rope_theta: float
    gdn_heads: int
    gdn_key_heads: int
    gdn_key_dim: int
    gdn_value_dim: int
    num_experts: int
    top_k: int
    experts_held: Tuple[int, ...]
    expert_dff: int
    shared_dff: int
    conv_width: int = 4
    chunk: int = 64
    eps: float = 1e-6
    tie_embeddings: bool = False
    remat: bool = True
    head_chunks: int = 1
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None  # None: the flash kernels

    @nn.compact
    def __call__(self, input_ids, labels=None):
        from bluefog_tpu.kernels.causal_conv import tiles
        from bluefog_tpu.kernels.flash_attention import flash_attention
        from bluefog_tpu.kernels.gdn import kernels_take
        from bluefog_tpu.telemetry import registry as _telemetry

        kinds = tuple(self.layer_kinds)
        if set(kinds) - {"gdn", "attention"}:
            raise ValueError(f"layer kinds {sorted(set(kinds))}: 'gdn' or 'attention'")
        if self.num_heads % self.num_kv_heads or self.gdn_heads % self.gdn_key_heads:
            raise ValueError(
                f"heads {self.num_heads} on {self.num_kv_heads} key-value heads, "
                f"{self.gdn_heads} value heads on {self.gdn_key_heads} key heads")
        n_gdn, n_att = kinds.count("gdn"), kinds.count("attention")
        keeps = GATED_DELTA_KEEPS if self.remat else ()
        reg = _telemetry.get_registry()
        if reg.enabled:
            tokens, width = input_ids.size, jnp.dtype(self.dtype).itemsize
            kept = {
                "attn_out": n_att * tokens * self.num_heads * self.head_dim * width,
                "attn_lse": n_att * tokens * self.num_heads * 4,
                "gdn_out": n_gdn * tokens * self.gdn_heads * self.gdn_value_dim * width,
                "mixer_out": len(kinds) * tokens * self.hidden_size * width,
            }
            for name, value in (
                    ("gdn.layers", n_gdn), ("gdn.heads", self.gdn_heads),
                    ("gdn.key_heads", self.gdn_key_heads), ("gdn.chunk", self.chunk),
                    # whose chunks' stateless stage the kernels take
                    ("gdn.kernel_layers", n_gdn * kernels_take(
                        self.gdn_key_dim, self.gdn_value_dim, self.gdn_heads,
                        self.gdn_heads // self.gdn_key_heads)),
                    ("gdn.conv_kernel_layers", n_gdn * tiles(
                        input_ids.shape[1], 2 * self.gdn_key_heads * self.gdn_key_dim
                        + self.gdn_heads * self.gdn_value_dim, self.conv_width)),
                    ("attention.gate", 1), ("attention.qk_norm", 1),
                    ("attention.rotary_dims", self.rotary_dims),
                    ("attention.layers_global", n_att),
                    ("attention.heads_global", self.num_heads),
                    ("attention.kv_heads", self.num_kv_heads),
                    ("attention.scale", self.head_dim ** -0.5),
                    ("moe.score", 0),  # 0: a softmax's scores (1: sigmoid)
                    ("moe.groups", 1), ("moe.groups_kept", 1),
                    ("moe.shared_width", self.shared_dff), ("moe.shared_gate", 1),
                    ("moe.routed_scale", 1.0), ("moe.dense_layers", 0),
                    ("lm.tied_head", int(self.tie_embeddings)),
                    ("lm.remat_blocks", len(kinds) if self.remat else 0),
                    ("lm.remat_kept_names", len(keeps)),
                    ("lm.remat_kept_mb", sum(kept[k] for k in keeps) / 1e6)):
                reg.gauge(name).set(value)
        mixers = {
            "gdn": partial(GatedDeltaNetMixer, self.gdn_heads, self.gdn_key_heads,
                           self.gdn_key_dim, self.gdn_value_dim, self.conv_width,
                           self.chunk, self.eps, self.dtype, name="mixer"),
            "attention": partial(
                _AttentionMixer, self.num_heads, self.num_kv_heads, self.head_dim,
                self.head_dim ** -0.5, self.dtype,
                self.attention_fn or partial(flash_attention, causal=True),
                rotary_frequencies(self.rotary_dims, self.rope_theta), self.eps,
                gate=True, zero_centered=True, name="mixer"),
        }
        ffn = expert_ffn(self.num_experts, self.top_k, tuple(self.experts_held),
                         self.expert_dff, self.shared_dff, 1.0, self.dtype,
                         shared_gate=True)
        return _decoder(self, input_ids, labels, [(mixers[kind], ffn) for kind in kinds],
                        keeps, zero_centered=True)

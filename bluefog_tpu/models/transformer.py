"""Transformer model family for the BASELINE configs: a BERT-style encoder
(config #3: async push-sum fine-tune) and a Llama-style decoder LM
(config #5: decentralized pretraining).

The reference has no attention code at all (SURVEY.md §2.3/§5.7) — these
models exist because the rebuild's tracked configs name BERT-base and
Llama-3-8B as gossip-training workloads; the architectures are the standard
public ones, written TPU-first: bfloat16 matmul compute with float32
accumulation/norms, static shapes, and optional *ring-attention sequence
parallelism* (``bluefog_tpu.parallel.ring_attention``) so long contexts
shard across the mesh — composing with the gossip data parallelism.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

__all__ = [
    "BertEncoder",
    "LlamaLM",
    "MixedAttentionMoELM",
    "Rotary",
    "rotary_frequencies",
    "dense_attention",
    "chunked_softmax_cross_entropy",
]


def dense_attention(q, k, v, *, causal: bool, dtype=jnp.float32):
    """Plain softmax attention, [B, T, H, D] layout; fp32 softmax."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# --------------------------------------------------------------------------
# BERT-style encoder
# --------------------------------------------------------------------------


class _EncoderBlock(nn.Module):
    num_heads: int
    dff: int
    dtype: Any

    @nn.compact
    def __call__(self, x, mask):
        d = x.shape[-1]
        h = nn.LayerNorm(dtype=jnp.float32)(x)
        qkv = nn.DenseGeneral(
            (3, self.num_heads, d // self.num_heads), dtype=self.dtype
        )(h)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        scale = 1.0 / math.sqrt(q.shape[-1])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        if mask is not None:
            scores = jnp.where(mask[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        att = att.reshape(att.shape[:2] + (d,))
        x = x + nn.Dense(d, dtype=self.dtype)(att)
        h = nn.LayerNorm(dtype=jnp.float32)(x)
        h = nn.Dense(self.dff, dtype=self.dtype)(h)
        h = nn.gelu(h)
        x = x + nn.Dense(d, dtype=self.dtype)(h)
        return x


class BertEncoder(nn.Module):
    """BERT-style encoder with a classification head (the push-sum
    fine-tuning workload of BASELINE config #3)."""

    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    dff: int = 3072
    max_len: int = 512
    num_classes: int = 2
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, attention_mask=None):
        B, T = input_ids.shape
        tok = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype)(input_ids)
        pos = self.param(
            "pos_embedding",
            nn.initializers.normal(0.02),
            (self.max_len, self.hidden_size),
        )
        x = tok + pos[None, :T].astype(self.dtype)
        for _ in range(self.num_layers):
            x = _EncoderBlock(self.num_heads, self.dff, self.dtype)(x, attention_mask)
        x = nn.LayerNorm(dtype=jnp.float32)(x)
        pooled = jnp.tanh(nn.Dense(self.hidden_size, dtype=jnp.float32)(x[:, 0]))
        return nn.Dense(self.num_classes, dtype=jnp.float32)(pooled)


# --------------------------------------------------------------------------
# Llama-style decoder LM
# --------------------------------------------------------------------------


class Rotary(NamedTuple):
    """A rotary's inverse frequencies (twice as many dimensions of a head are
    rotated, the rest pass untouched) and the factor on its cos and sin."""

    inv_freq: Tuple[float, ...]
    factor: float = 1.0


def rotary_frequencies(dims: int, base: float, *, factor: float = 1.0,
                       original_max: int = 0, beta_fast: float = 32.0,
                       beta_slow: float = 1.0) -> Rotary:
    """The rotary over ``dims`` dimensions of a head at ``base``: ``dims / 2``
    inverse frequencies ``f_i = base ** (-2 i / dims)``.  With ``factor`` > 1
    they are YaRN's (Peng et al., arXiv:2309.00071): ``f_i`` kept where
    dimension ``i`` turns ``beta_fast`` times or more in ``original_max``
    positions, ``f_i / factor`` where it turns ``beta_slow`` times or fewer,
    blended linearly in ``i`` between the two (the ramp's ends rounded
    outwards to whole dimensions), and cos and sin are multiplied by ``0.1 ln
    factor + 1``.  Worked out in float64 from the numbers, once."""
    i = np.arange(dims // 2, dtype=np.float64)
    freq = base ** (-2.0 * i / dims)
    if factor == 1.0:
        return Rotary(tuple(freq.tolist()))

    def turns(n):  # the dimension that turns n times in original_max positions
        return dims * math.log(original_max / (n * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dims - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    freq = freq * (1.0 - ramp) + freq / factor * ramp
    return Rotary(tuple(freq.tolist()), 0.1 * math.log(factor) + 1.0)


@jax.named_scope("attention_rotary")
def _rotary(x, positions, base=10000.0, rotary: Optional[Rotary] = None,
            interleaved: bool = False):
    """Rotary position embedding (half-split convention); x: [B, T, H, D],
    positions: [T].  With ``rotary`` its frequencies and factor take the
    place of ``base``'s: the first ``2 len(inv_freq)`` dimensions are rotated,
    half-split among themselves, and the others are returned as they came.

    ``interleaved``: the checkpoint pairs channel ``2i`` with ``2i + 1``
    (DeepSeek-V3's ``rope_interleave``).  The rotated channels are put evens
    first, odds after, once, and rotated half-split, and they **stay in that
    order** (as the published implementation leaves them): a query and its key
    are both handed through here, and their product sums over the channels in
    whatever order both have.  Rotating in place would shuffle the lanes a
    second time to put the pairs back, on the way in and on the cotangent's
    way out, for the same scores."""
    if rotary is None:
        half = x.shape[-1] // 2
        freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    else:
        half = len(rotary.inv_freq)
        freqs = jnp.asarray(rotary.inv_freq, jnp.float32)
    if interleaved:
        rotated, rest = x[..., :2 * half], x[..., 2 * half:]
        evens_first = jnp.swapaxes(rotated.reshape(x.shape[:-1] + (half, 2)), -1, -2)
        x = jnp.concatenate([evens_first.reshape(rotated.shape), rest], axis=-1)
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [T, half]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if rotary is not None and rotary.factor != 1.0:
        cos, sin = cos * rotary.factor, sin * rotary.factor
    x1, x2 = x[..., :half], x[..., half:2 * half]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if 2 * half < x.shape[-1]:
        parts.append(x[..., 2 * half:])
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


class RMSNorm(nn.Module):
    """``x rsqrt(mean(x^2) + eps) scale``; with ``zero_centered`` the leaf is
    the scale's distance from 1, ``x rsqrt(..) (1 + scale)`` from zeros
    (Qwen3-Next's norms: weight decay then pulls the scale to 1, not to 0)."""

    dtype: Any = jnp.float32
    eps: float = 1e-6
    zero_centered: bool = False

    @nn.compact
    def __call__(self, x):
        if self.zero_centered:
            scale = 1.0 + self.param("scale", nn.initializers.zeros_init(), (x.shape[-1],))
        else:
            scale = self.param("scale", nn.initializers.ones_init(), (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps) * scale).astype(
            self.dtype
        )


class _DecoderBlock(nn.Module):
    num_heads: int
    dff: int
    dtype: Any
    attention_fn: Optional[Callable] = None  # (q, k, v) -> out, e.g. ring attn
    num_kv_heads: Optional[int] = None  # grouped-query attention (GQA)
    head_dim: Optional[int] = None  # None: hidden // heads

    @nn.compact
    def __call__(self, x, positions):
        d = x.shape[-1]
        hd = self.head_dim or d // self.num_heads
        kvh = self.num_kv_heads or self.num_heads
        if self.num_heads % kvh:
            raise ValueError(
                f"num_heads {self.num_heads} not divisible by "
                f"num_kv_heads {kvh}")
        h = RMSNorm(dtype=self.dtype)(x)
        q = nn.DenseGeneral((self.num_heads, hd), use_bias=False, dtype=self.dtype)(h)
        # GQA (Ainslie et al. 2023; Llama-3's 8-kv-head layout): k/v
        # project to kvh heads (the parameter/KV-cache saving), then
        # repeat up to num_heads for the attention math — correct for
        # every attention_fn (flash/ring/dense) at the cost of not
        # exploiting the smaller kv in the kernel's memory traffic
        k = nn.DenseGeneral((kvh, hd), use_bias=False, dtype=self.dtype)(h)
        v = nn.DenseGeneral((kvh, hd), use_bias=False, dtype=self.dtype)(h)
        q = _rotary(q, positions)
        k = _rotary(k, positions)
        if kvh != self.num_heads:
            rep = self.num_heads // kvh
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if self.attention_fn is not None:
            att = self.attention_fn(q, k, v)
        else:
            att = dense_attention(q, k, v, causal=True, dtype=self.dtype)
        # named for remat_policy="attn" (see _remat_block); the flash
        # kernels name their own output and logsumexp, an `attention_fn`
        # that is not theirs has this name alone
        att = checkpoint_name(att, "attn_out")
        att = att.reshape(att.shape[:2] + (self.num_heads * hd,))
        x = x + nn.Dense(d, use_bias=False, dtype=self.dtype)(att)
        h = RMSNorm(dtype=self.dtype)(x)
        gate = nn.Dense(self.dff, use_bias=False, dtype=self.dtype)(h)
        up = nn.Dense(self.dff, use_bias=False, dtype=self.dtype)(h)
        x = x + nn.Dense(d, use_bias=False, dtype=self.dtype)(nn.silu(gate) * up)
        return x


def _remat_block(policy_name):
    """``nn.remat`` over the decoder block with a named checkpoint policy.

    ``None``/"" = recompute everything (minimum memory, +~2N flops/token);
    "dots" = ``jax.checkpoint_policies.checkpoint_dots`` (save matmul
    outputs: recompute shrinks to elementwise/norm passes at the cost of
    O(layers·B·T·dff) saved activations); "dots_no_batch" =
    ``checkpoint_dots_with_no_batch_dims``, the PaLM-style middle ground;
    "attn" = save only the flash kernels' named output and logsumexp
    (cheapest; see the dict comment).
    """
    if not policy_name:
        return nn.remat(_DecoderBlock)
    policies = {
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch":
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        # save ONLY what the flash forward hands its backward pass, named
        # in `kernels.flash_attention._flash_core_fwd` (~layers*B*T*d bf16
        # and a float32 a head a token).  Both, because with the output
        # alone saved the forward kernel runs again for the logsumexp: four
        # kernel calls a layer in the gradient's jaxpr, three with both
        # (tests/test_granite_hybrid.py; on the chip: PERF.md, PR 40, the
        # Granite cell, which keeps them under its own policy).  Speed
        # under this preset: not measured by any cell.
        "attn": jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse"),
    }
    return nn.remat(_DecoderBlock, policy=policies[policy_name])


class _ScannedDecoderBlock(nn.Module):
    """nn.scan body adapter: carry = activations, no per-step outputs."""

    num_heads: int
    dff: int
    dtype: Any
    attention_fn: Optional[Callable] = None
    remat: bool = False
    remat_policy: Optional[str] = None
    num_kv_heads: Optional[int] = None
    act_constraint: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, positions):
        cls = (_remat_block(self.remat_policy) if self.remat
               else _DecoderBlock)
        x = cls(self.num_heads, self.dff, self.dtype, self.attention_fn,
                self.num_kv_heads)(x, positions)
        if self.act_constraint is not None:
            x = self.act_constraint(x)
        return x, None


@jax.custom_vjp
def _bf16_matmul_f32_acc(x, kernel):
    """bf16-input matmul with f32 accumulation IN BOTH DIRECTIONS.

    Without the custom VJP, jax differentiates the forward's
    ``dot(bf16, bf16, preferred=f32)`` into backward dots that mix the
    f32 cotangent with the bf16 operands — dtype promotion turns those
    back into f32 matmuls AND re-casts the operands per use (measured:
    a naive bf16 head was 6% SLOWER end to end than the f32 head at
    134M).  Here the cotangent is rounded to bf16 (the standard
    mixed-precision training contract: every matmul operand is bf16,
    every accumulator f32), so fwd, dx, and dW all run 1-pass at full
    MXU rate, with dW emerging f32 for the optimizer.

    Builder reading on the v5e (2026-07, not re-measured): even with this
    VJP the bf16 head is NEUTRAL at 1B and −3% at 134M vs the f32 head — XLA's
    default-precision f32 matmul already sustains 153–166 TF/s (~80% of
    the bf16 rate, a script since deleted), so the rate gain cannot pay
    for the per-chunk operand casts.  f32 stays the default; the option
    exists for hardware where true-f32 matmul is actually slow.
    """
    y, _ = _bf16_matmul_f32_acc_fwd(x, kernel)
    return y


def _bf16_matmul_f32_acc_fwd(x, kernel):
    xb = x.astype(jnp.bfloat16)
    kb = kernel.astype(jnp.bfloat16)
    y = jax.lax.dot_general(
        xb, kb, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y, (xb, kb)


def _matmul_f32_acc_bwd(x, kernel, g):
    """``(dx, dW)`` of ``x @ kernel`` under the cotangent ``g``, the three in
    one dtype, both products accumulated and returned in f32."""
    nbatch = g.ndim - 1
    # dx[..., d] = g[..., v] @ kernel[d, v]^T
    dx = jax.lax.dot_general(
        g, kernel, (((nbatch,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # dW[d, v] = sum over batch dims of x[..., d] * g[..., v]
    batch_axes = tuple(range(nbatch))
    dw = jax.lax.dot_general(
        x, g, ((batch_axes, batch_axes), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dx, dw


def _bf16_matmul_f32_acc_bwd(res, g):
    return _matmul_f32_acc_bwd(*res, g.astype(jnp.bfloat16))


_bf16_matmul_f32_acc.defvjp(_bf16_matmul_f32_acc_fwd, _bf16_matmul_f32_acc_bwd)


def _head_matmul(x, kernel, dtype):
    """Logits matmul with f32 ACCUMULATION/output regardless of ``dtype``.

    ``dtype=float32`` reproduces the ``nn.Dense(dtype=f32)`` head (XLA
    lowers default-precision f32 matmul onto the MXU at 153–166 TF/s on
    the v5e — near the bf16 rate).  ``dtype=bfloat16`` rounds matmul
    operands — including the backward cotangent, via the custom VJP
    above — to bf16; accumulators and logits stay f32, so the
    downstream logsumexp/CE numerics are intact.  See the VJP docstring
    for the measured (neutral-to-negative on v5e) verdict.
    """
    if dtype == jnp.bfloat16:
        return _bf16_matmul_f32_acc(x, kernel)
    return jax.lax.dot_general(
        x.astype(dtype), kernel.astype(dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def chunked_softmax_cross_entropy(hidden, kernel, labels, num_chunks,
                                  dtype=jnp.float32, onehot_targets=False,
                                  kernel_constraint=None):
    """Next-token cross-entropy WITHOUT materializing the full logits.

    The LM-head logits ``[B, T, vocab]`` in f32 are the single biggest
    activation of a small-vocab 1B model (1.05 GB at B=4/T=2048/V=32k;
    its backward cotangent doubles that) and are flatly infeasible at
    Llama-3-8B's 128k vocab.  This computes the shifted-LM loss
    ``mean(CE(logits[:, :-1], labels[:, 1:]))`` as a ``lax.scan`` over
    ``num_chunks`` sequence chunks that holds one chunk's
    ``[B, T/num_chunks, vocab]`` logits at a time: peak logits memory drops
    by ``num_chunks``×.

    The loss is a mean of independent terms, so a chunk's gradient is known
    the moment its logits are: ``(softmax - onehot) * w / count``.  Under
    differentiation (a ``jax.custom_vjp``, reverse mode only) that same loop
    takes ``dx = dlogits W^T`` and ``dW += x^T dlogits`` on the logits while
    they are there, and the backward pass only scales the two kept arrays
    (``dx`` in ``hidden``'s dtype, ``dW`` in f32) by the loss's cotangent:
    three head matmuls a step and no loop backward.  Called without a
    gradient it is the forward loop alone, one matmul.

    Equivalent to the full-logits loss to f32 roundoff
    (`tests/test_training.py::test_llama_head_chunks_matches_full`,
    `::test_chunked_loss_takes_its_gradient_in_the_forward_loop`).

    Args:
      hidden: ``[B, T, d]`` final hidden states (any float dtype; logits
        are computed in f32, matching the full-logits head).
      kernel: ``[d, vocab]`` f32 head weight.
      labels: ``[B, T]`` int token ids; position t is scored against
        ``labels[:, t+1]``, the final position is masked out.
      num_chunks: number of sequence chunks; must divide T.
      onehot_targets: extract the target logit as ``sum(logits * onehot(y))``
        instead of ``take_along_axis`` — numerically identical, but a
        reduction GSPMD partitions cleanly over a VOCAB-SHARDED head
        kernel, where the gather forces it to replicate (the 8B FSDP
        compile measured full-batch f32 activation gathers from exactly
        this; see ``LlamaLM.spmd_vocab``).
      kernel_constraint: applied to ``kernel`` INSIDE the scan body, once
        per chunk, and to the ``dW`` accumulator the loop carries.  Under
        FSDP this must be the SHARDING-ONLY per-read marker
        (``fsdp_param_io_constraint(...).sharding_only`` — no grad-dtype
        cast: the accumulator sums in f32 and is rounded once, outside)
        and must sit inside the body: with the marker only outside, the
        accumulator is laid out replicated — measured as the largest single
        temps item of the 8B compile (f32[4096,128k] ≈ 2.1 GB per buffer).
    """
    B, T, _ = hidden.shape
    if T % num_chunks:
        raise ValueError(f"num_chunks {num_chunks} must divide T {T}")
    tc = T // num_chunks
    # the last token predicts nothing.  The loss itself divides by the weights'
    # sum, the same number: XLA makes a division by a literal a product, and
    # the value without a gradient is the checkpointed loop's to the bit
    count = B * (T - 1)
    pin = kernel_constraint or (lambda a: a)
    # under shard_map the kept dx and dW vary over the axes of both operands:
    # widen each to the other's out here, where autodiff sums back over them
    vma = jax.typeof(hidden).vma | jax.typeof(kernel).vma
    hidden, kernel = (
        jax.lax.pcast(a, tuple(sorted(vma - jax.typeof(a).vma)), to="varying")
        for a in (hidden, kernel))

    def split(hidden, labels):
        # shift the targets left so every chunk scores positions uniformly;
        # the pad at T-1 carries weight 0
        y = jnp.concatenate([labels[:, 1:], labels[:, :1]], axis=1)
        w = jnp.concatenate(
            [jnp.ones((B, T - 1), jnp.float32), jnp.zeros((B, 1), jnp.float32)],
            axis=1,
        )
        return tuple(
            jnp.moveaxis(a.reshape(B, num_chunks, tc, *a.shape[2:]), 1, 0)
            for a in (hidden, y, w))

    def chunk_loss(k, xc, yc, wc):
        logits = _head_matmul(xc, k, dtype)  # [B, tc, V] — the peak
        lse = jax.nn.logsumexp(logits, axis=-1)
        if onehot_targets:
            tgt = jnp.sum(
                logits * jax.nn.one_hot(yc, logits.shape[-1],
                                        dtype=logits.dtype), axis=-1)
        else:
            tgt = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return logits, lse, (((lse - tgt) * wc).sum(), wc.sum())

    @jax.custom_vjp
    def loss(hidden, kernel, labels):
        def body(carry, xyw):
            return carry, chunk_loss(pin(kernel), *xyw)[-1]

        with jax.named_scope("lm_head_loss"):
            # per-chunk outputs instead of a scalar carry: under shard_map a
            # plain-zeros carry init would mismatch the body's varying-axes
            # type (jax vma rules); stacked outputs inherit it automatically
            _, (tots, cnts) = jax.lax.scan(body, (), split(hidden, labels))
            return tots.sum() / cnts.sum()

    def loss_fwd(hidden, kernel, labels):
        def body(dw, xyw):
            xc, yc, wc = xyw
            # the chunk sliced on its own before the products read it: as a
            # dynamic slice of the stack fused into the dW product, that product
            # read 12.6 ms a step for the 8.6 of the same product behind a plain
            # chunk (v5e, SmallThinker's shapes, PERF.md section 6, PR 51)
            xc = jax.lax.optimization_barrier(xc)
            k = pin(kernel)
            logits, lse, sums = chunk_loss(k, xc, yc, wc)
            onehot = jax.nn.one_hot(yc, logits.shape[-1], dtype=logits.dtype)
            dlogits = ((jnp.exp(logits - lse[..., None]) - onehot)
                       * (wc / count)[..., None])
            # the operand types of _head_matmul's own VJP
            dxc, dwc = _matmul_f32_acc_bwd(
                xc.astype(dtype), k.astype(dtype), dlogits.astype(dtype))
            return pin(dw + dwc), (dxc.astype(xc.dtype), sums)

        with jax.named_scope("lm_head_loss"):
            # zeros of the kernel's own type: its varying axes under shard_map
            dw, (dxs, (tots, cnts)) = jax.lax.scan(
                body, pin(jnp.zeros_like(kernel, jnp.float32)),
                split(hidden, labels))
            dx = jnp.moveaxis(dxs, 0, 1).reshape(hidden.shape)
            return tots.sum() / cnts.sum(), (dx, dw.astype(kernel.dtype))

    def loss_bwd(kept, g):
        dx, dw = kept
        with jax.named_scope("lm_head_loss"):
            return (g * dx).astype(dx.dtype), (g * dw).astype(dw.dtype), None

    loss.defvjp(loss_fwd, loss_bwd)
    return loss(hidden, kernel, labels)


class _HeadKernel(nn.Module):
    """Owns the LM-head weight at the SAME pytree path (``Dense_0/kernel``,
    same lecun-normal init) as the ``nn.Dense`` head it replaces, so
    checkpoints and equivalence tests are unaffected — but exposes the raw
    kernel so the chunked-loss path can matmul per chunk."""

    vocab_size: int

    @nn.compact
    def __call__(self, d):
        return self.param(
            "kernel", nn.initializers.lecun_normal(), (d, self.vocab_size),
            jnp.float32,
        )


class LlamaLM(nn.Module):
    """Llama-style decoder-only LM: RMSNorm, rotary, SwiGLU, no biases.

    ``attention_fn`` plugs in sequence-parallel ring attention; when set,
    ``positions`` must be the device's global positions (the caller knows
    its sequence shard offset).
    """

    vocab_size: int = 32000
    hidden_size: int = 512
    num_layers: int = 4
    num_heads: int = 8
    dff: int = 1376
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    remat: bool = False  # rematerialize each block: activations O(layers·B·T·d) -> O(B·T·d)
    remat_policy: Optional[str] = None  # _remat_block: None|"dots"|"dots_no_batch"|"attn"
    scan_layers: bool = False  # lax.scan over stacked layers: O(1)-size HLO
    num_kv_heads: Optional[int] = None  # GQA: kv heads < query heads
    # >1: chunked LM loss (one loop that takes the head's gradient with the
    # loss), never materializes full logits
    head_chunks: int = 0
    head_dtype: Any = jnp.float32  # bf16: 1-pass MXU head, f32 accumulation
    # vocab-dim-sharded deployment mode (FSDP/ZeRO with the embedding and
    # head kernels sharded over their vocab axis): route every vocab-indexed
    # op through matmuls/reductions — one-hot-matmul embedding and one-hot
    # target extraction — instead of take/take_along_axis gathers.  GSPMD
    # partitions dots and reductions over a sharded vocab axis cleanly; the
    # gather lowering replicates the INDICES' batch axis instead, which the
    # 8B FSDP compile measured as full-batch f32 activations on every
    # device (~2.5 GB/layer of temps) and zero reduce-scatters.  Same
    # params, same math (tests/test_training.py::test_llama_spmd_vocab_
    # matches_default); the one-hot matmul is also the MXU-native lookup.
    # BEHAVIORAL DIFFERENCE on out-of-range token ids (only): gather-based
    # ``take``/``take_along_axis`` CLAMP the id to the vocab edge, so a
    # corrupt id silently embeds as (and extracts the logit of) the last
    # vocab entry; ``one_hot`` ZEROES — an out-of-range id embeds as the
    # zero vector and contributes -logsumexp (no target logit) to the
    # loss.  Neither mode validates ids; both are garbage-in, but the
    # garbage differs, so a dataset bug can shift metrics when toggling
    # this flag.  In-range ids are bit-identical between modes.
    spmd_vocab: bool = False
    # applied to the [B, T, d] hidden states after the embedding and after
    # every decoder block — the standard GSPMD FSDP recipe pins the
    # ACTIVATION layout (batch-sharded) at block boundaries, because with
    # weights sharded on their big dims, unconstrained propagation resolves
    # each x@W toward the locally-cheaper tensor-parallel layout (gather
    # the small activations, keep the big weight sharded) and the whole
    # model silently goes batch-replicated (measured on the 8B FSDP
    # compile: ~2.5 GB/layer of replicated f32 temps, zero
    # reduce-scatters).  See parallel/zero.py:fsdp_act_constraint.
    act_constraint: Optional[Callable] = None
    # applied to the one-hot embedding operand (``spmd_vocab`` path).  An
    # FSDP caller pins it VOCAB-sharded (parallel/zero.py:
    # fsdp_onehot_constraint) so the embedding dot partitions on its
    # contracting dim — partial [B,T,d] products + one small reduce —
    # instead of GSPMD's default resolution, which all-gathers the f32
    # table (measured 2.1 GB/device on the 8B compile).
    onehot_constraint: Optional[Callable] = None
    # applied (via nn.map_variables in the scan path) to each layer's
    # PARAM SLICES inside the scan body.  An FSDP caller passes
    # "replicate over the shard axis" — an explicit gather marker on a
    # loop-VARIANT value, which XLA cannot hoist out of the while loop.
    # Without it GSPMD gathers the whole stacked leaf outside the loop
    # (tests/test_hlo_contract.py::test_scan_stacked_leaves_gather_whole
    # pinned this; at 8B that is ~11 GB of stacked bf16 FFN gathers).
    weight_constraint: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, labels=None):
        B, T = input_ids.shape
        if positions is None:
            positions = jnp.arange(T)
        embed = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype)
        if self.spmd_vocab:
            table = embed.embedding
            if self.weight_constraint is not None:
                table = self.weight_constraint(table)
            oh = jax.nn.one_hot(input_ids, self.vocab_size, dtype=self.dtype)
            if self.onehot_constraint is not None:
                oh = self.onehot_constraint(oh)
            x = oh @ table.astype(self.dtype)
        else:
            x = embed(input_ids)
        if self.act_constraint is not None:
            x = self.act_constraint(x)
        if self.scan_layers:
            # params gain a leading [num_layers] axis; the compiled program
            # contains ONE block body instead of num_layers copies — at 1B+
            # scale the unrolled HLO overwhelms compile services
            body_cls = _ScannedDecoderBlock
            if self.weight_constraint is not None:
                wc = self.weight_constraint
                body_cls = nn.map_variables(
                    _ScannedDecoderBlock, "params",
                    trans_in_fn=partial(jax.tree_util.tree_map, wc),
                    trans_out_fn=lambda vs: vs,
                    mutable=True, init=True,
                )
            scan = nn.scan(
                body_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=self.num_layers,
                in_axes=nn.broadcast,
            )
            x, _ = scan(
                self.num_heads, self.dff, self.dtype, self.attention_fn,
                self.remat, self.remat_policy, self.num_kv_heads,
                self.act_constraint,
            )(x, positions)
        else:
            # remat selection for the scan path lives in _ScannedDecoderBlock
            block_cls = (_remat_block(self.remat_policy) if self.remat
                         else _DecoderBlock)
            if self.weight_constraint is not None:
                block_cls = nn.map_variables(
                    block_cls, "params",
                    trans_in_fn=partial(jax.tree_util.tree_map,
                                        self.weight_constraint),
                    trans_out_fn=lambda vs: vs,
                    mutable=True, init=True,
                )
            for _ in range(self.num_layers):
                x = block_cls(
                    self.num_heads, self.dff, self.dtype, self.attention_fn,
                    self.num_kv_heads,
                )(x, positions)
                if self.act_constraint is not None:
                    x = self.act_constraint(x)
        x = RMSNorm(dtype=jnp.float32)(x)
        kernel = _HeadKernel(self.vocab_size, name="Dense_0")(self.hidden_size)
        if self.weight_constraint is not None:
            # full marker once, OUTSIDE any chunk loop: grad_dtype rounding
            # must be one-shot on the accumulated head-kernel cotangent
            kernel = self.weight_constraint(kernel)
        if labels is None:
            return _head_matmul(x, kernel, self.head_dtype)  # f32 logits
        if self.head_chunks > 1:
            # sharding-only pin per chunk and on the loop's dW accumulator
            # (keeps it sharded); the cast already happened above
            wc = self.weight_constraint
            if wc is not None and not hasattr(wc, "sharding_only"):
                raise ValueError(
                    "head_chunks > 1 with a custom weight_constraint "
                    "requires a .sharding_only attribute (the per-chunk "
                    "pin without the grad-dtype cast, cf. parallel/zero."
                    "fsdp_param_io_constraint): passing the full "
                    "constraint would re-round the head-kernel cotangent "
                    "once per chunk instead of once on the accumulated "
                    "gradient"
                )
            return chunked_softmax_cross_entropy(
                x, kernel, labels, self.head_chunks, dtype=self.head_dtype,
                onehot_targets=self.spmd_vocab,
                kernel_constraint=getattr(wc, "sharding_only", wc),
            )
        logits = _head_matmul(x, kernel, self.head_dtype)
        lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
        if self.spmd_vocab:
            tgt = jnp.sum(
                logits[:, :-1] * jax.nn.one_hot(
                    labels[:, 1:], self.vocab_size, dtype=logits.dtype),
                axis=-1)
        else:
            tgt = jnp.take_along_axis(
                logits[:, :-1], labels[:, 1:, None], axis=-1
            )[..., 0]
        return (lse - tgt).mean()


# --------------------------------------------------------------------------
# Decoder with mixed attention (window + rotary / global without position)
# and a top-k expert layer that is told which experts it holds
# --------------------------------------------------------------------------


class _MixedBlock(nn.Module):
    """One layer: the router reads the layer's input, before the attention;
    grouped-query attention, a causal band with rotary (``window`` set) or
    causal over the whole sequence with no position signal at all
    (``window=None``); then this share's part of the top-k expert layer."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]
    rope_base: float
    num_experts: int
    top_k: int
    experts_held: Tuple[int, ...]
    expert_dff: int
    dtype: Any
    attention_fn: Callable  # (q, k, v, window=) -> out, causal

    @nn.compact
    def __call__(self, x, positions):
        # imported here, not with the module: the encoder and the dense
        # decoder above need neither, and Pallas costs their users 1.3 s
        from bluefog_tpu.parallel.expert import held_topk_experts, route_topk

        B, T, d = x.shape
        hd, H, kvh = self.head_dim, self.num_heads, self.num_kv_heads
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (d, self.num_experts), jnp.float32)
        experts, weights = route_topk(x.reshape(B * T, d), router, self.top_k)

        h = RMSNorm(dtype=self.dtype, name="attn_norm")(x)
        q = nn.DenseGeneral((H, hd), use_bias=False, dtype=self.dtype, name="q")(h)
        k = nn.DenseGeneral((kvh, hd), use_bias=False, dtype=self.dtype, name="k")(h)
        v = nn.DenseGeneral((kvh, hd), use_bias=False, dtype=self.dtype, name="v")(h)
        if self.window is not None:
            q = _rotary(q, positions, self.rope_base)
            k = _rotary(k, positions, self.rope_base)
        k = jnp.repeat(k, H // kvh, axis=2)
        v = jnp.repeat(v, H // kvh, axis=2)
        scope = "attention_global" if self.window is None else "attention_window"
        with jax.named_scope(scope):
            att = self.attention_fn(q, k, v, window=self.window)
        att = att.reshape(B, T, H * hd)
        x = x + nn.Dense(d, use_bias=False, dtype=self.dtype, name="o")(att)

        m = RMSNorm(dtype=self.dtype, name="ffn_norm")(x)
        n_held, f = len(self.experts_held), self.expert_dff
        stacks = {
            "wg": self.param("wg", init, (n_held, d, f), jnp.float32),
            "wu": self.param("wu", init, (n_held, d, f), jnp.float32),
            "wd": self.param("wd", init, (n_held, f, d), jnp.float32),
        }
        y = held_topk_experts(m.reshape(B * T, d), experts, weights, stacks,
                              self.experts_held, self.num_experts)
        return x + y.reshape(B, T, d)


class _GatedMLP(nn.Module):
    """``(silu(m wg) * (m wu)) wd`` as two products: gate and up in one."""

    dff: int
    dtype: Any

    @nn.compact
    def __call__(self, m):
        d, init = m.shape[-1], nn.initializers.normal(0.02)
        wg = self.param("wg", init, (d, self.dff), jnp.float32)
        wu = self.param("wu", init, (d, self.dff), jnp.float32)
        wd = self.param("wd", init, (self.dff, d), jnp.float32)
        gu = checkpoint_name(
            m @ jnp.concatenate([wg, wu], axis=1).astype(self.dtype), "mlp_gate_up")
        h = jax.nn.silu(gu[..., :self.dff]) * gu[..., self.dff:]
        return h @ wd.astype(self.dtype)


def expert_feed_forward(block, m, num_experts, top_k, experts_held, expert_dff,
                        shared_dff, routed_scale, dtype, **routing):
    """This share's part of a top-k expert layer beside a shared expert that
    every share computes, on the normed stream ``m [B, T, d]``; the leaves
    (``router``, the held experts' ``wg`` / ``wu`` / ``wd`` stacks, ``shared``)
    are ``block``'s, the module whose ``__call__`` this runs in.  With
    ``shared_dff`` 0 the model has no shared expert: no ``shared`` leaf, no
    ``moe_shared`` scope.  ``routing``:
    :func:`bluefog_tpu.parallel.expert.route_topk`'s keywords; with ``bias``
    true the choice's bias is the leaf ``router_bias``; with ``shared_gate``
    true the shared expert's output is times ``sigmoid(m W_sg)`` a token, the
    leaf ``shared_gate`` ``[d, 1]`` (Qwen3-Next's; without it no such leaf)."""
    from bluefog_tpu.parallel.expert import held_topk_experts, route_topk

    B, T, d = m.shape
    init = nn.initializers.normal(0.02)
    shared_gate = routing.pop("shared_gate", False)
    router = block.param("router", init, (d, num_experts), jnp.float32)
    if routing.pop("bias", False):
        routing["bias"] = block.param("router_bias", nn.initializers.zeros_init(),
                                      (num_experts,), jnp.float32)
    rows = m.reshape(B * T, d)
    experts, weights = route_topk(rows, router, top_k, routed_scale, **routing)
    n_held, f = len(experts_held), expert_dff
    stacks = {
        "wg": block.param("wg", init, (n_held, d, f), jnp.float32),
        "wu": block.param("wu", init, (n_held, d, f), jnp.float32),
        "wd": block.param("wd", init, (n_held, f, d), jnp.float32),
    }
    y = held_topk_experts(rows, experts, weights, stacks, experts_held,
                          num_experts, activation=jax.nn.silu)
    if not shared_dff:
        return y.reshape(B, T, d)
    with jax.named_scope("moe_shared"):
        routed = y.reshape(B, T, d)
        shared = _GatedMLP(shared_dff, dtype, name="shared")(m)
        if shared_gate:
            w_sg = block.param("shared_gate", init, (d, 1), jnp.float32)
            gate = jnp.dot(m, w_sg.astype(dtype), preferred_element_type=jnp.float32)
            shared = (shared * jax.nn.sigmoid(gate)).astype(shared.dtype)
        return routed + shared


class _GatedBlock(nn.Module):
    """One layer of the decoder whose layers differ by more than their window
    (poolside's Laguna): a head count and a rotary of the layer's own, key and
    value heads handed to the kernels as they are (the kernels read the shared
    head in place), every head's output times a sigmoid gate of the normed
    input, and after the attention either a dense gated MLP (``dense_dff``) or
    this share's part of the top-k expert layer, routed on the normed stream
    the experts read, beside a shared expert that every share computes."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]
    rotary: Rotary
    dense_dff: Optional[int]
    num_experts: int
    top_k: int
    experts_held: Tuple[int, ...]
    expert_dff: int
    shared_dff: int
    routed_scale: float
    dtype: Any
    attention_fn: Callable  # (q, k, v, window=) -> out, causal

    @nn.compact
    def __call__(self, x, positions):
        B, T, d = x.shape
        hd, H, kvh = self.head_dim, self.num_heads, self.num_kv_heads
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=self.dtype)
        h = RMSNorm(dtype=self.dtype, name="attn_norm")(x)
        q = _rotary(dense((H, hd), name="q")(h), positions, rotary=self.rotary)
        k = _rotary(dense((kvh, hd), name="k")(h), positions, rotary=self.rotary)
        v = dense((kvh, hd), name="v")(h)
        scope = "attention_global" if self.window is None else "attention_window"
        with jax.named_scope(scope):
            att = self.attention_fn(q, k, v, window=self.window)
        with jax.named_scope("attention_gate"):
            gate = jax.nn.sigmoid(dense(H, name="gate")(h).astype(jnp.float32))
            att = (att * gate[..., None]).astype(self.dtype)
        x = x + dense(d, name="o")(att.reshape(B, T, H * hd))

        m = RMSNorm(dtype=self.dtype, name="ffn_norm")(x)
        if self.dense_dff is not None:
            with jax.named_scope("mlp_dense"):
                return x + _GatedMLP(self.dense_dff, self.dtype, name="mlp")(m)
        return x + expert_feed_forward(
            self, m, self.num_experts, self.top_k, self.experts_held,
            self.expert_dff, self.shared_dff, self.routed_scale, self.dtype)


class MixedAttentionMoELM(nn.Module):
    """Decoder-only LM whose layers differ by kind and whose feed-forward
    is a top-k expert layer cut to the experts this chip holds.

    ``layer_windows`` gives one entry a layer: an int is a causal band of
    that many keys with rotary position (base ``rope_base``) on q and k;
    ``None`` is causal attention over the whole sequence with no position
    signal.  ``head_dim`` is independent of ``hidden_size / num_heads``;
    ``num_kv_heads`` divides ``num_heads``.  The router is ``num_experts``
    wide and picks ``top_k``; ``experts_held`` names the experts whose
    weights live here, and what the others would add is left out (see
    :func:`bluefog_tpu.parallel.expert.held_topk_experts`).  RMSNorm
    (eps 1e-6), no bias anywhere, embedding and head untied.

    With ``layer_heads`` (one query-head count a layer) the layers are of
    the gated kind (:class:`_GatedBlock`): ``layer_rotary`` gives each layer's
    :class:`Rotary` (:func:`rotary_frequencies`: how much of a head is
    rotated, at which frequencies, global layers too), ``layer_dense_dff`` an
    int where the layer's feed-forward is a dense gated MLP of that width and
    ``None`` where it is the expert layer, ``shared_dff`` the width of the
    shared expert beside it and ``routed_scale`` what the renormalised top-k
    weights are multiplied by; ``num_heads`` and ``rope_base`` are then not
    read.

    With ``labels`` it returns the shifted next-token loss through
    :func:`chunked_softmax_cross_entropy` (``head_chunks`` chunks), so
    :func:`bluefog_tpu.training.make_lm_loss_fns`' identity loss serves it;
    without, float32 logits.
    """

    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    layer_windows: Tuple[Optional[int], ...]
    num_experts: int
    top_k: int
    experts_held: Tuple[int, ...]
    expert_dff: int
    rope_base: float = 10000.0
    head_chunks: int = 1
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None  # None: the flash kernels
    layer_heads: Optional[Tuple[int, ...]] = None
    layer_rotary: Tuple[Rotary, ...] = ()
    layer_dense_dff: Tuple[Optional[int], ...] = ()
    shared_dff: int = 0
    routed_scale: float = 1.0

    @nn.compact
    def __call__(self, input_ids, positions=None, labels=None):
        from bluefog_tpu.kernels.flash_attention import flash_attention
        from bluefog_tpu.telemetry import registry as _telemetry

        windows = tuple(self.layer_windows)
        heads = self.layer_heads or (self.num_heads,) * len(windows)
        if any(h % self.num_kv_heads for h in heads):
            raise ValueError(
                f"num_heads {sorted(set(heads))} not divisible by "
                f"num_kv_heads {self.num_kv_heads}")
        if self.layer_heads is not None and not (
                len(heads) == len(self.layer_rotary) == len(self.layer_dense_dff)
                == len(windows)):
            raise ValueError(
                f"{len(windows)} layers, but {len(heads)} head counts, "
                f"{len(self.layer_rotary)} rotaries and "
                f"{len(self.layer_dense_dff)} feed-forward kinds")
        reg = _telemetry.get_registry()
        if reg.enabled:
            banded = [w for w in windows if w is not None]
            reg.gauge("attention.window").set(max(banded, default=0))
            reg.gauge("attention.layers_window").set(len(banded))
            reg.gauge("attention.layers_global").set(len(windows) - len(banded))
        if reg.enabled and self.layer_heads is not None:
            def of_kind(values, is_window):  # the kind's one value, 0 without one
                return max((v for v, w in zip(values, windows)
                            if (w is not None) == is_window), default=0)

            rotated = [2 * len(r.inv_freq) for r in self.layer_rotary]
            reg.gauge("attention.heads_window").set(of_kind(heads, True))
            reg.gauge("attention.heads_global").set(of_kind(heads, False))
            reg.gauge("attention.kv_heads").set(self.num_kv_heads)
            reg.gauge("attention.rotary_dims_window").set(of_kind(rotated, True))
            reg.gauge("attention.rotary_dims_global").set(of_kind(rotated, False))
            reg.gauge("moe.shared_width").set(self.shared_dff)
            reg.gauge("moe.routed_scale").set(self.routed_scale)
            reg.gauge("moe.dense_layers").set(
                sum(f is not None for f in self.layer_dense_dff))
        T = input_ids.shape[1]
        if positions is None:
            positions = jnp.arange(T)
        attention_fn = self.attention_fn or partial(flash_attention, causal=True)
        x = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype,
                     embedding_init=nn.initializers.normal(0.02),
                     name="embed")(input_ids)
        for i, window in enumerate(windows):
            if self.layer_heads is None:
                block = _MixedBlock(
                    self.num_heads, self.num_kv_heads, self.head_dim, window,
                    self.rope_base, self.num_experts, self.top_k,
                    tuple(self.experts_held), self.expert_dff, self.dtype,
                    attention_fn, name=f"layer_{i}")
            else:
                block = _GatedBlock(
                    heads[i], self.num_kv_heads, self.head_dim, window,
                    self.layer_rotary[i], self.layer_dense_dff[i],
                    self.num_experts, self.top_k, tuple(self.experts_held),
                    self.expert_dff, self.shared_dff, self.routed_scale,
                    self.dtype, attention_fn, name=f"layer_{i}")
            x = block(x, positions)
        x = RMSNorm(dtype=jnp.float32, name="final_norm")(x)
        kernel = _HeadKernel(self.vocab_size, name="head")(self.hidden_size)
        if labels is None:
            return _head_matmul(x, kernel, jnp.float32)
        return chunked_softmax_cross_entropy(
            x, kernel, labels, max(self.head_chunks, 1))

"""Plain reference for the `ling-3.0-flash-vl` configuration: the layer
equations of the text decoder of inclusionAI's Ling-3.0-flash-VL (config.json
of the source, with the readings the configuration file lists under
`assumed`) in straightforward jax.numpy, float32, every matrix product at
`jax.default_matmul_precision("highest")`, no kernel.  It imports nothing of
bluefog_tpu and is handed nothing the program made.  The vision tower is not
built.

Hidden d; h is the residual stream [T, d]; every layer is h <- h +
Mixer(RMSNorm(h)), then h <- h + FFN(RMSNorm(h)) (eps rms_norm_eps); u is the
mixer's normed input; H = num_attention_heads heads.

1. Mixer of a `kda` layer (Kimi Delta Attention, arXiv:2510.26692 section 3),
   heads of K = head_dim channels: [q~, k~, v] = u W_qkv [T, 3, H, K], each
   channel through silu(sum over j < short_conv_kernel_size of w[j] *
   x[t - (size - 1) + j]), zeros before the sequence, no bias.  A head at a
   time q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(K), k = k~ / sqrt(|k~|^2 + 1e-6).
   The log-decay a channel g[t] = kda_lower_bound x sigmoid(exp(A_log[head]) x
   ((u W_f)[t] + dt_bias)), in (kda_lower_bound, 0); the step a head b[t] =
   sigmoid((u W_b)[t]).  A state S [K, K] a head from zero:
       S[t] = (I - b[t] k[t] k[t]^T) Diag(exp g[t]) S[t-1] + b[t] k[t] v[t]^T
       o[t] = S[t]^T q[t]
   computed as written, **one token after another** (`kda_scan`: a `lax.scan`
   over tokens, so it shares no algebra with the chunked form the program
   computes).  Then y = [w * RMSNorm(o[t, head]) * sigmoid((u W_g)[t])] W_o,
   the norm over a head's K channels with one learned weight w [K].  No
   position signal.
2. Mixer of an `mla` layer (DeepSeek-V2's latent attention, arXiv:2405.04434
   section 2.1, no query compression): q = u W_q [T, H, nope + rope]; [c, k_r]
   = u W_kva, c [T, kv_lora_rank] through an RMS norm, k_r [T, rope] **one**
   head; [k_n, v] = c W_kvb [T, H, nope + v_head_dim]; rotary (rope_theta,
   half-split over the rope channels) on q's last rope channels and on k_r;
   causal softmax((q_n . k_n + q_r . k_r) / sqrt(nope + rope)) v over the whole
   sequence; head n's output times sigmoid((u W_gate)[t, n]); concat W_o.
3. FFN of a `dense` layer: (silu(m W_g) * (m W_u)) W_d at intermediate_size.
   Of a `sparse` layer (DeepSeek-V3, arXiv:2412.19437 section 2.1.2): s =
   sigmoid(m W_r) over all num_experts; the choice on s + bias: the experts in
   n_group equal groups, a group scores the sum of its two largest s + bias,
   the topk_group best groups stay, the num_experts_per_tok largest s + bias
   among their experts are chosen (S); w_e = routed_scaling_factor x s_e / sum
   over S of s (no bias in the weights); y = sum over the e of S held here of
   w_e E_e(m) + E_shared(m), every E of the gated SiLU form.  What the experts
   held elsewhere would add is left out (`expert_terms` is told which experts
   its stacks hold, so that a test can give it every share in turn).  The bias
   is a leaf; nothing of the loss reaches it.  No clamp: the swiglu limit
   lists are 0 on every layer held.
4. Final RMSNorm, an untied head over the vocabulary slice, next-token
   cross-entropy: position t against labels[t + 1], mean over the first T - 1
   positions.

It has to fit beside the 16 bytes a parameter that chipbench/check.py keeps on
the device, so it is computed in blocks: one sequence at a time, a latent-attention
layer one head at a time and one block of query rows at a time for the scores, a block of rows at
a time for the products and for the logits, a delta-rule layer HEADS heads at
a time with the scan under two levels of `jax.checkpoint` (blocks of
SCAN_BLOCK tokens), and a `jax.checkpoint` around each sequence, each layer
and each block: the check keeps 16 bytes a parameter, 14.15 GB of the chip's
16.9, so the reference's own working set has to stay under 2.5 GB.  Blocking
changes no number.

`lower=True` is the control: every matrix-product operand, and the scan's q, k
and v, rounded to float8_e4m3 first, the nearest precision below the
configuration's bfloat16.
"""

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 2048        # query rows, rows of the products and of logits, at a time
SCAN_BLOCK = 64    # tokens of the scan under one checkpoint
HEADS = 8          # heads of a delta-rule layer at a time: a state a token is 0.5 MB

# Limits of the comparison in chipbench/check.py, from chip readings (PERF.md
# section 6, PR 43, on the v5e at the cell's size): largest sound of the seeds
# run (timed and traced runs of the cell) / the reference with float8 operands
# against the float32 reference, one seed (`lower=True`; the harness's own
# `python -m chipbench.control` holds the program's and two references'
# captures at once and passed the machine's 40 GiB of host memory at 884 M
# parameters).  The payload control says nothing on one chip: no payload
# travels.  No leaf-by-leaf `params1_rel_l2`, for bert-base's reason: AdamW's
# first step is +-lr by the gradient's sign, and where a gradient is rounding
# noise its sign is too.
LIMITS = {
    # 1.09e-4 / 9.1e-5: the loss is ln 19648 and some whatever the products'
    # precision, so float8 gives it no upper reading; the limit of the
    # harness's accepted decoder cells, 3.7 times the sound reading
    "loss_gap": 4e-4,
    # 5.39e-3 (1.7e-3 to 5.4e-3, the widest leaf an expert layer's `wd` or its
    # router) / 1.0 (float8's cotangents underflow): 2.8 times the sound
    # reading.  The rehearsal at hidden 64 on the CPU reads 1.1e-2 and has to
    # pass too
    "grad_norm_gap": 0.015,
    # 2.02e-3 (7.8e-4 to 2.0e-3) / 0.998; a step that returns its state
    # unchanged gives 1: 5 times the sound reading, fresh seeds reading higher.
    # The rehearsal reads 4.6e-3
    "delta_norm_gap": 0.01,
    # 0.0455 (0.039 to 0.046: the entries whose gradient is under Adam's
    # epsilon move by less than lr, in proportion to it) / 0.634
    "change1_rel_l2": 0.15,
    "assoc_p_gap": 0.0,
}

PUBLISHED_LAYERS = 42


def kinds(sizes):
    """One (mixer kind, feed-forward is dense) a layer held here."""
    n = sizes["num_hidden_layers"]
    return list(zip(sizes["layer_types"][:n],
                    (k == "dense" for k in sizes["mlp_layer_types"][:n])))


def _held(sizes):
    return sizes["num_experts_held"]


def param_shapes(sizes):
    d, heads, hd = sizes["hidden_size"], sizes["num_attention_heads"], sizes["head_dim"]
    inner = heads * hd
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    vd, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    f, fs = sizes["moe_intermediate_size"], sizes["moe_shared_expert_intermediate_size"]
    p = {("embed", "embedding"): (sizes["vocab_size"], d),
         ("final_norm", "scale"): (d,),
         ("head", "kernel"): (d, sizes["vocab_size"])}
    for i, (kind, dense) in enumerate(kinds(sizes)):
        b, m = f"layer_{i}", (f"layer_{i}", "mixer")
        p[(b, "mixer_norm", "scale")] = (d,)
        if kind == "kda":
            p[m + ("kda_qkv", "kernel")] = (d, 3 * inner)
            p[m + ("conv_kernel",)] = (sizes["short_conv_kernel_size"], 3 * inner)
            p[m + ("A_log",)] = (heads,)
            p[m + ("dt_bias",)] = (inner,)
            p[m + ("kda_f", "kernel")] = (d, inner)
            p[m + ("kda_b", "kernel")] = (d, heads)
            p[m + ("kda_g", "kernel")] = (d, inner)
            p[m + ("kda_norm", "scale")] = (hd,)
            p[m + ("kda_o", "kernel")] = (inner, d)
        else:
            p[m + ("mla_q", "kernel")] = (d, heads, nope + rope)
            p[m + ("mla_kv_down", "kernel")] = (d, rank + rope)
            p[m + ("mla_kv_norm", "scale")] = (rank,)
            p[m + ("mla_kv_up", "kernel")] = (rank, heads, nope + vd)
            p[m + ("gate", "kernel")] = (d, heads)
            p[m + ("o", "kernel")] = (heads * vd, d)
        p[(b, "mlp_norm", "scale")] = (d,)
        if dense:
            ffn = {("mlp",): sizes["intermediate_size"]}
        else:
            p[(b, "router")] = (d, sizes["num_experts"])
            p[(b, "router_bias")] = (sizes["num_experts"],)
            p[(b, "wg")] = (_held(sizes), d, f)
            p[(b, "wu")] = (_held(sizes), d, f)
            p[(b, "wd")] = (_held(sizes), f, d)
            ffn = {("shared",): fs}
        for name, width in ffn.items():
            p[(b,) + name + ("wg",)] = (d, width)
            p[(b,) + name + ("wu",)] = (d, width)
            p[(b,) + name + ("wd",)] = (width, d)
    return p, {}


def _drawn(path, shape):
    """Uniform numbers in [0, 1) that belong to the leaf, the same in every
    run: chipbench/seeded.py draws normal leaves from the seed and fills the
    others with what this rule hands it."""
    return np.random.default_rng(zlib.crc32("/".join(path).encode())).random(shape)


def init_rule(path, shape):
    """Seeded weights under which every part of a layer does work and the
    layers see tokens that differ.  The embedding at std 1
    (torch.nn.Embedding's default; at 0.02 throughout the stream a layer norms
    is nearly the same vector for every token, PERF.md section 6, PR 29); the
    three that write to the residual stream (`kda_o`, `o`, every `wd`) at 0.02
    / sqrt(2 x 42) (GPT-2's and Megatron's scaled initialisation, at the
    published depth); everything else normal at 0.02; norm scales 1.  The
    linear layer's own as the public KDA layer (and Mamba-2, as Granite's)
    draws them: `A` a head uniform in [1, 16], `A_log` its logarithm; a step
    size a channel log-uniform in [0.001, 0.1], not under 1e-4, through the
    inverse of the softplus into `dt_bias`; the convolution's taps at 0.29
    (torch.nn.Conv1d's default uniform(-1/2, 1/2) for four taps a channel).
    The router's bias uniform in [-0.05, 0.05], drawn from the leaf's name:
    small beside the scores' spread and not zero, so that the choice differs
    from the weights' order for some tokens."""
    name = path[-1]
    if name == "scale":
        return ("const", 1.0)
    if name == "embedding":
        return ("normal", 1.0)
    if name == "dt_bias":
        dt = np.maximum(np.exp(_drawn(path, shape) * np.log(0.1 / 0.001)
                               + np.log(0.001)), 1e-4)
        return ("const", (dt + np.log(-np.expm1(-dt))).astype(np.float32))
    if name == "A_log":
        return ("const", np.log(1.0 + 15.0 * _drawn(path, shape)).astype(np.float32))
    if name == "router_bias":
        return ("const", (0.1 * (_drawn(path, shape) - 0.5)).astype(np.float32))
    if name == "conv_kernel":
        return ("normal", 0.29)
    if name == "wd" or path[-2:] in (("o", "kernel"), ("kda_o", "kernel")):
        return ("normal", 0.02 / (2 * PUBLISHED_LAYERS) ** 0.5)
    return ("normal", 0.02)


def input_shapes(sizes):
    ids = ((sizes["seq_len"],), "int32", sizes["vocab_size"])
    return {"x": ids, "y": ids}


def _low(t):
    return t.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mm(spec, a, b, lower):
    if lower:
        a, b = _low(a), _low(b)
    return jnp.einsum(spec, a, b, precision="highest")


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _by_rows(fn, m):
    """fn over m [T, d], a block of rows at a time."""
    t = m.shape[0]
    rows = min(ROWS, t)
    out = jax.lax.map(jax.checkpoint(fn), m.reshape(t // rows, rows, -1))
    return out.reshape(t, -1)


def _product(u, w, lower):
    """u [T, d] w [d, f], a block of rows at a time."""
    return _by_rows(lambda rows: _mm("td,df->tf", rows, w, lower), u)


def causal_conv(x, kernel):
    """x [T, C], kernel [W, C]: out[t] = sum over j of kernel[j] * x[t - (W -
    1) + j], zeros before the sequence."""
    w, t = kernel.shape[0], x.shape[0]
    padded = jnp.pad(x, ((w - 1, 0), (0, 0)))
    return sum(kernel[j] * padded[j:j + t] for j in range(w))


def kda_scan(q, k, v, g, beta):
    """The recurrence of equation 1, one token after another.  q, k, g [T, H,
    K], v [T, H, V], beta [T, H] -> o [T, H, V]."""
    t, h, kd = q.shape
    block = math.gcd(SCAN_BLOCK, t)

    def token(s, inp):                               # s [H, K, V]
        qt, kt, vt, gt, bt = inp
        s = jnp.exp(gt)[:, :, None] * s
        seen = jnp.sum(kt[:, :, None] * s, axis=1)   # [H, V]: what k reads back
        s = s + (bt[:, None] * kt)[:, :, None] * (vt - seen)[:, None, :]
        return s, jnp.sum(qt[:, :, None] * s, axis=1)

    @jax.checkpoint
    def tokens(s, inp):
        return jax.lax.scan(token, s, inp)

    split = lambda a: a.reshape((t // block, block) + a.shape[1:])
    _, o = jax.lax.scan(tokens, jnp.zeros((h, kd, v.shape[2]), jnp.float32),
                        tuple(map(split, (q, k, v, g, beta))))
    return o.reshape(t, h, -1)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_mixer(u, p, m, sizes, lower):
    """Equation 1, `HEADS` heads at a time: their columns of W_qkv, W_f, W_b
    and W_g, their convolutions, their scan and their gated norm, under one
    `jax.checkpoint`, so that the backward pass holds one group's
    intermediates."""
    t, heads, hd = u.shape[0], sizes["num_attention_heads"], sizes["head_dim"]
    n = math.gcd(HEADS, heads)
    by_heads = lambda w: jnp.moveaxis(          # [d, ..., heads, hd] -> groups first
        w.reshape(w.shape[:-1] + (heads // n, n * hd)), -2, 0)
    wide = lambda w: by_heads(w.reshape(w.shape[0], -1, heads * hd))
    w_b = jnp.moveaxis(p[m + ("kda_b", "kernel")].reshape(-1, heads // n, n), 1, 0)

    @jax.checkpoint
    def group(w_qkv, taps, w_f, dt_bias, a_log, w_b, w_g):
        x = jnp.stack([_product(u, w_qkv[:, i], lower) for i in range(3)], axis=1)
        x = jax.nn.silu(causal_conv(x.reshape(t, -1), taps.reshape(taps.shape[0], -1)))
        q, k, v = x.reshape(t, 3, n, hd).transpose(1, 0, 2, 3)
        q, k = _unit(q) * hd ** -0.5, _unit(k)
        if lower:
            q, k, v = _low(q), _low(k), _low(v)
        f = _product(u, w_f[:, 0], lower) + dt_bias[0, 0]
        g = sizes["kda_lower_bound"] * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None] * f.reshape(t, n, hd))
        beta = jax.nn.sigmoid(_mm("td,dh->th", u, w_b, lower))
        o = _rms_norm(kda_scan(q, k, v, g, beta), p[m + ("kda_norm", "scale")],
                      sizes["rms_norm_eps"])
        return o.reshape(t, -1) * jax.nn.sigmoid(_product(u, w_g[:, 0], lower))

    gated = jax.lax.map(lambda a: group(*a), (
        wide(p[m + ("kda_qkv", "kernel")]), wide(p[m + ("conv_kernel",)]),
        wide(p[m + ("kda_f", "kernel")]), wide(p[m + ("dt_bias",)][None]),
        p[m + ("A_log",)].reshape(-1, n), w_b, wide(p[m + ("kda_g", "kernel")])))
    return _product(jnp.moveaxis(gated, 0, 1).reshape(t, -1),
                    p[m + ("kda_o", "kernel")], lower)


def _rotary(x, theta):
    """x [T, heads, n]: the half-split rotary over all n channels."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, scale, lower):
    """One head: q, k [T, dq], v [T, dv] -> [T, dv]: causal, the whole
    sequence, scores times `scale`.  A block of query rows at a time: scores
    [rows, T], the mask explicit."""
    t = q.shape[0]
    rows = min(ROWS, t)

    @jax.checkpoint
    def block(qb, first):
        s = _mm("qd,kd->qk", qb, k, lower) * scale
        seen = jnp.arange(t)[None, :] <= first + jnp.arange(rows)[:, None]
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("qk,kd->qd", prob, v, lower)

    firsts = jnp.arange(t // rows) * rows
    return jax.lax.map(lambda a: block(*a), (q.reshape(t // rows, rows, -1), firsts)) \
        .reshape(t, -1)


def mla_mixer(u, p, m, sizes, lower):
    """Equation 2, from the compressed form, one head at a time under a
    `jax.checkpoint`: its queries, its keys and values from the latent, the one
    rotary key head laid beside its own, its scores, its gate."""
    t = u.shape[0]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rank, theta = sizes["kv_lora_rank"], sizes["rope_theta"]
    down = _mm("td,df->tf", u, p[m + ("mla_kv_down", "kernel")], lower)
    c = _rms_norm(down[:, :rank], p[m + ("mla_kv_norm", "scale")], sizes["rms_norm_eps"])
    k_r = _rotary(down[:, None, rank:], theta)[:, 0]       # [T, rope]: one head

    @jax.checkpoint
    def head(w_q, w_kv, w_gate):
        q = _mm("td,dk->tk", u, w_q, lower)
        kv = _mm("tr,rk->tk", c, w_kv, lower)
        q = jnp.concatenate([q[:, :nope], _rotary(q[:, None, nope:], theta)[:, 0]], -1)
        k = jnp.concatenate([kv[:, :nope], k_r], axis=-1)
        att = _attention(q, k, kv[:, nope:], (nope + rope) ** -0.5, lower)
        return att * jax.nn.sigmoid(_mm("td,d->t", u, w_gate, lower))[:, None]

    att = jax.lax.map(lambda a: head(*a), (
        jnp.moveaxis(p[m + ("mla_q", "kernel")], 1, 0),
        jnp.moveaxis(p[m + ("mla_kv_up", "kernel")], 1, 0),
        p[m + ("gate", "kernel")].T))                      # [H, T, dv]
    return _product(jnp.moveaxis(att, 0, 1).reshape(t, -1), p[m + ("o", "kernel")], lower)


def gated_mlp(v, p, prefix, lower):
    hg = _mm("td,df->tf", v, p[prefix + ("wg",)], lower)
    hu = _mm("td,df->tf", v, p[prefix + ("wu",)], lower)
    return _mm("tf,fd->td", jax.nn.silu(hg) * hu, p[prefix + ("wd",)], lower)


def route(m, p, b, sizes):
    """m [T, d] -> (experts [T, k], weights [T, k]) by equation 3, float32,
    the groups one after another."""
    s = jax.nn.sigmoid(_mm("td,de->te", m, p[(b, "router")], False))
    choice = s + p[(b, "router_bias")]
    groups, per = sizes["n_group"], sizes["num_experts"] // sizes["n_group"]
    score = jnp.stack([jnp.sum(jax.lax.top_k(choice[:, n * per:(n + 1) * per], 2)[0], -1)
                       for n in range(groups)], axis=-1)            # [T, groups]
    kept = jax.lax.top_k(score, sizes["topk_group"])[1]             # [T, kept]
    stays = jnp.any(kept[:, :, None] == jnp.arange(groups)[None, None, :], axis=1)
    choice = jnp.where(jnp.repeat(stays, per, axis=1), choice, -jnp.inf)
    idx = jax.lax.top_k(choice, sizes["num_experts_per_tok"])[1]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, sizes["routed_scaling_factor"] * chosen / jnp.sum(
        chosen, axis=-1, keepdims=True)


def expert_terms(m, p, b, sizes, lower, held_ids):
    """sum over e in held_ids of w_e E_e(m): the dense way, every expert given
    applied to every token.  `p[(b, "wg")]`'s leading axis is in the order of
    `held_ids`."""
    idx, w = route(m, p, b, sizes)
    gate_all = jnp.zeros((m.shape[0], sizes["num_experts"]), jnp.float32) \
        .at[jnp.arange(m.shape[0])[:, None], idx].set(w)
    gate = gate_all[:, jnp.asarray(held_ids)]              # [T, held]
    hg = _mm("td,edf->etf", m, p[(b, "wg")], lower)
    hu = _mm("td,edf->etf", m, p[(b, "wu")], lower)
    y = _mm("etf,efd->etd", jax.nn.silu(hg) * hu, p[(b, "wd")], lower)
    return jnp.einsum("te,etd->td", gate, y, precision="highest")


def layer(h, p, b, kind, sizes, lower, held_ids=None):
    """h [T, d] -> [T, d]: one layer for one sequence."""
    mixer_kind, dense = kind
    eps = sizes["rms_norm_eps"]
    if held_ids is None:
        held_ids = tuple(range(_held(sizes)))  # this share: experts 0 .. held-1
    u = _rms_norm(h, p[(b, "mixer_norm", "scale")], eps)
    mixer = kda_mixer if mixer_kind == "kda" else mla_mixer
    h = h + mixer(u, p, (b, "mixer"), sizes, lower)

    def ffn(rows):
        m = _rms_norm(rows, p[(b, "mlp_norm", "scale")], eps)
        if dense:
            return gated_mlp(m, p, (b, "mlp"), lower)
        return (expert_terms(m, p, b, sizes, lower, held_ids)
                + gated_mlp(m, p, (b, "shared"), lower))

    return h + _by_rows(ffn, h)


def _sequence_loss(p, ids, y, sizes, lower):
    """Sum over positions t < T - 1 of the cross-entropy of position t
    against y[t + 1], for one sequence."""
    h = p[("embed", "embedding")][ids]
    for i, kind in enumerate(kinds(sizes)):
        h = jax.checkpoint(functools.partial(
            layer, b=f"layer_{i}", kind=kind, sizes=sizes, lower=lower))(h, p)
    h = _rms_norm(h, p[("final_norm", "scale")], sizes["rms_norm_eps"])
    t = h.shape[0]
    target = jnp.concatenate([y[1:], y[:1]])
    weight = (jnp.arange(t) < t - 1).astype(jnp.float32)
    rows = min(ROWS, t)

    @jax.checkpoint
    def block(hb, yb, wb):
        logits = _mm("td,dv->tv", hb, p[("head", "kernel")], lower)
        picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * wb)

    return jnp.sum(jax.lax.map(lambda a: block(*a), (
        h.reshape(t // rows, rows, -1), target.reshape(-1, rows),
        weight.reshape(-1, rows))))


def loss_fn(p, s, ids, y, sizes, lower=False):
    """ids, y [B, T] -> (mean next-token cross-entropy, {})."""
    one = jax.checkpoint(functools.partial(_sequence_loss, sizes=sizes, lower=lower))
    sums = jax.lax.map(lambda a: one(p, a[0], a[1]), (ids, y))
    return jnp.sum(sums) / (ids.shape[0] * (ids.shape[1] - 1)), {}

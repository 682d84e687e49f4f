"""Plain reference for the `granite-4.0-h-micro` configuration: the layer
equations of IBM's Granite 4.0-H Micro (config.json of the source,
`model_type` granitemoehybrid; its state-space layer is Mamba-2, Dao and Gu,
arXiv:2405.21060) in straightforward jax.numpy, float32, every matrix product
at `jax.default_matmul_precision("highest")`, no kernel.  It imports nothing
of bluefog_tpu and is handed nothing the program made.

Hidden d; h is the residual stream [T, d], u = RMSNorm(h) (eps rms_norm_eps).

1. Input: h = embedding_multiplier x E[ids].
2. Every layer: h <- h + residual_multiplier x Mixer(RMSNorm(h)), then
   h <- h + residual_multiplier x MLP(RMSNorm(h)); MLP(v) = (silu(v W_g) *
   (v W_u)) W_d, no bias.
3. Mixer of an `attention` layer: q = u W_q [T, 32, 64], k = u W_k, v = u W_v
   [T, 8, 64]; query head n attends with key-value head n // 4; causal
   softmax(attention_multiplier x q k^T) v over the whole sequence in
   float32, **no position signal**; concat(heads) W_o.
4. Mixer of a `mamba` layer: [z, xBC, dt] = u W_in, widths H P, H P + 2 G N,
   H (H = mamba_n_heads, P = mamba_d_head, N = mamba_d_state, G =
   mamba_n_groups).  xBC[t] <- silu(b_c + sum over k < mamba_d_conv of
   w_c[k] * xBC[t - (mamba_d_conv - 1) + k]), a channel at a time, zeros
   before the sequence; [x, B, C] = xBC.  A head n of P channels, reading
   B[t], C[t] of group n // (H / G):
       delta[t, n] = softplus(dt[t, n] + dt_bias[n])
       a[t, n] = exp(-exp(A_log[n]) delta[t, n])
       S[t, n] = a[t, n] S[t-1, n] + delta[t, n] x[t, n] B[t]^T,  S[-1] = 0
       y[t, n] = S[t, n] C[t] + D[n] x[t, n]
   computed as written, **one token after another** (`ssm_scan`: a `lax.scan`
   over tokens, so it shares no algebra with the chunked form the program's
   kernels compute); then g[t] = w * RMSNorm(y[t] * silu(z[t])) over all H P
   channels (gate first, one norm), and g W_out.
5. Final RMSNorm; logits = h E^T / logits_scaling with E the embedding's own
   tensor; next-token cross-entropy: position t against labels[t + 1], mean
   over the first T - 1 positions.

It has to fit beside the 16 bytes a parameter that chipbench/check.py keeps on
the device, so it is computed in blocks: one sequence at a time, one query
head and one block of query rows at a time for the scores, a block of rows at
a time for the feed-forward and for the logits, the scan under two levels of
`jax.checkpoint` (blocks of SCAN_BLOCK tokens: the backward pass keeps a state
a block and the states of one block, not one a token), and a
`jax.checkpoint` around each sequence, each layer and each block.  Blocking
changes no number.

`lower=True` is the control: every matrix-product operand, and the scan's x, B
and C, rounded to float8_e4m3 first, the nearest precision below the
configuration's bfloat16.
"""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 2048        # query rows, rows of the feed-forward and of logits, at a time
SCAN_BLOCK = 256   # tokens of the scan under one checkpoint

# Limits of the comparison in chipbench/check.py, from chip readings (PERF.md
# section 6, PR 39, on the v5e at the cell's size): largest sound of 10 seeds (2
# traced and 7 timed runs of the cell, 1 of the controls' run) / the reference
# with float8 operands / half the step's tokens replaced / the state handed
# back unchanged, one seed each.  No leaf-by-leaf `params1_rel_l2`, for
# bert-base's reason: AdamW's first step is +-lr by the gradient's sign, and
# where a gradient is rounding noise its sign is too.
LIMITS = {
    # 2.7e-5 / 1.3e-5 / 2.1e-3 / 8.6e-6: the loss is ln 12544 and some whatever
    # the products' precision, so float8 gives it no upper reading; the limit of
    # the harness's accepted decoder cells, 15 times the sound reading, a fifth
    # of the half step's
    "loss_gap": 4e-4,
    # 2.50e-3 / 1.0 (float8's cotangents underflow) / 0.427 / 1.0
    "grad_norm_gap": 0.015,
    # 5.83e-3 (1.8e-3 to 5.8e-3: the widest leaf is always a `dt_bias` or an
    # `A_log`, 64 numbers whose three steps are +-lr by a gradient's sign, so
    # one sign that rounding turns shows) / 0.998 / 0.116 / 1.0: 5 times the
    # sound reading, a quarter of the lowest control's.  The rehearsal on the
    # CPU reads 2.7e-3
    "delta_norm_gap": 0.03,
    # 0.02895 (0.02891..0.02895: the entries whose gradient is under Adam's
    # epsilon move by less than lr, in proportion to it) / 0.731 / 0.078 (not
    # its to catch) / 1.0
    "change1_rel_l2": 0.15,
    "assoc_p_gap": 0.0,
}

PUBLISHED_LAYERS = 40


def kinds(sizes):
    return sizes["layer_types"][:sizes["num_hidden_layers"]]


def ssm_widths(sizes):
    """(heads, channels a head, state, groups, inner = heads x channels,
    convolved channels = inner + 2 groups x state)."""
    h, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    n, g = sizes["mamba_d_state"], sizes["mamba_n_groups"]
    return h, p, n, g, h * p, h * p + 2 * g * n


def param_shapes(sizes):
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["attention_head_dim"]
    h, _, _, _, inner, conv = ssm_widths(sizes)
    p = {("embed", "embedding"): (sizes["vocab_size"], d),
         ("final_norm", "scale"): (d,)}
    for i, kind in enumerate(kinds(sizes)):
        b, m = f"layer_{i}", (f"layer_{i}", "mixer")
        p[(b, "mixer_norm", "scale")] = (d,)
        if kind == "attention":
            p[m + ("q", "kernel")] = (d, heads, hd)
            p[m + ("k", "kernel")] = (d, kv, hd)
            p[m + ("v", "kernel")] = (d, kv, hd)
            p[m + ("o", "kernel")] = (heads * hd, d)
        else:
            p[m + ("in_proj", "kernel")] = (d, inner + conv + h)
            p[m + ("conv_kernel",)] = (sizes["mamba_d_conv"], conv)
            p[m + ("conv_bias",)] = (conv,)
            p[m + ("dt_bias",)] = (h,)
            p[m + ("A_log",)] = (h,)
            p[m + ("D",)] = (h,)
            p[m + ("norm", "scale")] = (inner,)
            p[m + ("out_proj", "kernel")] = (inner, d)
        p[(b, "mlp_norm", "scale")] = (d,)
        p[(b, "mlp", "wg")] = (d, f)
        p[(b, "mlp", "wu")] = (d, f)
        p[(b, "mlp", "wd")] = (f, d)
    return p, {}


def _drawn(path, shape):
    """Uniform numbers in [0, 1) that belong to the leaf, the same in every
    run: chipbench/seeded.py draws normal leaves from the seed and fills the
    others with what this rule hands it, so what is neither normal nor one
    constant is drawn here, from the leaf's name."""
    return np.random.default_rng(zlib.crc32("/".join(path).encode())).random(shape)


def init_rule(path, shape):
    """Seeded weights under which every part of a layer does work.  The
    embedding, the projections and the gated MLP at 0.02 (the family's
    `initializer_range`), the three that write to the residual stream at 0.02 /
    sqrt(2 x 40) (GPT-2's and Megatron's scaled initialisation, at the
    published depth); norm scales 1.  The state-space layer's own, as Mamba-2's
    reference code draws them: the step size a head log-uniform in [0.001,
    0.1], not under 1e-4, put through the inverse of the softplus into
    `dt_bias`; `A` uniform in [1, 16], `A_log` its logarithm; `D` 1; the
    convolution's taps and bias at 0.29, the standard deviation of
    torch.nn.Conv1d's default uniform(-1/2, 1/2) for four taps a channel."""
    name = path[-1]
    if name in ("scale", "D"):
        return ("const", 1.0)
    if name == "dt_bias":
        dt = np.maximum(np.exp(_drawn(path, shape) * np.log(0.1 / 0.001)
                               + np.log(0.001)), 1e-4)
        return ("const", (dt + np.log(-np.expm1(-dt))).astype(np.float32))
    if name == "A_log":
        return ("const", np.log(1.0 + 15.0 * _drawn(path, shape)).astype(np.float32))
    if name in ("conv_kernel", "conv_bias"):
        return ("normal", 0.29)
    if name == "wd" or path[-2:] in (("o", "kernel"), ("out_proj", "kernel")):
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


def _attention(q, k, v, scale, lower):
    """q [T, H, hd], k and v [T, KV, hd] -> [T, H, hd]: causal, the whole
    sequence, scores times `scale`.  One head and one block of query rows at a
    time: scores [rows, T], the mask explicit."""
    t, h, hd = q.shape
    group = h // k.shape[1]
    rows = min(ROWS, t)

    @jax.checkpoint
    def block(qb, kh, vh, first):
        s = _mm("qd,kd->qk", qb, kh, lower) * scale
        seen = jnp.arange(t)[None, :] <= first + jnp.arange(rows)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("qk,kd->qd", p, vh, lower)

    def head(n):
        kh, vh = k[:, n // group], v[:, n // group]
        qh = q[:, n].reshape(t // rows, rows, hd)
        firsts = jnp.arange(t // rows) * rows
        return jax.lax.map(lambda a: block(a[0], kh, vh, a[1]), (qh, firsts)) \
            .reshape(t, hd)

    return jax.lax.map(head, jnp.arange(h)).transpose(1, 0, 2)


def attention_mixer(u, p, m, sizes, lower):
    """Equation 3."""
    q = _mm("td,dhk->thk", u, p[m + ("q", "kernel")], lower)
    k = _mm("td,dhk->thk", u, p[m + ("k", "kernel")], lower)
    v = _mm("td,dhk->thk", u, p[m + ("v", "kernel")], lower)
    att = _attention(q, k, v, sizes["attention_multiplier"], lower)
    return _mm("tk,kd->td", att.reshape(u.shape[0], -1), p[m + ("o", "kernel")], lower)


def causal_conv(xbc, kernel, bias):
    """xbc [T, C], kernel [W, C], bias [C]: out[t] = bias + sum over k of
    kernel[k] * xbc[t - (W - 1) + k], zeros before the sequence."""
    w, t = kernel.shape[0], xbc.shape[0]
    padded = jnp.pad(xbc, ((w - 1, 0), (0, 0)))
    return bias + sum(kernel[k] * padded[k:k + t] for k in range(w))


def ssm_scan(x, delta, a_log, bm, cm, d_skip):
    """The recurrence of equation 4, one token after another.  x [T, H, P],
    delta [T, H], bm and cm [T, G, N] -> y [T, H, P]."""
    t, h, p = x.shape
    rep = h // bm.shape[1]
    a = jnp.exp(-jnp.exp(a_log) * delta)
    block = min(SCAN_BLOCK, t)

    def token(s, inp):
        xt, dt, at, bt, ct = inp
        bt, ct = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)  # [H, N]
        s = at[:, None, None] * s + (dt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return s, jnp.sum(s * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def tokens(s, inp):
        return jax.lax.scan(token, s, inp)

    split = lambda v: v.reshape((t // block, block) + v.shape[1:])
    _, y = jax.lax.scan(tokens, jnp.zeros((h, p, bm.shape[2]), jnp.float32),
                        tuple(map(split, (x, delta, a, bm, cm))))
    return y.reshape(t, h, p) + d_skip[:, None] * x


def mamba_mixer(u, p, m, sizes, lower):
    """Equation 4."""
    h, ph, n, g, inner, conv = ssm_widths(sizes)
    t = u.shape[0]
    zxbcdt = _by_rows(
        lambda rows: _mm("td,df->tf", rows, p[m + ("in_proj", "kernel")], lower), u)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv],
                  zxbcdt[:, inner + conv:])
    xbc = jax.nn.silu(causal_conv(xbc, p[m + ("conv_kernel",)], p[m + ("conv_bias",)]))
    x, bm, cm = (xbc[:, :inner], xbc[:, inner:inner + g * n], xbc[:, inner + g * n:])
    if lower:
        x, bm, cm = _low(x), _low(bm), _low(cm)
    y = ssm_scan(x.reshape(t, h, ph), jax.nn.softplus(dt + p[m + ("dt_bias",)]),
                 p[m + ("A_log",)], bm.reshape(t, g, n), cm.reshape(t, g, n),
                 p[m + ("D",)])
    gated = _rms_norm(y.reshape(t, inner) * jax.nn.silu(z), p[m + ("norm", "scale")],
                      sizes["rms_norm_eps"])
    return _by_rows(
        lambda rows: _mm("tf,fd->td", rows, p[m + ("out_proj", "kernel")], lower), gated)


def gated_mlp(v, p, prefix, lower):
    hg = _mm("td,df->tf", v, p[prefix + ("wg",)], lower)
    hu = _mm("td,df->tf", v, p[prefix + ("wu",)], lower)
    return _mm("tf,fd->td", jax.nn.silu(hg) * hu, p[prefix + ("wd",)], lower)


def layer(h, p, b, kind, sizes, lower):
    """h [T, d] -> [T, d]: equation 2 for one sequence."""
    eps, res = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    u = _rms_norm(h, p[(b, "mixer_norm", "scale")], eps)
    mixer = attention_mixer if kind == "attention" else mamba_mixer
    h = h + res * mixer(u, p, (b, "mixer"), sizes, lower)
    return h + res * _by_rows(
        lambda rows: gated_mlp(_rms_norm(rows, p[(b, "mlp_norm", "scale")], eps),
                               p, (b, "mlp"), lower), h)


def _sequence_loss(p, ids, y, sizes, lower):
    """Sum over positions t < T - 1 of the cross-entropy of position t
    against y[t + 1], for one sequence."""
    table = p[("embed", "embedding")]
    h = sizes["embedding_multiplier"] * table[ids]
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
        logits = _mm("td,vd->tv", hb, table, lower) / sizes["logits_scaling"]
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

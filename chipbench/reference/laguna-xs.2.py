"""Plain reference for the `laguna-xs.2` configuration: the layer equations of
poolside's Laguna-XS.2 (config.json of the source, with the five readings the
configuration file lists under `assumed`) in straightforward jax.numpy,
float32, every matrix product at `jax.default_matmul_precision("highest")`, no
kernel.  It imports nothing of bluefog_tpu and is handed nothing the program
made.

For layer l with input x [T, d], H = num_attention_heads_per_layer[l], kind =
layer_types[l]:

1. a = RMSNorm(x); q = a W_q [T, H, 128], k = a W_k, v = a W_v [T, 8, 128];
   query head h attends with key-value head h // (H / 8).
2. Rotary on q and k by rope_parameters[kind] (`rotary_table`): the first
   partial_rotary_factor x 128 dimensions, half-split among themselves, the
   others untouched.  `default`: f_i = theta ** (-2 i / dims).  `yarn`
   (arXiv:2309.00071): f_i where dimension i turns beta_fast times or more in
   original_max_position_embeddings positions, f_i / factor where it turns
   beta_slow times or fewer, blended linearly in i between (the ramp's ends
   rounded outwards to whole dimensions), and cos and sin multiplied by
   attention_factor.
3. Causal softmax(q k^T / sqrt(128)) v in float32; where kind is
   `sliding_attention`, key j is seen by query i iff 0 <= i - j <
   sliding_window.
4. g = sigmoid(a W_gate) [T, H]; head h's output times g[:, h];
   x' = x + concat(heads) W_o.
5. m = RMSNorm(x').  mlp_layer_types[l] `dense`: y = x' + (silu(m W_g) *
   (m W_u)) W_d.  `sparse`: p = softmax(m W_r) over all the experts, S = the
   num_experts_per_tok largest, w_e = moe_routed_scaling_factor x p_e / sum
   over S of p; y = x' + sum over the experts e of S held here of w_e E_e(m) +
   E_shared(m), every E of the gated form.  What the experts held elsewhere
   would add is left out (`expert_terms` is told which experts its stacks
   hold, so that a test can give it every share in turn).
6. RMSNorm, head over the vocabulary slice, next-token cross-entropy: position
   t against labels[t + 1], mean over the first T - 1 positions.

It has to fit beside the 16 bytes a parameter that chipbench/check.py keeps on
the device, so it is computed in blocks: one sequence at a time, one query
head and one block of query rows at a time for the scores, a block of rows at
a time for the feed-forward and for the logits, a `jax.checkpoint` around each
sequence, each layer and each block.  Blocking changes no number.

`lower=True` is the control: every matrix-product operand rounded to
float8_e4m3 first, the nearest precision below the configuration's bfloat16.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
ROWS = 2048  # query rows, rows of the feed-forward and of logits, at a time

# Limits of the comparison in chipbench/check.py, from chip readings (PERF.md
# section 6, PR 35, on the v5e at the cell's size): largest sound of 7 seeds
# (4 timed runs and 2 traced runs of the cell, 1 of `python -m
# chipbench.control`) / float8 on the one seed the chip budget left for it
# (the standing decoder's 15 float8 seeds read within 1e-3 of one another).
# No leaf-by-leaf `params1_rel_l2`, for bert-base's reason: AdamW's first step
# is +-lr by the gradient's sign, and where a gradient is rounding noise its
# sign is too.
LIMITS = {
    # 9.3e-5 / 6.3e-5: the loss is ln 12544 and some, whatever the precision of
    # the products, so float8 gives it no upper reading; the limit of the
    # harness's accepted decoder cell, four times the sound reading
    "loss_gap": 4e-4,
    # 1.78e-3 / 1.0 (float8's cotangents underflow).  The rehearsal at hidden
    # 64 on the CPU reads 1.3e-3 and has to pass too
    "grad_norm_gap": 0.015,
    # 4.0e-4 / 0.998; the rehearsal reads 1.6e-3: 4 x that.  A step that
    # returns its state unchanged gives 1
    "delta_norm_gap": 0.007,
    # 0.0502 (0.0489..0.0502: the entries whose gradient is under Adam's
    # epsilon move by less than lr, in proportion to it) / 0.672
    "change1_rel_l2": 0.15,
    "assoc_p_gap": 0.0,
}


def _held(sizes):
    return sizes["num_experts_held"]


def layers(sizes):
    """One (kind, heads, window, dense) a layer held here."""
    out = []
    for l in range(sizes["num_hidden_layers"]):
        kind = sizes["layer_types"][l]
        window = sizes["sliding_window"] if kind == "sliding_attention" else None
        out.append((kind, sizes["num_attention_heads_per_layer"][l], window,
                    sizes["mlp_layer_types"][l] == "dense"))
    return out


def param_shapes(sizes):
    d, kv, hd = sizes["hidden_size"], sizes["num_key_value_heads"], sizes["head_dim"]
    f, fs = sizes["moe_intermediate_size"], sizes["shared_expert_intermediate_size"]
    p = {
        ("embed", "embedding"): (sizes["vocab_size"], d),
        ("final_norm", "scale"): (d,),
        ("head", "kernel"): (d, sizes["vocab_size"]),
    }
    for i, (_, h, _, dense) in enumerate(layers(sizes)):
        b = f"layer_{i}"
        p[(b, "attn_norm", "scale")] = (d,)
        p[(b, "q", "kernel")] = (d, h, hd)
        p[(b, "k", "kernel")] = (d, kv, hd)
        p[(b, "v", "kernel")] = (d, kv, hd)
        p[(b, "gate", "kernel")] = (d, h)
        p[(b, "o", "kernel")] = (h * hd, d)
        p[(b, "ffn_norm", "scale")] = (d,)
        if dense:
            ffn = {("mlp",): sizes["intermediate_size"]}
        else:
            p[(b, "router")] = (d, sizes["num_experts"])
            p[(b, "wg")] = (_held(sizes), d, f)
            p[(b, "wu")] = (_held(sizes), d, f)
            p[(b, "wd")] = (_held(sizes), f, d)
            ffn = {("shared",): fs}
        for name, width in ffn.items():
            p[(b,) + name + ("wg",)] = (d, width)
            p[(b,) + name + ("wu",)] = (d, width)
            p[(b,) + name + ("wd",)] = (width, d)
    return p, {}


PUBLISHED_LAYERS = 40


def init_rule(path, shape):
    """Seeded weights under which the layers see what they see in a trained
    model: tokens that differ.  The embedding is drawn at std 1
    (torch.nn.Embedding's default), so that the stream a layer norms is its
    token's and not the attention's output, which at std 0.02 throughout is
    nearly the same vector for every token (PERF.md section 6, PR 29); the
    projections that write to the residual stream at 0.02 / sqrt(2 x 40) (the
    scaled initialisation of GPT-2 and Megatron, at the published depth);
    everything else at 0.02, norm scales 1."""
    if path[-1] == "scale":
        return ("const", 1.0)
    if path[-1] == "embedding":
        return ("normal", 1.0)
    if path[-1] == "wd" or path[-2:] == ("o", "kernel"):
        return ("normal", 0.02 / (2 * PUBLISHED_LAYERS) ** 0.5)
    return ("normal", 0.02)


def input_shapes(sizes):
    ids = ((sizes["seq_len"],), "int32", sizes["vocab_size"])
    return {"x": ids, "y": ids}


def _mm(spec, a, b, lower):
    if lower:
        a, b = (t.astype(jnp.float8_e4m3fn).astype(jnp.float32) for t in (a, b))
    return jnp.einsum(spec, a, b, precision="highest")


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def rotary_table(sizes, kind):
    """(inverse frequencies float64[dims / 2], factor on cos and sin) of
    rope_parameters[kind], by equation 2."""
    r = sizes["rope_parameters"][kind]
    dims = int(sizes["head_dim"] * r["partial_rotary_factor"])
    i = np.arange(dims // 2, dtype=np.float64)
    freq = r["rope_theta"] ** (-2.0 * i / dims)
    if r["rope_type"] == "default":
        return freq, 1.0
    assert r["rope_type"] == "yarn", r["rope_type"]

    def turns(n):  # the dimension that turns n times in the original positions
        return (dims * math.log(r["original_max_position_embeddings"] / (n * 2 * math.pi))
                / (2 * math.log(r["rope_theta"])))

    low = max(math.floor(turns(r["beta_fast"])), 0)
    high = min(math.ceil(turns(r["beta_slow"])), dims - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freq * (1 - ramp) + freq / r["factor"] * ramp, r["attention_factor"]


def _rotary(x, table):
    """x [T, heads, hd]; the half-split convention over the first 2 n
    dimensions, n the number of frequencies; the others as they came."""
    freq, factor = table
    n = len(freq)
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(freq, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :] * factor, jnp.sin(ang)[:, None, :] * factor
    x1, x2 = x[..., :n], x[..., n:2 * n]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., 2 * n:]], axis=-1)


def _attention(q, k, v, window, lower):
    """q [T, H, hd], k and v [T, KV, hd] -> [T, H, hd].  One head and one
    block of query rows at a time: scores [rows, T], the mask explicit."""
    t, h, hd = q.shape
    group = h // k.shape[1]
    rows = min(ROWS, t)
    assert t % rows == 0
    scale = hd ** -0.5

    @jax.checkpoint
    def block(qb, kh, vh, first):
        s = _mm("qd,kd->qk", qb, kh, lower) * scale
        i = first + jnp.arange(rows)[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (i - j < window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("qk,kd->qd", p, vh, lower)

    def head(n):
        kh, vh = k[:, n // group], v[:, n // group]
        qh = q[:, n].reshape(t // rows, rows, hd)
        firsts = jnp.arange(t // rows) * rows
        out = jax.lax.map(lambda a: block(a[0], kh, vh, a[1]), (qh, firsts))
        return out.reshape(t, hd)

    return jax.lax.map(head, jnp.arange(h)).transpose(1, 0, 2)


def _by_rows(fn, m):
    """fn over m [T, d], a block of rows at a time."""
    t = m.shape[0]
    rows = min(ROWS, t)
    out = jax.lax.map(jax.checkpoint(fn), m.reshape(t // rows, rows, -1))
    return out.reshape(t, -1)


def gated_mlp(m, p, prefix, lower):
    """(silu(m W_g) * (m W_u)) W_d."""
    hg = _mm("td,df->tf", m, p[prefix + ("wg",)], lower)
    hu = _mm("td,df->tf", m, p[prefix + ("wu",)], lower)
    return _mm("tf,fd->td", jax.nn.silu(hg) * hu, p[prefix + ("wd",)], lower)


def route(m, p, b, sizes):
    """m [T, d] -> (experts [T, k], weights [T, k]) by equation 5, float32."""
    r = _mm("td,de->te", m, p[(b, "router")], False)  # float32 as stated
    prob = jax.nn.softmax(r, axis=-1)
    top, idx = jax.lax.top_k(prob, sizes["num_experts_per_tok"])
    scale = sizes["moe_routed_scaling_factor"]
    return idx, scale * top / jnp.sum(top, axis=-1, keepdims=True)


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


def attention_part(x, p, b, kind, window, sizes, lower):
    """x [T, d] -> x' [T, d]: equations 1 to 4."""
    t = x.shape[0]
    a = _rms_norm(x, p[(b, "attn_norm", "scale")])
    q = _mm("td,dhk->thk", a, p[(b, "q", "kernel")], lower)
    k = _mm("td,dhk->thk", a, p[(b, "k", "kernel")], lower)
    v = _mm("td,dhk->thk", a, p[(b, "v", "kernel")], lower)
    table = rotary_table(sizes, kind)
    att = _attention(_rotary(q, table), _rotary(k, table), v, window, lower)
    g = jax.nn.sigmoid(_mm("td,dh->th", a, p[(b, "gate", "kernel")], lower))
    att = (att * g[..., None]).reshape(t, -1)
    return x + _mm("tk,kd->td", att, p[(b, "o", "kernel")], lower)


def layer(x, p, b, spec, sizes, lower, held_ids=None):
    """x [T, d] -> [T, d]: one layer for one sequence."""
    kind, _, window, dense = spec
    if held_ids is None:
        held_ids = tuple(range(_held(sizes)))  # this share: experts 0 .. held-1
    x = attention_part(x, p, b, kind, window, sizes, lower)

    def ffn(xb):
        m = _rms_norm(xb, p[(b, "ffn_norm", "scale")])
        if dense:
            return gated_mlp(m, p, (b, "mlp"), lower)
        return (expert_terms(m, p, b, sizes, lower, held_ids)
                + gated_mlp(m, p, (b, "shared"), lower))

    return x + _by_rows(ffn, x)


def held_rows(p, ids, sizes):
    """ids [B, T] -> int[sparse layers]: how many of the batch's T x top-k
    assignments go, in each layer that has experts, to the experts held here,
    by equations 1 to 5.  At even routing that is B x T x k x held / experts
    a layer; the routing tool reads it before and after a window."""
    specs = layers(sizes)

    def one(seq):
        x, counts = p[("embed", "embedding")][seq], []
        for i, spec in enumerate(specs):
            b = f"layer_{i}"
            if not spec[3]:
                x_att = attention_part(x, p, b, spec[0], spec[2], sizes, False)
                m = _rms_norm(x_att, p[(b, "ffn_norm", "scale")])
                counts.append(jnp.sum(route(m, p, b, sizes)[0] < _held(sizes)))
            if i + 1 < len(specs):
                x = layer(x, p, b, spec, sizes, False)
        return jnp.stack(counts)

    return jnp.sum(jax.lax.map(one, ids), axis=0)


def _sequence_loss(p, ids, y, sizes, lower):
    """Sum over positions t < T - 1 of the cross-entropy of position t
    against y[t + 1], for one sequence."""
    x = p[("embed", "embedding")][ids]
    for i, spec in enumerate(layers(sizes)):
        x = jax.checkpoint(functools.partial(
            layer, b=f"layer_{i}", spec=spec, sizes=sizes, lower=lower))(x, p)
    x = _rms_norm(x, p[("final_norm", "scale")])
    t = x.shape[0]
    target = jnp.concatenate([y[1:], y[:1]])
    weight = (jnp.arange(t) < t - 1).astype(jnp.float32)
    rows = min(ROWS, t)

    @jax.checkpoint
    def block(xb, yb, wb):
        logits = _mm("td,dv->tv", xb, p[("head", "kernel")], lower)
        picked = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * wb)

    parts = jax.lax.map(lambda a: block(*a), (
        x.reshape(t // rows, rows, -1), target.reshape(-1, rows),
        weight.reshape(-1, rows)))
    return jnp.sum(parts)


def loss_fn(p, s, ids, y, sizes, lower=False):
    """ids, y [B, T] -> (mean next-token cross-entropy, {})."""
    one = jax.checkpoint(functools.partial(_sequence_loss, sizes=sizes, lower=lower))
    sums = jax.lax.map(lambda a: one(p, a[0], a[1]), (ids, y))
    return jnp.sum(sums) / (ids.shape[0] * (ids.shape[1] - 1)), {}

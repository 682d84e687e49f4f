"""Plain reference for the `smallthinker-21b-a3b` configuration: the layer
equations of SmallThinker-21BA3B-Instruct (config.json of the source;
arXiv:2507.20984) in straightforward jax.numpy, float32, every matrix product
at `jax.default_matmul_precision("highest")`, no kernel.  It imports nothing
of bluefog_tpu and is handed nothing the program made.

For layer l with input x [T, d]:

1. r = x W_r (the router reads the layer's input, before the attention);
   top-6 of r, the weights are the softmax over those six logits.
2. a = RMSNorm(x); q = a W_q, k = a W_k, v = a W_v; query head h attends
   with key-value head h // 7; scale 1/sqrt(128); softmax in float32.
3. sliding_window_layout[l] = rope_layout[l] = 0: causal over the whole
   sequence, no position signal.  Otherwise rotary (half-split, base
   rope_theta) on q and k, and key j is seen by query i iff
   0 <= i - j < sliding_window_size.
4. x' = x + attn W_o; m = RMSNorm(x').
5. E_e(m) = (relu(m W_g^e) * (m W_u^e)) W_d^e; y = x' + sum over the experts
   e held here of gate_e E_e(m), where gate is the dense [T, held] matrix
   that carries the top-6 weight where e is among the token's six and zero
   elsewhere.  Every expert held is applied to every token.  What the
   experts held elsewhere would add is left out (`expert_terms` is told
   which experts its stacks hold, so that a test can give it all 64).
6. RMSNorm, head over the vocabulary slice, next-token cross-entropy:
   position t against labels[t + 1], mean over the first T - 1 positions.

It has to fit beside the 16 bytes a parameter that chipbench/check.py keeps
on the device, so it is computed in blocks: one sequence at a time, one
query head and one block of query rows at a time for the scores, a
`jax.checkpoint` around each sequence, each layer and each block, the logits
for a block of rows at a time.  Blocking changes no number.

`lower=True` is the control: every matrix-product operand rounded to
float8_e4m3 first, the nearest precision below the configuration's bfloat16.
"""

import functools

import jax
import jax.numpy as jnp

EPS = 1e-6
ROWS = 2048  # query rows, and rows of logits, computed at a time

# Limits of the comparison in chipbench/check.py, from chip readings (PERF.md
# section 6, PR 29: `python -m chipbench.control` on the v5e, 3 seeds at four
# minutes a seed, and 17 runs of the cell itself on seeds of their own):
# largest sound / smallest float8.  No leaf-by-leaf `params1_rel_l2`, for
# bert-base's reason (0.0050 / 0.0206 here): AdamW's first step is +-lr by the
# gradient's sign, and where a gradient is rounding noise its sign is too.
LIMITS = {
    # 1.03e-4 / 6.9e-5: the loss is ln 18992 and some, whatever the precision of
    # the products: four times the sound reading, as for a norm that the
    # precision hardly moves
    "loss_gap": 4e-4,
    # 2.06e-3 / 1.0 (float8's cotangents underflow).  The rehearsal at hidden
    # 64 on the CPU reads 3.4e-3 and has to pass too: 4 x that
    "grad_norm_gap": 0.015,
    # 1.1e-4 / 0.997; the rehearsal reads 1.8e-3: 4 x that.  A step that
    # returns its state unchanged gives 1
    "delta_norm_gap": 0.007,
    # 0.0467 (0.0445..0.0467 on 20 seeds: the entries whose gradient is under
    # Adam's epsilon move by less than lr, in proportion to it) / 0.589
    "change1_rel_l2": 0.15,
    "assoc_p_gap": 0.0,
}


def _held(sizes):
    return sizes["moe_num_primary_experts_held"]


def layer_windows(sizes):
    """One entry a layer: None for global attention without position, else
    the window (with rotary)."""
    out = []
    for l in range(sizes["num_hidden_layers"]):
        banded = sizes["sliding_window_layout"][l]
        assert banded == sizes["rope_layout"][l], "window and rotary go together"
        out.append(sizes["sliding_window_size"] if banded else None)
    return out


def param_shapes(sizes):
    d, f = sizes["hidden_size"], sizes["moe_ffn_hidden_size"]
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    p = {
        ("embed", "embedding"): (sizes["vocab_size"], d),
        ("final_norm", "scale"): (d,),
        ("head", "kernel"): (d, sizes["vocab_size"]),
    }
    for i in range(sizes["num_hidden_layers"]):
        b = f"layer_{i}"
        p[(b, "router")] = (d, sizes["moe_num_primary_experts"])
        p[(b, "attn_norm", "scale")] = (d,)
        p[(b, "q", "kernel")] = (d, h, hd)
        p[(b, "k", "kernel")] = (d, kv, hd)
        p[(b, "v", "kernel")] = (d, kv, hd)
        p[(b, "o", "kernel")] = (h * hd, d)
        p[(b, "ffn_norm", "scale")] = (d,)
        p[(b, "wg")] = (_held(sizes), d, f)
        p[(b, "wu")] = (_held(sizes), d, f)
        p[(b, "wd")] = (_held(sizes), f, d)
    return p, {}


PUBLISHED_LAYERS = 52


def init_rule(path, shape):
    """Seeded weights under which the router sees what it sees in a trained
    model: tokens that differ.  At std 0.02 throughout, the first layer's
    attention output, nearly the same vector for every token, outweighs the
    embedding 7 to 1 in the un-normed stream the router reads, and by the
    third layer every token picks the same six experts (PERF.md section 6,
    PR 29).  So the embedding is drawn at std 1 (torch.nn.Embedding's
    default) and the two projections that write to the residual stream at
    0.02 / sqrt(2 x 52) (the scaled initialisation of GPT-2 and Megatron, at
    the published depth); everything else at 0.02, norm scales 1."""
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


def _rotary(x, base):
    """x [T, heads, hd]; the half-split convention."""
    t, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


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


def expert_terms(m, r, p, b, sizes, lower, held_ids):
    """sum over e in held_ids of gate_e E_e(m): the dense way, every expert
    given applied to every token.  `p[(b, "wg")]`'s leading axis is in the
    order of `held_ids`."""
    k = sizes["moe_num_active_primary_experts"]
    top, idx = jax.lax.top_k(r, k)
    w = jax.nn.softmax(top, axis=-1)                       # [T, k]
    gate_all = jnp.zeros_like(r).at[jnp.arange(r.shape[0])[:, None], idx].set(w)
    gate = gate_all[:, jnp.asarray(held_ids)]              # [T, held]
    hg = _mm("td,edf->etf", m, p[(b, "wg")], lower)
    hu = _mm("td,edf->etf", m, p[(b, "wu")], lower)
    y = _mm("etf,efd->etd", jax.nn.relu(hg) * hu, p[(b, "wd")], lower)
    return jnp.einsum("te,etd->td", gate, y, precision="highest")


def layer(x, p, b, window, sizes, lower, held_ids=None):
    """x [T, d] -> [T, d]: one layer for one sequence."""
    if held_ids is None:
        held_ids = tuple(range(_held(sizes)))  # this share: experts 0 .. held-1
    t, d = x.shape
    h, hd = sizes["num_attention_heads"], sizes["head_dim"]
    r = _mm("td,de->te", x, p[(b, "router")], False)  # float32 as stated
    a = _rms_norm(x, p[(b, "attn_norm", "scale")])
    q = _mm("td,dhk->thk", a, p[(b, "q", "kernel")], lower)
    k = _mm("td,dhk->thk", a, p[(b, "k", "kernel")], lower)
    v = _mm("td,dhk->thk", a, p[(b, "v", "kernel")], lower)
    if window is not None:
        q, k = _rotary(q, sizes["rope_theta"]), _rotary(k, sizes["rope_theta"])
    att = _attention(q, k, v, window, lower).reshape(t, h * hd)
    x = x + _mm("tk,kd->td", att, p[(b, "o", "kernel")], lower)
    m = _rms_norm(x, p[(b, "ffn_norm", "scale")])
    return x + expert_terms(m, r, p, b, sizes, lower, held_ids)


def held_rows(p, ids, sizes):
    """ids [B, T] -> int[layers]: how many of the batch's T x top-6
    assignments go, in each layer, to the experts held here, by equations 1 to
    5 above.  At even routing that is B x T x 6 x held / 64 a layer; it is
    what `python -m chipbench.routing` reads before and after a window."""
    k = sizes["moe_num_active_primary_experts"]
    windows = layer_windows(sizes)

    def one(seq):
        x, counts = p[("embed", "embedding")][seq], []
        for i, window in enumerate(windows):
            b = f"layer_{i}"
            r = _mm("td,de->te", x, p[(b, "router")], False)
            counts.append(jnp.sum(jax.lax.top_k(r, k)[1] < _held(sizes)))
            if i + 1 < len(windows):
                x = layer(x, p, b, window, sizes, False)
        return jnp.stack(counts)

    return jnp.sum(jax.lax.map(one, ids), axis=0)


def _sequence_loss(p, ids, y, sizes, lower):
    """Sum over positions t < T - 1 of the cross-entropy of position t
    against y[t + 1], for one sequence."""
    x = p[("embed", "embedding")][ids]
    for i, window in enumerate(layer_windows(sizes)):
        x = jax.checkpoint(functools.partial(
            layer, b=f"layer_{i}", window=window, sizes=sizes, lower=lower))(x, p)
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

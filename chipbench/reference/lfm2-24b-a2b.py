"""Plain reference for the `lfm2-24b-a2b` configuration: LiquidAI's
LFM2-24B-A2B (`model_type` lfm2_moe) as its config.json defines it, in
straightforward jax.numpy, float32, every matrix product at
`jax.default_matmul_precision("highest")`, no kernel, no remat of the
program's.  It imports nothing of bluefog_tpu and nothing of another
configuration's reference, and is handed nothing the program made.

The residual stream is h [T, d], one sequence at a time.  Every layer is
    h <- h + Operator(RMSNorm(h)),   h <- h + FeedForward(RMSNorm(h))
with RMSNorm(v) = w * v / sqrt(mean(v^2) + norm_eps); which operator and which
feed-forward part a held layer has is read from the published tables at the
layer's published index (`layer_types[i]`, `i < num_dense_layers`).

A. Operator of a `conv` layer, the gated short convolution (`conv_L_cache` 3
   taps, `conv_bias` false).  With u the normed input:
       [B_t; C_t; x_t] = W_in u_t              three chunks of d, in that order
       z_t   = B_t * x_t                       (element by element)
       c_t   = sum_{k=0..2} w_k * z_{t-2+k}    a channel, z before the sequence 0
       out_t = W_out (C_t * c_t)
   no bias, no activation, no state beyond the two tokens before.
B. Operator of a `full_attention` layer: n_h query heads on n_kv key-value
   heads of d / n_h channels, no bias:
       q_t,i = RoPE_t(RMSNorm(W_Q,i u_t)),  k_t,j = RoPE_t(RMSNorm(W_K,j u_t))
   the two norms over a head's channels with one learned scale each that the
   heads share (`q_layernorm`, `k_layernorm`, eps norm_eps); RoPE turns the
   pair of channels (i, i + hd / 2) by the angle t * rope_theta^(-2i / hd)
   (half-split, `rope_type` default, over the whole head);
       s_t,u,i = q_t,i . k_u,g(i) / sqrt(hd), u <= t;   g(i) = i // (n_h / n_kv)
       o_t,i   = sum_u softmax_u(s_t,u,i) v_u,g(i);     out_t = W_O [o_t,1; ...]
C. FeedForward of a layer whose published index is under `num_dense_layers`:
   the gated MLP W_2 (silu(W_1 m) * (W_3 m)) at intermediate_size.
D. FeedForward of every later layer (`use_expert_bias`, `norm_topk_prob`):
       s_e = sigmoid(m . r_e)                  every expert e of num_experts, float32
       S   = the num_experts_per_tok experts with the largest s_e + b_e
       g_e = routed_scaling_factor * s_e / (sum over S of s + 1e-6)    (no b)
       y   = sum over e in S of g_e FFN_e(m)
   every FFN the gated MLP of C at moe_intermediate_size; **no shared expert**.
   This chip holds `num_experts_held` of the experts (experts 0 .. held-1); the
   sum runs over the e of S that are held, and what the others would add is
   left out (`routed_part` is told which experts its stacks hold, so that a
   test can hand it every share in turn).  b is a leaf that nothing of the
   loss reaches.
E. One final RMSNorm (the model's `embedding_norm`), the head tied to the
   embedding over the vocabulary slice, next-token cross-entropy: position t
   against labels[t + 1], the mean over the first T - 1 positions.

Beside the 16 bytes a parameter that chipbench/check.py keeps on the device
(11.8 GB of the chip's 16.9) the reference's own working set has to stay
small, so everything is walked in pieces under `jax.checkpoint`: a sequence,
a layer, a head, ROWS query rows of a head's scores, ROWS rows of a product,
of a feed-forward part or of the logits at a time.  Walking in pieces changes
no number.

`lower=True` is the control: every matrix-product operand rounded to
float8_e4m3 first, the nearest precision below the configuration's bfloat16.
"""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 2048  # rows at a time: of a head's scores, of a product, of logits

# Limits of the comparison in chipbench/check.py, beside the chip readings at
# the cell's size on the v5e (PERF.md section 6, PR 49: the sound readings of
# the seeds run, traced and timed runs of the cell).  The float8 control
# (`python -m chipbench.control`) was ended at the chip machine's 40 GiB of
# host memory at this size and is read on the CPU at the rehearsal's sizes,
# where it fails three limits as it does in every standing cell; the values
# are the standing expert decoder cells'
# (Kanana's, Laguna's, SmallThinker's).  The payload control says nothing on
# one chip: on ExponentialTwoGraph(1) no payload travels.  No leaf-by-leaf
# `params1_rel_l2`: AdamW's first step is +-lr by the gradient's sign, and
# where a gradient is rounding noise so is its sign.
# **No `loss_gap`**: the head is the embedding and the labels are drawn, so a
# token's own logit (its embedding against the normed stream that began as it)
# stands e^4 to e^8 above the others and the loss moves with the stream's
# norm; the bfloat16 program reads 6e-5 to 3.6e-4 from this reference at
# hidden 1024 and 8,192 tokens and -5.1e-4 to 3.1e-4 at hidden 512 and 4,096,
# either sign (my CPU runs, PR 49, `_scratch/loss_gap.py`, `loss_gap_kinds.py`),
# which leaves the accepted cells' 4e-4 no room where the contract asks for
# three times, and says to leave the number out then.  The loss is held
# through its gradient: every leaf's norm below.
LIMITS = {
    # 3.57e-3 (1.6e-3 to 3.6e-3, the widest leaf a router) / 1.0 (rehearsal):
    # four times the sound reading
    "grad_norm_gap": 0.015,
    # 5.38e-4 (3.4e-4 to 5.4e-4) / 0.9998 (rehearsal); a step that returns its
    # state unchanged gives 1: thirteen times the sound reading, fresh seeds
    # reading higher (the CPU's at hidden 512 read 9.8e-4 to 3.6e-3)
    "delta_norm_gap": 0.007,
    # 0.0343 (0.0341 to 0.0343) / 0.982 (rehearsal; 0.59 to 0.69 on the chip in
    # the standing expert decoder cells)
    "change1_rel_l2": 0.15,
    "assoc_p_gap": 0.0,
}

DEPTH_PUBLISHED = 40
ROUTE_EPS = 1e-6   # in the denominator of the chosen scores' sum
KINDS = {"conv": "conv", "full_attention": "attention"}


def kinds(sizes):
    """[(operator, dense feed-forward?)] of the layers held, from the
    published tables at each layer's published index."""
    held = sizes["published_layer_index"][:sizes["num_hidden_layers"]]
    return [(KINDS[sizes["layer_types"][i]], i < sizes["num_dense_layers"])
            for i in held]


def head_dim(sizes):
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def param_shapes(sizes):
    d, n_h, n_kv, hd = (sizes["hidden_size"], sizes["num_attention_heads"],
                        sizes["num_key_value_heads"], head_dim(sizes))
    held, width = sizes["num_experts_held"], sizes["moe_intermediate_size"]
    shapes = {("embed", "embedding"): (sizes["vocab_size"], d)}
    for i, (operator, dense) in enumerate(kinds(sizes)):
        layer, mix = f"layer_{i}", (f"layer_{i}", "mixer")
        shapes[(layer, "mixer_norm", "scale")] = (d,)
        if operator == "conv":
            shapes[mix + ("in_proj", "kernel")] = (d, 3 * d)
            shapes[mix + ("conv_kernel",)] = (sizes["conv_L_cache"], d)
            shapes[mix + ("out_proj", "kernel")] = (d, d)
        else:
            shapes[mix + ("q", "kernel")] = (d, n_h, hd)
            shapes[mix + ("k", "kernel")] = (d, n_kv, hd)
            shapes[mix + ("v", "kernel")] = (d, n_kv, hd)
            shapes[mix + ("q_norm", "scale")] = (hd,)
            shapes[mix + ("k_norm", "scale")] = (hd,)
            shapes[mix + ("o", "kernel")] = (n_h * hd, d)
        shapes[(layer, "mlp_norm", "scale")] = (d,)
        if dense:
            f = sizes["intermediate_size"]
            shapes[(layer, "mlp", "wg")] = (d, f)
            shapes[(layer, "mlp", "wu")] = (d, f)
            shapes[(layer, "mlp", "wd")] = (f, d)
            continue
        shapes[(layer, "router")] = (d, sizes["num_experts"])
        shapes[(layer, "router_bias")] = (sizes["num_experts"],)
        shapes[(layer, "wg")] = (held, d, width)
        shapes[(layer, "wu")] = (held, d, width)
        shapes[(layer, "wd")] = (held, width, d)
    shapes[("final_norm", "scale")] = (d,)
    return shapes, {}


def init_rule(path, shape):
    """The seeded weights (the source gives none; the configuration's
    `assumed` states the rule): norm scales 1; the embedding, which is the head
    too, and every product at 0.02; the tensors that write to the residual
    stream (`out_proj`, `o`, every `wd`) at 0.02 / sqrt(2 x 40), the scaled
    initialisation at the published depth; the convolution's taps at 0.33, the
    standard deviation of torch.nn.Conv1d's default uniform(-1/sqrt(3),
    1/sqrt(3)) for three taps a channel; the router's bias uniform in [-0.05,
    0.05], drawn from the leaf's name (chipbench/seeded.py draws only normal
    leaves from the seed), small beside the scores' spread and not zero, so
    that the choice is not the weights' order for every token."""
    leaf = path[-1]
    if leaf == "scale":
        return "const", 1.0
    if leaf == "router_bias":
        drawn = np.random.default_rng(zlib.crc32("/".join(path).encode())).random(shape)
        return "const", (0.1 * drawn - 0.05).astype(np.float32)
    if leaf == "conv_kernel":
        return "normal", 0.33
    if leaf == "wd" or path[-2:] in (("o", "kernel"), ("out_proj", "kernel")):
        return "normal", 0.02 / (2 * DEPTH_PUBLISHED) ** 0.5
    return "normal", 0.02


def input_shapes(sizes):
    tokens = ((sizes["seq_len"],), "int32", sizes["vocab_size"])
    return {"x": tokens, "y": tokens}


# ---- products, norms, pieces ---------------------------------------------------


def _dot(spec, a, b, lower):
    if lower:
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision="highest")


def rms_norm(v, w, eps):
    return w * v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + eps)


def _in_pieces(fn, rows):
    """fn [n, d] -> [n, e] over rows [T, d], ROWS rows at a time, each piece
    under a checkpoint of its own."""
    t = rows.shape[0]
    n = min(ROWS, t)
    done = jax.lax.map(jax.checkpoint(fn), rows.reshape(t // n, n, rows.shape[1]))
    return done.reshape(t, done.shape[-1])


# ---- A: the gated short convolution ----------------------------------------------


def short_conv(z, w):
    """z [T, d], w [L, d] -> c[t] = sum_k w[k] z[t - (L - 1) + k], zeros before
    the sequence: L shifted multiply-adds."""
    t, taps = z.shape[0], w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z], axis=0)
    out = w[0] * padded[0:t]
    for k in range(1, taps):
        out = out + w[k] * padded[k:k + t]
    return out


def conv_mixer(u, p, mix, sizes, lower):
    """The three equations of A on u [T, d], the leaves under the path `mix`."""
    d = u.shape[1]
    w_in, w_out = p[mix + ("in_proj", "kernel")], p[mix + ("out_proj", "kernel")]
    bcx = _in_pieces(lambda rows: _dot("td,de->te", rows, w_in, lower), u)
    gate_b, gate_c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    y = gate_c * short_conv(gate_b * x, p[mix + ("conv_kernel",)])
    return _in_pieces(lambda rows: _dot("td,de->te", rows, w_out, lower), y)


# ---- B: grouped-query attention, a norm a head on q and k, then the rotary -----------


def rope(v, theta):
    """v [T, n] -> [T, n]: channel i paired with channel i + n / 2, the pair
    turned by t * theta^(-2i / n)."""
    t, n = v.shape
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, n, 2, dtype=jnp.float32) / n)[None, :]
    first, second = v[:, :n // 2], v[:, n // 2:]
    return jnp.concatenate([first * jnp.cos(angle) - second * jnp.sin(angle),
                            second * jnp.cos(angle) + first * jnp.sin(angle)], axis=1)


def normed_turned(v, scale, sizes):
    """A head's queries or keys [T, hd]: the RMS norm over the head's
    channels, then the rotary."""
    return rope(rms_norm(v, scale, sizes["norm_eps"]), float(sizes["rope_theta"]))


def causal_softmax_head(q, k, v, lower):
    """One head's o = softmax(q k^T / sqrt(hd), u <= t) v: q, k, v [T, hd].
    ROWS queries at a time against every key, the mask written out."""
    t, hd = q.shape
    n = min(ROWS, t)
    keys = jnp.arange(t)

    @jax.checkpoint
    def piece(q_rows, start):
        s = _dot("qc,kc->qk", q_rows, k, lower) / np.sqrt(hd)
        allowed = keys[None, :] <= (start + jnp.arange(n))[:, None]
        prob = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return _dot("qk,kc->qc", prob, v, lower)

    out = jax.lax.map(lambda a: piece(*a),
                      (q.reshape(t // n, n, hd), jnp.arange(0, t, n)))
    return out.reshape(t, hd)


def attention_mixer(u, p, mix, sizes, lower):
    """B on u [T, d], a query head at a time against its group's key-value
    head."""
    n_h, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    q_scale, k_scale = p[mix + ("q_norm", "scale")], p[mix + ("k_norm", "scale")]

    @jax.checkpoint
    def one_head(w_q, w_k, w_v):                         # [d, hd] each
        q = normed_turned(_dot("td,dc->tc", u, w_q, lower), q_scale, sizes)
        k = normed_turned(_dot("td,dc->tc", u, w_k, lower), k_scale, sizes)
        return causal_softmax_head(q, k, _dot("td,dc->tc", u, w_v, lower), lower)

    group = jnp.arange(n_h) // (n_h // n_kv)             # the head's key-value head
    heads = jax.lax.map(lambda w: one_head(*w), (
        jnp.swapaxes(p[mix + ("q", "kernel")], 0, 1),
        jnp.swapaxes(p[mix + ("k", "kernel")], 0, 1)[group],
        jnp.swapaxes(p[mix + ("v", "kernel")], 0, 1)[group]))          # [n_h, T, hd]
    joined = jnp.swapaxes(heads, 0, 1).reshape(u.shape[0], -1)
    w_o = p[mix + ("o", "kernel")]
    return _in_pieces(lambda rows: _dot("te,ed->td", rows, w_o, lower), joined)


# ---- C and D: the feed-forward parts --------------------------------------------------


def gated_mlp(m, p, where, lower):
    gate = _dot("td,df->tf", m, p[where + ("wg",)], lower)
    up = _dot("td,df->tf", m, p[where + ("wu",)], lower)
    return _dot("tf,fd->td", jax.nn.silu(gate) * up, p[where + ("wd",)], lower)


def route(m, p, layer, sizes):
    """m [T, d] -> (S [T, k], g [T, k]) of D, float32 whatever `lower`."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", m, p[(layer, "router")],
                                  precision="highest"))
    chosen = jax.lax.top_k(s + p[(layer, "router_bias")],
                           sizes["num_experts_per_tok"])[1]
    s_chosen = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, sizes["routed_scaling_factor"] * s_chosen / (
        jnp.sum(s_chosen, axis=1, keepdims=True) + ROUTE_EPS)


def routed_part(m, p, layer, sizes, lower, held_ids):
    """sum over the e of S in `held_ids` of g_e FFN_e(m): a loop over the
    experts given, each applied to every token and weighed by g_e or by 0.
    The leading axis of `p[(layer, "wg")]` is in the order of `held_ids`."""
    chosen, g = route(m, p, layer, sizes)
    out = jnp.zeros_like(m)
    for n, e in enumerate(held_ids):
        weight = jnp.sum(jnp.where(chosen == e, g, 0.0), axis=1)         # [T]
        gate = _dot("td,df->tf", m, p[(layer, "wg")][n], lower)
        up = _dot("td,df->tf", m, p[(layer, "wu")][n], lower)
        out = out + weight[:, None] * _dot(
            "tf,fd->td", jax.nn.silu(gate) * up, p[(layer, "wd")][n], lower)
    return out


def feed_forward(h, p, i, dense, sizes, lower, held_ids):
    layer, eps = f"layer_{i}", sizes["norm_eps"]

    def rows(h_rows):
        m = rms_norm(h_rows, p[(layer, "mlp_norm", "scale")], eps)
        if dense:
            return gated_mlp(m, p, (layer, "mlp"), lower)
        return routed_part(m, p, layer, sizes, lower, held_ids)

    return _in_pieces(rows, h)


def operator_part(h, p, i, operator, sizes, lower):
    layer = f"layer_{i}"
    u = rms_norm(h, p[(layer, "mixer_norm", "scale")], sizes["norm_eps"])
    mixer = conv_mixer if operator == "conv" else attention_mixer
    return h + mixer(u, p, (layer, "mixer"), sizes, lower)


def decoder_layer(h, p, i, kind, sizes, lower, held_ids=None):
    """h [T, d] -> [T, d]: layer i for one sequence, `kind` its entry of
    `kinds`."""
    if held_ids is None:
        held_ids = tuple(range(sizes["num_experts_held"]))  # this share: experts 0 ..
    operator, dense = kind
    h = operator_part(h, p, i, operator, sizes, lower)
    return h + feed_forward(h, p, i, dense, sizes, lower, held_ids)


def held_rows(p, ids, sizes):
    """ids [B, T] -> int[expert layers]: how many of the batch's T x
    num_experts_per_tok assignments go, in each layer that has experts, to
    the experts held here.  At even routing B x T x k x held / experts a
    layer; the routing tool reads it before and after a window."""
    table, held = kinds(sizes), sizes["num_experts_held"]

    def one(seq):
        h, counts = p[("embed", "embedding")][seq], []
        for i, (operator, dense) in enumerate(table):
            if not dense:
                m = rms_norm(operator_part(h, p, i, operator, sizes, False),
                             p[(f"layer_{i}", "mlp_norm", "scale")], sizes["norm_eps"])
                counts.append(jnp.sum(route(m, p, f"layer_{i}", sizes)[0] < held))
            if i + 1 < len(table):
                h = decoder_layer(h, p, i, (operator, dense), sizes, False)
        return jnp.stack(counts)

    return jnp.sum(jax.lax.map(one, ids), axis=0)


# ---- E: the loss -------------------------------------------------------------------


def _sequence_nll(p, ids, labels, sizes, lower):
    """The summed cross-entropy of positions 0 .. T - 2 against labels[1 ..],
    for one sequence."""
    embedding = p[("embed", "embedding")]
    h = embedding[ids]
    for i, kind in enumerate(kinds(sizes)):
        h = jax.checkpoint(functools.partial(
            decoder_layer, i=i, kind=kind, sizes=sizes, lower=lower))(h, p)
    h = rms_norm(h, p[("final_norm", "scale")], sizes["norm_eps"])
    t = h.shape[0]
    n = min(ROWS, t)
    nxt = jnp.roll(labels, -1)
    counted = jnp.arange(t) < t - 1

    @jax.checkpoint
    def piece(h_rows, want, counts):
        logits = _dot("td,vd->tv", h_rows, embedding, lower)   # the head is the embedding
        nll = jax.nn.logsumexp(logits, axis=1) - jnp.take_along_axis(
            logits, want[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.where(counts, nll, 0.0))

    return jnp.sum(jax.lax.map(lambda a: piece(*a), (
        h.reshape(t // n, n, -1), nxt.reshape(-1, n), counted.reshape(-1, n))))


def loss_fn(p, s, ids, y, sizes, lower=False):
    """ids, y [B, T] -> (mean next-token cross-entropy, {})."""
    per_sequence = jax.checkpoint(
        functools.partial(_sequence_nll, sizes=sizes, lower=lower))
    total = jnp.sum(jax.lax.map(lambda a: per_sequence(p, a[0], a[1]), (ids, y)))
    return total / (ids.shape[0] * (ids.shape[1] - 1)), {}

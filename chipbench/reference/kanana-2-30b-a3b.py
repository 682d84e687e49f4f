"""Plain reference for the `kanana-2-30b-a3b` configuration: kakaocorp's
kanana-2-30b-a3b-instruct-2601 (`model_type` deepseek_v3) as its config.json
and the two DeepSeek reports define it, in straightforward jax.numpy, float32,
every matrix product at `jax.default_matmul_precision("highest")`, no kernel.
It imports nothing of bluefog_tpu and nothing of another configuration's
reference, and is handed nothing the program made.

The residual stream is x [T, d], one sequence at a time.  Every layer is
    x <- x + Attention(RMSNorm(x)),   x <- x + FeedForward(RMSNorm(x))
with RMSNorm(v) = w * v / sqrt(mean(v^2) + rms_norm_eps).

A. Attention: multi-head latent attention, DeepSeek-V2 (arXiv:2405.04434)
   section 2.1, equations 9 to 19, with no query compression (`q_lora_rank`
   null) and n_h = num_attention_heads heads.  With u the normed input:
       q_t        = W_Q u_t                    [n_h, d_nope + d_rope]
       [c_t; r_t] = W_DKV u_t                  c_t [kv_lora_rank], r_t [d_rope]
       c_t        <- RMSNorm(c_t)              (the latent's own norm)
       [k_t,i^C; v_t,i] = W_UKV,i c_t          [d_nope + v_head_dim] a head i
       q_t,i^R = RoPE(q_t,i[d_nope:]),  k_t^R = RoPE(r_t)   one rotary key, every head's
       s_t,j,i = (q_t,i^C . k_j,i^C + q_t,i^R . k_j^R) / sqrt(d_nope + d_rope), j <= t
       o_t,i   = sum_j softmax_j(s_t,j,i) v_j,i
       out_t   = W_O [o_t,1; ...; o_t,n_h]
   RoPE turns the pair of channels (2i, 2i + 1) of its d_rope channels by the
   angle t * rope_theta^(-2i / d_rope), **in place** (`rope_interleave` true:
   a complex number a pair).  No gate on the heads, no bias anywhere, no
   factor on the softmax scale (`rope_scaling` null).
B. FeedForward of layer 0 (`first_k_dense_replace` 1): the gated MLP
   W_down (silu(W_gate m) * (W_up m)) at intermediate_size.
C. FeedForward of every later layer: DeepSeek-V3 (arXiv:2412.19437) section
   2.1.2, equations 12 to 16, with the bias of its auxiliary-loss-free
   balancing (`topk_method` noaux_tc):
       s_e   = sigmoid(m . e_e)                       every routed expert e of num_experts
       S     = the num_experts_per_tok experts with the largest s_e + b_e
               (`n_group` 1, `topk_group` 1: one group, nothing to limit)
       g_e   = routed_scaling_factor * s_e / sum over S of s    (no b in the weights)
       y     = sum over e in S of g_e FFN_e(m) + FFN_shared(m)
   every FFN the gated MLP of B, a routed one at moe_intermediate_size, the
   shared one at n_shared_experts * moe_intermediate_size (two shared experts
   are one gated MLP of twice the width: a sum of two is a concatenation).
   This chip holds `num_experts_held` of the routed experts (experts 0 ..
   held-1); the sum runs over the e of S that are held, and what the others
   would add is left out (`routed_part` is told which experts its stacks
   hold, so that a test can hand it every share in turn).  b is a leaf that
   nothing of the loss reaches.
D. Final RMSNorm, an untied head over the vocabulary slice, next-token
   cross-entropy: position t against labels[t + 1], the mean over the first
   T - 1 positions of every sequence.

Beside the 16 bytes a parameter that chipbench/check.py keeps on the device
(11.0 GB of the chip's 16.9) the reference's own working set has to stay
under about 4 GB, so everything is walked in pieces under `jax.checkpoint`:
a sequence, a layer, a head, ROWS query rows of a head's scores, ROWS rows of
a feed-forward part or of the logits at a time.  Walking in pieces changes no
number.

`lower=True` is the control: every matrix-product operand rounded to
float8_e4m3 first, the nearest precision below the configuration's bfloat16.
"""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 2048  # rows at a time: of a head's scores, of a feed-forward part, of logits

# Limits of the comparison in chipbench/check.py, from chip readings at the
# cell's size on the v5e (PERF.md section 6, PR 45): the largest sound reading
# of the seeds run (timed and traced runs of the cell and `python -m
# chipbench.control`) / the reference with float8 operands against the float32
# reference (`lower=True`, one seed; ten sound seeds).  The payload control
# says nothing on one chip: on ExponentialTwoGraph(1) no payload travels.  No
# leaf-by-leaf `params1_rel_l2`: AdamW's first step is +-lr by the gradient's
# sign, and where a gradient is rounding noise so is its sign.
LIMITS = {
    # 1.04e-4 / 8.7e-5: the loss is ln 16032 and some whatever the
    # products' precision, so float8 gives it no upper reading; the limit of
    # the harness's accepted decoder cells, 3.8 times the sound reading
    "loss_gap": 4e-4,
    # 2.39e-3 (8.5e-4 to 2.4e-3) / 1.0: six times the sound reading.
    # The rehearsal at hidden 64 on the CPU reads 1.2e-3
    "grad_norm_gap": 0.015,
    # 3.15e-4 (1.3e-4 to 3.2e-4) / 0.998; a step that returns its state
    # unchanged gives 1: the SmallThinker and Laguna cells' limit, 22 times the
    # sound reading, fresh seeds reading higher.  The rehearsal reads 7.5e-4 to
    # 2.9e-3 while every held expert has its rows (the configuration's
    # `rehearsal_note`)
    "delta_norm_gap": 0.007,
    # 0.0477 (0.0439 to 0.0477: the entries whose gradient is under Adam's
    # epsilon move by less than lr, in proportion to it) / 0.691
    "change1_rel_l2": 0.15,
    "assoc_p_gap": 0.0,
}

DEPTH_PUBLISHED = 48


def _dense_layers(sizes):
    return sizes["first_k_dense_replace"]


def param_shapes(sizes):
    d, n_h = sizes["hidden_size"], sizes["num_attention_heads"]
    d_nope, d_rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    d_v, d_c = sizes["v_head_dim"], sizes["kv_lora_rank"]
    held, width = sizes["num_experts_held"], sizes["moe_intermediate_size"]
    shapes = {}

    def gated_mlp(where, f):
        shapes[where + ("wg",)] = (d, f)
        shapes[where + ("wu",)] = (d, f)
        shapes[where + ("wd",)] = (f, d)

    shapes[("embed", "embedding")] = (sizes["vocab_size"], d)
    for i in range(sizes["num_hidden_layers"]):
        layer, att = f"layer_{i}", (f"layer_{i}", "mixer")
        shapes[(layer, "mixer_norm", "scale")] = (d,)
        shapes[att + ("mla_q", "kernel")] = (d, n_h, d_nope + d_rope)
        shapes[att + ("mla_kv_down", "kernel")] = (d, d_c + d_rope)
        shapes[att + ("mla_kv_norm", "scale")] = (d_c,)
        shapes[att + ("mla_kv_up", "kernel")] = (d_c, n_h, d_nope + d_v)
        shapes[att + ("o", "kernel")] = (n_h * d_v, d)
        shapes[(layer, "mlp_norm", "scale")] = (d,)
        if i < _dense_layers(sizes):
            gated_mlp((layer, "mlp"), sizes["intermediate_size"])
            continue
        shapes[(layer, "router")] = (d, sizes["num_experts"])
        shapes[(layer, "router_bias")] = (sizes["num_experts"],)
        shapes[(layer, "wg")] = (held, d, width)
        shapes[(layer, "wu")] = (held, d, width)
        shapes[(layer, "wd")] = (held, width, d)
        gated_mlp((layer, "shared"), sizes["n_shared_experts"] * width)
    shapes[("final_norm", "scale")] = (d,)
    shapes[("head", "kernel")] = (d, sizes["vocab_size"])
    return shapes, {}


def init_rule(path, shape):
    """The seeded weights (the source gives none; the configuration's
    `assumed` states the rule): norm scales 1; the embedding at std 1, so that
    the tokens a layer norms differ; the tensors that write to the residual
    stream (`o`, every `wd`) at 0.02 / sqrt(2 x 48), the scaled initialisation
    at the published depth; every other product at 0.02; the router's bias
    uniform in [-0.05, 0.05], drawn from the leaf's name (chipbench/seeded.py
    draws only normal leaves from the seed), small beside the scores' spread
    and not zero, so that the choice is not the weights' order for every
    token."""
    leaf = path[-1]
    if leaf == "scale":
        return "const", 1.0
    if leaf == "router_bias":
        drawn = np.random.default_rng(zlib.crc32("/".join(path).encode())).random(shape)
        return "const", (0.1 * drawn - 0.05).astype(np.float32)
    if leaf == "embedding":
        return "normal", 1.0
    if leaf == "wd" or path[-2:] == ("o", "kernel"):
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


# ---- A: latent attention ---------------------------------------------------------


def rope(v, theta):
    """v [T, n] -> [T, n]: the pair (2i, 2i + 1) turned by t * theta^(-2i / n),
    where it stands."""
    t, n = v.shape
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, n, 2, dtype=jnp.float32) / n)[None, :]
    even, odd = v[:, 0::2], v[:, 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        odd * jnp.cos(angle) + even * jnp.sin(angle)], axis=-1)
    return turned.reshape(t, n)


def causal_softmax_head(q, k, v, lower):
    """One head's o = softmax(q k^T / sqrt(dq), j <= t) v: q, k [T, dq], v [T,
    dv].  ROWS queries at a time against every key, the mask written out."""
    t, dq = q.shape
    n = min(ROWS, t)
    keys = jnp.arange(t)

    @jax.checkpoint
    def piece(q_rows, start):
        s = _dot("qc,kc->qk", q_rows, k, lower) / np.sqrt(dq)
        allowed = keys[None, :] <= (start + jnp.arange(n))[:, None]
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return _dot("qk,kc->qc", p, v, lower)

    out = jax.lax.map(lambda a: piece(*a),
                      (q.reshape(t // n, n, dq), jnp.arange(0, t, n)))
    return out.reshape(t, v.shape[1])


def latent_attention(u, p, att, sizes, lower):
    """Equations 9 to 19 of A on u [T, d], the leaves under the path `att`,
    from the compressed form, a head at a time."""
    d_nope, d_c = sizes["qk_nope_head_dim"], sizes["kv_lora_rank"]
    theta = float(sizes["rope_theta"])
    down = _dot("td,dc->tc", u, p[att + ("mla_kv_down", "kernel")], lower)
    latent = rms_norm(down[:, :d_c], p[att + ("mla_kv_norm", "scale")],
                      sizes["rms_norm_eps"])
    key_rope = rope(down[:, d_c:], theta)           # one for all the heads

    @jax.checkpoint
    def one_head(w_q, w_ukv):                       # [d, dq], [d_c, d_nope + d_v]
        q = _dot("td,dc->tc", u, w_q, lower)
        kv = _dot("tc,ce->te", latent, w_ukv, lower)
        q = jnp.concatenate([q[:, :d_nope], rope(q[:, d_nope:], theta)], axis=1)
        k = jnp.concatenate([kv[:, :d_nope], key_rope], axis=1)
        return causal_softmax_head(q, k, kv[:, d_nope:], lower)

    heads = jax.lax.map(lambda w: one_head(*w), (
        jnp.swapaxes(p[att + ("mla_q", "kernel")], 0, 1),
        jnp.swapaxes(p[att + ("mla_kv_up", "kernel")], 0, 1)))    # [n_h, T, d_v]
    joined = jnp.swapaxes(heads, 0, 1).reshape(u.shape[0], -1)
    w_o = p[att + ("o", "kernel")]
    return _in_pieces(lambda rows: _dot("te,ed->td", rows, w_o, lower), joined)


# ---- B and C: the feed-forward parts -----------------------------------------------


def gated_mlp(m, p, where, lower):
    gate = _dot("td,df->tf", m, p[where + ("wg",)], lower)
    up = _dot("td,df->tf", m, p[where + ("wu",)], lower)
    return _dot("tf,fd->td", jax.nn.silu(gate) * up, p[where + ("wd",)], lower)


def route(m, p, layer, sizes):
    """m [T, d] -> (S [T, k], g [T, k]) of C, float32 whatever `lower`."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", m, p[(layer, "router")],
                                  precision="highest"))
    chosen = jax.lax.top_k(s + p[(layer, "router_bias")],
                           sizes["num_experts_per_tok"])[1]
    s_chosen = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, sizes["routed_scaling_factor"] * s_chosen / jnp.sum(
        s_chosen, axis=1, keepdims=True)


def routed_part(m, p, layer, sizes, lower, held_ids):
    """sum over the e of S in `held_ids` of g_e FFN_e(m), every expert given
    applied to every token and weighed by g_e or by 0.  The leading axis of
    `p[(layer, "wg")]` is in the order of `held_ids`."""
    chosen, g = route(m, p, layer, sizes)
    is_e = chosen[:, :, None] == jnp.asarray(held_ids)[None, None, :]   # [T, k, held]
    weight = jnp.sum(jnp.where(is_e, g[:, :, None], 0.0), axis=1)       # [T, held]
    gate = _dot("td,edf->etf", m, p[(layer, "wg")], lower)
    up = _dot("td,edf->etf", m, p[(layer, "wu")], lower)
    out = _dot("etf,efd->etd", jax.nn.silu(gate) * up, p[(layer, "wd")], lower)
    return jnp.einsum("te,etd->td", weight, out, precision="highest")


def feed_forward(x, p, i, sizes, lower, held_ids):
    layer, eps = f"layer_{i}", sizes["rms_norm_eps"]

    def rows(x_rows):
        m = rms_norm(x_rows, p[(layer, "mlp_norm", "scale")], eps)
        if i < _dense_layers(sizes):
            return gated_mlp(m, p, (layer, "mlp"), lower)
        return (routed_part(m, p, layer, sizes, lower, held_ids)
                + gated_mlp(m, p, (layer, "shared"), lower))

    return _in_pieces(rows, x)


def attention_part(x, p, i, sizes, lower):
    layer = f"layer_{i}"
    u = rms_norm(x, p[(layer, "mixer_norm", "scale")], sizes["rms_norm_eps"])
    return x + latent_attention(u, p, (layer, "mixer"), sizes, lower)


def decoder_layer(x, p, i, sizes, lower, held_ids=None):
    """x [T, d] -> [T, d]: layer i for one sequence."""
    if held_ids is None:
        held_ids = tuple(range(sizes["num_experts_held"]))  # this share: experts 0 ..
    x = attention_part(x, p, i, sizes, lower)
    return x + feed_forward(x, p, i, sizes, lower, held_ids)


def held_rows(p, ids, sizes):
    """ids [B, T] -> int[expert layers]: how many of the batch's T x
    num_experts_per_tok assignments go, in each layer that has experts, to
    the experts held here.  At even routing B x T x k x held / experts a
    layer; the routing tool reads it before and after a window."""
    n, held = sizes["num_hidden_layers"], sizes["num_experts_held"]

    def one(seq):
        x, counts = p[("embed", "embedding")][seq], []
        for i in range(n):
            if i >= _dense_layers(sizes):
                m = rms_norm(attention_part(x, p, i, sizes, False),
                             p[(f"layer_{i}", "mlp_norm", "scale")], sizes["rms_norm_eps"])
                counts.append(jnp.sum(route(m, p, f"layer_{i}", sizes)[0] < held))
            if i + 1 < n:
                x = decoder_layer(x, p, i, sizes, False)
        return jnp.stack(counts)

    return jnp.sum(jax.lax.map(one, ids), axis=0)


# ---- D: the loss -------------------------------------------------------------------


def _sequence_nll(p, ids, labels, sizes, lower):
    """The summed cross-entropy of positions 0 .. T - 2 against labels[1 ..],
    for one sequence."""
    x = p[("embed", "embedding")][ids]
    for i in range(sizes["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            decoder_layer, i=i, sizes=sizes, lower=lower))(x, p)
    x = rms_norm(x, p[("final_norm", "scale")], sizes["rms_norm_eps"])
    t = x.shape[0]
    n = min(ROWS, t)
    nxt = jnp.roll(labels, -1)
    counted = jnp.arange(t) < t - 1

    @jax.checkpoint
    def piece(x_rows, want, counts):
        logits = _dot("td,dv->tv", x_rows, p[("head", "kernel")], lower)
        nll = jax.nn.logsumexp(logits, axis=1) - jnp.take_along_axis(
            logits, want[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.where(counts, nll, 0.0))

    return jnp.sum(jax.lax.map(lambda a: piece(*a), (
        x.reshape(t // n, n, -1), nxt.reshape(-1, n), counted.reshape(-1, n))))


def loss_fn(p, s, ids, y, sizes, lower=False):
    """ids, y [B, T] -> (mean next-token cross-entropy, {})."""
    per_sequence = jax.checkpoint(
        functools.partial(_sequence_nll, sizes=sizes, lower=lower))
    total = jnp.sum(jax.lax.map(lambda a: per_sequence(p, a[0], a[1]), (ids, y)))
    return total / (ids.shape[0] * (ids.shape[1] - 1)), {}

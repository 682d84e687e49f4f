"""Plain reference for the `bert-base` configuration: the encoder's forward,
loss and gradients in straightforward jax.numpy at the precision the
configuration states (bfloat16 matmuls, float32 parameters, layer norms,
softmax and head).  It imports nothing of bluefog_tpu and is handed nothing
the program made.  The shapes are google-bert/bert-base-uncased's; the
departures the configuration file lists under `assumed` (pre-LN blocks, no
segment embeddings, no dropout, tanh GELU, classifier on token 0) are the
repo's model's, and are followed here because that is the model the cell runs.

`lower=True` is the control: every matmul operand rounded to float8_e4m3
first, one step below the configuration's bfloat16.
"""

import jax
import jax.numpy as jnp

LN_EPS = 1e-6

# Limits of the comparison in chipbench/check.py, set from chip readings
# (PERF.md section 6, PR 25).  No leaf-by-leaf `params1_rel_l2` here: Adam's
# first step is +-lr by the gradient's sign, and where a gradient is zero by
# the mathematics (the key bias: softmax does not see a shift of the scores)
# its sign is rounding noise, so single leaves differ by their whole change
# between two sound computations.  `change1_rel_l2` compares the sizes of the
# changes over the whole tree instead, and a rounded payload moves it by O(1).
# Readings (PR 25, chipbench.control and the cell's own runs on the v5e, 33
# sound seeds and 3 of each control, B32 S128): largest sound / smallest
# float8 / smallest bf16 payload.
LIMITS = {
    # 0.014 / 0.57 / 0.21.  The sound readings have a long tail (32 seeds read
    # 1e-4..3e-3, one 0.014: two classes, logits near zero, so the loss is
    # rounding on ln 2), hence the room above them
    "loss_gap": 0.06,
    # 0.019 / 1 (float8's cotangents underflow) / the payload does not reach it
    "grad_norm_gap": 0.1,
    # 0.068 (the key bias, whose gradient is rounding noise) / 1 / 1.14; a step
    # that returns its state unchanged gives 1: 3 to 4 x sound
    "delta_norm_gap": 0.25,
    # 0.018 (0.014..0.018) / 0.997 / 1.55
    "change1_rel_l2": 0.1,
    # ring(1): p = 1/2 exactly; ring(n > 1): 1/2 + 1/2
    "assoc_p_gap": 0.0,
}


def param_shapes(sizes):
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    h, hd = sizes["num_attention_heads"], sizes["head_dim"]
    p = {
        ("Embed_0", "embedding"): (sizes["vocab_size"], d),
        ("pos_embedding",): (sizes["max_position_embeddings"], d),
        ("LayerNorm_0", "scale"): (d,), ("LayerNorm_0", "bias"): (d,),
        ("Dense_0", "kernel"): (d, d), ("Dense_0", "bias"): (d,),
        ("Dense_1", "kernel"): (d, sizes["num_labels"]),
        ("Dense_1", "bias"): (sizes["num_labels"],),
    }
    for i in range(sizes["num_hidden_layers"]):
        b = f"_EncoderBlock_{i}"
        p[(b, "LayerNorm_0", "scale")] = (d,)
        p[(b, "LayerNorm_0", "bias")] = (d,)
        p[(b, "DenseGeneral_0", "kernel")] = (d, 3, h, hd)
        p[(b, "DenseGeneral_0", "bias")] = (3, h, hd)
        p[(b, "Dense_0", "kernel")] = (h * hd, d)
        p[(b, "Dense_0", "bias")] = (d,)
        p[(b, "LayerNorm_1", "scale")] = (d,)
        p[(b, "LayerNorm_1", "bias")] = (d,)
        p[(b, "Dense_1", "kernel")] = (d, ff)
        p[(b, "Dense_1", "bias")] = (ff,)
        p[(b, "Dense_2", "kernel")] = (ff, d)
        p[(b, "Dense_2", "bias")] = (d,)
    return p, {}


def init_rule(path, shape):
    leaf = path[-1]
    if leaf in ("kernel", "embedding", "pos_embedding"):
        return ("normal", 0.02)
    if leaf == "scale":
        return ("const", 1.0)
    return ("const", 0.0)


def input_shapes(sizes):
    return {"x": ((sizes["seq_len"],), "int32", sizes["vocab_size"]),
            "y": ((), "int32", sizes["num_labels"])}


def _round(a, lower):
    if lower:
        a = a.astype(jnp.float8_e4m3fn)
    return a.astype(jnp.bfloat16)


def _layer_norm(x, p, prefix):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.maximum(jnp.mean(xf * xf, axis=-1, keepdims=True) - mean * mean, 0.0)
    return ((xf - mean) * jax.lax.rsqrt(var + LN_EPS) * p[prefix + ("scale",)]
            + p[prefix + ("bias",)])


def _dense(x, p, prefix, lower):
    return (_round(x, lower) @ _round(p[prefix + ("kernel",)], lower)
            + p[prefix + ("bias",)].astype(jnp.bfloat16))


def forward(p, s, ids, sizes, lower=False):
    t = ids.shape[1]
    x = p[("Embed_0", "embedding")].astype(jnp.bfloat16)[ids]
    x = x + p[("pos_embedding",)][None, :t].astype(jnp.bfloat16)
    scale = sizes["head_dim"] ** -0.5
    for i in range(sizes["num_hidden_layers"]):
        b = f"_EncoderBlock_{i}"
        h = _layer_norm(x, p, (b, "LayerNorm_0"))
        qkv = (jnp.einsum("btd,dchk->btchk", _round(h, lower),
                          _round(p[(b, "DenseGeneral_0", "kernel")], lower))
               + p[(b, "DenseGeneral_0", "bias")].astype(jnp.bfloat16))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q, lower),
                            _round(k, lower)).astype(jnp.float32) * scale
        probs = jax.nn.softmax(scores, axis=-1)
        att = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, lower), _round(v, lower))
        att = att.reshape(att.shape[:2] + (-1,))
        x = x + _dense(att, p, (b, "Dense_0"), lower)
        h = _layer_norm(x, p, (b, "LayerNorm_1"))
        h = jax.nn.gelu(_dense(h, p, (b, "Dense_1"), lower), approximate=True)
        x = x + _dense(h, p, (b, "Dense_2"), lower)
    x = _layer_norm(x, p, ("LayerNorm_0",))
    cls, wp, wc = x[:, 0], p[("Dense_0", "kernel")], p[("Dense_1", "kernel")]
    if lower:
        cls, wp, wc = (_round(a, True).astype(jnp.float32) for a in (cls, wp, wc))
    pooled = jnp.tanh(cls @ wp + p[("Dense_0", "bias")])
    return pooled @ wc + p[("Dense_1", "bias")], {}


def loss_fn(p, s, ids, y, sizes, lower=False):
    logits, new = forward(p, s, ids, sizes, lower)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked), new

"""Plain reference for the `resnet50` configuration: ResNet-50 v1.5 forward,
loss and gradients in straightforward jax.numpy, at the precision the
configuration states (bfloat16 convolutions, float32 parameters, float32
batch-norm statistics, float32 head).  It imports nothing of bluefog_tpu and
is handed nothing the program made: its parameter shapes are written here
from He et al. (arXiv:1512.03385, table 1) with the stride on the 3x3
convolution (v1.5), and the seeded weights are made from these shapes.

`lower=True` is the control: the same mathematics with every convolution and
matmul operand rounded to float8_e4m3 first, the precision one step below
the configuration's bfloat16.  It has to come out as not correct.
"""

import jax
import jax.numpy as jnp
from jax import lax

BN_MOMENTUM = 0.9
BN_EPS = 1e-5

# Limits of the comparison in chipbench/check.py.  Each is set from two chip
# readings at the cells' own sizes (PR 25, chipbench.control, v5e): the largest
# that sound runs give over the seeds, and the smallest that the control gives
# (the reference in float8_e4m3 in the program's place; on four chips also the
# payload rounded to bfloat16).  PERF.md section 6 (PR 25) has the table.
# Readings: one chip, 15 sound seeds and 3 control seeds; four chips, 9 and 3.
LIMITS = {
    # sound <= 7.5e-4 (1 chip), 5.5e-4 (4 chips); float8 >= 2.3e-3.  Held
    # against part of the batch left out (which moves the loss by percents):
    # 3 x sound
    "loss_gap": 2.5e-3,
    # sound <= 0.040 (1 chip), 0.102 (4 chips; batch-norm scales, bf16
    # reordering over 50 layers); float8 >= 0.98 (its cotangents underflow)
    "grad_norm_gap": 0.35,
    # sound <= 0.085 (1 chip), 0.134 (4 chips); float8 >= 0.92; a step that
    # returns its state unchanged gives 1: 3 x sound
    "delta_norm_gap": 0.4,
    # sound 3.8e-4..4.3e-4 (1 chip), 5.3e-4..5.6e-4 (4 chips), always
    # conv_init/kernel; float8 >= 1.7e-3 (4 chips), 2.9e-3 (1 chip).  The
    # bf16 payload reads 1.1e-3 and is caught by change1_rel_l2
    "params1_rel_l2": 1.0e-3,
    # sound 0.037..0.044 (1 chip), 0.084..0.088 (4 chips); float8 >= 0.27;
    # bf16 payload >= 1.87
    "change1_rel_l2": 0.16,
    # W is doubly stochastic and the ones are exact
    "assoc_p_gap": 0.0,
}


def _block_names(sizes):
    """(block name, filters, stride, has projection) in forward order."""
    out, idx, width = [], 0, sizes["num_filters"]
    for i, count in enumerate(sizes["stage_sizes"]):
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out.append((f"BottleneckBlock_{idx}", width * 2 ** i, stride, j == 0))
            idx += 1
    return out


def param_shapes(sizes):
    """({path: shape} of parameters, {path: shape} of batch statistics); a
    path is a tuple of names, as the published layer list orders them."""
    params, stats = {}, {}
    exp = sizes["bottleneck_expansion"]

    def bn(prefix, c):
        params[prefix + ("scale",)] = (c,)
        params[prefix + ("bias",)] = (c,)
        stats[prefix + ("mean",)] = (c,)
        stats[prefix + ("var",)] = (c,)

    c_in = sizes["num_filters"]
    params[("conv_init", "kernel")] = (7, 7, sizes["channels"], c_in)
    bn(("bn_init",), c_in)
    for name, f, _stride, proj in _block_names(sizes):
        params[(name, "Conv_0", "kernel")] = (1, 1, c_in, f)
        bn((name, "BatchNorm_0"), f)
        params[(name, "Conv_1", "kernel")] = (3, 3, f, f)
        bn((name, "BatchNorm_1"), f)
        params[(name, "Conv_2", "kernel")] = (1, 1, f, f * exp)
        bn((name, "BatchNorm_2"), f * exp)
        if proj:
            params[(name, "Conv_3", "kernel")] = (1, 1, c_in, f * exp)
            bn((name, "BatchNorm_3"), f * exp)
        c_in = f * exp
    params[("Dense_0", "kernel")] = (c_in, sizes["num_classes"])
    params[("Dense_0", "bias")] = (sizes["num_classes"],)
    return params, stats


def init_rule(path, shape):
    """How --seed makes one leaf: ("normal", std) or ("const", value)."""
    leaf = path[-1]
    if leaf == "kernel" and len(shape) == 4:
        fan_in = shape[0] * shape[1] * shape[2]
        return ("normal", (2.0 / fan_in) ** 0.5)
    if leaf == "kernel":
        return ("normal", 0.01)
    if leaf == "scale":
        return ("const", 0.25 if path[-2] == "BatchNorm_2" else 1.0)
    if leaf == "var":
        return ("const", 1.0)
    return ("const", 0.0)


def input_shapes(sizes):
    s = sizes["image_size"]
    return {"x": ((s, s, sizes["channels"]), "float32", None),
            "y": ((), "int32", sizes["num_classes"])}


def _round(a, lower):
    if lower:
        a = a.astype(jnp.float8_e4m3fn)
    return a.astype(jnp.bfloat16)


def _conv(x, kernel, stride, lower):
    return lax.conv_general_dilated(
        _round(x, lower), _round(kernel, lower), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, s, prefix, new_stats):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    var = jnp.maximum(jnp.mean(xf * xf, axis=(0, 1, 2)) - mean * mean, 0.0)
    new_stats[prefix + ("mean",)] = (
        BN_MOMENTUM * s[prefix + ("mean",)] + (1 - BN_MOMENTUM) * mean)
    new_stats[prefix + ("var",)] = (
        BN_MOMENTUM * s[prefix + ("var",)] + (1 - BN_MOMENTUM) * var)
    mul = lax.rsqrt(var + BN_EPS) * p[prefix + ("scale",)]
    return ((xf - mean) * mul + p[prefix + ("bias",)]).astype(jnp.bfloat16)


def forward(p, s, x, sizes, lower=False):
    """Training-mode forward: (float32 logits, new batch statistics)."""
    new = {}
    y = _conv(x, p[("conv_init", "kernel")], 2, lower)
    y = jax.nn.relu(_batch_norm(y, p, s, ("bn_init",), new))
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for name, _f, stride, proj in _block_names(sizes):
        r = y
        y = _conv(y, p[(name, "Conv_0", "kernel")], 1, lower)
        y = jax.nn.relu(_batch_norm(y, p, s, (name, "BatchNorm_0"), new))
        y = _conv(y, p[(name, "Conv_1", "kernel")], stride, lower)
        y = jax.nn.relu(_batch_norm(y, p, s, (name, "BatchNorm_1"), new))
        y = _conv(y, p[(name, "Conv_2", "kernel")], 1, lower)
        y = _batch_norm(y, p, s, (name, "BatchNorm_2"), new)
        if proj:
            r = _conv(r, p[(name, "Conv_3", "kernel")], stride, lower)
            r = _batch_norm(r, p, s, (name, "BatchNorm_3"), new)
        y = jax.nn.relu(y + r)
    pooled = jnp.mean(y, axis=(1, 2)).astype(jnp.float32)
    kernel = p[("Dense_0", "kernel")]
    if lower:
        pooled, kernel = (_round(pooled, True).astype(jnp.float32),
                          _round(kernel, True).astype(jnp.float32))
    return pooled @ kernel + p[("Dense_0", "bias")], new


def loss_fn(p, s, x, y, sizes, lower=False):
    """Mean softmax cross-entropy of one rank's batch, and the new statistics."""
    logits, new = forward(p, s, x, sizes, lower)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked), new

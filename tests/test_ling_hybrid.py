"""The delta-rule / latent-attention expert decoder
(`models.hybrid.DeltaLatentMoELM`, inclusionAI's Ling-3.0-flash-VL, chipbench's
`ling-3.0-flash-vl`) at a small size on the CPU: the chunked delta rule
(`kernels/kda.py`, its kernels in interpret mode) against the recurrence taken
token by token, values and all five gradients, with decays at the bound and
near none; the chunk's stateless stage, its forward kernel's six outputs and
its backward kernel's five gradients, against the XLA expression it was
(`tests/kda_oracle.py`); latent attention through the flash kernels' two head sizes against
dense softmax from the compressed form; the router against a written-out
loop; the shares of an expert layer adding up to the uncut layer; the model's
loss and every gradient against the configuration's plain reference, and three
steps of AdamW as the cell's `correct` compares them; bfloat16 products
failing the float32 tolerance; recomputation changing nothing; the gauges; the
configuration file against its published source; the FLOP count against a hand
count; the new readers; the cell's rehearsal through `python -m chipbench` and
its controls."""

import functools
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import kda_oracle

import bluefog_tpu as bf
from bluefog_tpu.kernels import kda
from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.kernels.kda import kda_chunked
from bluefog_tpu.models import hybrid
from bluefog_tpu.parallel.expert import held_topk_experts, route_topk
from bluefog_tpu.telemetry import registry as telemetry
from bluefog_tpu.training import make_lm_loss_fns

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import check, control, manifest, optimizers, runner, seeded  # noqa: E402

CELL = "ling-3.0-flash-vl-atc-warmup-b1-s8k-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL)


@pytest.fixture(scope="module")
def ref(cell):
    return cell.module("reference")


# ---- the chunked delta rule against the recurrence ----------------------------


def _delta_inputs(seed, t, gate, heads=4, k=16, batch=2):
    """q and k as the mixer hands them (raw: normal draws, lengths about
    sqrt K; the kernels take the unit vectors and the recurrence's side takes
    them through `kda_oracle.on_units`), the log-decay in (-5, 0): `bound`
    within 1e-2 of -5 on every channel and token, `none` within 1e-2 of 0,
    `spread` over the whole range."""
    r = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, kk, v = (jax.random.normal(key, (batch, t, heads, k)) for key in r[:3])
    shift, spread = {"bound": (9.0, 0.5), "none": (-9.0, 0.5), "spread": (0.0, 2.0)}[gate]
    g = -5.0 * jax.nn.sigmoid(shift + spread * jax.random.normal(r[3], (batch, t, heads, k)))
    beta = jax.nn.sigmoid(jax.random.normal(r[4], (batch, t, heads)))
    return (q, kk, v, g, beta), jax.random.normal(r[5], v.shape)


def _values_and_grads(fn, args, weight):
    def loss(*a):
        o = fn(*a)
        return jnp.sum(o * weight), o
    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, tuple(range(5)), has_aux=True))(*args)
    return (o,) + grads


NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


# lengths the chunk divides and does not, a chunk of one, two and four
# sub-blocks, the decay at its bound for whole chunks, near none and between,
# the heads in one group and in two
@pytest.mark.parametrize("t,chunk,gate,at_once", [
    (64, 32, "spread", 8), (128, 64, "bound", 8), (100, 32, "none", 8),
    (70, 64, "spread", 2), (48, 16, "bound", 2), (128, 64, "none", 8),
    (96, 64, "bound", 2)])
def test_the_chunked_delta_rule_is_the_token_recurrence(ref, t, chunk, gate, at_once):
    """float32: the chunked form sums in another order and inverts a triangle
    where the recurrence substitutes token by token: 2e-5 of each array's
    largest entry (1e-4 on the decay's gradient at the bound, where what is
    left of a cotangent after e^-40 is rounding)."""
    args, weight = _delta_inputs(t, t, gate)
    got = _values_and_grads(
        lambda *a: kda_chunked(*a, chunk=chunk, heads_at_once=at_once), args, weight)
    want = _values_and_grads(kda_oracle.on_units(jax.vmap(ref.kda_scan)), args, weight)
    if gate != "spread":
        assert float(jnp.max(args[3])) < -4.99 or float(jnp.min(args[3])) > -0.01
    for name, a, b in zip(NAMES, got, want):
        scale = float(jnp.max(jnp.abs(b)))
        tol = 1e-4 if (name, gate) == ("dg", "bound") else 2e-5
        assert float(jnp.max(jnp.abs(a - b))) <= tol * scale, (name, scale)


def test_the_chunk_changes_nothing_but_the_order_of_sums():
    args, _ = _delta_inputs(3, 128, "spread")
    outs = [kda_chunked(*args, chunk=c) for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=2e-6)


def test_the_delta_rule_in_bfloat16_stays_within_its_smoke_tolerance(ref):
    """As the mixer calls it in training: q, k and v in bfloat16, the decay
    and the step in float32.  `chip_smoke.py`'s phase holds the chip to 2e-2."""
    (q, k, v, g, beta), _ = _delta_inputs(4, 128, "spread")
    low = lambda a: a.astype(jnp.bfloat16)
    got = kda_chunked(low(q), low(k), low(v), g, beta, chunk=64)
    assert got.dtype == jnp.bfloat16
    want = kda_oracle.on_units(jax.vmap(ref.kda_scan))(q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 2e-2 * float(
        jnp.max(jnp.abs(want)))


def _across(d, x):
    """`sum(d * x)` a head and token over the largest `|d| |x|`: 0 where `d`
    lies across `x`."""
    return float(jnp.max(jnp.abs(jnp.sum(d * x, axis=-1)))) / float(
        jnp.max(jnp.linalg.norm(d, axis=-1) * jnp.linalg.norm(x, axis=-1)))


def test_a_keys_length_changes_nothing_and_its_gradient_lies_across_it():
    """The kernels take a head's vector over its length: seven times the key
    (the second sequence of the batch) is the key, and the norm's adjoint
    projects the unit vector's cotangent onto what lies across the raw one
    (the query's too)."""
    args, weight = _delta_inputs(7, 32, "spread", heads=2, batch=1)
    twice = tuple(jnp.concatenate([a, a]) for a in args)
    q, k = twice[0], twice[1].at[1].multiply(7.0)
    got = _values_and_grads(lambda *a: kda_chunked(*a, chunk=16), (q, k) + twice[2:],
                            jnp.concatenate([weight, weight]))
    for name, a in zip(NAMES, got):
        one, seven = (a[0], a[1] * 7.0) if name == "dk" else a
        assert float(jnp.max(jnp.abs(seven - one))) <= 2e-6 * float(
            jnp.max(jnp.abs(one))), name
    assert _across(got[1], q) <= 1e-6 and _across(got[2], k) <= 1e-6


def test_a_head_of_zero_keys_gives_finite_values_and_the_recurrences_gradients(ref):
    """`rsqrt(0 + 1e-6)` is 1e3 and `0 x 1e3` is 0: the head writes nothing,
    reads nothing back, and its key's gradient is 1e3 times the unit
    vector's cotangent, as JAX's own of the expression."""
    (q, k, *rest), weight = _delta_inputs(6, 32, "spread", heads=2, batch=1)
    args = (q, k.at[:, :, 1].set(0.0)) + tuple(rest)
    got = _values_and_grads(lambda *a: kda_chunked(*a, chunk=16), args, weight)
    want = _values_and_grads(kda_oracle.on_units(jax.vmap(ref.kda_scan)), args, weight)
    assert float(jnp.max(jnp.abs(got[0][:, :, 1]))) == 0.0
    for name, a, b in zip(NAMES, got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(jnp.max(jnp.abs(b))), name


def test_the_padding_is_tokens_that_follow_and_are_not_looked_at():
    """24 tokens in chunks of 16: the raw keys are padded with zeros, whose
    unit vector is zero, so the 8 tokens of padding write nothing, and no
    gradient of the 24 differs from what it is when 8 real tokens follow that
    the loss does not look at."""
    args, weight = _delta_inputs(8, 32, "spread", heads=2, batch=1)
    fn = lambda *a: kda_chunked(*a, chunk=16)
    short = _values_and_grads(fn, tuple(a[:, :24] for a in args), weight[:, :24])
    whole = _values_and_grads(fn, args, weight.at[:, 24:].set(0.0))
    for name, a, b in zip(NAMES, short, whole):
        assert a.shape[1] == 24 and bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a - b[:, :24]))) <= 2e-6 * float(
            jnp.max(jnp.abs(b))), name


@pytest.mark.parametrize("chunk", [24, 48, 8])
def test_a_chunk_that_is_not_sub_blocks_doubled_is_refused(chunk):
    args, _ = _delta_inputs(5, 48, "spread")
    with pytest.raises(ValueError, match="power of two"):
        kda_chunked(*args, chunk=chunk)


# ---- the chunk's stateless stage against the expression it was ------------------


STAGE_OUTPUTS = ("q exp G", "P", "W", "U0", "k exp(G[last] - G)", "exp G[last]")


@functools.lru_cache(maxsize=None)
def _stage_case(chunk, gate, on_grid=True):
    """Two chunks of four heads through `kda._intra` (both kernels, the four
    heads in one grid step) and through the oracle under `jax.vjp`, the same random
    cotangents of all six outputs into both.  `on_grid`: the log-decay rounded
    to a multiple of 2^-12, so that a running sum of 128 of them is exact in
    float32 in whatever order it is taken and what is left between the two is
    the stage's own arithmetic."""
    args, _ = _delta_inputs(chunk, 2 * chunk, gate)
    if on_grid:
        args = args[:3] + (jnp.round(args[3] * 4096) / 4096,) + args[4:]
    flat = lambda a: a.reshape(a.shape[:2] + (-1,))  # the heads side by side
    got, pull = jax.vjp(lambda q, k, v, g, beta: kda._intra(
        flat(q), flat(k), flat(v), flat(g), beta, chunk, True), *args)
    want, pull_oracle = jax.vjp(kda_oracle.on_units(lambda *a: kda_oracle.intra(
        *(kda_oracle.by_chunk(x, chunk) for x in a), jnp.float32)), *args)
    cotangents = tuple(jax.random.normal(jax.random.PRNGKey(n), w.shape)
                       for n, w in enumerate(want))
    return got, want, pull(cotangents), pull_oracle(cotangents)


def _gaps(got, want):
    """Largest difference of each array over the oracle's largest entry."""
    return [float(jnp.max(jnp.abs(a - b))) / max(float(jnp.max(jnp.abs(b))), 1e-30)
            for a, b in zip(got, want)]


@pytest.mark.parametrize("gate", ["bound", "none", "spread"])
@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_the_stage_forward_kernel_is_the_expression(chunk, gate):
    got, want, _, _ = _stage_case(chunk, gate)
    assert [a.shape for a in got] == [w.shape for w in want]
    for name, gap in zip(STAGE_OUTPUTS, _gaps(got, want)):
        assert gap <= 2e-6, (name, gap)


@pytest.mark.parametrize("gate", ["bound", "none", "spread"])
@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_the_stage_backward_kernel_is_the_expressions_vjp(chunk, gate):
    """The adjoint written out against JAX's transpose of the expression:
    2e-5 of each gradient's largest entry, 1e-4 on the decay's at the bound."""
    _, _, got, want = _stage_case(chunk, gate)
    assert [a.shape for a in got] == [w.shape for w in want]
    for name, gap in zip(NAMES[1:], _gaps(got, want)):
        assert gap <= (1e-4 if (name, gate) == ("dg", "bound") else 2e-5), (name, gap)


@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_the_stages_running_sum_is_cumsums_to_float32_rounding(chunk):
    """Off the grid the kernels' running sum (a product with a triangle of
    ones at `HIGHEST`) and `jnp.cumsum` round a sum that reaches 5 x chunk each
    in its own way: an ulp of 320 is 3e-5, and that is what `exp(G[last] - G)` and
    the gradients then differ by.  4e-7 a token of the chunk, twice what was
    read (3.8e-6 at 16, 7.6e-6 at 32, 7.5e-6 at 64, 3.0e-5 at 128)."""
    got, want, dgot, dwant = _stage_case(chunk, "spread", on_grid=False)
    assert max(_gaps(got, want) + _gaps(dgot, dwant)) <= 4e-7 * chunk


# ---- two head sizes in one attention call -------------------------------------


def _dense_attention(q, k, v):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / q.shape[-1] ** 0.5
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision="highest")


@pytest.mark.parametrize("heads,kv,dq,dv,impl", [
    (4, 4, 24, 16, "auto"), (4, 2, 24, 16, "auto"), (4, 4, 24, 16, "xla"),
    (4, 4, 16, 32, "auto"), (2, 1, 40, 8, "auto")])
def test_the_flash_kernels_take_a_value_head_of_another_size(heads, kv, dq, dv, impl):
    r = jax.random.split(jax.random.PRNGKey(dq + dv), 4)
    q = jax.random.normal(r[0], (2, 64, heads, dq))
    k = jax.random.normal(r[1], (2, 64, kv, dq))
    v = jax.random.normal(r[2], (2, 64, kv, dv))
    weight = jax.random.normal(r[3], (2, 64, heads, dv))
    fast = lambda *a: flash_attention(*a, causal=True, block_q=16, block_k=16, impl=impl)
    out = fast(q, k, v)
    assert out.shape == (2, 64, heads, dv)
    np.testing.assert_allclose(out, _dense_attention(q, k, v), atol=2e-6)
    grads = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * weight), (0, 1, 2))(q, k, v)
    for a, b in zip(grads(fast), grads(_dense_attention)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_query_and_key_heads_of_two_sizes_are_refused():
    q, k, v = jnp.zeros((1, 16, 2, 24)), jnp.zeros((1, 16, 2, 16)), jnp.zeros((1, 16, 2, 16))
    with pytest.raises(ValueError, match="share a head size"):
        flash_attention(q, k, v, causal=True)


def _mixer_case(ref, cell, kind):
    """One mixer of the rehearsal's sizes, its reference's leaves seeded, a
    normed input and a cotangent."""
    sizes = dict(cell.sizes(rehearse=True), num_hidden_layers=1, layer_types=[kind],
                 mlp_layer_types=["dense"])
    params = seeded.make_weights(ref, sizes, seed=5)[0]
    mixer = {p[2:]: v for p, v in params.items() if p[:2] == ("layer_0", "mixer")}
    r = jax.random.split(jax.random.PRNGKey(6), 2)
    u = jax.random.normal(r[0], (2, sizes["seq_len"], sizes["hidden_size"]))
    return sizes, mixer, u, jax.random.normal(r[1], u.shape)


def _against_reference(module, ref_fn, mixer, u, weight, sizes):
    def program(p, u_):
        return jnp.sum(module.apply({"params": seeded.nest(p)}, u_) * weight)

    def reference(p, u_):
        full = {("layer_0", "mixer") + path: v for path, v in p.items()}
        out = jax.vmap(lambda one: ref_fn(one, full, ("layer_0", "mixer"), sizes, False))(u_)
        return jnp.sum(out * weight)

    got = jax.jit(jax.value_and_grad(program, (0, 1)))(mixer, u)
    want = jax.jit(jax.value_and_grad(reference, (0, 1)))(mixer, u)
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * max(1.0, abs(float(want[0])))
    for a, b in zip(jax.tree_util.tree_leaves(got[1]), jax.tree_util.tree_leaves(want[1])):
        gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)
        assert gap < 1e-4, gap


def test_latent_attention_is_dense_softmax_from_the_compressed_form(cell, ref):
    """The program's mixer (one 24-wide product a head through the flash
    kernels, 16 + 8 rotary, values of 16) against the reference's: queries and
    keys laid out from the latent and the one rotary head, scores over
    sqrt(24), the mask explicit."""
    sizes, mixer, u, weight = _mixer_case(ref, cell, "mla")
    module = hybrid.LatentAttentionMixer(
        sizes["num_attention_heads"], sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
        sizes["qk_rope_head_dim"], sizes["v_head_dim"],
        hybrid.rotary_frequencies(sizes["qk_rope_head_dim"], sizes["rope_theta"]),
        sizes["rms_norm_eps"], jnp.float32,
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16))
    assert mixer[("mla_q", "kernel")].shape[-1] == 24
    assert mixer[("mla_kv_down", "kernel")].shape[-1] == sizes["kv_lora_rank"] + 8
    _against_reference(module, ref.mla_mixer, mixer, u, weight, sizes)


@pytest.mark.parametrize("tokens,channels,kernels", [(64, 64, False), (64, 128, True)])
def test_the_delta_mixer_is_the_references(cell, ref, tokens, channels, kernels):
    """`KDAMixer` against the reference's token-by-token mixer: at the
    rehearsal's 4 heads of 16 the three convolutions take the expression, at 8
    heads of 16 (384 channels) the kernels of `kernels/causal_conv.py`."""
    heads = channels // 16
    sizes, mixer, u, weight = _mixer_case(ref, dict_cell(cell, num_attention_heads=heads),
                                          "kda")
    assert hybrid.kda_conv_kernels_take(tokens, channels, 4) is kernels
    module = hybrid.KDAMixer(heads, 16, 4, 32, sizes["kda_lower_bound"],
                             sizes["rms_norm_eps"], jnp.float32)
    _against_reference(module, ref.kda_mixer, mixer, u, weight, sizes)


def _primitives_under(jaxpr, scope, inside=False):
    """The primitives of `jaxpr` traced under the named scope `scope`, nested
    jaxprs walked, a Pallas kernel's body left out."""
    found = []
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack).split("/")
        if eqn.primitive.name == "pallas_call":
            continue
        nested = [getattr(v, "jaxpr", v) for value in eqn.params.values()
                  for v in (value if isinstance(value, (tuple, list)) else (value,))
                  if hasattr(getattr(v, "jaxpr", v), "eqns")]
        if not nested and here:
            found.append(eqn.primitive.name)
        for inner in nested:
            found.extend(_primitives_under(inner, scope, here))
    return found


def test_the_mixer_takes_no_norm_of_q_or_k_outside_the_kernels():
    """The witness that the kernels took the unit vectors: in the mixer's
    forward and backward pass, under `kda_chunk` and outside the Pallas calls,
    nothing is summed over a head's channels, rooted or multiplied: reshapes,
    transposes, slices and casts of what the kernels are handed and hand
    back.  Under `kda_gate_norm` the same walk finds the gated norm's sum."""
    module = hybrid.KDAMixer(8, 16, 4, 32, -5.0, 1e-6, jnp.bfloat16)
    u = jnp.ones((1, 64, 32), jnp.bfloat16)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), u)
    traced = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(
        module.apply(p, x).astype(jnp.float32)), (0, 1)))(params, u)
    under = set(_primitives_under(traced.jaxpr, "kda_chunk"))
    assert under and not under & {"reduce_sum", "rsqrt", "mul", "div", "integer_pow",
                                  "sqrt", "dot_general"}, sorted(under)
    assert "reduce_sum" in _primitives_under(traced.jaxpr, "kda_gate_norm")


def dict_cell(cell, **changed):
    """`cell` with its rehearsal sizes changed: what `_mixer_case` asks of it."""
    return types.SimpleNamespace(
        sizes=lambda rehearse=False: dict(cell.sizes(rehearse), **changed))


# ---- the router ---------------------------------------------------------------


def _router_case(tokens=96, d=24, experts=16, seed=0):
    r = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(r[0], (tokens, d))
    router = jax.random.normal(r[1], (d, experts)) * d ** -0.5
    bias = 0.3 * jax.random.normal(r[2], (experts,))
    return x, router, bias


def _routed_by_hand(x, router, bias, top_k, scale, groups, kept):
    """The choice and the weights, a token and a group at a time."""
    s = 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64) @ np.asarray(router, np.float64)))
    per = s.shape[1] // groups
    chosen, weights = [], []
    for row in s:
        biased = row + np.asarray(bias, np.float64)
        score = [np.sort(biased[g * per:(g + 1) * per])[-2:].sum() for g in range(groups)]
        stay = np.argsort(score)[-kept:]
        allowed = [e for e in range(len(row)) if e // per in stay]
        picked = sorted(allowed, key=lambda e: -biased[e])[:top_k]
        chosen.append(picked)
        weights.append(scale * row[picked] / row[picked].sum())
    return np.array(chosen), np.array(weights)


@pytest.mark.parametrize("groups,kept,top_k", [(4, 2, 4), (8, 4, 3), (1, 1, 5), (4, 4, 6)])
def test_the_router_is_the_written_out_loop(ref, groups, kept, top_k):
    """Sigmoid scores, the bias in the choice only, a group's score its two
    largest, the weights without the bias: the library's, the reference's and
    a loop in float64 choose the same experts and weigh them alike."""
    x, router, bias = _router_case()
    experts, weights = route_topk(x, router, top_k, 2.5, score="sigmoid", bias=bias,
                                  groups=groups, groups_kept=kept)
    want_e, want_w = _routed_by_hand(x, router, bias, top_k, 2.5, groups, kept)
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(weights, want_w, rtol=2e-5)
    np.testing.assert_allclose(np.sum(weights, -1), 2.5, rtol=1e-5)
    sizes = {"n_group": groups, "topk_group": kept, "num_experts": 16,
             "num_experts_per_tok": top_k, "routed_scaling_factor": 2.5}
    p = {("b", "router"): router, ("b", "router_bias"): bias}
    ref_e, ref_w = ref.route(x, p, "b", sizes)
    np.testing.assert_array_equal(np.asarray(ref_e), want_e)
    np.testing.assert_allclose(ref_w, want_w, rtol=2e-5)
    # the bias moved the choice for some token, and never the weights
    plain, _ = route_topk(x, router, top_k, 2.5, score="sigmoid", groups=groups,
                          groups_kept=kept)
    assert np.any(np.sort(np.asarray(plain), -1) != np.sort(want_e, -1))


def test_no_gradient_reaches_the_routers_bias_and_the_router_has_one():
    x, router, bias = _router_case()
    def loss(r, b):
        _, w = route_topk(x, r, 4, 2.5, score="sigmoid", bias=b, groups=4, groups_kept=2)
        return jnp.sum(w * jnp.arange(4.0))
    dr, db = jax.grad(loss, (0, 1))(router, bias)
    assert float(jnp.max(jnp.abs(db))) == 0.0 and float(jnp.max(jnp.abs(dr))) > 0


@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_the_routers_defaults_are_what_they_were_bit_for_bit(scale):
    """SmallThinker's and Laguna's call: logits, top-k, a softmax over the k
    chosen logits, times the scale where it is not 1."""
    x, router, _ = _router_case()
    logits = jnp.einsum("td,de->te", x, router, precision=jax.lax.Precision.HIGHEST)
    top, experts = jax.lax.top_k(logits, 4)
    weights = jax.nn.softmax(top, axis=-1)
    got_e, got_w = route_topk(x, router, 4, scale)
    np.testing.assert_array_equal(got_e, experts)
    np.testing.assert_array_equal(got_w, weights if scale == 1.0 else weights * scale)
    # and a softmax over all the experts, chosen and renormalised, is that number
    same_e, same_w = route_topk(x, router, 4, scale, score="softmax", groups=1,
                                bias=jnp.zeros((16,)))
    np.testing.assert_array_equal(same_e, experts)
    np.testing.assert_allclose(same_w, got_w, rtol=1e-5)


def test_a_score_the_router_does_not_have_is_refused():
    x, router, bias = _router_case()
    with pytest.raises(ValueError, match="tanh"):
        route_topk(x, router, 4, score="tanh", bias=bias)
    with pytest.raises(ValueError, match="groups"):
        route_topk(x, router, 4, score="sigmoid", groups=3)


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """16 experts in 4 groups over 4 shares of 4: the routed parts that the
    four shares compute (`held_topk_experts`, each told its experts) plus the
    shared expert once are the uncut reference's layer, every expert held."""
    d, f, experts, per = 24, 16, 16, 4
    sizes = {"n_group": 4, "topk_group": 2, "num_experts": experts,
             "num_experts_per_tok": 4, "routed_scaling_factor": 2.5}
    x, router, bias = _router_case(d=d, experts=experts, seed=3)
    r = jax.random.split(jax.random.PRNGKey(9), 6)
    stack = lambda key, *shape: 0.3 * jax.random.normal(key, shape)
    p = {("b", "router"): router, ("b", "router_bias"): bias,
         ("b", "wg"): stack(r[0], experts, d, f), ("b", "wu"): stack(r[1], experts, d, f),
         ("b", "wd"): stack(r[2], experts, f, d),
         ("b", "shared", "wg"): stack(r[3], d, f), ("b", "shared", "wu"): stack(r[4], d, f),
         ("b", "shared", "wd"): stack(r[5], f, d)}
    whole = (ref.expert_terms(x, p, "b", sizes, False, tuple(range(experts)))
             + ref.gated_mlp(x, p, ("b", "shared"), False))
    chosen, weights = route_topk(x, router, 4, 2.5, score="sigmoid", bias=bias,
                                 groups=4, groups_kept=2)
    total = ref.gated_mlp(x, p, ("b", "shared"), False)     # every share's alike: once
    for share in range(experts // per):
        held = tuple(range(share * per, (share + 1) * per))
        stacks = {n: p[("b", n)][share * per:(share + 1) * per] for n in ("wg", "wu", "wd")}
        part = held_topk_experts(x, chosen, weights, stacks, held, experts,
                                 activation=jax.nn.silu)
        one = ref.expert_terms(x, {**p, **{("b", n): stacks[n] for n in stacks}}, "b",
                               sizes, False, held)
        np.testing.assert_allclose(part, one, atol=2e-5)   # a share is the reference's
        assert float(jnp.max(jnp.abs(part))) > 0
        total = total + part
    np.testing.assert_allclose(total, whole, atol=5e-5)


# ---- the model against the plain reference ------------------------------------


def _float32_model(cell, sizes, **changed):
    return cell.module("program").build(sizes)["model"].clone(
        dtype=jnp.float32, **changed)


def _loss_and_grads(model, params, x, y):
    apply_fn = make_lm_loss_fns(model)[0]
    return jax.jit(jax.value_and_grad(
        lambda p: apply_fn({"params": seeded.nest(p)}, x, labels=y)))(params)


def _seeded_case(ref, sizes):
    params = seeded.make_weights(ref, sizes, seed=11)[0]
    (x, y), = seeded.make_batches(ref, sizes, 11, ranks=1, pool=1)
    x, y = x[0], y[0]
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, {}, x, y, sizes), has_aux=True))(params)
    return sizes, params, x, y, float(loss), grads


@pytest.fixture(scope="module")
def seeded_case(cell, ref):
    return _seeded_case(ref, cell.sizes(rehearse=True))


def _worst_gap(got, want):
    """The widest relative L2 of a leaf's gradient from the reference's, the
    leaves that no gradient reaches left out."""
    gaps = {}
    for path in want:
        a, b = np.asarray(got[path], np.float64), np.asarray(want[path], np.float64)
        if np.linalg.norm(b):
            gaps["/".join(path)] = np.linalg.norm(a - b) / np.linalg.norm(b)
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


# What float32 on both sides leaves between the program and the reference on
# the rehearsal's four layers: the order of sums, and the chunked form's
# triangle against the recurrence's substitution.  The widest leaf reads 2e-5;
# with the products in bfloat16 the same comparison reads 8e-3 or more on
# every seed tried, so a limit of 2e-4 passes the one and fails the other with
# an order of magnitude on each side.
FLOAT32_GAP = 2e-4


def test_loss_and_gradients_match_the_plain_reference(cell, ref, seeded_case):
    """KDA + dense, KDA + experts, MLA + experts, KDA + experts: 4 heads of
    16, chunks of 32, a latent of 32 with 16 + 8 query-key channels, 16
    experts in 4 groups of which 4 held, a shared expert, an untied head, the
    chunked loss; float32 throughout."""
    sizes, params, x, y, loss, grads = seeded_case
    lp, gp = _loss_and_grads(_float32_model(cell, sizes), params, x, y)
    assert abs(float(lp) - loss) < 1e-5
    assert set(gp) == set(grads) == set(ref.param_shapes(sizes)[0])
    gap, where = _worst_gap(gp, grads)
    assert gap < FLOAT32_GAP, (where, gap)
    biases = [p for p in grads if p[-1] == "router_bias"]
    assert len(biases) == 3
    for p in biases:  # a leaf, and no gradient reaches it on either side
        assert float(jnp.max(jnp.abs(gp[p]))) == float(jnp.max(jnp.abs(grads[p]))) == 0.0


def test_bfloat16_products_fail_the_float32_tolerance(cell, seeded_case):
    """The program as it trains (bfloat16 products, float32 norms, gates,
    decays, state, router and head) is another number than the float32
    reference by far more than `FLOAT32_GAP`: a comparison that states float32
    would catch it."""
    sizes, params, x, y, loss, grads = seeded_case
    model = cell.module("program").build(sizes)["model"]
    assert model.dtype == jnp.bfloat16
    _, gp = _loss_and_grads(model, params, x, y)
    gap, _ = _worst_gap(gp, grads)
    assert gap > 10 * FLOAT32_GAP, gap


@pytest.mark.parametrize("changed", [
    dict(lower_bound=-2.0), dict(routed_scale=1.0), dict(groups_kept=4),
    dict(rope_theta=1e4), dict(layer_kinds=("kda", "kda", "kda", "kda")),
    dict(layer_dense=(True, False, False, True))],
    ids=["the_gates_bound", "routed_scale", "every_group_eligible", "rope_theta",
         "no_latent_layer", "a_second_dense_layer"])
def test_each_of_the_models_own_rules_matters(cell, seeded_case, changed):
    sizes, params, x, y, loss, grads = seeded_case
    model = _float32_model(cell, sizes, **changed)
    ids = jax.ShapeDtypeStruct(x.shape, x.dtype)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    if set(seeded.flatten(shapes)) != set(params):
        return  # another tree: another model by its leaves alone
    lp, gp = _loss_and_grads(model, params, x, y)
    gap, _ = _worst_gap(gp, grads)
    assert gap > 1e-2 or abs(float(lp) - loss) > 1e-3


def test_recomputing_the_blocks_changes_no_gradient(cell, seeded_case):
    sizes, params, x, y, _, _ = seeded_case
    la, ga = _loss_and_grads(_float32_model(cell, sizes), params, x, y)
    lb, gb = _loss_and_grads(_float32_model(cell, sizes, remat=False), params, x, y)
    assert float(la) == float(lb)
    for path in ga:
        np.testing.assert_allclose(ga[path], gb[path], rtol=1e-5, atol=1e-9)


def test_three_steps_of_adamw_as_the_cells_correct_compares_them(cell, ref):
    """The float32 program's first three steps under the mix's optimizer
    against `check.reference_run`, every number the cell's LIMITS name; the
    router's bias is decayed alike on both sides."""
    sizes = cell.sizes(rehearse=True)
    seed, M = 2**31 + 7, np.ones((1, 1))
    batches = seeded.make_batches(ref, sizes, seed, ranks=1, pool=4)
    params0, _ = seeded.make_weights(ref, sizes, seed)
    tx = optimizers.make(cell.mix["optimizer"])
    apply_fn = make_lm_loss_fns(_float32_model(cell, sizes))[0]

    @jax.jit
    def step(p, o, x, y):
        loss, g = jax.value_and_grad(
            lambda p_: apply_fn({"params": seeded.nest(p_)}, x, labels=y))(p)
        updates, o = tx.update(g, o, p)
        return optax.apply_updates(p, updates), o, loss

    rank = lambda tree: {k: np.asarray(v)[None] for k, v in tree.items()}
    got, p, o = {"losses": []}, params0, tx.init(params0)
    for k in range(check.STEPS):
        p, o, loss = step(p, o, batches[k][0][0], batches[k][1][0])
        got["losses"].append([float(loss)])
        if k == 0:
            got["grad_norms"] = check._np_norms(rank(
                optimizers.first_gradient(cell.mix["optimizer"], o)))
            got["params1"] = rank(p)
            got["assoc_p"] = np.ones(1)
    got["losses"] = np.array(got["losses"])
    got["delta_norms"] = check._np_norms(
        {k: np.asarray(p[k])[None] - np.asarray(params0[k])[None] for k in p})
    want = check.reference_run(ref, sizes, cell.mix["optimizer"], M, seed, batches)
    numbers, ok = check.compare(got, want, ref.LIMITS)
    assert ok, numbers
    assert set(numbers) == set(ref.LIMITS)
    assert numbers["delta_norm_gap"]["value"] < 1e-3  # float32 on both sides
    bias = ("layer_1", "router_bias")
    moved = np.asarray(p[bias]) - np.asarray(params0[bias])
    assert 0 < np.max(np.abs(moved)) < 1e-6 * np.max(np.abs(params0[bias]))


def test_the_routing_tool_counts_the_rows_on_the_experts_held(cell, ref, capsys):
    """`python -m chipbench.routing_ling`: `chipbench.routing`'s readings for
    this configuration, whose reference has no `held_rows`; the count is made
    from the reference's own mixers, norms and `route`, in the three layers of
    the rehearsal that have experts."""
    from chipbench import routing_ling

    assert routing_ling.main(["--workload", CELL, "--seeds", "1", "--seconds", "0.5",
                              "--rehearse"]) == 0
    row, = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    sizes = cell.sizes(rehearse=True)
    total = sizes["per_rank_batch"] * sizes["seq_len"] * sizes["num_experts_per_tok"]
    assert row["seed"] == 300 and row["failed"] == 0 and row["steps_in_window"] >= 2
    assert row["even_rows"] == total * sizes["num_experts_held"] / sizes["num_experts"]
    for rows in (row["held_rows_first_step"], row["held_rows_last_step"]):
        # one routing group of four: a layer's count follows how often it stays
        assert len(rows) == 3 and all(0 < r < total for r in rows)
    # every expert held: every assignment, in every layer that has experts
    every = dict(sizes, num_experts_held=sizes["num_experts"])
    params = seeded.make_weights(ref, every, seed=9)[0]
    (x, _), = seeded.make_batches(ref, every, 9, ranks=1, pool=1)
    assert np.asarray(routing_ling.held_rows(ref, params, x[0], every)).tolist() \
        == [total] * 3
    assert routing_ling.main(["--workload", CELL, "--seeds", "1"]) == 2
    captured = capsys.readouterr()
    assert captured.out == "" and "no TPU" in captured.err


# ---- gauges, the configuration, the FLOP count, the readers ------------------


WANTED_GAUGES = {
    "kda.layers": 3, "kda.heads": 4, "kda.head_dim": 16, "kda.chunk": 32,
    "kda.lower_bound": -5, "kda.kernel_layers": 0, "kda.intra_kernel_layers": 3,
    "mla.layers": 1, "mla.kv_rank": 32,
    "mla.qk_dims": 24, "mla.v_dims": 16, "attention.layers_global": 1,
    "attention.heads_global": 4, "moe.score": 1, "moe.groups": 4,
    "moe.groups_kept": 2, "moe.shared_width": 32, "moe.routed_scale": 2.5,
    "moe.dense_layers": 1, "moe.experts_held": 4, "moe.experts_total": 16,
    "moe.top_k": 4, "lm.tied_head": 0, "lm.remat_blocks": 4, "lm.remat_kept_names": 3}


@pytest.mark.parametrize("heads,tokens,kernel_layers", [
    pytest.param(4, 32, 0, id="as-rehearsed"),
    pytest.param(8, 32, 3, id="channels-that-tile"),
    pytest.param(8, 12, 0, id="tokens-that-do-not")])
def test_the_model_sets_its_gauges(cell, monkeypatch, tmp_path, heads, tokens,
                                   kernel_layers):
    monkeypatch.setenv("BFTPU_TELEMETRY", str(tmp_path))
    telemetry.reset()
    try:
        model = cell.module("program").build(cell.sizes(rehearse=True))["model"]
        jax.eval_shape(lambda i: model.clone(num_heads=heads).init(
            jax.random.PRNGKey(0), i), jax.ShapeDtypeStruct((1, tokens), jnp.int32))
        gauges = {g["name"]: g["value"] for g in
                  telemetry.get_registry().snapshot()["gauges"]}
    finally:
        telemetry.reset()
    wanted = {**WANTED_GAUGES, "kda.heads": heads, "attention.heads_global": heads,
              "kda.kernel_layers": kernel_layers,
              # bfloat16 of the tokens: the latent layer's [heads, T, 16] and
              # float32 [heads, T], three delta layers' [T, heads x 16]
              "lm.remat_kept_mb": tokens * heads * (32 + 4 + 3 * 32) / 1e6}
    assert {k: v for k, v in gauges.items() if k in wanted} == wanted


def test_a_mixer_kind_the_decoder_does_not_have_is_refused(cell):
    model = _float32_model(cell, cell.sizes(rehearse=True),
                           layer_kinds=("kda", "mamba", "mla", "kda"))
    with pytest.raises(ValueError, match="mamba"):
        jax.eval_shape(lambda i: model.init(jax.random.PRNGKey(0), i),
                       jax.ShapeDtypeStruct((1, 32), jnp.int32))


def test_granites_decoder_is_what_it_was_by_its_leaves():
    """`_HybridBlock` takes its feed-forward part as it takes its mixer: the
    state-space decoder's tree has the leaves it had."""
    model = hybrid.HybridMambaLM(
        vocab_size=64, hidden_size=32, layer_kinds=("mamba", "attention"), dff=48,
        num_heads=4, num_kv_heads=2, head_dim=8, ssm_heads=4, ssm_head_dim=16,
        ssm_state=16, chunk=8)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    paths = set(seeded.flatten(shapes))
    assert {p for p in paths if p[0] == "layer_1" and p[1] != "mixer"} == {
        ("layer_1", "mixer_norm", "scale"), ("layer_1", "mlp_norm", "scale"),
        ("layer_1", "mlp", "wg"), ("layer_1", "mlp", "wu"), ("layer_1", "mlp", "wd")}
    assert ("head", "kernel") not in paths and len(paths) == 24


def test_no_width_differs_from_the_source_and_the_cut_is_stated(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    row = next(json.loads(line) for line in open(CATALOG)
               if '"Ling-3.0-flash-VL"' in line)
    cfg = cell.config
    cut = {"num_hidden_layers": 7, "num_experts": 8, "vocab_size": 19648}
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == cut.get(key, value), key
    sizes = cfg["sizes"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads", "head_dim",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "rope_theta", "rms_norm_eps", "num_experts_per_tok",
                "moe_intermediate_size", "moe_shared_expert_intermediate_size",
                "routed_scaling_factor", "n_group", "topk_group",
                "short_conv_kernel_size", "kda_lower_bound", "vocab_size",
                "num_hidden_layers"):
        assert sizes[key] == cfg[key], key  # one number, stated twice
    assert sizes["num_experts"] == 512 == cfg["published"]["num_experts"]
    assert sizes["num_experts_held"] == cfg["num_experts"] == 8
    assert sizes["published_layer_index"] == [0, 2, 3, 4, 5, 6, 7]
    group = cfg["layer_group_size"]
    assert sizes["layer_types"] == [
        "mla" if (i + 1) % group == 0 else "kda" for i in sizes["published_layer_index"]]
    assert sizes["layer_types"].count("kda") == 5 + 1 and sizes["layer_types"][4] == "mla"
    assert sizes["mlp_layer_types"] == [
        "dense" if i < cfg["first_k_dense_replace"] else "sparse"
        for i in sizes["published_layer_index"]]
    assert all(cfg["expert_swiglu_limit_list"][i] == 0
               and cfg["share_expert_swiglu_limit_list"][i] == 0
               for i in sizes["published_layer_index"])
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert set(cfg["reduced"]) < set(cfg["cut"])
    assert cfg["published"]["vocab_size"] == 157184 == 8 * cfg["vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 42
    assert "64" in cfg["deployment"] and "stage of six" in cfg["deployment"]
    assert "vision tower is not" in cfg["published"]["described_as"]
    assert "sixty-fourth" in cfg["expert_load"]
    assumed = " ".join(cfg["assumed"])
    for mark in ("(i + 1) % layer_group_size", "no rotary in the KDA layers",
                 "reading not taken", "kda_safe_gate", "no_kda_lora", "half-split",
                 "multi-token prediction", "log-uniform in [0.001, 0.1]",
                 "uniform in [-0.05, 0.05]", "recomputed", "balancing update"):
        assert mark in assumed, mark


def test_the_parameters_of_the_cut_are_the_issues_arithmetic(cell):
    shapes = cell.module("reference").param_shapes(cell.sizes())[0]
    count = lambda pick: sum(int(np.prod(s)) for p, s in shapes.items() if pick(p))
    mixer = lambda i: count(lambda p: p[:2] == (f"layer_{i}", "mixer"))
    assert mixer(0) == 63_049_888 and mixer(4) == 31_965_696
    ffn = lambda p: p[0] == "layer_1" and p[1] not in ("mixer", "mixer_norm", "mlp_norm")
    assert count(ffn) == 54_395_392
    assert count(lambda p: p[0] == "layer_0") == 110_240_928
    assert count(lambda p: p[0] == "layer_1") == 117_450_400
    assert count(lambda p: p[0] == "layer_4") == 86_366_208
    assert count(lambda p: p[0] in ("embed", "head", "final_norm")) == 100_600_320
    assert count(lambda p: True) == 884_459_456


def test_the_cell_is_the_manifests(cell):
    cfg, mix = cell.config, cell.mix
    assert mix["sizes"] == {"per_rank_batch": 1, "seq_len": 8192}
    standing = manifest.resolve("laguna-xs.2-atc-warmup-b1-s8k-1chip").mix
    assert {k: v for k, v in mix.items() if k != "describes"} == {
        k: v for k, v in standing.items() if k != "describes"}
    assert mix["describes"] != standing["describes"] and "19,648" in mix["describes"]
    assert mix["optimizer"] == dict(cfg["optimizer"], warmup_steps=2000)
    bench = manifest.load_manifest()
    assert len(bench["configs"]) >= 6 and len(bench["workloads"]) >= 9
    entry = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert entry["source"] == cfg["source"] and entry["source"].endswith("config.json")
    assert entry["reduced"] == cfg["reduced"]
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == ["resnet50-atc-exp2-4chip"]
    assert bench["workloads"][8]["name"] == CELL and cell.chips == 1
    named = {p["name"] for p in bench["per_layer"] if CELL in p.get("workloads", [])}
    assert named == {
        "train_step_host_ms_per_step", "attention_ms_per_step",
        "attention_global_ms_per_step", "expert_ms_per_step",
        "expert_dispatch_ms_per_step", "optimizer_ms_per_step",
        "head_loss_ms_per_step", "mlp_ms_per_step", "attention_proj_ms_per_step",
        "recompute_ms_per_step",
        # this configuration's own
        "kda_kernels_ms_per_step", "kda_chunk_fwd_roofline", "kda_chunk_bwd_roofline",
        "kda_mixer_ms_per_step", "latent_proj_ms_per_step"}
    # not under the partition's remainder: the kda_* and mla_* ops would be
    # counted there a second time until RULES has groups for them
    unscoped = next(p for p in bench["per_layer"] if p["name"] == "unscoped_ms_per_step")
    assert CELL not in unscoped["workloads"]
    # the five this configuration brought, one of which a later cell reads too
    own = [p for p in bench["per_layer"][37:42] if CELL in p["workloads"]]
    assert [p["name"] for p in own] == [
        "kda_kernels_ms_per_step", "kda_chunk_fwd_roofline", "kda_chunk_bwd_roofline",
        "kda_mixer_ms_per_step", "latent_proj_ms_per_step"]
    assert [p["name"] for p in bench["per_layer"] if p.get("workloads") == [CELL]] \
        == [p["name"] for p in own[:4]]
    for p in own:
        assert p["moves"] == "train_samples_s_chip" and p["source"] == "device_trace"
        assert (p["unit"], p["better"]) == (
            ("%", "higher") if p["name"].endswith("_roofline") else ("ms", "lower"))


def test_flops_against_a_hand_count(cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    d, s, inner = 2560, 8192, 4096
    vis = 64 * 65 // 2
    fwd_kernel = 3 * 64 * 128 * 128 + vis * 128
    delta = 128 * 32 * ((vis - 64) * 128 + 3 * vis * 128 + fwd_kernel)
    kda = s * (6 * d * inner + d * 32) + delta
    mla = (s * (d * 32 * 192 + d * 576 + 512 * 32 * 256 + d * 32 + inner * d)
           + (s * (s + 1) // 2) * 32 * (192 + 128))
    experts = s * (d * 512 + 8 * 8 / 512 * 3 * d * 768 + 3 * d * 768)
    want = (s * d * 19648 + 6 * kda + mla + s * 3 * d * 6144 + 6 * experts)
    assert flops.forward_macs(sizes) == pytest.approx(want, rel=1e-12)
    assert flops.train_flops_per_sample(sizes) == pytest.approx(6 * want, rel=1e-12)
    assert flops.kernel_macs(sizes, "fwd") == fwd_kernel
    assert flops.kernel_macs(sizes, "bwd") == 7 * 64 * 128 * 128 + 2 * vis * 128
    # the delta rule's chunk products are a twentieth of a layer's products
    assert 0.03 < delta / kda < 0.08


def _op(name, path, within=None):
    return types.SimpleNamespace(name=name, path=path, within=within, recomputed=False)


def test_the_new_readers_find_their_kernels_and_scopes(cell):
    from chipbench import step_scopes

    flops, sizes = cell.module("flops"), cell.sizes()
    ops = {"%kda_chunk_fwd.3 = (bf16[...]": 4.0, "%kda_chunk_fwd.4": 6.0,
           "%kda_chunk_bwd.1": 9.0, "%attention_global.2": 7.0, "%fusion.9": 100.0,
           "%kda_chunk_fwd_other": 50.0, "%fusion.1": 2.0, "%fusion.2": 3.0,
           "%fusion.3": 5.0}
    root = "jit(local_step)/forward_backward/layer_2/mixer/"
    record = [(_op("kda_chunk_fwd.3", root + "kda_chunk/while/body", "while.1"), "x", 4.0),
              (_op("kda_chunk_fwd.4", root + "kda_chunk/while/body", "while.2"), "x", 6.0),
              (_op("kda_chunk_bwd.1", root + "kda_chunk/while/body", "while.3"), "x", 9.0),
              (_op("fusion.1", root + "kda_chunk/kda_intra/dot_general"), "x", 2.0),
              (_op("fusion.2", root + "kda_gates/kda_f/dot_general"), "x", 3.0),
              (_op("fusion.3", root + "mla_kv_up/dot_general"), "x", 5.0),
              (_op("fusion.9", root + "o/dot_general"), "attention_proj", 100.0)]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"trace": {"ops_ms_per_step": ops, step_scopes.MEMO: {
               "ops": record, "groups": {}, "recomputed": 0.0, "found": 0.0}},
           "peaks": peaks, "flops_per_sample": flops.train_flops_per_sample(sizes)}
    assert cell.reader("kda_kernels_ms_per_step").read(run) == 19.0
    assert cell.reader("kda_mixer_ms_per_step").read(run) == 5.0
    assert cell.reader("latent_proj_ms_per_step").read(run) == 5.0
    assert cell.reader("attention_global_ms_per_step").read(run) == 7.0
    trips = 32 // flops.HEADS_A_CALL
    for kernel, ms, sites in (("fwd", 10.0, 2), ("bwd", 9.0, 1)):
        work, nbytes = flops.kernel_call(sizes, kernel)
        ideal = sites * trips * max(work / 197e12, nbytes / 819e9)
        assert cell.reader(f"kda_chunk_{kernel}_roofline").read(run) \
            == pytest.approx(100 * ideal / (ms / 1e3))
    # a call outside a loop counts once
    record[0] = (_op("kda_chunk_fwd.3", root + "kda_chunk"), "x", 4.0)
    work, nbytes = flops.kernel_call(sizes, "fwd")
    assert cell.reader("kda_chunk_fwd_roofline").read(run) == pytest.approx(
        100 * (1 + trips) * max(work / 197e12, nbytes / 819e9) / 10e-3)
    # a program without such kernels or without a record of its step (the
    # parent's), a run without a trace, a rehearsal, a run of another cell:
    # nothing, no raise
    bare = {"trace": {"ops_ms_per_step": {"%fusion": 1.0}, step_scopes.MEMO: {
        "ops": [], "groups": {}, "recomputed": 0.0, "found": 0.0}}, "peaks": peaks,
        "flops_per_sample": run["flops_per_sample"]}
    for empty in ({"trace": None}, bare, dict(run, peaks=None),
                  dict(run, flops_per_sample=1.0)):
        assert cell.reader("kda_chunk_fwd_roofline").read(empty) is None
        assert cell.reader("kda_chunk_bwd_roofline").read(empty) is None
    for name in ("kda_kernels_ms_per_step", "kda_mixer_ms_per_step",
                 "latent_proj_ms_per_step"):
        assert cell.reader(name).read({"trace": None}) is None
        assert cell.reader(name).read(bare) is None


# ---- the cell's rehearsal: its limits and its controls ------------------------


def test_sound_readings_pass_and_the_float8_control_fails(cell):
    """chipbench.control at the rehearsal sizes, one CPU device, under the
    warm-up (the three steps run at 1.5e-7 to 4.5e-7)."""
    ses = runner.Session(cell, rehearse=True)
    try:
        row = control.readings(ses, 2**31 + 35, ["step"])
    finally:
        bf.shutdown()
    limits = ses.reference.LIMITS
    failed = lambda part: [k for k, v in row[part].items()
                           if k in limits and not v <= limits[k]]
    assert failed("sound") == [], row["sound"]
    assert failed("control_step"), row["control_step"]
    assert row["sound"]["change1_rel_l2"] > 0  # the parameters did move


def test_a_step_that_returns_its_state_unchanged_is_not_correct(cell):
    """The runner with a broken job in the timed path's place: `correct` has
    to come out false by one of the cell's limits."""
    class Unchanged:
        def __init__(self, job):
            self.job = job
            self.start = jax.tree_util.tree_map(jnp.copy, job.state)

        def __getattr__(self, name):
            return getattr(self.job, name)

        def step(self, k):
            out = self.job.step(k)
            self.job.state = jax.tree_util.tree_map(jnp.copy, self.start)
            return out

    args = type("Args", (), dict(rehearse=True, seed=2**31 + 99, seconds=0.5, trace=0))
    result = runner.run(args, 0.0, cell, wrap_job=Unchanged)
    assert result["correct"] is False
    over = [k for k, c in result["checks"].items()
            if c["limit"] and c["value"] is not None and c["value"] > c["limit"]]
    assert over, result["checks"]


def test_the_cell_rehearses_through_the_command_line():
    out = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", CELL, "--rehearse",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"train_samples_s_chip", "step_ms_p95", "setup_s"}
    assert set(line["checks"]) >= {"loss_gap", "grad_norm_gap", "delta_norm_gap",
                                   "change1_rel_l2", "assoc_p_gap"}

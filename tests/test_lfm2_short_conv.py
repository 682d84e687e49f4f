"""What the `lfm2-24b-a2b` configuration has of its own: the gated short
convolution's kernels against the definition and its `jax.grad`, the mixer and
the attention layer's norm-then-rotary against the plain reference's, the
router's epsilon, an expert layer without a shared expert, and the eight
shares adding up to the uncut layer.  The cases every decoder configuration
shares are `tests/test_decoder_cells.py`'s, over its entry of
`decoder_cells.TABLE`."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.kernels import causal_conv
from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models import hybrid
from bluefog_tpu.models.transformer import expert_feed_forward, rotary_frequencies
from bluefog_tpu.parallel.expert import route_topk
from decoder_cells import (LFM2, leaf_shapes, mixer_case, mixer_is_the_references,
                           no_gradient_reaches_the_bias, router_case, router_is_the_loop,
                           the_shares_add_up, values_and_grads)

REF = LFM2.reference
CELL = LFM2.cell_name
CONV, ATTENTION = [1], [2]   # a published layer of each kind


# ---- the manifest's entries --------------------------------------------------------


def test_the_cell_and_its_four_metrics_are_the_manifests():
    """One configuration, one cell on Kanana's traffic file, four metrics of
    its own; appended to the standing metrics whose readers read it right, and
    to none whose rules or kernels' names would read it wrong."""
    from chipbench import manifest

    cell, bench = LFM2.cell, manifest.load_manifest()
    assert cell.mix_name == "atc-warmup-b1-s8k-1chip" and cell.chips == 1
    assert [c["name"] for c in bench["configs"]].index(cell.config_name) == 7
    order = [w["name"] for w in bench["workloads"]]
    assert order.index(CELL) == 10
    by_name = {p["name"]: p for p in bench["per_layer"]}
    for name in ("unscoped_ms_per_step", "flash_fwd_global_roofline",
                 "flash_bwd_dkv_global_roofline", "flash_bwd_dq_global_roofline"):
        assert CELL not in by_name[name]["workloads"], name
    for name, unit, better, layer in (
            ("short_conv_mixer_ms_per_step", "ms", "lower", "train step"),
            ("short_conv_kernels_ms_per_step", "ms", "lower", "kernels"),
            ("short_conv_fwd_roofline", "%", "higher", "kernels"),
            ("short_conv_bwd_roofline", "%", "higher", "kernels")):
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": "device_trace",
            "layer": layer, "moves": "train_samples_s_chip", "workloads": [CELL]}
    for p in bench["per_layer"]:  # appended: only later cells stand after it
        listed = p.get("workloads", [])
        if CELL in listed:
            assert all(order.index(w) > 10 for w in listed[listed.index(CELL) + 1:])


# ---- the kernels against the definition --------------------------------------------


def _conv_case(batch, tokens, d, offset, beside, dtype):
    r = jax.random.split(jax.random.PRNGKey(tokens + d), 3)
    bcx = jax.random.normal(r[0], (batch, tokens, offset + 3 * d + beside)).astype(dtype)
    return bcx, 0.5 * jax.random.normal(r[1], (3, d)), jax.random.normal(r[2], (batch, tokens, d))


@pytest.mark.parametrize("batch,tokens,d,offset,beside,dtype,tol", [
    pytest.param(2, 32, 128, 0, 0, jnp.float32, 1e-5, id="shapes-that-tile"),
    pytest.param(1, 1040, 256, 0, 0, jnp.float32, 1e-5, id="a-last-block-cut-by-the-sequence"),
    pytest.param(2, 48, 128, 256, 128, jnp.float32, 1e-5, id="an-offset-product"),
    pytest.param(2, 64, 256, 0, 0, jnp.bfloat16, 2e-2, id="bfloat16-one-rounding")])
def test_short_conv_kernels_are_the_definition_and_its_gradient(batch, tokens, d, offset,
                                                                beside, dtype, tol):
    """`short_conv_fwd` / `short_conv_bwd` in interpret mode against `C *
    causal_conv(B * x, w, 0)` and its `jax.grad`: the output, the one `[T, 3
    d]` cotangent (zeros where the array is wider than the three chunks) and
    the taps' gradient."""
    bcx, taps, weight = _conv_case(batch, tokens, d, offset, beside, dtype)
    chunks = lambda a: a[..., offset:offset + 3 * d]
    got = values_and_grads(
        lambda a, k: causal_conv.short_conv(a, k, offset=offset).astype(jnp.float32),
        (bcx, taps), weight)
    want = values_and_grads(
        lambda a, k: hybrid.gated_short_conv(chunks(a), k).astype(jnp.float32),
        (bcx, taps), weight)
    assert got[0].shape == (batch, tokens, d) and got[1].shape == bcx.shape
    assert got[1].dtype == bcx.dtype
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b)
    if beside:  # the channels beside the three chunks got nothing
        outside = np.asarray(got[1], np.float32)
        assert not outside[..., :offset].any() and not outside[..., offset + 3 * d:].any()


def test_the_definition_is_three_shifted_multiply_adds():
    """`gated_short_conv` by hand: token t sees z[t - 2], z[t - 1], z[t], zeros
    before the sequence, and nothing of a later token."""
    bcx, taps, _ = _conv_case(1, 16, 8, 0, 0, jnp.float32)
    gate_b, gate_c, x = (np.asarray(bcx[0, :, i * 8:(i + 1) * 8], np.float64)
                         for i in range(3))
    z, w = gate_b * x, np.asarray(taps, np.float64)
    want = np.zeros_like(z)
    for t in range(16):
        for k in range(3):
            if t - 2 + k >= 0:
                want[t] += w[k] * z[t - 2 + k]
    np.testing.assert_allclose(hybrid.gated_short_conv(bcx, taps)[0], gate_c * want,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(REF.short_conv(jnp.asarray(z, jnp.float32), taps), want,
                               rtol=1e-5, atol=1e-6)
    later = bcx.at[0, 9:].set(0.0)
    np.testing.assert_array_equal(hybrid.gated_short_conv(later, taps)[0, :9],
                                  hybrid.gated_short_conv(bcx, taps)[0, :9])


@pytest.mark.parametrize("tokens,d,width", [(12, 128, 3), (32, 96, 3), (32, 128, 8)])
def test_shapes_that_do_not_tile_are_refused_by_the_kernels(tokens, d, width):
    assert not hybrid.short_conv_kernels_take(tokens, d, width)
    with pytest.raises(ValueError, match="128-lane"):
        causal_conv.short_conv(jnp.zeros((1, tokens, 3 * d)), jnp.zeros((width, d)))


def test_the_short_convolutions_kernels_stay_within_their_smoke_tolerance():
    """What `chip_smoke.py --only short_conv` holds the compiled kernels to at
    the cell's shapes, here at its rehearsal's: the kernels and the expression
    against the reference's float32 multiply-adds."""
    import chip_smoke

    chip_smoke.phase_short_conv(chip_smoke.TINY["short_conv"], 0, False,
                                chip_smoke._CompileClock())
    assert chip_smoke.PHASES[-1] == "short_conv"


# ---- the mixers against the reference's ------------------------------------------------


@pytest.mark.parametrize("tokens,kernels", [(64, True), (12, False)],
                         ids=["through-the-kernels", "tokens-that-do-not-tile"])
def test_short_conv_mixer_is_the_references(tokens, kernels):
    """`ShortConvMixer` at the rehearsal's hidden 128, value and every
    gradient: through the kernel pair where the tokens tile, through the
    definition where they do not."""
    sizes, leaves, u, weight = mixer_case(LFM2, published_layer_index=CONV, seq_len=tokens)
    assert set(leaves) == {("in_proj", "kernel"), ("conv_kernel",), ("out_proj", "kernel")}
    assert hybrid.short_conv_kernels_take(tokens, sizes["hidden_size"], 3) is kernels
    mixer_is_the_references(hybrid.ShortConvMixer(sizes["conv_L_cache"], jnp.float32),
                            REF.conv_mixer, leaves, u, weight, sizes)


def _attention_mixer(sizes, **fields):
    hd = sizes["hidden_size"] // sizes["num_attention_heads"]
    return hybrid._AttentionMixer(
        sizes["num_attention_heads"], sizes["num_key_value_heads"], hd, hd ** -0.5,
        jnp.float32,
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16),
        **fields)


def _attention_case():
    """The attention layer's leaves with the two head norms' scales drawn, so
    that a norm after the rotary is another function (under scales of 1 the
    two commute: a turn keeps a pair's length)."""
    sizes, leaves, u, weight = mixer_case(LFM2, published_layer_index=ATTENTION)
    r = jax.random.split(jax.random.PRNGKey(17), 2)
    for key, name in zip(r, ("q_norm", "k_norm")):
        leaves[(name, "scale")] = 1.0 + 0.5 * jax.random.normal(
            key, leaves[(name, "scale")].shape)
    return sizes, leaves, u, weight


def test_norm_a_head_then_rotary_is_the_references():
    sizes, leaves, u, weight = _attention_case()
    hd = sizes["hidden_size"] // sizes["num_attention_heads"]
    mixer = _attention_mixer(sizes, rotary=rotary_frequencies(hd, sizes["rope_theta"]),
                             qk_norm_eps=sizes["norm_eps"])
    mixer_is_the_references(mixer, REF.attention_mixer, leaves, u, weight, sizes)


@pytest.mark.parametrize("fields", [
    dict(qk_norm_eps=1e-5), dict(rotary=rotary_frequencies(32, 1e6))],
    ids=["no-rotary", "no-norm"])
def test_either_field_alone_is_another_layer(fields):
    sizes, leaves, u, weight = _attention_case()
    with pytest.raises(AssertionError):
        mixer_is_the_references(_attention_mixer(sizes, **fields), REF.attention_mixer,
                                leaves, u, weight, sizes)


def test_the_attention_mixers_defaults_are_what_granite_calls():
    """No norm, no rotary, no `q_norm` / `k_norm` leaf: Granite's leaves as
    they were; the two fields add the two scales of a head's width."""
    sizes = LFM2.cell.sizes(rehearse=True)
    u = jnp.zeros((1, 16, sizes["hidden_size"]), jnp.float32)
    shapes = lambda m: {p: s for p, s in leaf_shapes(m, u).items()}
    plain = shapes(_attention_mixer(sizes))
    assert {p[0] for p in plain} == {"q", "k", "v", "o"}
    both = shapes(_attention_mixer(sizes, rotary=rotary_frequencies(32, 1e6),
                                   qk_norm_eps=1e-5))
    assert {p: s for p, s in both.items() if p not in plain} == {
        ("q_norm", "scale"): (32,), ("k_norm", "scale"): (32,)}
    assert {p: both[p] for p in plain} == plain


# ---- the router's epsilon, an expert layer without a shared expert ---------------------


def test_route_topk_without_the_epsilon_is_todays_to_the_bit():
    """`eps` left at 0: the chosen scores over their bare sum, the expression
    as it stood, bit for bit; with LFM2's 1e-6 the weights' sum is `S / (S +
    eps)` and the reference's `route` agrees."""
    x, router, bias = router_case()
    experts, weights = route_topk(x, router, 4, 1.5, score="sigmoid", bias=bias)
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", x, router, precision="highest"))
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    np.testing.assert_array_equal(
        weights, chosen * (1.5 / jnp.sum(chosen, axis=-1, keepdims=True)))
    same, zero = route_topk(x, router, 4, 1.5, score="sigmoid", bias=bias, eps=0.0)
    np.testing.assert_array_equal(same, experts)
    np.testing.assert_array_equal(zero, weights)
    again, with_eps = route_topk(x, router, 4, 1.5, score="sigmoid", bias=bias, eps=0.25)
    np.testing.assert_array_equal(again, experts)    # the choice does not see it
    total = np.sum(np.asarray(chosen, np.float64), -1)
    np.testing.assert_allclose(np.sum(with_eps, -1), 1.5 * total / (total + 0.25), rtol=1e-6)
    # a softmax router's renormalisation takes it too
    _, soft = route_topk(x, router, 4, eps=0.25)
    assert np.all(np.sum(soft, -1) < 0.999)


def test_the_references_router_is_the_librarys_with_the_epsilon():
    sizes = dict(num_experts_per_tok=4, routed_scaling_factor=1)
    router_is_the_loop(REF, sizes, router_case())
    x, router, bias = router_case()
    p = {("b", "router"): router, ("b", "router_bias"): bias}
    e_ref, w_ref = REF.route(x, p, "b", sizes)
    e_lib, w_lib = route_topk(x, router, 4, 1, score="sigmoid", bias=bias, eps=REF.ROUTE_EPS)
    np.testing.assert_array_equal(e_ref, e_lib)
    np.testing.assert_allclose(w_ref, w_lib, rtol=1e-6)
    no_gradient_reaches_the_bias(4, 1.0, eps=REF.ROUTE_EPS)


class _Layer(nn.Module):
    shared_dff: int

    @nn.compact
    def __call__(self, m):
        return expert_feed_forward(self, m, 16, 4, (0, 1, 2, 3), 8, self.shared_dff, 1.0,
                                   jnp.float32, score="sigmoid", bias=True, eps=1e-6)


def test_an_expert_layer_without_a_shared_expert_has_no_such_leaf():
    m = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 16))
    bare, beside = _Layer(0), _Layer(8)
    params = beside.init(jax.random.PRNGKey(3), m)["params"]
    assert set(bare.init(jax.random.PRNGKey(3), m)["params"]) == {
        "router", "router_bias", "wg", "wu", "wd"}
    assert set(params) == {"router", "router_bias", "wg", "wu", "wd", "shared"}
    routed = {k: v for k, v in params.items() if k != "shared"}
    # the routed part alone: the layer beside its shared expert, less that expert
    from bluefog_tpu.models.transformer import _GatedMLP
    shared = _GatedMLP(8, jnp.float32).apply({"params": params["shared"]}, m)
    np.testing.assert_allclose(bare.apply({"params": routed}, m),
                               beside.apply({"params": params}, m) - shared, atol=1e-6)
    scopes = str(jax.make_jaxpr(lambda p: bare.apply({"params": p}, m))(routed))
    assert "moe_shared" not in scopes


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """64 experts over eight shares of 8 (experts 0-7, 8-15, ...), top-4 with
    the epsilon: the routed parts the shares compute add up to the reference's
    layer with every expert held."""
    sizes = dict(num_experts_per_tok=4, routed_scaling_factor=1)
    the_shares_add_up(REF, REF.routed_part, sizes, experts=64, per=8, shared_width=8,
                      eps=REF.ROUTE_EPS)

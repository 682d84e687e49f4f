"""What the `qwen3-next-80b-a3b` configuration has of its own: the gated delta
rule's stage kernels and the walk against the token-by-token scan at decays
the channel-decay kernels cannot take, value heads on fewer key heads against
explicit repetition, the stage's expression against its kernels, the gated
attention mixer and the gated-delta mixer against the plain reference's, the
shared expert's gate, the sixteen shares adding up to the uncut layer, and
what the lowered step does not hold.  The cases every decoder configuration
shares are `tests/test_decoder_cells.py`'s, over its entry of
`decoder_cells.TABLE`."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.kernels import causal_conv, gdn, kda
from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models import hybrid
from bluefog_tpu.models.transformer import (RMSNorm, expert_feed_forward,
                                            rotary_frequencies)
from bluefog_tpu.parallel.expert import held_topk_experts, route_topk
from decoder_cells import (QWEN3_NEXT, leaf_shapes, mixer_case, mixer_is_the_references,
                           router_case, values_and_grads)

REF = QWEN3_NEXT.reference
CELL = QWEN3_NEXT.cell_name
GDN, ATTENTION = [2], [3]   # a published layer of each kind
NEW_METRICS = (("gdn_mixer_ms_per_step", "ms", "lower", "train step"),
               ("gdn_kernels_ms_per_step", "ms", "lower", "kernels"),
               ("gdn_intra_fwd_roofline", "%", "higher", "kernels"),
               ("gdn_intra_bwd_roofline", "%", "higher", "kernels"),
               ("gdn_chunk_fwd_roofline", "%", "higher", "kernels"),
               ("gdn_chunk_bwd_roofline", "%", "higher", "kernels"))


# ---- the manifest's entries --------------------------------------------------------


def test_the_cell_and_its_six_metrics_are_the_manifests():
    """One configuration, one cell on the standing traffic file, six metrics
    of its own (the walk's two rooflines among them: Ling's readers take the
    loop's trips from `num_attention_heads`, which here are attention's 16 and
    not the delta rule's 32, and `tests/test_ling_hybrid.py` holds Ling's four
    to Ling alone); appended to the standing metrics whose readers read it
    right, and not to the flash rooflines, which cannot tell three kernels
    apart at 256 beside 256."""
    from chipbench import manifest

    cell, bench = QWEN3_NEXT.cell, manifest.load_manifest()
    assert cell.mix_name == "atc-warmup-b1-s8k-1chip" and cell.chips == 1
    assert [c["name"] for c in bench["configs"]].index(cell.config_name) == 8
    order = [w["name"] for w in bench["workloads"]]
    assert order.index(CELL) == 11 and len(order) == 12
    by_name = {p["name"]: p for p in bench["per_layer"]}
    for name in ("unscoped_ms_per_step", "flash_fwd_global_roofline",
                 "flash_bwd_dkv_global_roofline", "flash_bwd_dq_global_roofline",
                 "kda_chunk_fwd_roofline", "kda_chunk_bwd_roofline", "kda_mixer_ms_per_step",
                 "kda_kernels_ms_per_step"):
        assert CELL not in by_name[name]["workloads"], name
    for name, unit, better, layer in NEW_METRICS:
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": "device_trace",
            "layer": layer, "moves": "train_samples_s_chip", "workloads": [CELL]}
    for p in bench["per_layer"]:  # appended: only later cells stand after it
        listed = p.get("workloads", [])
        if CELL in listed:
            assert all(order.index(w) > 11 for w in listed[listed.index(CELL) + 1:])


# ---- the delta rule against the recurrence -----------------------------------------


def _scan(q, k, v, g, beta):
    """The reference's token-by-token recurrence on a batch, the key heads
    repeated and the unit vectors taken as its mixer takes them."""
    share = v.shape[2] // q.shape[2]
    q = jnp.repeat(REF.unit(q) * q.shape[-1] ** -0.5, share, axis=2)
    k = jnp.repeat(REF.unit(k), share, axis=2)
    return jax.vmap(REF.gdn_scan)(q, k, v, g, beta)


def _delta_case(batch, tokens, key_heads, heads, kd, vd, decay, seed=0):
    """q, k, v, the log-decay between `decay` / 5 and `decay` a token, the
    step, and a cotangent."""
    r = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (batch, tokens, key_heads, kd)) for key in r[:2])
    v = jax.random.normal(r[2], (batch, tokens, heads, vd))
    g = decay * jax.random.uniform(r[3], (batch, tokens, heads), minval=0.2, maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(r[4], (batch, tokens, heads)))
    return (q, k, v, g, beta), jax.random.normal(r[5], v.shape)


def _agree(got, want, tol):
    """Every array of `got` within `tol` of `want`'s, relative to the largest
    norm among `want`'s gradients where its own is smaller (at -30 a token
    g's gradient is 1e-4 of the others': a term the recurrence has lost)."""
    floor = max(float(jnp.linalg.norm(b)) for b in want[1:])
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        gap = float(jnp.linalg.norm(a - b)) / max(float(jnp.linalg.norm(b)), 1e-2 * floor)
        assert gap < tol, (name, gap)


@pytest.mark.parametrize("shape,decay,chunk,kernels", [
    pytest.param((1, 128, 2, 4, 128, 128), -0.1, 64, True, id="kernels-g-near-0"),
    pytest.param((1, 128, 2, 4, 128, 128), -30.0, 64, True, id="kernels-g-to-minus-30"),
    pytest.param((1, 192, 4, 8, 128, 128), -3.0, 64, True, id="kernels-two-groups-of-heads"),
    pytest.param((1, 100, 1, 2, 128, 128), -1.0, 32, True, id="kernels-a-padded-sequence"),
    pytest.param((2, 64, 2, 4, 16, 24), -0.1, 32, False, id="expression-g-near-0"),
    pytest.param((2, 64, 2, 4, 16, 24), -30.0, 32, False, id="expression-g-to-minus-30"),
    pytest.param((1, 64, 1, 3, 128, 128), -2.0, 32, True, id="kernels-three-on-one"),
    pytest.param((1, 64, 1, 6, 128, 128), -2.0, 32, False,
                 id="expression-six-on-one-no-whole-key-head-a-step")])
def test_stage_and_walk_are_the_recurrence(shape, decay, chunk, kernels):
    """`gdn_chunked` (the stage's kernels in interpret mode, or its expression
    where the shapes do not tile, and the walk's kernels) against the
    reference's scan: the output and all five gradients, at decays from near 0
    to -30 a token, where `exp(-G)` of the channel-decay form has left
    float32 after three tokens."""
    *_, heads, kd, vd = shape
    share = heads // shape[2]
    assert gdn.kernels_take(kd, vd, heads, share) is kernels
    args, weight = _delta_case(*shape, decay)
    got = values_and_grads(lambda *a: gdn.gdn_chunked(*a, chunk=chunk), args, weight)
    want = values_and_grads(_scan, args, weight)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in got)
    _agree(got, want, 2e-5)


def test_the_channel_decay_kernels_cannot_be_handed_this_decay():
    """`kda_chunked` with the same decay broadcast to a head's channels is not
    finite at -30 a token: its stage forms exp(+-(G - G[m])) on sub-blocks of
    16 tokens.  At -0.1 it is the same function."""
    for decay, finite in ((-0.1, True), (-30.0, False)):
        (q, k, v, g, beta), _ = _delta_case(1, 64, 2, 2, 16, 16, decay)
        wide = jnp.broadcast_to(g[..., None], q.shape)
        out = kda.kda_chunked(q, k, v, wide, beta, chunk=32)
        assert bool(jnp.all(jnp.isfinite(out))) is finite
        if finite:
            np.testing.assert_allclose(out, gdn.gdn_chunked(q, k, v, g, beta, chunk=32),
                                       atol=2e-6)


def test_shared_key_heads_are_the_heads_repeated():
    """32-on-16 in small: value heads 2j and 2j + 1 on key head j, through the
    kernels, against the same call with q and k repeated to a key head a value
    head; the shared heads' cotangents are the pair's sums."""
    args, weight = _delta_case(1, 64, 2, 4, 128, 128, -1.0, seed=3)
    q, k, *rest = args
    got = values_and_grads(lambda *a: gdn.gdn_chunked(*a, chunk=32), args, weight)
    repeated = (jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), *rest)
    want = values_and_grads(lambda *a: gdn.gdn_chunked(*a, chunk=32), repeated, weight)
    pairs = lambda d: d.reshape(d.shape[:2] + (2, 2, -1)).sum(axis=3)
    want = (want[0], pairs(want[1]), pairs(want[2])) + want[3:]
    _agree(got, want, 2e-6)


def test_the_stages_expression_is_its_kernels():
    """`stage_expression` (the definition) against `_intra`'s kernels: the six
    arrays the walk takes, `exp G[last]` the one number in every lane."""
    (q, k, v, g, beta), _ = _delta_case(1, 64, 1, 2, 128, 128, -4.0, seed=5)
    flat = tuple(a.reshape(a.shape[:2] + (-1,)) for a in (q, k, v)) + (g, beta)
    made = gdn._intra(*flat, 2, 32, True)
    want = gdn.stage_expression(*flat, 2, 32)
    for name, a, b in zip(("qg", "p", "w", "u0", "kd", "gam"), made, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=3e-6, err_msg=name)
    gam = np.asarray(made[5])
    assert gam.shape == (1, 2, 2, 1, 128) and np.all(gam == gam[..., :1]) and np.all(gam <= 1)


def test_a_chunk_that_is_no_power_of_two_of_sub_blocks_is_refused():
    (q, k, v, g, beta), _ = _delta_case(1, 48, 1, 2, 16, 16, -1.0)
    with pytest.raises(ValueError, match="16 times a power of two"):
        gdn.gdn_chunked(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="value heads on key heads"):  # three on two
        gdn.gdn_chunked(jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)[:, :, :3],
                        g, beta, chunk=16)


def _pallas_calls(jaxpr):
    """Every `pallas_call` equation of a jaxpr and of the jaxprs within it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


def test_the_lowered_mixer_hands_the_kernels_no_broadcast_decay_and_no_repeated_key_head():
    """At the cell's widths (8,192 tokens, 32 value heads on 16 key heads of
    128) the mixer's jaxpr, forward and backward: `g` and `beta` reach the
    stage's kernels as `[1, T, 4]` a group of four value heads, q and k as that
    group's two key heads `[1, T, 256]` beside v's `[1, T, 512]`; the chunk's
    decay reaches the walk as `[.., 1, 128]` a chunk; nowhere is the decay an
    array of `[.., T, 32, 128]` or are q and k arrays of 32 heads."""
    mixer = hybrid.GatedDeltaNetMixer(32, 16, 128, 128, dtype=jnp.bfloat16)
    u = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16)
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros(
        (1, 64, 2048), jnp.bfloat16)))["params"]

    def loss(p, x):
        return jnp.sum(mixer.apply({"params": p}, x).astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, u)
    calls = {}
    for eqn in _pallas_calls(jaxpr.jaxpr):
        calls.setdefault(eqn.params["name"], []).append(
            [v.aval.str_short() for v in eqn.invars])
    assert set(calls) == {"causal_conv_fwd", "causal_conv_bwd", "gdn_intra_fwd",
                          "gdn_intra_bwd", "kda_chunk_fwd", "kda_chunk_bwd"}
    read = ["bfloat16[1,8192,256]"] * 2 + ["bfloat16[1,8192,512]"] + ["float32[1,8192,4]"] * 2
    assert [c[:5] for c in calls["gdn_intra_fwd"]] == [read, read]  # and the group's again
    assert [c[:5] for c in calls["gdn_intra_bwd"]] == [read]
    assert all(c[5] == "float32[1,4,128,1,128]" for c in calls["kda_chunk_fwd"])
    # the convolution reads the product where it is; nothing else is a kernel's
    assert calls["causal_conv_fwd"][0][0] == "bfloat16[1,8192,12288]"
    text = str(jaxpr)
    for shape in ("f32[1,8192,16,256]", "bf16[1,8192,16,256]", "f32[8,1,8192,512]",
                  "f32[8,1,8192,4,128]", "f32[1,8192,32,128] = exp", "f32[1,8192,4096] = exp"):
        assert shape not in text, shape
    assert "f32[8,1,8192,4]" in text and "bf16[8,1,8192,256]" in text  # the groups' split


# ---- the mixers against the reference's ------------------------------------------------


@pytest.mark.parametrize("tokens,conv_kernels", [(64, True), (36, False)],
                         ids=["through-the-kernels", "tokens-that-do-not-tile"])
def test_gated_delta_mixer_is_the_references(tokens, conv_kernels):
    """`GatedDeltaNetMixer` at the rehearsal's 2 value heads on 1 key head of
    128, value and every gradient (A_log and dt_bias among them), the sequence
    padded to whole chunks where it is none."""
    sizes, leaves, u, weight = mixer_case(QWEN3_NEXT, published_layer_index=GDN,
                                          seq_len=tokens)
    # decays under which a token's state lasts: A_log's and dt_bias's gradients
    # are then of the others' size, not float32's noise beside them
    leaves[("A_log",)] = jnp.log(jnp.array([0.05, 0.5]))
    assert set(leaves) == {("gdn_qkvz", "kernel"), ("gdn_ba", "kernel"), ("conv_kernel",),
                           ("A_log",), ("dt_bias",), ("gdn_norm", "scale"),
                           ("gdn_o", "kernel")}
    assert causal_conv.tiles(tokens, 512, 4) is conv_kernels
    mixer = hybrid.GatedDeltaNetMixer(
        sizes["linear_num_value_heads"], sizes["linear_num_key_heads"],
        sizes["linear_key_head_dim"], sizes["linear_value_head_dim"],
        sizes["linear_conv_kernel_dim"], sizes["gdn_chunk_size"], sizes["rms_norm_eps"],
        jnp.float32)
    mixer_is_the_references(mixer, REF.gdn_mixer, leaves, u, weight, sizes)


def _attention_mixer(sizes, **fields):
    hd = sizes["head_dim"]
    return hybrid._AttentionMixer(
        sizes["num_attention_heads"], sizes["num_key_value_heads"], hd, hd ** -0.5,
        jnp.float32,
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16),
        **fields)


def _attention_case():
    """The attention layer's leaves with the two head norms' zero-centred
    scales drawn, so that a plain scale, or a norm after the rotary, is another
    function."""
    sizes, leaves, u, weight = mixer_case(QWEN3_NEXT, published_layer_index=ATTENTION)
    r = jax.random.split(jax.random.PRNGKey(17), 2)
    for key, name in zip(r, ("q_norm", "k_norm")):
        leaves[(name, "scale")] = 0.5 * jax.random.normal(key, leaves[(name, "scale")].shape)
    return sizes, leaves, u, weight


def _qwen_fields(sizes):
    return dict(rotary=rotary_frequencies(REF.rotary_dims(sizes), sizes["rope_theta"]),
                qk_norm_eps=sizes["rms_norm_eps"], gate=True, zero_centered=True)


def test_gated_attention_with_its_norms_and_quarter_rotary_is_the_references():
    sizes, leaves, u, weight = _attention_case()
    assert leaves[("q", "kernel")].shape == (128, 4, 64) and REF.rotary_dims(sizes) == 8
    mixer_is_the_references(_attention_mixer(sizes, **_qwen_fields(sizes)),
                            REF.attention_mixer, leaves, u, weight, sizes)


@pytest.mark.parametrize("changed", [
    dict(zero_centered=False), dict(rotary=rotary_frequencies(32, 1e7)), dict(rotary=None)],
    ids=["plain-scales", "the-whole-head-turned", "no-rotary"])
def test_each_field_of_the_attention_mixer_matters(changed):
    sizes, leaves, u, weight = _attention_case()
    with pytest.raises(AssertionError):
        mixer_is_the_references(
            _attention_mixer(sizes, **dict(_qwen_fields(sizes), **changed)),
            REF.attention_mixer, leaves, u, weight, sizes)


def test_the_new_fields_defaults_are_what_lfm2_and_granite_call():
    """No gate: `q`'s product a head wide and no `attention_gate` scope; plain
    scales from ones under the same leaf names; the gate doubles `q`'s product
    and adds no leaf."""
    sizes = QWEN3_NEXT.cell.sizes(rehearse=True)
    u = jnp.zeros((1, 16, sizes["hidden_size"]), jnp.float32)
    lfm2 = dict(rotary=rotary_frequencies(32, 1e6), qk_norm_eps=1e-5)
    plain, gated = _attention_mixer(sizes, **lfm2), _attention_mixer(sizes, gate=True, **lfm2)
    assert leaf_shapes(plain, u)[("q", "kernel")] == (128, 4, 32)
    assert {p: s for p, s in leaf_shapes(gated, u).items()
            if leaf_shapes(plain, u)[p] != s} == {("q", "kernel"): (128, 4, 64)}
    params = plain.init(jax.random.PRNGKey(0), u)["params"]
    assert float(params["q_norm"]["scale"][0]) == 1.0
    assert "attention_gate" not in str(jax.make_jaxpr(
        lambda p: plain.apply({"params": p}, u))(params))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    for centred, start in ((False, 1.0), (True, 0.0)):
        norm = RMSNorm(zero_centered=centred)
        p = norm.init(jax.random.PRNGKey(0), x)
        assert float(p["params"]["scale"][0]) == start
        np.testing.assert_allclose(  # both start as the norm of scale 1
            norm.apply(p, x), x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6),
            rtol=1e-6)


# ---- the shared expert's gate, the shares ----------------------------------------------


class _Layer(nn.Module):
    shared_gate: bool

    @nn.compact
    def __call__(self, m):
        return expert_feed_forward(self, m, 16, 4, (0, 1, 2, 3), 8, 8, 1.0, jnp.float32,
                                   shared_gate=self.shared_gate)


def test_the_shared_experts_gate_is_a_keyword_and_a_leaf():
    """Without the keyword no `shared_gate` leaf and the layer as it stood;
    with it the shared expert's output times sigmoid(m W_sg) a token."""
    from bluefog_tpu.models.transformer import _GatedMLP

    m = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 16))
    bare, gated = _Layer(False), _Layer(True)
    params = gated.init(jax.random.PRNGKey(3), m)["params"]
    assert set(bare.init(jax.random.PRNGKey(3), m)["params"]) == {
        "router", "wg", "wu", "wd", "shared"}
    assert set(params) == {"router", "wg", "wu", "wd", "shared", "shared_gate"}
    assert params["shared_gate"].shape == (16, 1)
    params = dict(params, shared_gate=jax.random.normal(jax.random.PRNGKey(4), (16, 1)))
    routed = {k: v for k, v in params.items() if k != "shared_gate"}
    shared = _GatedMLP(8, jnp.float32).apply({"params": params["shared"]}, m)
    gate = jax.nn.sigmoid(m @ params["shared_gate"])
    np.testing.assert_allclose(
        gated.apply({"params": params}, m),
        bare.apply({"params": routed}, m) - shared + gate * shared, atol=1e-6)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """512 experts over sixteen shares of 32 (experts 0-31, 32-63, ...), top-10
    of a softmax over all 512, renormalised: the routed parts the shares
    compute (`held_topk_experts`, each told its experts) plus the gated shared
    expert once are the uncut reference's layer, every share is the
    reference's, and every share adds."""
    d, f, experts, per = 24, 8, 512, 32
    sizes = dict(num_experts_per_tok=10)
    x, router, _ = router_case(tokens=192, d=d, experts=experts, seed=3)
    r = jax.random.split(jax.random.PRNGKey(9), 7)
    stack = lambda key, *shape: 0.3 * jax.random.normal(key, shape)
    p = {("b", "router"): router,
         ("b", "wg"): stack(r[0], experts, d, f), ("b", "wu"): stack(r[1], experts, d, f),
         ("b", "wd"): stack(r[2], experts, f, d),
         ("b", "shared", "wg"): stack(r[3], d, f), ("b", "shared", "wu"): stack(r[4], d, f),
         ("b", "shared", "wd"): stack(r[5], f, d), ("b", "shared_gate"): stack(r[6], d, 1)}
    shared = REF.shared_part(x, p, "b", False)
    whole = REF.routed_part(x, p, "b", sizes, False, tuple(range(experts))) + shared
    chosen, weights = route_topk(x, router, 10, 1.0)
    ref_chosen, ref_weights = REF.route(x, p, "b", sizes)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(ref_chosen, -1))
    np.testing.assert_allclose(np.sort(weights, -1), np.sort(ref_weights, -1), rtol=2e-5)
    np.testing.assert_allclose(np.sum(weights, -1), 1.0, rtol=1e-5)
    total = shared                                          # every share's alike: once
    for share in range(experts // per):
        held = tuple(range(share * per, (share + 1) * per))
        stacks = {n: p[("b", n)][share * per:(share + 1) * per] for n in ("wg", "wu", "wd")}
        part = held_topk_experts(x, chosen, weights, stacks, held, experts,
                                 activation=jax.nn.silu)
        one = REF.routed_part(x, {**p, **{("b", n): stacks[n] for n in stacks}}, "b", sizes,
                              False, held)
        np.testing.assert_allclose(part, one, atol=2e-5)   # a share is the reference's
        assert float(jnp.max(jnp.abs(part))) > 0
        total = total + part
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert float(jnp.max(jnp.abs(whole - shared))) > 10 * 5e-5


# ---- what chip_smoke.py holds the compiled kernels to ----------------------------------


def test_the_gated_delta_rule_stays_within_its_smoke_tolerance():
    """What `chip_smoke.py --only gdn_vs_recurrence` holds the compiled kernels
    to at the cell's shapes, here at tiny ones in interpret mode: the stage and
    the walk against the scan at `g` near 0 and down to -30 a token, and the
    flash kernels at a gated layer's head size against dense attention."""
    import chip_smoke

    chip_smoke.phase_gdn(chip_smoke.TINY["gdn"], 0, False, chip_smoke._CompileClock())
    assert "gdn_vs_recurrence" in chip_smoke.PHASES

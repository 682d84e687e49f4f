"""The gated layers of `models.transformer.MixedAttentionMoELM` (poolside's
Laguna-XS.2, chipbench's `laguna-xs.2`) against the configuration's plain
reference at a small size: loss and gradients, the shares of the expert layer
adding up to the uncut layer, the two rotaries, the gate, the head counts by
layer kind; the flash kernels reading shared key-value heads in place against
the repeated call; `route_topk`'s scale; the configuration file against its
published source; the FLOP count against a hand count; the cell's rehearsal
against its limits and its float8 control."""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bluefog_tpu as bf
from bluefog_tpu.kernels.flash_attention import _Band, flash_attention
from bluefog_tpu.models import transformer as tr
from bluefog_tpu.parallel import expert as ep
from bluefog_tpu.telemetry import registry as telemetry
from bluefog_tpu.training import make_lm_loss_fns

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import control, manifest, runner, seeded  # noqa: E402

CELL = "laguna-xs.2-atc-warmup-b1-s8k-1chip"


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL)


def _small(cell, **over):
    """The rehearsal's sizes with a router that has to choose: 3 of 8
    experts, 4 of them held here."""
    return dict(cell.sizes(rehearse=True), num_experts=8, num_experts_per_tok=3,
                num_experts_held=4, **over)


def _widened(params):
    """std 0.02 at hidden 64 leaves the experts' and the gate's terms at 1e-4
    of the stream: widen them so that a wrong expert or gate shows."""
    return {p: a * (12.0 if p[-1] in ("wg", "wu", "wd", "router")
                    or p[-2] == "gate" else 1.0) for p, a in params.items()}


def _float32_program(cell, sizes):
    model = cell.module("program").build(sizes)["model"].clone(dtype=jnp.float32)
    return make_lm_loss_fns(model)[0]


# the band inside the sequence and across several blocks (16 rows a block),
# as long as the sequence, and past it
@pytest.mark.parametrize("seq_len,window", [(64, 24), (32, 32), (32, 80)])
def test_loss_and_gradients_match_the_plain_reference(cell, seq_len, window):
    """4 (full) and 6 (window) query heads on 2 key-value heads of 16 read in
    place, YaRN over half a head and a plain rotary over a whole one, the
    gate, the leading dense layer, the router after the attention choosing 3
    of 8 with 4 held, the shared expert, the chunked loss over the slice."""
    sizes = _small(cell, seq_len=seq_len, sliding_window=window)
    ref = cell.module("reference")
    apply_fn = _float32_program(cell, sizes)
    params = _widened(seeded.make_weights(ref, sizes, seed=11)[0])
    (x, y), = seeded.make_batches(ref, sizes, 11, ranks=1, pool=1)
    x, y = x[0], y[0]
    lp, gp = jax.jit(jax.value_and_grad(
        lambda p: apply_fn({"params": seeded.nest(p)}, x, labels=y)))(params)
    (lr, _), gr = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, {}, x, y, sizes), has_aux=True))(params)
    assert abs(float(lp) - float(lr)) < 1e-5
    assert set(gp) == set(gr) == set(ref.param_shapes(sizes)[0])
    for path in gr:
        a, b = np.asarray(gp[path], np.float64), np.asarray(gr[path], np.float64)
        assert np.linalg.norm(b) > 0, path
        assert np.linalg.norm(a - b) / np.linalg.norm(b) < 2e-3, path


def test_the_shares_add_up_to_the_uncut_layer(cell):
    """64 experts at 8 a share: the eight shares' expert terms, with what
    every share computes alike (the attention, the shared expert) counted
    once, are the uncut reference layer; the program's layer given a share
    computes that share's part."""
    sizes = dict(_small(cell, seq_len=32), num_experts=64, num_experts_per_tok=6,
                 num_experts_held=64)
    ref = cell.module("reference")
    params = _widened(seeded.make_weights(ref, sizes, seed=5)[0])
    spec, b = ref.layers(sizes)[1], "layer_1"  # a window layer with experts
    x = jax.random.normal(jax.random.PRNGKey(5), (32, sizes["hidden_size"]))
    whole = ref.layer(x, params, b, spec, sizes, False, tuple(range(64)))
    after_attention = ref.attention_part(x, params, b, spec[0], spec[2], sizes, False)
    m = ref._rms_norm(after_attention, params[(b, "ffn_norm", "scale")])
    alike = after_attention + ref.gated_mlp(m, params, (b, "shared"), False)

    block = tr._GatedBlock(
        num_heads=spec[1], num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], window=spec[2],
        rotary=cell.module("program").rotary(sizes, spec[0]), dense_dff=None,
        num_experts=64, top_k=6, experts_held=(), expert_dff=32, shared_dff=32,
        routed_scale=sizes["moe_routed_scaling_factor"], dtype=jnp.float32,
        attention_fn=lambda q, k, v, window: flash_attention(
            q, k, v, causal=True, window=window, block_q=8, block_k=8))
    terms_ref, terms_program = [], []
    for s in range(8):
        ids = tuple(range(8 * s, 8 * s + 8))
        share = dict(params)
        for name in ("wg", "wu", "wd"):
            share[(b, name)] = params[(b, name)][8 * s:8 * s + 8]
        terms_ref.append(ref.layer(x, share, b, spec, sizes, False, ids) - alike)
        out = block.clone(experts_held=ids).apply(
            {"params": seeded.nest(share)[b]}, x[None], jnp.arange(32))[0]
        terms_program.append(out - alike)
        np.testing.assert_allclose(terms_program[-1], terms_ref[-1], atol=2e-5)
    assert max(float(jnp.max(jnp.abs(t))) for t in terms_ref) > 1e-3  # every share adds
    np.testing.assert_allclose(alike + sum(terms_ref), whole, atol=2e-5)
    np.testing.assert_allclose(alike + sum(terms_program), whole, atol=5e-5)


# ---- the two rotaries ------------------------------------------------------


def test_yarn_frequencies_and_factor_against_the_closed_form(cell):
    """rope_parameters.full_attention as published: 64 of 128 dimensions,
    base 500,000, factor 64 over 4,096 positions, beta 64 and 1."""
    sizes = cell.sizes()
    got = cell.module("program").rotary(sizes, "full_attention")
    freq, factor = cell.module("reference").rotary_table(sizes, "full_attention")
    assert len(got.inv_freq) == 32
    np.testing.assert_allclose(got.inv_freq, freq, rtol=1e-12)
    # the config's own attention_factor is 0.1 ln 64 + 1 to ten digits
    assert factor == 1.4158883083359672
    assert got.factor == pytest.approx(0.1 * math.log(64) + 1, rel=1e-15)
    assert got.factor == pytest.approx(factor, rel=1e-10)
    # frequency i turns 4096 f_i / 2 pi times: 64 times at i = 5.7, once at 15.8
    plain = 500000.0 ** (-np.arange(32) / 32)
    turns = 4096 * plain / (2 * math.pi)
    assert turns[5] > 64 > turns[6] and turns[15] > 1 > turns[16]
    ramp = np.clip((np.arange(32) - 5) / (16 - 5), 0, 1)  # ends rounded outwards
    np.testing.assert_allclose(
        got.inv_freq, plain * (1 - ramp) + plain / 64 * ramp, rtol=1e-12)
    assert got.inv_freq[:6] == pytest.approx(plain[:6], rel=1e-15)   # kept
    assert got.inv_freq[16:] == pytest.approx(plain[16:] / 64, rel=1e-15)
    window = cell.module("program").rotary(sizes, "sliding_attention")
    assert window.factor == 1.0 and len(window.inv_freq) == 64
    np.testing.assert_allclose(window.inv_freq, 10000.0 ** (-np.arange(64) / 64),
                               rtol=1e-12)


def test_a_rotary_over_half_a_head_leaves_the_other_half_untouched():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 128))
    pos = jnp.arange(8)
    rot = tr.rotary_frequencies(64, 500000.0, factor=64.0, original_max=4096,
                                beta_fast=64.0, beta_slow=1.0)
    out = tr._rotary(x, pos, rotary=rot)
    assert np.array_equal(out[..., 64:], x[..., 64:])
    assert not np.allclose(out[:, 1:, :, :64], x[:, 1:, :, :64])
    # position 0 turns nothing and is scaled by the factor; the rotation keeps
    # the norm of each pair (i, i + 32) of the rotated half, times the factor
    np.testing.assert_allclose(out[:, 0, :, :64], rot.factor * x[:, 0, :, :64],
                               rtol=1e-6)
    pairs = lambda a: jnp.hypot(a[..., :32], a[..., 32:64])
    np.testing.assert_allclose(pairs(out), rot.factor * pairs(x), rtol=1e-5)
    # a plain rotary over the whole head is the call that takes a base, up to
    # the float64 in which the frequencies are worked out
    whole = tr._rotary(x, pos, rotary=tr.rotary_frequencies(128, 10000.0))
    np.testing.assert_allclose(whole, tr._rotary(x, pos, 10000.0), atol=1e-5)


# ---- the gate and the head counts -----------------------------------------


def test_a_zero_gate_halves_every_head(cell):
    sizes = _small(cell, seq_len=32)
    ref = cell.module("reference")
    params = seeded.make_weights(ref, sizes, seed=3)[0]
    b, (kind, heads, window, _) = "layer_1", ref.layers(sizes)[1]
    zero = dict(params)
    zero[(b, "gate", "kernel")] = jnp.zeros_like(params[(b, "gate", "kernel")])
    x = jax.random.normal(jax.random.PRNGKey(3), (32, sizes["hidden_size"]))
    seen = []

    def attention(q, k, v, window):
        seen.append(flash_attention(q, k, v, causal=True, window=window,
                                    block_q=8, block_k=8))
        return seen[-1]

    block = tr._GatedBlock(
        num_heads=heads, num_kv_heads=2, head_dim=16, window=window,
        rotary=cell.module("program").rotary(sizes, kind),
        dense_dff=None, num_experts=8, top_k=3, experts_held=(0, 1, 2, 3),
        expert_dff=32, shared_dff=32, routed_scale=2.5, dtype=jnp.float32,
        attention_fn=attention)
    _, state = block.apply({"params": seeded.nest(zero)[b]}, x[None],
                           jnp.arange(32), capture_intermediates=True,
                           mutable=["intermediates"])
    written = state["intermediates"]["o"]["__call__"][0][0]   # what W_o returned
    ungated = seen[0][0].reshape(32, -1) @ params[(b, "o", "kernel")]
    np.testing.assert_allclose(written, 0.5 * ungated, rtol=1e-5, atol=1e-7)
    got = ref.attention_part(x, zero, b, kind, window, sizes, False) - x
    np.testing.assert_allclose(got, 0.5 * ungated, rtol=1e-4, atol=1e-6)


def test_head_counts_by_layer_kind_in_the_parameter_shapes(cell):
    """The program's parameter tree at the cell's sizes is the reference's:
    48 query heads where the layer is full attention, 64 where it slides, 8
    key-value heads everywhere, a gate of one number a head, the dense layer
    first, the shared expert beside the 32 held."""
    sizes = cell.sizes()
    model = cell.module("program").build(sizes)["model"]
    assert model.layer_windows == (None, 512, 512, 512, None)
    assert model.layer_heads == (48, 64, 64, 64, 48) and model.num_kv_heads == 8
    assert model.layer_dense_dff == (8192, None, None, None, None)
    assert (model.top_k, model.num_experts, model.routed_scale) == (8, 256, 2.5)
    assert model.experts_held == tuple(range(32)) and model.shared_dff == 512
    assert [2 * len(r.inv_freq) for r in model.layer_rotary] == [64, 128, 128, 128, 64]
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)  # the shapes ask no sequence
    tree = jax.eval_shape(lambda i: model.init(jax.random.PRNGKey(0), i), ids)
    got = {p: a.shape for p, a in seeded.flatten(tree["params"]).items()}
    want, _ = cell.module("reference").param_shapes(sizes)
    assert got == want
    assert want[("layer_0", "q", "kernel")] == (2048, 48, 128)
    assert want[("layer_1", "q", "kernel")] == (2048, 64, 128)
    assert want[("layer_4", "gate", "kernel")] == (2048, 48)
    assert want[("layer_2", "k", "kernel")] == (2048, 8, 128)
    assert want[("layer_0", "mlp", "wg")] == (2048, 8192)
    assert want[("layer_3", "wd")] == (32, 512, 2048)
    assert want[("layer_3", "shared", "wu")] == (2048, 512)
    assert ("layer_0", "router") not in want and ("layer_1", "mlp", "wg") not in want
    count = lambda prefix: sum(int(np.prod(s)) for p, s in want.items()
                               if p[0] == prefix and p[-1] not in ("wg", "wu", "wd")
                               or p[:2] == (prefix, "shared") or p[:2] == (prefix, "mlp"))
    assert count("layer_0") == 79_794_176          # the dense layer, full attention
    assert count("layer_1") == 41_553_920          # a window layer beside its experts
    assert count("layer_4") == 33_132_544          # the full sparse layer beside its
    assert sum(int(np.prod(s)) for s in want.values()) == 691_623_936


def test_the_model_sets_its_gauges(cell, monkeypatch, tmp_path):
    monkeypatch.setenv("BFTPU_TELEMETRY", str(tmp_path))
    telemetry.reset()
    try:
        model = cell.module("program").build(_small(cell, seq_len=32))["model"]
        jax.eval_shape(lambda i: model.init(jax.random.PRNGKey(0), i),
                       jax.ShapeDtypeStruct((1, 32), jnp.int32))
        gauges = {g["name"]: g["value"] for g in
                  telemetry.get_registry().snapshot()["gauges"]}
    finally:
        telemetry.reset()
    assert {k: v for k, v in gauges.items() if k in WANTED_GAUGES} == WANTED_GAUGES
    assert gauges["attention.window"] == 24 and gauges["moe.experts_held"] == 4


WANTED_GAUGES = {
    "attention.heads_window": 6, "attention.heads_global": 4,
    "attention.kv_heads": 2, "attention.rotary_dims_window": 16,
    "attention.rotary_dims_global": 8, "moe.shared_width": 32,
    "moe.routed_scale": 2.5, "moe.dense_layers": 1}


def test_per_layer_lists_of_unequal_length_are_refused(cell):
    model = cell.module("program").build(_small(cell))["model"]
    with pytest.raises(ValueError, match="5 layers, but 4 head counts"):
        model.clone(layer_heads=(4, 6, 6, 6)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    with pytest.raises(ValueError, match="not divisible"):
        model.clone(layer_heads=(4, 5, 6, 6, 4)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))


# ---- the kernels read the shared heads where they lie -------------------------

B, T, H, KV, D = 2, 64, 6, 2, 16


@pytest.mark.parametrize("blocks", [(8, 8), (16, 8), (8, 16)])
@pytest.mark.parametrize("window", [None, 5, 24, T + 36])
def test_shared_heads_in_place_are_the_repeated_call(window, blocks):
    """Values and all three gradients (dQ; dK and dV summed over the group
    inside the kernel), Pallas in interpret mode."""
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q, g = (jax.random.normal(k, (B, T, H, D)) for k in keys[:2])
    k, v = (jax.random.normal(k, (B, T, KV, D)) for k in keys[2:])
    kw = dict(causal=True, window=window, block_q=blocks[0], block_k=blocks[1],
              impl="pallas")

    def loss(q, k, v, repeat):
        if repeat:
            k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
        out = flash_attention(q, k, v, **kw)
        return jnp.sum(out * g), out

    (_, out), got = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v, False)
    (_, ref), want = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v, True)
    assert np.array_equal(out, ref) and np.array_equal(got[0], want[0])  # same arithmetic
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for a, b in zip(got[1:], want[1:]):  # another order of the group's sum
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_the_xla_fall_back_takes_shared_heads_too():
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (1, 32, 4, 8))
    k, v = (jax.random.normal(key, (1, 32, 2, 8)) for key in keys[1:])
    loss = lambda impl: lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=12, block_q=8, block_k=8, impl=impl) ** 2)
    for a, b in zip(jax.grad(loss("xla"), (0, 1, 2))(q, k, v),
                    jax.grad(loss("pallas"), (0, 1, 2))(q, k, v)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)
    with pytest.raises(ValueError, match="divides"):
        flash_attention(q, jnp.zeros((1, 32, 3, 8)), jnp.zeros((1, 32, 3, 8)))


def test_the_key_block_is_no_larger_than_the_band():
    """Pairs computed over pairs visible, a window layer of the cell (8,192
    tokens, 512 keys), from `_Band`: what the choice of blocks costs."""
    from bluefog_tpu.kernels.flash_attention import _default_blocks

    assert _default_blocks(8192, 8192, None, None, None) == (1024, 1024)
    assert _default_blocks(8192, 8192, None, None, 4096) == (1024, 1024)
    assert _default_blocks(8192, 8192, None, None, 512) == (512, 512)
    assert _default_blocks(8192, 8192, None, None, 5) == (512, 512)  # no smaller
    assert _default_blocks(8192, 8192, None, 1024, 512) == (512, 1024)  # as asked
    assert _default_blocks(1024, 1024, None, None, 64) == (512, 512)  # as without

    def computed(bq, bk):
        band = _Band(bq, bk, 512, 8192 // bq, 8192 // bk)
        return sum(band.k_hi(i) - band.k_lo(i) + 1 for i in range(8192 // bq)) * bq * bk

    visible = 512 * 513 // 2 + (8192 - 512) * 512
    assert visible == 4_063_488
    assert [round(computed(*b) / visible, 2) for b in
            [(1024, 1024), (1024, 512), (512, 512), (256, 256)]] == [3.87, 2.97, 2.0, 1.5]


# ---- the router's scale ------------------------------------------------------


def test_route_topk_with_scale_one_is_todays_to_the_bit():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    e0, w0 = ep.route_topk(x, router, 4)
    e1, w1 = ep.route_topk(x, router, 4, 1.0)
    assert np.array_equal(e0, e1) and np.array_equal(w0, w1)
    text = lambda *scale: jax.jit(
        lambda x, r: ep.route_topk(x, r, 4, *scale)).lower(x, router).as_text()
    assert text() == text(1.0)
    e2, w2 = ep.route_topk(x, router, 4, 2.5)
    assert np.array_equal(e0, e2)
    np.testing.assert_allclose(w2, 2.5 * w0, rtol=1e-6)
    # the softmax over the chosen logits is the softmax over all, renormalised
    p = jax.nn.softmax(x @ router, axis=-1)
    top = jnp.take_along_axis(p, e0, axis=-1)
    np.testing.assert_allclose(w2, 2.5 * top / top.sum(-1, keepdims=True), rtol=2e-5)


def test_the_held_experts_take_their_activation():
    m = jax.random.normal(jax.random.PRNGKey(0), (24, 16))
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    router = jax.random.normal(keys[0], (16, 4))
    stacks = {"wg": jax.random.normal(keys[1], (2, 16, 8)),
              "wu": jax.random.normal(keys[2], (2, 16, 8)),
              "wd": jax.random.normal(keys[3], (2, 8, 16))}
    experts, weights = ep.route_topk(m, router, 2)

    def plain(act):
        gate = jnp.zeros((24, 4)).at[jnp.arange(24)[:, None], experts].set(weights)
        h = act(jnp.einsum("td,edf->etf", m, stacks["wg"])) \
            * jnp.einsum("td,edf->etf", m, stacks["wu"])
        return jnp.einsum("te,etd->td", gate[:, 1:3],
                          jnp.einsum("etf,efd->etd", h, stacks["wd"]))

    for kw, act in (({}, jax.nn.relu), ({"activation": jax.nn.silu}, jax.nn.silu)):
        fn = lambda s: ep.held_topk_experts(m, experts, weights, s, (1, 2), 4, **kw)
        np.testing.assert_allclose(fn(stacks), plain(act), rtol=2e-4, atol=2e-4)
        got = jax.grad(lambda s: jnp.sum(fn(s) ** 2))(stacks)
        assert all(float(jnp.linalg.norm(a)) > 0 for a in got.values())


# ---- the configuration file against its source -------------------------------

PUBLISHED = {  # config.json of the source, as the guide's catalog copies it
    "vocab_size": 100352, "hidden_size": 2048, "intermediate_size": 8192,
    "num_hidden_layers": 40, "num_attention_heads": 48, "num_key_value_heads": 8,
    "head_dim": 128, "max_position_embeddings": 262144, "rms_norm_eps": 1e-06,
    "num_experts": 256, "num_experts_per_tok": 8, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "sliding_window": 512,
    "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5,
}
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
        "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
    "original_max_position_embeddings": 4096,
}


def test_no_width_differs_from_the_source_and_the_cut_is_stated(cell):
    cfg = cell.config
    cut = {"num_hidden_layers": 5, "vocab_size": 12544}
    for key, value in PUBLISHED.items():
        assert cfg[key] == cut.get(key, value), key
        if key in cfg["sizes"]:
            assert cfg["sizes"][key] == cfg[key], key  # one number, stated twice
    assert cfg["rope_parameters"] == cfg["sizes"]["rope_parameters"] == ROPE
    assert cfg["layer_types"] == cfg["sizes"]["layer_types"] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention"] * 10
    assert cfg["mlp_layer_types"] == cfg["sizes"]["mlp_layer_types"] \
        == ["dense"] + ["sparse"] * 39
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10 \
        == cfg["sizes"]["num_attention_heads_per_layer"]
    assert cfg["gating"] is True and cfg["tie_word_embeddings"] is False
    assert cfg["attention_bias"] is False
    assert cfg["moe_apply_router_weight_on_input"] is False
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert set(cfg["cut"]) == set(cfg["reduced"])
    assert cfg["num_experts_held"] == cfg["sizes"]["num_experts_held"] == 32
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["published"]["num_experts"] == 256 == 8 * cfg["num_experts_held"]
    assert cfg["published"]["vocab_size"] == 100352 == 8 * cfg["vocab_size"]
    assert "eight" in cfg["deployment"] and "one period" in cfg["deployment"]
    assert "leading dense layer" in cfg["deployment"]
    assumed = " ".join(cfg["assumed"])
    for mark in ("(i) the gate is a sigmoid", "(ii) softmax router",
                 "(iii) SiLU", "(iv) no gate on the shared expert",
                 "(v) the half-split rotary convention"):
        assert mark in assumed, mark
    mix = cell.mix
    assert mix["sizes"] == {"per_rank_batch": 1, "seq_len": 8192}
    assert mix["optimizer"] == dict(cfg["optimizer"], warmup_steps=2000) == {
        "name": "adamw", "learning_rate": 3e-4, "weight_decay": 0.1,
        "warmup_steps": 2000}
    standing = manifest.resolve("smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip").mix
    assert {k: v for k, v in mix.items() if k not in ("sizes", "describes")} == {
        k: v for k, v in standing.items() if k not in ("sizes", "describes")}
    bench = manifest.load_manifest()
    entry = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert entry["source"] == cfg["source"] and entry["source"].endswith("config.json")
    assert entry["reduced"] == cfg["reduced"]
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == ["resnet50-atc-exp2-4chip"]
    assert len(bench["workloads"]) >= 7
    named = {p["name"] for p in bench["per_layer"] if CELL in p.get("workloads", [])}
    assert named == {
        "train_step_host_ms_per_step", "attention_ms_per_step", "expert_ms_per_step",
        "flash_fwd_window_roofline", "flash_bwd_dkv_window_roofline",
        "flash_bwd_dq_window_roofline", "attention_window_ms_per_step",
        "attention_global_ms_per_step",
        # PR 41: the step's split by scope
        "optimizer_ms_per_step", "head_loss_ms_per_step", "mlp_ms_per_step",
        "attention_proj_ms_per_step", "expert_dispatch_ms_per_step",
        "unscoped_ms_per_step"}


# ---- the FLOP count and the readers -----------------------------------------


def test_flops_against_a_hand_count(cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    assert flops.visible_pairs(8192) == 33_558_528
    assert flops.visible_pairs(8192, 512) == 4_063_488
    assert flops.windows(sizes) == [None, 512, 512, 512, None]
    d, s = 2048, 8192
    attn = lambda heads: d * heads * 128 * 2 + 2 * d * 1024 + d * heads  # q, o, k, v, gate
    sparse = d * 256 + 1 * 3 * d * 512 + 3 * d * 512  # router, 8 x 32 / 256 experts, shared
    macs = (s * (attn(48) + 3 * d * 8192)                      # layer 0
            + 3 * s * (attn(64) + sparse) + s * (attn(48) + sparse)
            + 2 * 2 * 33_558_528 * 48 * 128 + 3 * 2 * 4_063_488 * 64 * 128
            + s * d * 12544)
    assert flops.forward_macs(sizes) == macs
    assert flops.train_flops_per_sample(sizes) == 6 * macs
    assert 6 * macs == pytest.approx(19.705e12, rel=1e-4)
    pairs = 6 * (2 * 2 * 33_558_528 * 48 * 128 + 3 * 2 * 4_063_488 * 64 * 128)
    assert pairs == pytest.approx(6.147e12, rel=1e-3)  # 4.95 of it in the two full layers
    # a kernel call counts the head count of its layer's kind
    f, fb = flops.kernel_call(sizes, "fwd", 512)
    g, _ = flops.kernel_call(sizes, "fwd", None)
    assert f == 2 * 2 * 128 * 4_063_488 * 64 and g == 2 * 2 * 128 * 33_558_528 * 48
    assert flops.kernel_call(sizes, "dkv", 512)[0] == 2 * f
    assert flops.kernel_call(sizes, "dq", 512)[0] == 3 * f // 2
    # the blocks are the program's; at 512 x 512 a row block meets 2 key blocks
    # (1 the first): q and o once, k and v a tile, 64 heads
    bq, bk = flops.program_blocks()["sliding_attention"]
    tiles = sum(min(i * (bq // bk) + bq // bk, 8192 // bk)
                - max(i * bq - 511, 0) // bk for i in range(8192 // bq))
    assert fb == 64 * (2 * (8192 // bq) * bq + 2 * tiles * bk) * 128 * 2
    # dK/dV writes the 8 shared heads once, not the 64
    small = flops.kernel_call(sizes, "dkv", 512, (1024, 512))[1]
    assert small == 8 * 4 * 8192 * 256 + 64 * 2 * 23 * 1024 * 256


def test_the_two_new_readers_split_the_attention_by_kind(cell):
    ops = {"%flash_fwd_window.3 = bf16[...]": 4.0, "%flash_fwd_window.4": 4.5,
           "%flash_bwd_dkv_window.1": 6.0, "%flash_bwd_dq_window.1": 5.0,
           "%attention_global.2": 9.0, "%attention_global.7": 12.5,
           "%fusion.9": 100.0, "%flash_fwd_windowed": 50.0}
    run = {"trace": {"ops_ms_per_step": ops}}
    window = cell.reader("attention_window_ms_per_step").read(run)
    glob = cell.reader("attention_global_ms_per_step").read(run)
    assert (window, glob) == (19.5, 21.5)
    assert window + glob == cell.reader("attention_ms_per_step").read(run)
    # a program without such kernels, and a run without a trace: nothing, no raise
    for empty in ({"trace": None}, {"trace": {"ops_ms_per_step": {"%fusion": 1.0}}}):
        assert cell.reader("attention_window_ms_per_step").read(empty) is None
        assert cell.reader("attention_global_ms_per_step").read(empty) is None


# ---- the cell's rehearsal: its limits and its control -------------------------


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


def test_held_rows_count_the_references_routing(cell):
    """`held_rows` is the count of equation 5's top-k that falls on the experts
    held, in each layer that has experts (four of the five)."""
    sizes = _small(cell, seq_len=32)
    ref = cell.module("reference")
    params = _widened(seeded.make_weights(ref, sizes, seed=9)[0])
    (x, _), = seeded.make_batches(ref, sizes, 9, ranks=1, pool=1)
    rows = np.asarray(jax.jit(lambda p, i: ref.held_rows(p, i, sizes))(params, x[0]))
    assert rows.shape == (4,) and rows.dtype.kind == "i"
    total = x[0].size * sizes["num_experts_per_tok"]
    assert (rows > 0).all() and (rows < total).all()
    assert abs(rows.sum() / (4 * total) - 0.5) < 0.15  # 4 of 8 held, near even
    # every expert held: every assignment
    every = dict(sizes, num_experts_held=8)
    p8 = _widened(seeded.make_weights(ref, every, seed=9)[0])
    assert np.asarray(ref.held_rows(p8, x[0], every)).tolist() == [total] * 4


def test_the_routing_tool_counts_this_configurations_rows(capsys):
    """`chipbench.routing`'s readings under this configuration's own names
    for the three sizes that tool reads, at rehearsal sizes."""
    from chipbench import routing_laguna

    assert routing_laguna.main(["--workload", CELL, "--seeds", "1", "--seconds",
                                "0.5", "--rehearse"]) == 0
    row, = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]
    sizes = manifest.resolve(CELL).sizes(rehearse=True)
    # top-4 of 4 experts, 2 held: every token reaches both, in the four sparse layers
    even = sizes["per_rank_batch"] * sizes["seq_len"] * 4 * 2 / 4
    assert row["seed"] == 300 and row["failed"] == 0 and row["steps_in_window"] >= 2
    assert row["even_rows"] == even
    assert row["held_rows_first_step"] == row["held_rows_last_step"] == [int(even)] * 4
    assert routing_laguna.main(["--workload", CELL, "--seeds", "1"]) == 2
    captured = capsys.readouterr()
    assert captured.out == "" and "no TPU" in captured.err


def test_chip_smokes_shared_heads_phase_walks_both_kinds_of_kernel():
    """`chip_smoke.phase_shared_heads` holds the kernels to the repeated call
    at the cell's sizes on the chip; here its rehearsal, in interpret mode."""
    import chip_smoke

    got = chip_smoke.phase_shared_heads(
        chip_smoke.TINY["shared_heads"], 0, False, chip_smoke._CompileClock())
    assert sorted(got) == ["global", "window"]
    for gaps in got.values():
        assert gaps["out"] == gaps["dq"] == 0.0  # the same arithmetic
        assert 0 < gaps["dk"] <= chip_smoke.SHARED_HEADS_L2_RTOL
        assert 0 < gaps["dv"] <= chip_smoke.SHARED_HEADS_L2_RTOL
    assert "shared_heads" in chip_smoke.PHASES

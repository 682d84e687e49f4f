"""The latent-attention expert decoder with MLA in every layer
(`models.hybrid.DeltaLatentMoELM` with no `"kda"` layer: kakaocorp's
kanana-2-30b-a3b, chipbench's `kanana-2-30b-a3b`) at a small size on the CPU:
the interleaved rotary against a complex-number one-liner; the mixer with the
head gate off against the reference's head-at-a-time attention; the router
with sigmoid scores, a bias and one group against a NumPy loop; the eight
shares of an expert layer adding up to the uncut layer; the model's loss and
every gradient against the configuration's plain reference, and three steps
of AdamW as the cell's `correct` compares them; the gauges; the configuration
file against its published source; the FLOP count against a hand count; the
three roofline readers on traced op names of each output shape; the cell's
rehearsal through `python -m chipbench` and its control; and the standing
decoders' programs lowering to the text they lowered to before the mixer and
the rotary had their new fields."""

import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models import hybrid
from bluefog_tpu.models.transformer import _rotary, rotary_frequencies
from bluefog_tpu.parallel.expert import held_topk_experts, route_topk
from bluefog_tpu.telemetry import registry as telemetry
from bluefog_tpu.training import make_lm_loss_fns

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import (check, control, manifest, optimizers, runner,  # noqa: E402
                       seeded, trace_reduce)

CELL = "kanana-2-30b-a3b-atc-warmup-b1-s8k-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROOFLINES = ("flash_fwd_global_roofline", "flash_bwd_dkv_global_roofline",
             "flash_bwd_dq_global_roofline")


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL)


@pytest.fixture(scope="module")
def ref(cell):
    return cell.module("reference")


# ---- the rotary's pairing -------------------------------------------------------


def _complex_rotary(x, theta):
    """x [B, T, H, n]: the pair (2i, 2i + 1) as one complex number times
    exp(j t theta^(-2i / n))."""
    n = x.shape[-1]
    z = np.asarray(x[..., 0::2], np.float64) + 1j * np.asarray(x[..., 1::2], np.float64)
    turn = np.exp(1j * np.arange(x.shape[1])[:, None, None]
                  * theta ** (-np.arange(0, n, 2) / n))
    return z * turn


@pytest.mark.parametrize("n,theta", [(8, 1e6), (64, 1e6), (16, 1e4)])
def test_the_interleaved_rotary_is_a_complex_product_a_pair(ref, n, theta):
    """The library's puts evens before odds and rotates half-split: real
    parts first, imaginary parts after.  The reference's rotates where the
    pair stands."""
    x = jax.random.normal(jax.random.PRNGKey(n), (2, 24, 3, n))
    want = _complex_rotary(x, theta)
    got = _rotary(x, jnp.arange(24), rotary=rotary_frequencies(n, theta),
                  interleaved=True)
    np.testing.assert_allclose(got[..., :n // 2], want.real, atol=1e-5)
    np.testing.assert_allclose(got[..., n // 2:], want.imag, atol=1e-5)
    in_place = np.asarray(jax.vmap(lambda one: ref.rope(one, theta))(x[:, :, 0]))
    np.testing.assert_allclose(in_place[..., 0::2], want[:, :, 0].real, atol=1e-5)
    np.testing.assert_allclose(in_place[..., 1::2], want[:, :, 0].imag, atol=1e-5)


def test_the_pairing_changes_no_score_and_is_not_the_half_split_one():
    """q . k sums over the channels in whatever order both have, so the
    de-interleaved layout gives the in-place rotation's scores; the half-split
    pairing of the same channels gives others."""
    r = jax.random.split(jax.random.PRNGKey(0), 2)
    q, k = (jax.random.normal(key, (1, 16, 2, 8)) for key in r)
    rot = rotary_frequencies(8, 1e6)
    turn = lambda x, **kw: _rotary(x, jnp.arange(16), rotary=rot, **kw)
    scores = lambda a, b: jnp.einsum("bqhd,bkhd->bhqk", a, b)
    want = np.einsum("bqhd,bkhd->bhqk", _complex_rotary(q, 1e6),
                     np.conj(_complex_rotary(k, 1e6))).real
    np.testing.assert_allclose(scores(turn(q, interleaved=True),
                                      turn(k, interleaved=True)), want, atol=1e-5)
    assert float(jnp.max(jnp.abs(scores(turn(q), turn(k)) - want))) > 1e-2


def test_channels_past_the_rotated_ones_pass_untouched():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 12))
    got = _rotary(x, jnp.arange(8), rotary=rotary_frequencies(8, 1e4), interleaved=True)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(got[:, 0, :, :4], x[:, 0, :, 0:8:2])  # position 0: no turn


# ---- the mixer with the gate off ---------------------------------------------------


def _mixer(sizes, **fields):
    return hybrid.LatentAttentionMixer(
        sizes["num_attention_heads"], sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
        sizes["qk_rope_head_dim"], sizes["v_head_dim"],
        rotary_frequencies(sizes["qk_rope_head_dim"], sizes["rope_theta"]),
        sizes["rms_norm_eps"], jnp.float32,
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16),
        **fields)


@pytest.fixture(scope="module")
def mixer_case(cell, ref):
    sizes = dict(cell.sizes(rehearse=True), num_hidden_layers=1)
    params = seeded.make_weights(ref, sizes, seed=5)[0]
    leaves = {p[2:]: v for p, v in params.items() if p[:2] == ("layer_0", "mixer")}
    r = jax.random.split(jax.random.PRNGKey(6), 2)
    u = jax.random.normal(r[0], (2, sizes["seq_len"], sizes["hidden_size"]))
    return sizes, leaves, u, jax.random.normal(r[1], u.shape)


def test_the_mixer_without_its_gate_is_the_references_attention(ref, mixer_case):
    """The program's mixer (one 24-wide product a head through the flash
    kernels, the rotary channels evens first) against the reference's: a head
    at a time from the compressed form, the pairs turned in place, scores over
    sqrt(24), the mask explicit.  Value and every gradient."""
    sizes, leaves, u, weight = mixer_case
    module = _mixer(sizes, head_gate=False, rotary_interleaved=True)
    assert ("gate", "kernel") not in leaves and len(leaves) == 5

    def program(p, u_):
        return jnp.sum(module.apply({"params": seeded.nest(p)}, u_) * weight)

    def reference(p, u_):
        full = {("layer_0", "mixer") + path: v for path, v in p.items()}
        out = jax.vmap(lambda one: ref.latent_attention(
            one, full, ("layer_0", "mixer"), sizes, False))(u_)
        return jnp.sum(out * weight)

    got = jax.jit(jax.value_and_grad(program, (0, 1)))(leaves, u)
    want = jax.jit(jax.value_and_grad(reference, (0, 1)))(leaves, u)
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * max(1.0, abs(float(want[0])))
    for a, b in zip(jax.tree_util.tree_leaves(got[1]), jax.tree_util.tree_leaves(want[1])):
        assert np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12) < 1e-4


@pytest.mark.parametrize("fields,leaf,scope", [
    (dict(), True, True), (dict(head_gate=False), False, False),
    (dict(head_gate=False, rotary_interleaved=True), False, False)],
    ids=["as-ling-calls-it", "gate-off", "gate-off-interleaved"])
def test_the_gate_is_a_field_and_off_leaves_no_leaf_and_no_scope(mixer_case, fields,
                                                                 leaf, scope):
    sizes, _, u, _ = mixer_case
    module = _mixer(sizes, **fields)
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), u))["params"]
    assert ("gate" in shapes) is leaf
    assert set(shapes) - {"gate"} == {"mla_q", "mla_kv_down", "mla_kv_norm", "mla_kv_up", "o"}
    # the paths the compiler keeps for its instructions (`op_name`), which the
    # program's record of its step hands the benchmark's readers
    text = jax.jit(module.apply).lower({"params": shapes}, u).compile().as_text()
    assert ("/attention_gate/" in text) is scope
    for name in ("mla_q", "mla_kv_down", "mla_kv_norm", "mla_kv_up", "attention_global",
                 "attention_rotary", "o"):
        assert f"/{name}/" in text, name


def test_the_pairing_matters_to_the_mixer(mixer_case):
    sizes, leaves, u, _ = mixer_case
    outs = [_mixer(sizes, head_gate=False, rotary_interleaved=flag).apply(
        {"params": seeded.nest(leaves)}, u) for flag in (False, True)]
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) > 1e-4 * float(jnp.max(jnp.abs(outs[0])))


# ---- the router: sigmoid, a bias, one group ---------------------------------------------


def _router_case(tokens=96, d=24, experts=16, seed=0):
    r = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(r[0], (tokens, d))
    router = jax.random.normal(r[1], (d, experts)) * d ** -0.5
    return x, router, 0.3 * jax.random.normal(r[2], (experts,))


def _routed_by_hand(x, router, bias, top_k, scale):
    s = 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64) @ np.asarray(router, np.float64)))
    chosen, weights = [], []
    for row in s:
        picked = sorted(range(len(row)), key=lambda e: -(row[e] + float(bias[e])))[:top_k]
        chosen.append(picked)
        weights.append(scale * row[picked] / row[picked].sum())
    return np.array(chosen), np.array(weights)


@pytest.mark.parametrize("experts,top_k,kept", [(16, 6, None), (16, 3, 1), (128, 6, 1)])
def test_the_one_group_router_is_the_written_out_loop(ref, experts, top_k, kept):
    """Sigmoid scores, the bias in the choice only, no group to limit it, the
    weights without the bias over their sum times 2.448: the library's, the
    reference's and a loop in float64 choose the same experts and weigh them
    alike."""
    x, router, bias = _router_case(experts=experts, seed=experts + top_k)
    got_e, got_w = route_topk(x, router, top_k, 2.448, score="sigmoid", bias=bias,
                              groups=1, groups_kept=kept)
    want_e, want_w = _routed_by_hand(x, router, bias, top_k, 2.448)
    np.testing.assert_array_equal(np.asarray(got_e), want_e)
    np.testing.assert_allclose(got_w, want_w, rtol=2e-5)
    np.testing.assert_allclose(np.sum(got_w, -1), 2.448, rtol=1e-5)
    sizes = {"num_experts_per_tok": top_k, "routed_scaling_factor": 2.448}
    ref_e, ref_w = ref.route(x, {("b", "router"): router, ("b", "router_bias"): bias},
                             "b", sizes)
    np.testing.assert_array_equal(np.asarray(ref_e), want_e)
    np.testing.assert_allclose(ref_w, want_w, rtol=2e-5)
    plain, _ = route_topk(x, router, top_k, 2.448, score="sigmoid", groups=1)
    assert np.any(np.sort(np.asarray(plain), -1) != np.sort(want_e, -1))  # the bias chose


def test_no_gradient_reaches_the_bias_of_the_one_group_router():
    x, router, bias = _router_case()

    def loss(r, b):
        _, w = route_topk(x, r, 6, 2.448, score="sigmoid", bias=b, groups=1)
        return jnp.sum(w * jnp.arange(6.0))
    dr, db = jax.grad(loss, (0, 1))(router, bias)
    assert float(jnp.max(jnp.abs(db))) == 0.0 and float(jnp.max(jnp.abs(dr))) > 0


def test_the_eight_shares_add_up_to_the_uncut_layer(ref):
    """128 experts over 8 shares of 16, top-6: the routed parts that the eight
    shares compute (`held_topk_experts`, each told its experts) plus the
    shared experts once are the uncut reference's layer, every expert held."""
    d, f, experts, per = 24, 16, 128, 16
    sizes = {"num_experts_per_tok": 6, "routed_scaling_factor": 2.448}
    x, router, bias = _router_case(d=d, experts=experts, seed=3)
    r = jax.random.split(jax.random.PRNGKey(9), 6)
    stack = lambda key, *shape: 0.3 * jax.random.normal(key, shape)
    p = {("b", "router"): router, ("b", "router_bias"): bias,
         ("b", "wg"): stack(r[0], experts, d, f), ("b", "wu"): stack(r[1], experts, d, f),
         ("b", "wd"): stack(r[2], experts, f, d),
         ("b", "shared", "wg"): stack(r[3], d, 2 * f),
         ("b", "shared", "wu"): stack(r[4], d, 2 * f),
         ("b", "shared", "wd"): stack(r[5], 2 * f, d)}
    shared = ref.gated_mlp(x, p, ("b", "shared"), False)
    whole = ref.routed_part(x, p, "b", sizes, False, tuple(range(experts))) + shared
    chosen, weights = route_topk(x, router, 6, 2.448, score="sigmoid", bias=bias, groups=1)
    total, busy = shared, 0                                   # every share's alike: once
    for share in range(experts // per):
        held = tuple(range(share * per, (share + 1) * per))
        stacks = {n: p[("b", n)][share * per:(share + 1) * per] for n in ("wg", "wu", "wd")}
        part = held_topk_experts(x, chosen, weights, stacks, held, experts,
                                 activation=jax.nn.silu)
        one = ref.routed_part(x, {**p, **{("b", n): stacks[n] for n in stacks}}, "b",
                              sizes, False, held)
        np.testing.assert_allclose(part, one, atol=2e-5)   # a share is the reference's
        busy += float(jnp.max(jnp.abs(part))) > 0
        total = total + part
    assert busy == 8
    np.testing.assert_allclose(total, whole, atol=5e-5)
    # two shared experts are one gated MLP of twice the width
    halves = sum(ref.gated_mlp(x, {("s", "wg"): p[("b", "shared", "wg")][:, h],
                                   ("s", "wu"): p[("b", "shared", "wu")][:, h],
                                   ("s", "wd"): p[("b", "shared", "wd")][h]}, ("s",), False)
                 for h in (slice(0, f), slice(f, 2 * f)))
    np.testing.assert_allclose(halves, shared, atol=1e-5)


# ---- the model against the plain reference ------------------------------------


def _float32_model(cell, sizes, **changed):
    return cell.module("program").build(sizes)["model"].clone(
        dtype=jnp.float32, **changed)


def _loss_and_grads(model, params, x, y):
    apply_fn = make_lm_loss_fns(model)[0]
    return jax.jit(jax.value_and_grad(
        lambda p: apply_fn({"params": seeded.nest(p)}, x, labels=y)))(params)


@pytest.fixture(scope="module")
def seeded_case(cell, ref):
    sizes = cell.sizes(rehearse=True)
    params = seeded.make_weights(ref, sizes, seed=11)[0]
    (x, y), = seeded.make_batches(ref, sizes, 11, ranks=1, pool=1)
    x, y = x[0], y[0]
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, {}, x, y, sizes), has_aux=True))(params)
    return sizes, params, x, y, float(loss), grads


def _worst_gap(got, want):
    gaps = {}
    for path in want:
        a, b = np.asarray(got[path], np.float64), np.asarray(want[path], np.float64)
        if np.linalg.norm(b):
            gaps["/".join(path)] = np.linalg.norm(a - b) / np.linalg.norm(b)
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


# What float32 on both sides leaves between the program and the reference on
# the rehearsal's three layers is the order of sums (the widest leaf reads
# under 3e-5); with the products in bfloat16 the same comparison reads 5e-3 or
# more, so 2e-4 passes the one and fails the other with room on each side.
FLOAT32_GAP = 2e-4


def test_loss_and_every_gradient_match_the_plain_reference(cell, ref, seeded_case):
    """MLA + dense, MLA + experts, MLA + experts: 4 heads of 16 + 8 rotary
    beside values of 16, a latent of 32, 16 experts of which 4 held, top-3, two
    shared experts, an untied head, the chunked loss; float32 throughout."""
    sizes, params, x, y, loss, grads = seeded_case
    lp, gp = _loss_and_grads(_float32_model(cell, sizes), params, x, y)
    assert abs(float(lp) - loss) < 1e-5
    assert set(gp) == set(grads) == set(ref.param_shapes(sizes)[0])
    gap, where = _worst_gap(gp, grads)
    assert gap < FLOAT32_GAP, (where, gap)
    biases = [p for p in grads if p[-1] == "router_bias"]
    assert len(biases) == 2 and not any("gate" in p or "kda" in "/".join(p) for p in gp)
    for p in biases:  # a leaf, and no gradient reaches it on either side
        assert float(jnp.max(jnp.abs(gp[p]))) == float(jnp.max(jnp.abs(grads[p]))) == 0.0


def test_bfloat16_products_fail_the_float32_tolerance(cell, seeded_case):
    sizes, params, x, y, loss, grads = seeded_case
    model = cell.module("program").build(sizes)["model"]
    assert model.dtype == jnp.bfloat16
    _, gp = _loss_and_grads(model, params, x, y)
    assert _worst_gap(gp, grads)[0] > 10 * FLOAT32_GAP


@pytest.mark.parametrize("changed", [
    dict(rotary_interleaved=False), dict(routed_scale=1.0), dict(rope_theta=1e4),
    dict(head_gate=True), dict(layer_dense=(True, False, True)),
    dict(shared_dff=32), dict(top_k=4)],
    ids=["half_split_pairs", "routed_scale", "rope_theta", "a_head_gate",
         "a_second_dense_layer", "one_shared_expert", "top_k"])
def test_each_of_the_models_own_rules_matters(cell, seeded_case, changed):
    sizes, params, x, y, loss, grads = seeded_case
    model = _float32_model(cell, sizes, **changed)
    ids = jax.ShapeDtypeStruct(x.shape, x.dtype)
    shapes = seeded.flatten(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"])
    if {p: s.shape for p, s in shapes.items()} != {p: v.shape for p, v in params.items()}:
        return  # another tree: another model by its leaves alone
    lp, gp = _loss_and_grads(model, params, x, y)
    assert _worst_gap(gp, grads)[0] > 1e-2 or abs(float(lp) - loss) > 1e-3


def test_recomputing_the_blocks_changes_no_gradient(cell, seeded_case):
    sizes, params, x, y, _, _ = seeded_case
    la, ga = _loss_and_grads(_float32_model(cell, sizes), params, x, y)
    lb, gb = _loss_and_grads(_float32_model(cell, sizes, remat=False), params, x, y)
    assert float(la) == float(lb)
    for path in ga:
        np.testing.assert_allclose(ga[path], gb[path], rtol=1e-5, atol=1e-9)


def test_three_steps_of_adamw_as_the_cells_correct_compares_them(cell, ref):
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


def test_the_reference_counts_the_rows_on_the_held_experts(cell, ref, seeded_case):
    sizes, params, x, _, _, _ = seeded_case
    rows = np.asarray(jax.jit(lambda p, i: ref.held_rows(p, i, sizes))(params, x))
    even = x.size * sizes["num_experts_per_tok"] * sizes["num_experts_held"] \
        / sizes["num_experts"]
    # 16 experts' scores spread no wider than the bias at hidden 64: far from even
    assert rows.shape == (2,) and np.all(rows > 0.5 * even) and np.all(rows < 3 * even)


def test_the_reference_is_its_own(cell):
    body = open(cell.files["reference"]).read().split('"""', 2)[2]
    assert "bluefog_tpu" not in body and "load_module" not in body
    assert set(re.findall(r"^ *(?:import|from) +(\S+) *(?:import|as|$)", body, re.M)) == {
        "functools", "zlib", "jax", "jax.numpy", "numpy"}


# ---- the decoder class: no fourth one, no KDA where there is none ---------------


WANTED_GAUGES = {
    "mla.layers": 3, "mla.kv_rank": 32, "mla.qk_dims": 24, "mla.v_dims": 16,
    "mla.head_gate": 0, "mla.rotary_interleaved": 1, "attention.layers_global": 3,
    "attention.heads_global": 4, "moe.score": 1, "moe.groups": 1, "moe.groups_kept": 1,
    "moe.shared_width": 64, "moe.routed_scale": 2.448, "moe.dense_layers": 1,
    "moe.experts_held": 4, "moe.experts_total": 16, "moe.top_k": 3, "lm.tied_head": 0,
    "lm.remat_blocks": 3, "lm.remat_kept_names": 3,
    # bfloat16 of the tokens: three layers' [heads, T, 16] and float32 [heads, T]
    "lm.remat_kept_mb": 3 * 32 * 4 * (32 + 4) / 1e6}


def _gauges(model, tokens, monkeypatch, tmp_path):
    monkeypatch.setenv("BFTPU_TELEMETRY", str(tmp_path))
    telemetry.reset()
    try:
        jax.eval_shape(lambda i: model.init(jax.random.PRNGKey(0), i),
                       jax.ShapeDtypeStruct((1, tokens), jnp.int32))
        return {g["name"]: g["value"] for g in telemetry.get_registry().snapshot()["gauges"]}
    finally:
        telemetry.reset()


def test_the_model_sets_its_gauges_and_none_of_the_delta_rules(cell, monkeypatch, tmp_path):
    model = cell.module("program").build(cell.sizes(rehearse=True))["model"]
    assert type(model) is hybrid.DeltaLatentMoELM and model.head_dim is None
    gauges = _gauges(model, 32, monkeypatch, tmp_path)
    assert {k: v for k, v in gauges.items() if k in WANTED_GAUGES} == WANTED_GAUGES
    assert not [k for k in gauges if k.startswith("kda.")]


def test_lings_model_says_its_gate_and_its_pairing(monkeypatch, tmp_path):
    ling = manifest.resolve("ling-3.0-flash-vl-atc-warmup-b1-s8k-1chip")
    model = ling.module("program").build(ling.sizes(rehearse=True))["model"]
    gauges = _gauges(model, 32, monkeypatch, tmp_path)
    assert gauges["mla.head_gate"] == 1 and gauges["mla.rotary_interleaved"] == 0
    assert gauges["kda.layers"] == 3 and gauges["moe.groups"] == 4


def test_a_delta_rule_layer_without_a_head_size_is_refused(cell):
    model = _float32_model(cell, cell.sizes(rehearse=True),
                           layer_kinds=("mla", "kda", "mla"))
    with pytest.raises(ValueError, match="head_dim"):
        jax.eval_shape(lambda i: model.init(jax.random.PRNGKey(0), i),
                       jax.ShapeDtypeStruct((1, 32), jnp.int32))


# The standing decoders' programs at their rehearsal sizes: the StableHLO text
# of loss-and-gradient, as `jit(...).lower(...).as_text()` of the parent commit
# (f1fb8b5, PR 44) gave it.  A hash, because the texts are 1.2 to 2.6 MB; a PR
# that means to change what one of them lowers to replaces its line.  PR 47
# replaced Ling's (771adfbc...: its mixer hands q and k raw to the delta rule's
# kernels, which take the unit vectors) and added this file's own cell at the
# hash of PR 47's parent (8f82114): it builds Ling's decoder class with no
# delta-rule layer, and a change to that layer must not reach it.
LOWERED = {
    "ling-3.0-flash-vl-atc-warmup-b1-s8k-1chip":
        "e5569a6361513621afed6f11fd8916e241870bfaec61d7b3ca90e16299e1729b",
    "granite-4.0-h-micro-atc-warmup-b1-s8k-1chip":
        "709927d17eea333679c74e2f51cdb16b1f040eb879cfac4ef45e269dd07ad59b",
    "laguna-xs.2-atc-warmup-b1-s8k-1chip":
        "dd5029b60057b96eace882c332b03b25479409159ae6eed99674f13af36d6c62",
    "smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip":
        "1b418ca17393ef5375d3c733fd0da3f148a16a668f4084bcad4bb73e2134e8eb",
    "kanana-2-30b-a3b-atc-warmup-b1-s8k-1chip":
        "0d0396b84222beec9e78bea16e21404b82fbbeb2d69d1cae796b35741ee3873e",
}


@pytest.mark.parametrize("name", LOWERED, ids=[n.split("-atc-")[0] for n in LOWERED])
def test_the_standing_decoders_lower_to_what_they_lowered_to(name):
    standing = manifest.resolve(name)
    sizes = standing.sizes(rehearse=True)
    apply_fn = standing.module("program").build(sizes)["apply_fn"]
    shapes = standing.module("reference").param_shapes(sizes)[0]
    params = {p: jax.ShapeDtypeStruct(s, jnp.float32) for p, s in shapes.items()}
    ids = jax.ShapeDtypeStruct((sizes["per_rank_batch"], sizes["seq_len"]), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, x, y: apply_fn({"params": seeded.nest(p)}, x, labels=y))).lower(
            params, ids, ids).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[name]


def test_smallthinkers_expert_layer_at_its_cells_size_lowers_to_what_it_did():
    """The rehearsal sizes above are too small to say: there a pass holds all
    the rows there can be, whatever sizes it.  The layer alone at the
    SmallThinker cells' 16,384 tokens, 6 of 64, 8 held, hidden 2560, width 768,
    loss-and-gradient: the text the parent commit (80edb39, before a pass was
    sized from the load) lowered it to, one pass of 16,384 rows in it."""
    from bluefog_tpu.parallel import expert as ep

    small = manifest.resolve("smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip").sizes()
    T, d = small["per_rank_batch"] * small["seq_len"], small["hidden_size"]
    k, H = small["moe_num_active_primary_experts"], small["moe_num_primary_experts_held"]
    E, f = small["moe_num_primary_experts"], small["moe_ffn_hidden_size"]
    assert (T, k, H, E, d, f) == (16384, 6, 8, 64, 2560, 768)
    S = jax.ShapeDtypeStruct
    stacks = {"wg": S((H, d, f), jnp.float32), "wu": S((H, d, f), jnp.float32),
              "wd": S((H, f, d), jnp.float32)}

    def loss(m, weights, stacks, experts):
        y = ep.held_topk_experts(m, experts, weights, stacks, range(H), E)
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        S((T, d), jnp.bfloat16), S((T, k), jnp.float32), stacks,
        S((T, k), jnp.int32)).as_text()
    assert "16384x2560" in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == "08fddf530150f5d79740e1e4b19af5b542e1fcfa3be28abf5cf7817801a8b08c"


# ---- the configuration, the FLOP count, the manifest, the readers ----------------


def test_no_width_differs_from_the_source_and_the_cut_is_stated(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    row = next(json.loads(line) for line in open(CATALOG)
               if '"kanana-2-30b-a3b-instruct-2601"' in line)
    cfg = cell.config
    cut = {"num_hidden_layers": 6, "n_routed_experts": 16, "vocab_size": 16032}
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == cut.get(key, value), key
    sizes = cfg["sizes"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
                "rope_interleave", "rms_norm_eps", "num_experts_per_tok",
                "moe_intermediate_size", "n_shared_experts", "routed_scaling_factor",
                "n_group", "topk_group", "first_k_dense_replace", "vocab_size",
                "num_hidden_layers", "n_routed_experts"):
        assert sizes[key] == cfg[key], key  # one number, stated twice
    assert sizes["num_experts"] == 128 == cfg["published"]["n_routed_experts"]
    assert sizes["num_experts_held"] == cfg["n_routed_experts"] == 16
    assert sizes["published_layer_index"] == [0, 1, 2, 3, 4, 5]
    assert cfg["qk_head_dim"] == sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    assert "head_dim" not in sizes  # the rotary's width under another name: `assumed`
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert set(cfg["reduced"]) < set(cfg["cut"])
    assert cfg["published"]["vocab_size"] == 128256 == 8 * cfg["vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 48
    assert "one chip of 8" in cfg["deployment"] and "stage of eight" in cfg["deployment"]
    assert "an eighth" in cfg["expert_load"] and "6,144" in cfg["expert_load"]
    assumed = " ".join(cfg["assumed"])
    for mark in ("`head_dim` 64 is read as the rotary's width", "one gated MLP of 2 x 768",
                 "noaux_tc", "balancing update", "rotates the pairs in place",
                 "no YaRN factor", "multi-token prediction", "sqrt(2 x 48)",
                 "uniform in [-0.05, 0.05]", "AdamW 3e-4", "recomputed", "segment_ids"):
        assert mark in assumed, mark


def test_the_parameters_of_the_cut_are_the_issues_arithmetic(cell):
    shapes = cell.module("reference").param_shapes(cell.sizes())[0]
    count = lambda pick: sum(int(np.prod(s)) for p, s in shapes.items() if pick(p))
    assert count(lambda p: p[:2] == ("layer_3", "mixer")) == 26_345_984
    ffn = lambda p: p[0] == "layer_1" and p[1] not in ("mixer", "mixer_norm", "mlp_norm")
    assert count(ffn) == 85_196_928
    assert count(lambda p: p[0] == "layer_0") == 64_098_816
    assert count(lambda p: p[0] == "layer_5") == 111_547_008
    assert count(lambda p: p[0] in ("embed", "head", "final_norm")) == 65_669_120
    assert count(lambda p: True) == 687_502_976


def test_flops_against_a_hand_count(cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    d, s = 2048, 8192
    pairs = s * (s + 1) // 2
    mla = s * (d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d) + pairs * 32 * 320
    experts = s * (d * 128 + 6 * 16 / 128 * 3 * d * 768 + 3 * d * 1536)
    want = s * d * 16032 + 6 * mla + s * 3 * d * 6144 + 5 * experts
    assert flops.forward_macs(sizes) == pytest.approx(want, rel=1e-12)
    assert flops.train_flops_per_sample(sizes) == pytest.approx(6 * want, rel=1e-12)
    assert 26.5e12 < flops.train_flops_per_sample(sizes) < 27.5e12
    # attention's pairs and MLA's own products are three quarters of the step
    assert 0.7 < 6 * mla / want < 0.8
    for kernel, macs in (("fwd", 192 + 128), ("dkv", 2 * 192 + 2 * 128),
                         ("dq", 2 * 192 + 128)):
        work, nbytes = flops.kernel_call(sizes, kernel)
        assert work == 2 * macs * pairs * 32
        # the pairs lead: the bytes would take under a fifth of the products' time
        assert nbytes / 819e9 < 0.2 * work / 197e12
    assert flops.kernel_call(sizes, "fwd")[1] == 32 * s * (2 * (2 * 192 + 2 * 128) + 4)


def test_the_cell_and_its_three_metrics_are_the_manifests(cell):
    cfg, mix = cell.config, cell.mix
    assert cell.mix_name == "atc-warmup-b1-s8k-1chip" and cell.chips == 1
    assert mix == manifest.resolve("laguna-xs.2-atc-warmup-b1-s8k-1chip").mix
    assert mix["sizes"] == {"per_rank_batch": 1, "seq_len": 8192}
    assert mix["optimizer"] == dict(cfg["optimizer"], warmup_steps=2000)
    bench = manifest.load_manifest()
    entry = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert entry == bench["configs"][-1] and len(bench["configs"]) == 7
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert bench["workloads"][-1]["name"] == CELL and len(bench["workloads"]) == 10
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == ["resnet50-atc-exp2-4chip"]
    named = {p["name"] for p in bench["per_layer"] if CELL in p.get("workloads", [])}
    assert named == {
        "train_step_host_ms_per_step", "attention_ms_per_step",
        "attention_global_ms_per_step", "attention_proj_ms_per_step",
        "latent_proj_ms_per_step", "expert_ms_per_step", "expert_dispatch_ms_per_step",
        "mlp_ms_per_step", "head_loss_ms_per_step", "optimizer_ms_per_step",
        "recompute_ms_per_step", *ROOFLINES}
    unscoped = next(p for p in bench["per_layer"] if p["name"] == "unscoped_ms_per_step")
    assert CELL not in unscoped["workloads"]  # the mla_* ops would be counted twice
    own = bench["per_layer"][-3:]
    assert [p["name"] for p in own] == list(ROOFLINES)
    for p in own:
        assert p == {"name": p["name"], "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "train_samples_s_chip", "workloads": [CELL]}
    # appended, and the standing cells' lists otherwise as they were
    for p in bench["per_layer"][:-3]:
        if CELL in p.get("workloads", []):
            assert p["workloads"][-1] == CELL


def _hlo_name(n, result):
    return (f"%attention_global.{n} = {result} custom-call(s32[1,1]{{1,0}} %a, "
            "s32[1,1]{1,0} %b), custom_call_target=\"tpu_custom_call\"")[:trace_reduce.NAME_CUT]


BF = "{2,1,0:T(8,128)(2,1)}"
TRACED = {
    # as the device trace names them: the start of the op's HLO text
    "fwd": _hlo_name(3, f"(bf16[32,8192,128]{BF}, f32[32,8192,128]{{2,1,0:T(8,128)}})"),
    "dkv": _hlo_name(4, f"(bf16[32,8192,192]{BF}, bf16[32,8192,128]{BF})"),
    "dq": _hlo_name(5, f"bf16[32,8192,192]{BF}"),
}


@pytest.mark.parametrize("kernel,metric", zip(("fwd", "dkv", "dq"), ROOFLINES))
def test_a_reader_tells_its_kernel_by_the_output_shape(cell, kernel, metric):
    flops, sizes = cell.module("flops"), cell.sizes()
    ms = {"fwd": 7.0, "dkv": 12.0, "dq": 9.5}
    ops = {TRACED[k]: v for k, v in ms.items()}
    second = TRACED[kernel].replace(".", ".1", 1)     # a second layer's call
    ops.update({second: ms[kernel] + 1.0, "%fusion.7 = bf16[32,8192,192]{2,1,0}": 50.0,
                "%attention_global_other.2 = bf16[32,8192,192]{2,1,0}": 50.0,
                "%attention_global.9 = f32[8]{0} custom-call": 50.0})
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"trace": {"ops_ms_per_step": ops}, "peaks": peaks,
           "flops_per_sample": flops.train_flops_per_sample(sizes)}
    work, nbytes = flops.kernel_call(sizes, kernel)
    got = cell.reader(metric).read(run)
    assert got == pytest.approx(
        100 * 2 * max(work / 197e12, nbytes / 819e9) / ((2 * ms[kernel] + 1.0) / 1e3))
    assert 30 < got < 100
    # the whole-sequence kernels' milliseconds are still the sum over the name
    assert cell.reader("attention_global_ms_per_step").read(run) == pytest.approx(
        sum(ms.values()) + ms[kernel] + 1.0 + 50.0)
    # no trace, a rehearsal (no peaks), a run of another cell, a program with
    # no such kernel: nothing, no raise
    empties = [{"trace": None}, dict(run, peaks=None), dict(run, flops_per_sample=1.0),
               dict(run, trace={"ops_ms_per_step": {"%fusion.1": 1.0}})]
    if kernel != "dq":  # a tuple's name cut before its second array does not say
        empties.append(dict(run, trace={"ops_ms_per_step": {TRACED[kernel][:60]: 5.0}}))
    for empty in empties:
        assert cell.reader(metric).read(empty) is None


def test_equal_head_sizes_are_not_told_apart(cell):
    shared = cell.reader("flash_fwd_global_roofline")
    assert [shared.kind_of(TRACED[k], 192, 128) for k in ("fwd", "dkv", "dq")] \
        == ["fwd", "dkv", "dq"]
    assert [shared.kind_of(TRACED[k], 128, 128) for k in ("fwd", "dkv", "dq")] == [None] * 3
    assert shared.kind_of("%attention_global.3 = (f32[4]{0}, f32[4]{0})", 192, 128) is None
    assert shared.kind_of("%flash_fwd_window.3 = (bf16[32,8192,128]{2,1,0}, f32[1])",
                          192, 128) is None


# ---- the cell's rehearsal: its limits and its control ---------------------------


def test_sound_readings_pass_and_the_float8_control_fails(cell):
    ses = runner.Session(cell, rehearse=True)
    try:
        # at hidden 64 held expert 0 of layer 1 gets no token (its bias is the
        # lowest and the scores spread no wider); a seed under which bfloat16
        # sends it one (2**31 + 35) reads 0.1 on `delta_norm_gap`: the
        # configuration's `rehearsal_note`
        row = control.readings(ses, 2**31 + 5, ["step"])
    finally:
        bf.shutdown()
    limits = ses.reference.LIMITS
    failed = lambda part: [k for k, v in row[part].items()
                           if k in limits and not v <= limits[k]]
    assert failed("sound") == [], row["sound"]
    assert failed("control_step"), row["control_step"]
    assert row["sound"]["change1_rel_l2"] > 0  # the parameters did move


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

"""`kanana-2-30b-a3b`'s own (`DeltaLatentMoELM` with MLA in every layer): the
interleaved rotary against a complex-number one-liner; the mixer with its gate
off against the reference's attention; the one-group router against a loop;
the eight shares of an expert layer adding up; the reference's held rows and
independence; the decoder class with no delta-rule layer; the cell's place in
the manifest; equal head sizes not told apart; and the standing decoders
lowering to the text they lowered to.  The cases it shares with the other
decoder configurations are in `tests/test_decoder_cells.py`."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models import hybrid
from bluefog_tpu.models.transformer import _rotary, rotary_frequencies
import decoder_cells as dc
from decoder_cells import KANANA, LING

from chipbench import manifest, seeded

CELL = KANANA.cell_name


@pytest.fixture(scope="module")
def cell():
    return KANANA.cell


@pytest.fixture(scope="module")
def ref():
    return KANANA.reference


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


@pytest.fixture(scope="module")
def mixer_case():
    return dc.mixer_case(KANANA)


def test_the_mixer_without_its_gate_is_the_references_attention(ref, mixer_case):
    """The program's mixer (one 24-wide product a head through the flash
    kernels, the rotary channels evens first) against the reference's: a head
    at a time from the compressed form, the pairs turned in place, scores over
    sqrt(24), the mask explicit.  Value and every gradient."""
    sizes, leaves, u, weight = mixer_case
    module = dc.latent_mixer(sizes, head_gate=False, rotary_interleaved=True)
    assert ("gate", "kernel") not in leaves and len(leaves) == 5
    dc.mixer_is_the_references(module, ref.latent_attention, leaves, u, weight, sizes)


@pytest.mark.parametrize("fields,leaf,scope", [
    (dict(), True, True), (dict(head_gate=False), False, False),
    (dict(head_gate=False, rotary_interleaved=True), False, False)],
    ids=["as-ling-calls-it", "gate-off", "gate-off-interleaved"])
def test_the_gate_is_a_field_and_off_leaves_no_leaf_and_no_scope(mixer_case, fields,
                                                                 leaf, scope):
    sizes, _, u, _ = mixer_case
    module = dc.latent_mixer(sizes, **fields)
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
    outs = [dc.latent_mixer(sizes, head_gate=False, rotary_interleaved=flag).apply(
        {"params": seeded.nest(leaves)}, u) for flag in (False, True)]
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) > 1e-4 * float(jnp.max(jnp.abs(outs[0])))


# ---- the router: sigmoid, a bias, one group ---------------------------------------------


@pytest.mark.parametrize("experts,top_k,kept", [(16, 6, None), (16, 3, 1), (128, 6, 1)])
def test_the_one_group_router_is_the_written_out_loop(ref, experts, top_k, kept):
    """Sigmoid scores, the bias in the choice only, no group to limit it, the
    weights without the bias over their sum times 2.448: the library's, the
    reference's and a loop in float64 choose the same experts and weigh them
    alike."""
    dc.router_is_the_loop(
        ref, {"num_experts_per_tok": top_k, "routed_scaling_factor": 2.448},
        dc.router_case(experts=experts, seed=experts + top_k), kept=kept)


def test_no_gradient_reaches_the_bias_of_the_one_group_router():
    dc.no_gradient_reaches_the_bias(6, 2.448, groups=1)


def test_the_eight_shares_add_up_to_the_uncut_layer(ref):
    """128 experts over 8 shares of 16, top-6, one group, two shared experts."""
    sizes = {"num_experts_per_tok": 6, "routed_scaling_factor": 2.448}
    x, p = dc.the_shares_add_up(ref, ref.routed_part, sizes, 128, 16, 32, groups=1)
    # two shared experts are one gated MLP of twice the width
    halves = sum(ref.gated_mlp(x, {("s", "wg"): p[("b", "shared", "wg")][:, h],
                                   ("s", "wu"): p[("b", "shared", "wu")][:, h],
                                   ("s", "wd"): p[("b", "shared", "wd")][h]}, ("s",), False)
                 for h in (slice(0, 16), slice(16, 32)))
    np.testing.assert_allclose(halves, ref.gated_mlp(x, p, ("b", "shared"), False),
                               atol=1e-5)


# ---- the reference: its count of held rows, its independence --------------------


def test_the_reference_counts_the_rows_on_the_held_experts(cell, ref):
    sizes, params, x, _, _, _ = KANANA.seeded_case
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


def test_lings_model_says_its_gate_and_its_pairing(monkeypatch, tmp_path):
    model = LING.cell.module("program").build(LING.cell.sizes(rehearse=True))["model"]
    gauges = dc.gauges_of(model, 32, monkeypatch, tmp_path)
    assert gauges["mla.head_gate"] == 1 and gauges["mla.rotary_interleaved"] == 0
    assert gauges["kda.layers"] == 3 and gauges["moe.groups"] == 4


def test_a_delta_rule_layer_without_a_head_size_is_refused(cell):
    model = cell.module("program").build(cell.sizes(rehearse=True))["model"]
    assert type(model) is hybrid.DeltaLatentMoELM and model.head_dim is None
    model = dc._float32_model(cell, cell.sizes(rehearse=True),
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
# delta-rule layer, and a change to that layer must not reach it.  PR 50
# replaced the four that run `held_topk_experts` (Ling, Laguna, SmallThinker,
# Kanana: a pass gathers its own weights and the held experts are named by
# comparison); Granite's, which has no expert layer, was PR 44's until PR 51
# replaced all five: every decoder ends in `chunked_softmax_cross_entropy`,
# whose gradient is now taken in its forward loop (a `custom_vjp`, no
# `checkpoint`; Granite's was 709927d1..., this file's cell's 45bac186...).
LOWERED = {
    "ling-3.0-flash-vl-atc-warmup-b1-s8k-1chip":
        "f8d62086177dd395bcf60d19d2617d8dc763e0edf1664c441b1cba38d492ae33",
    "granite-4.0-h-micro-atc-warmup-b1-s8k-1chip":
        "83b707c64d21c73664894b8bd56ff6fd9c4914c3493b29e2c0ad091125f6e5e8",
    "laguna-xs.2-atc-warmup-b1-s8k-1chip":
        "dd842a6c622a527f5434db51401d9e1857e02d1aae4119bc04446a1f95d1f907",
    "smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip":
        "3ebb25b9ee424522c76b3b73201abae353d56de657ec2aa97aeb1062301212bb",
    "kanana-2-30b-a3b-atc-warmup-b1-s8k-1chip":
        "4133bcd4b79c76b20887251512a4e72be61118307fb0ddd9ce1570f34f37f993",
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
    loss-and-gradient: one pass of 16,384 rows in it, as at 80edb39, before a
    pass was sized from the load; the text is what PR 50's tree lowers it to
    (its parent, 1cede2d, gathered every weight before the passes: 08fddf53...)."""
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
        == "9c7c576fcb635e0f7f99bf034c11c04bdb424161dd1b6f70d78b1abcd1d6cb98"


# ---- the manifest, the readers --------------------------------------------------


def test_the_cell_and_its_three_metrics_are_the_manifests(cell):
    assert cell.mix_name == "atc-warmup-b1-s8k-1chip" and cell.chips == 1
    bench = manifest.load_manifest()
    # the seventh configuration and the tenth cell; later ones stand after them
    assert [c["name"] for c in bench["configs"]].index(cell.config_name) == 6
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 9
    unscoped = next(p for p in bench["per_layer"] if p["name"] == "unscoped_ms_per_step")
    assert CELL not in unscoped["workloads"]  # the mla_* ops would be counted twice
    names = [p["name"] for p in bench["per_layer"]]
    first = names.index(dc.KANANA_ROOFLINES[0])
    own = bench["per_layer"][first:first + 3]
    assert [p["name"] for p in own] == list(dc.KANANA_ROOFLINES)
    for p in own:
        assert p == {"name": p["name"], "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "train_samples_s_chip", "workloads": [CELL]}
    # appended, and the standing cells' lists otherwise as they were: only
    # cells the manifest lists after this one stand after it
    order = [w["name"] for w in bench["workloads"]]
    for p in bench["per_layer"][:first]:
        if CELL in p.get("workloads", []):
            assert all(order.index(w) > 9 for w in
                       p["workloads"][p["workloads"].index(CELL) + 1:])


def test_the_rotarys_width_goes_by_another_name_than_the_sources(cell):
    cfg, sizes = cell.config, cell.config["sizes"]
    assert cfg["qk_head_dim"] == sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    assert "head_dim" not in sizes  # the rotary's width under another name: `assumed`


def test_equal_head_sizes_are_not_told_apart(cell):
    shared = cell.reader("flash_fwd_global_roofline")
    assert [shared.kind_of(dc.KANANA_TRACED[k], 192, 128) for k in ("fwd", "dkv", "dq")] \
        == ["fwd", "dkv", "dq"]
    assert [shared.kind_of(dc.KANANA_TRACED[k], 128, 128) for k in ("fwd", "dkv", "dq")] == [None] * 3
    assert shared.kind_of("%attention_global.3 = (f32[4]{0}, f32[4]{0})", 192, 128) is None
    assert shared.kind_of("%flash_fwd_window.3 = (bf16[32,8192,128]{2,1,0}, f32[1])",
                          192, 128) is None

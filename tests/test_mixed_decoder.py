"""The mixed-attention expert decoder (`models.transformer.MixedAttentionMoELM`)
against the configuration's plain reference at a small size, the pieces it
shares with the models that were there (`_rotary`'s base, `_DecoderBlock`'s
head size), the configuration file against its published source, and the
lowered text of what was there before: `switch_moe`, `LlamaLM`, `BertEncoder`
and `window=None` attention lower to the text the parent's tree gave."""

import hashlib
import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models import transformer as tr
from bluefog_tpu.models.transformer import BertEncoder, LlamaLM, MixedAttentionMoELM
from bluefog_tpu.parallel import expert as ep
from bluefog_tpu.training import make_lm_loss_fns

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import manifest, seeded  # noqa: E402

CELL = "smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip"


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL)


def _float32_program(cell, sizes):
    """The program's model at `sizes`, computing in float32 so that the
    comparison with the float32 reference is of the mathematics."""
    program = cell.module("program").build(sizes)
    model = program["model"].clone(dtype=jnp.float32)
    return make_lm_loss_fns(model)[0]


@pytest.mark.parametrize("seq_len,window", [(64, 24), (32, 32), (32, 80)])
def test_loss_and_gradients_match_the_plain_reference(cell, seq_len, window):
    """Windows smaller than, equal to and larger than the sequence; the
    global layer, the rotary layers, 4 query heads on 2 key-value heads of
    16 with hidden 64, the router ahead of the attention, the held experts,
    the chunked loss over the slice."""
    sizes = dict(cell.sizes(rehearse=True), seq_len=seq_len,
                 sliding_window_size=window)
    ref = cell.module("reference")
    apply_fn = _float32_program(cell, sizes)
    params, _ = seeded.make_weights(ref, sizes, seed=11)
    # std 0.02 at hidden 64 leaves the experts' terms at 1e-4 of the stream:
    # widen them so that a wrong expert shows in the loss
    params = {p: a * (12.0 if p[-1] in ("wg", "wu", "wd", "router") else 1.0)
              for p, a in params.items()}
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


def test_the_layers_differ_by_kind_and_the_head_size_is_its_own(cell):
    sizes = cell.sizes()
    program = cell.module("program").build(sizes)
    model = program["model"]
    assert model.layer_windows == (None, 4096, 4096, 4096)
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (28, 4, 128)
    assert model.head_dim != model.hidden_size // model.num_heads  # 91
    assert model.experts_held == tuple(range(8)) and model.num_experts == 64
    assert model.rope_base == 1_500_000 and model.top_k == 6
    assert program["loss_fn"]("the loss", None) == "the loss"
    shapes, _ = cell.module("reference").param_shapes(sizes)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 370_547_200
    assert shapes[("layer_0", "q", "kernel")] == (2560, 28, 128)
    assert shapes[("layer_3", "wd")] == (8, 768, 2560)


def test_rotary_takes_its_base_and_keeps_its_default():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    pos = jnp.arange(8)
    assert np.array_equal(tr._rotary(x, pos), tr._rotary(x, pos, 10000.0))
    far = tr._rotary(x, pos, 1.5e6)
    assert not np.allclose(far, tr._rotary(x, pos))
    # position 0 is the identity whatever the base; norms are kept
    np.testing.assert_allclose(far[:, 0], x[:, 0], atol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(far, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_decoder_block_takes_an_explicit_head_size():
    block = tr._DecoderBlock(num_heads=4, dff=16, dtype=jnp.float32, head_dim=24)
    x = jnp.ones((1, 8, 32))
    v = block.init(jax.random.PRNGKey(0), x, jnp.arange(8))
    shapes = jax.tree_util.tree_map(lambda a: a.shape, v["params"])
    assert shapes["DenseGeneral_0"]["kernel"] == (32, 4, 24)
    assert shapes["Dense_0"]["kernel"] == (96, 32)
    assert block.apply(v, x, jnp.arange(8)).shape == x.shape


# ---- the configuration file against its source ---------------------------

PUBLISHED = {  # config.json of the source, as the guide's catalog copies it
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
    "moe_num_primary_experts": 64, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_theta": 1500000, "sliding_window_size": 4096, "vocab_size": 151936,
}


def test_no_width_differs_from_the_source_and_the_cut_is_stated(cell):
    cfg = cell.config
    cut = {"num_hidden_layers": 4, "vocab_size": 18992}
    for key, value in PUBLISHED.items():
        assert cfg[key] == cut.get(key, value), key
        if key in cfg["sizes"]:
            assert cfg["sizes"][key] == cfg[key], key  # one number, stated twice
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts_held",
                              "vocab_size"]
    assert cfg["sizes"]["moe_num_primary_experts_held"] == 8 \
        == cfg["moe_num_primary_experts_held"]
    assert cfg["published"]["num_hidden_layers"] == 52
    assert cfg["published"]["moe_num_primary_experts"] == 64
    assert cfg["published"]["vocab_size"] == 151936 == 8 * cfg["vocab_size"]
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == [0, 1, 1, 1] * 13
    assert cfg["sizes"]["sliding_window_layout"] == cfg["sliding_window_layout"]
    assert cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]
    assert cfg["tie_word_embeddings"] is False
    assert "eight" in cfg["deployment"] and "one period" in cfg["deployment"]
    mix = cell.mix
    assert mix["sizes"] == {"per_rank_batch": 2, "seq_len": 8192}
    assert cfg["optimizer"] == {
        "name": "adamw", "learning_rate": 3e-4, "weight_decay": 0.1}
    assert mix["optimizer"] == dict(cfg["optimizer"], warmup_steps=2000)
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["source"] == cfg["source"] and entry["source"].endswith("config.json")


def test_flops_count_the_visible_pairs_the_held_experts_and_the_slice(cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    assert flops.visible_pairs(8192) == 33_558_528
    assert flops.visible_pairs(8192, 4096) == 25_167_872
    assert flops.visible_pairs(8192, 9000) == flops.visible_pairs(8192)
    per_token = 2 * flops.forward_macs(sizes) / sizes["seq_len"]
    assert per_token == pytest.approx(492.57e6, rel=1e-4)
    head = 2 * 2560 * 18992
    assert head == pytest.approx(97.2e6, rel=1e-3)
    assert flops.train_flops_per_sample(sizes) * 2 == pytest.approx(24.21e12, rel=1e-3)
    # a kernel call: the dK/dV kernel does twice the forward's products
    f, fb = flops.kernel_call(sizes, "fwd", 4096)
    d, db = flops.kernel_call(sizes, "dkv", 4096)
    assert d == 2 * f and f == 2 * 2 * 128 * 25_167_872 * 56
    assert 1.0e9 < fb < db < 1.6e9


# ---- what was there lowers to what it lowered to -------------------------

# sha256 of the lowered text on the parent's tree (2a9d9f7), from the calls
# below run there; this file's calls give the same on this tree.  A PR that
# means to change one of these programs changes its line here.  PR 40 changed
# `flash_attention`'s: the names on the forward rule's output and logsumexp
# lower to nothing, but two private functions get the next number from MLIR's
# symbol table (`@_where_72` -> `_73`); the text is otherwise 2a9d9f7's.
PARENT = {
    "LlamaLM": "5132018cf045a8abf40fbcfe99a3b7d75f27ce4c26cd17302d4777940aa3c43c",
    "BertEncoder": "b55ab75494e5f6b94feddadcfbff1b4134554ee9aa1e5a6af46ac72030240faf",
    "switch_moe": "30cac6f0ea3fae97bdb8a9a1cde3f7ff1b92e436d0cf6777209f1d9c9df3bfa0",
    "flash_attention": "75e30d9c91ad971b469d7f443d44a78454f48bc233ece481fb0991ba59d8bd00",
}


def _sha(fn, *args):
    return hashlib.sha256(jax.jit(fn).lower(*args).as_text().encode()).hexdigest()


def _lowered(name, devices):
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    zeros = jnp.zeros((2, 16), jnp.int32)
    if name == "LlamaLM":
        model = LlamaLM(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                        dff=48, num_kv_heads=2, head_chunks=2)
        v = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), zeros))
        return _sha(jax.grad(lambda v, i: model.apply(v, i, labels=i)), v, ids)
    if name == "BertEncoder":
        model = BertEncoder(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                            dff=48, max_len=16, num_classes=2)
        v = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), zeros))
        return _sha(jax.grad(lambda v, i: model.apply(v, i).sum()), v, ids)
    if name == "switch_moe":
        mesh = Mesh(np.array(devices[:4]), ("ep",))
        params = jax.eval_shape(
            lambda: ep.init_moe_params(jax.random.PRNGKey(0), 8, 16, 8))

        def moe(x, params):
            def body(x, p):
                o, aux = ep.switch_moe(x[0], p, "ep")
                return o[None], aux
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P("ep"), {"router": P(), "wi": P("ep"), "wo": P("ep")}),
                out_specs=(P("ep"), P()))(x, params)

        return _sha(moe, jax.ShapeDtypeStruct((4, 8, 8), jnp.float32), params)
    q = jax.ShapeDtypeStruct((1, 64, 2, 16), jnp.float32)

    def flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                       block_k=16, interpret=True))

    return _sha(jax.grad(flash, (0, 1, 2)), q, q, q)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_what_was_there_lowers_to_the_parents_text(name, devices):
    assert _lowered(name, devices) == PARENT[name]

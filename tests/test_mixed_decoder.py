"""`smallthinker-21b-a3b`'s own (`models.transformer.MixedAttentionMoELM`): loss
and gradients against the plain reference under windows smaller than, equal to
and larger than the sequence; `_rotary`'s base and `_DecoderBlock`'s head size;
and what was there before lowering to the parent's text (`switch_moe`,
`LlamaLM`, `BertEncoder`, `window=None` attention).  The cases it shares with
the other decoder configurations are in `tests/test_decoder_cells.py`."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models import transformer as tr
from bluefog_tpu.models.transformer import BertEncoder, LlamaLM
from bluefog_tpu.parallel import expert as ep
from decoder_cells import SMALLTHINKER, model_matches, reference_case, widened


@pytest.fixture(scope="module")
def cell():
    return SMALLTHINKER.cell


@pytest.mark.parametrize("seq_len,window", [(64, 24), (32, 32), (32, 80)])
def test_loss_and_gradients_match_the_plain_reference(cell, seq_len, window):
    """Windows smaller than, equal to and larger than the sequence; the
    global layer, the rotary layers, 4 query heads on 2 key-value heads of
    16 with hidden 64, the router ahead of the attention, the held experts,
    the chunked loss over the slice."""
    case = reference_case(cell, dict(cell.sizes(rehearse=True), seq_len=seq_len,
                                     sliding_window_size=window), widened)
    assert all(np.linalg.norm(g) > 0 for g in case[-1].values())
    model_matches(cell, case, 2e-3)


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


# ---- what was there lowers to what it lowered to -------------------------

# sha256 of the lowered text on the parent's tree (2a9d9f7), from the calls
# below run there; this file's calls give the same on this tree.  A PR that
# means to change one of these programs changes its line here.  PR 40 changed
# `flash_attention`'s: the names on the forward rule's output and logsumexp
# lower to nothing, but two private functions get the next number from MLIR's
# symbol table (`@_where_72` -> `_73`); the text is otherwise 2a9d9f7's.
# PR 51 changed `LlamaLM`'s, built here with `head_chunks=2`: the chunked loss
# takes its gradient in its forward loop (5132018c... before).
PARENT = {
    "LlamaLM": "57c6b2cc61e0c646e182c3e2869fe300aa0dfe869ef1f65e390f145ab56e477c",
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

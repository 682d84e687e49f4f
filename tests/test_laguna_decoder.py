"""`laguna-xs.2`'s own (the gated layers of `MixedAttentionMoELM`): loss and
gradients against the plain reference with a router that has to choose, under
three shapes of window; the shares of the expert layer adding up; the two
rotaries, the gate, the head counts by layer kind; the flash kernels reading
shared key-value heads in place; `route_topk`'s scale; the held rows and the
routing tool.  The cases it shares with the other decoder configurations are
in `tests/test_decoder_cells.py`."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.kernels.flash_attention import _Band, flash_attention
from bluefog_tpu.models import transformer as tr
from bluefog_tpu.parallel import expert as ep
from decoder_cells import LAGUNA, model_matches, reference_case, routing_row, widened

from chipbench import seeded

CELL = LAGUNA.cell_name


@pytest.fixture(scope="module")
def cell():
    return LAGUNA.cell


def _small(cell, **over):
    """The rehearsal's sizes with a router that has to choose: 3 of 8
    experts, 4 of them held here."""
    return dict(cell.sizes(rehearse=True), num_experts=8, num_experts_per_tok=3,
                num_experts_held=4, **over)


# the band inside the sequence and across several blocks (16 rows a block),
# as long as the sequence, and past it
@pytest.mark.parametrize("seq_len,window", [(64, 24), (32, 32), (32, 80)])
def test_loss_and_gradients_match_the_plain_reference(cell, seq_len, window):
    """4 (full) and 6 (window) query heads on 2 key-value heads of 16 read in
    place, YaRN over half a head and a plain rotary over a whole one, the
    gate, the leading dense layer, the router after the attention choosing 3
    of 8 with 4 held, the shared expert, the chunked loss over the slice."""
    case = reference_case(cell, _small(cell, seq_len=seq_len, sliding_window=window),
                          widened)
    assert all(np.linalg.norm(g) > 0 for g in case[-1].values())
    model_matches(cell, case, 2e-3)


def test_the_shares_add_up_to_the_uncut_layer(cell):
    """64 experts at 8 a share: the eight shares' expert terms, with what
    every share computes alike (the attention, the shared expert) counted
    once, are the uncut reference layer; the program's layer given a share
    computes that share's part."""
    sizes = dict(_small(cell, seq_len=32), num_experts=64, num_experts_per_tok=6,
                 num_experts_held=64)
    ref = cell.module("reference")
    params = widened(seeded.make_weights(ref, sizes, seed=5)[0])
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


# ---- the routing, and the chip smoke's phase ---------------------------------


def test_held_rows_count_the_references_routing(cell):
    """`held_rows` is the count of equation 5's top-k that falls on the experts
    held, in each layer that has experts (four of the five)."""
    sizes = _small(cell, seq_len=32)
    ref = cell.module("reference")
    params = widened(seeded.make_weights(ref, sizes, seed=9)[0])
    (x, _), = seeded.make_batches(ref, sizes, 9, ranks=1, pool=1)
    rows = np.asarray(jax.jit(lambda p, i: ref.held_rows(p, i, sizes))(params, x[0]))
    assert rows.shape == (4,) and rows.dtype.kind == "i"
    total = x[0].size * sizes["num_experts_per_tok"]
    assert (rows > 0).all() and (rows < total).all()
    assert abs(rows.sum() / (4 * total) - 0.5) < 0.15  # 4 of 8 held, near even
    # every expert held: every assignment
    every = dict(sizes, num_experts_held=8)
    p8 = widened(seeded.make_weights(ref, every, seed=9)[0])
    assert np.asarray(ref.held_rows(p8, x[0], every)).tolist() == [total] * 4


def test_the_routing_tool_counts_this_configurations_rows(capsys):
    """`chipbench.routing`'s readings under this configuration's own names
    for the three sizes that tool reads, at rehearsal sizes."""
    from chipbench import routing_laguna

    row = routing_row(routing_laguna, CELL, capsys)
    sizes = LAGUNA.cell.sizes(rehearse=True)
    # top-4 of 4 experts, 2 held: every token reaches both, in the four sparse layers
    even = sizes["per_rank_batch"] * sizes["seq_len"] * 4 * 2 / 4
    assert row["even_rows"] == even
    assert row["held_rows_first_step"] == row["held_rows_last_step"] == [int(even)] * 4


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

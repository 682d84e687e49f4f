"""`ling-3.0-flash-vl`'s own (`models.hybrid.DeltaLatentMoELM`): the chunked
delta rule (`kernels/kda.py`, interpret mode) against the token-by-token
recurrence, values and all five gradients, decays at the bound and near none;
the chunk's stateless stage, both kernels against the XLA expression it was
(`tests/kda_oracle.py`); the flash kernels' two head sizes; both mixers
against the reference's; the router against a written-out loop; the shares of
an expert layer adding up; the routing tool; the period of mixers in the cut;
the cell's place in the manifest.  The cases it shares with the other decoder
configurations are in `tests/test_decoder_cells.py`."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kda_oracle

from bluefog_tpu.kernels import kda
from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.kernels.kda import kda_chunked
from bluefog_tpu.models import hybrid
from bluefog_tpu.parallel.expert import route_topk
import decoder_cells as dc
from decoder_cells import LING

from chipbench import manifest, seeded

CELL = LING.cell_name


@pytest.fixture(scope="module")
def cell():
    return LING.cell


@pytest.fixture(scope="module")
def ref():
    return LING.reference


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
    got = dc.values_and_grads(
        lambda *a: kda_chunked(*a, chunk=chunk, heads_at_once=at_once), args, weight)
    want = dc.values_and_grads(kda_oracle.on_units(jax.vmap(ref.kda_scan)), args, weight)
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
    got = dc.values_and_grads(lambda *a: kda_chunked(*a, chunk=16), (q, k) + twice[2:],
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
    got = dc.values_and_grads(lambda *a: kda_chunked(*a, chunk=16), args, weight)
    want = dc.values_and_grads(kda_oracle.on_units(jax.vmap(ref.kda_scan)), args, weight)
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
    short = dc.values_and_grads(fn, tuple(a[:, :24] for a in args), weight[:, :24])
    whole = dc.values_and_grads(fn, args, weight.at[:, 24:].set(0.0))
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
def _stage_forward(chunk, gate, on_grid=True):
    """Two chunks of four heads through `kda._intra` (the four heads in one
    grid step) and through the oracle, both under `jax.vjp`: the six outputs
    of each and the two pull-backs.  `on_grid`: the log-decay rounded to a
    multiple of 2^-12, so that a running sum of 128 of them is exact in
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
    return got, want, pull, pull_oracle


@functools.lru_cache(maxsize=None)
def _stage_backward(chunk, gate, on_grid=True):
    """The same random cotangents of all six outputs through both pull-backs
    (the backward kernel, JAX's transpose of the expression): two thirds of a
    case's time, which the forward's cases leave alone."""
    _, want, pull, pull_oracle = _stage_forward(chunk, gate, on_grid)
    cotangents = tuple(jax.random.normal(jax.random.PRNGKey(n), w.shape)
                       for n, w in enumerate(want))
    return pull(cotangents), pull_oracle(cotangents)


def _gaps(got, want):
    """Largest difference of each array over the oracle's largest entry."""
    return [float(jnp.max(jnp.abs(a - b))) / max(float(jnp.max(jnp.abs(b))), 1e-30)
            for a, b in zip(got, want)]


@pytest.mark.parametrize("gate", ["bound", "none", "spread"])
@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_the_stage_forward_kernel_is_the_expression(chunk, gate):
    got, want, _, _ = _stage_forward(chunk, gate)
    assert [a.shape for a in got] == [w.shape for w in want]
    for name, gap in zip(STAGE_OUTPUTS, _gaps(got, want)):
        assert gap <= 2e-6, (name, gap)


@pytest.mark.parametrize("gate", ["bound", "none", "spread"])
@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_the_stage_backward_kernel_is_the_expressions_vjp(chunk, gate):
    """The adjoint written out against JAX's transpose of the expression:
    2e-5 of each gradient's largest entry, 1e-4 on the decay's at the bound."""
    got, want = _stage_backward(chunk, gate)
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
    got, want, _, _ = _stage_forward(chunk, "spread", on_grid=False)
    dgot, dwant = _stage_backward(chunk, "spread", on_grid=False)
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


ONE_DENSE = dict(mlp_layer_types=["dense"])  # beside the one mixer of `mixer_case`


def test_latent_attention_is_dense_softmax_from_the_compressed_form(ref):
    """The program's mixer (one 24-wide product a head through the flash
    kernels, 16 + 8 rotary, values of 16) against the reference's: queries and
    keys laid out from the latent and the one rotary head, scores over
    sqrt(24), the mask explicit."""
    sizes, mixer, u, weight = dc.mixer_case(LING, layer_types=["mla"], **ONE_DENSE)
    assert mixer[("mla_q", "kernel")].shape[-1] == 24
    assert mixer[("mla_kv_down", "kernel")].shape[-1] == sizes["kv_lora_rank"] + 8
    dc.mixer_is_the_references(dc.latent_mixer(sizes), ref.mla_mixer, mixer, u, weight, sizes)


@pytest.mark.parametrize("tokens,channels,kernels", [(64, 64, False), (64, 128, True)])
def test_the_delta_mixer_is_the_references(ref, tokens, channels, kernels):
    """`KDAMixer` against the reference's token-by-token mixer: at the
    rehearsal's 4 heads of 16 the three convolutions take the expression, at 8
    heads of 16 (384 channels) the kernels of `kernels/causal_conv.py`."""
    heads = channels // 16
    sizes, mixer, u, weight = dc.mixer_case(LING, layer_types=["kda"], **ONE_DENSE,
                                            num_attention_heads=heads)
    assert hybrid.kda_conv_kernels_take(tokens, channels, 4) is kernels
    module = hybrid.KDAMixer(heads, 16, 4, 32, sizes["kda_lower_bound"],
                             sizes["rms_norm_eps"], jnp.float32)
    dc.mixer_is_the_references(module, ref.kda_mixer, mixer, u, weight, sizes)


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


# ---- the router ---------------------------------------------------------------


@pytest.mark.parametrize("groups,kept,top_k", [(4, 2, 4), (8, 4, 3), (1, 1, 5), (4, 4, 6)])
def test_the_router_is_the_written_out_loop(ref, groups, kept, top_k):
    """Sigmoid scores, the bias in the choice only, a group's score its two
    largest, the weights without the bias: the library's, the reference's and
    a loop in float64 choose the same experts and weigh them alike."""
    sizes = {"n_group": groups, "topk_group": kept, "num_experts": 16,
             "num_experts_per_tok": top_k, "routed_scaling_factor": 2.5}
    dc.router_is_the_loop(ref, sizes, dc.router_case(), groups, kept)


def test_no_gradient_reaches_the_routers_bias_and_the_router_has_one():
    dc.no_gradient_reaches_the_bias(4, 2.5, groups=4, groups_kept=2)


@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_the_routers_defaults_are_what_they_were_bit_for_bit(scale):
    """SmallThinker's and Laguna's call: logits, top-k, a softmax over the k
    chosen logits, times the scale where it is not 1."""
    x, router, _ = dc.router_case()
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
    x, router, bias = dc.router_case()
    with pytest.raises(ValueError, match="tanh"):
        route_topk(x, router, 4, score="tanh", bias=bias)
    with pytest.raises(ValueError, match="groups"):
        route_topk(x, router, 4, score="sigmoid", groups=3)


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """16 experts in 4 groups over 4 shares of 4, top-4 of the two groups kept,
    one shared expert."""
    sizes = {"n_group": 4, "topk_group": 2, "num_experts": 16,
             "num_experts_per_tok": 4, "routed_scaling_factor": 2.5}
    dc.the_shares_add_up(ref, ref.expert_terms, sizes, 16, 4, 16, groups=4, groups_kept=2)


# ---- the routing tool, the decoder class, the cut, the manifest -------------------


def test_the_routing_tool_counts_the_rows_on_the_experts_held(cell, ref, capsys):
    """`python -m chipbench.routing_ling`: `chipbench.routing`'s readings for
    this configuration, whose reference has no `held_rows`; the count is made
    from the reference's own mixers, norms and `route`, in the three layers of
    the rehearsal that have experts."""
    from chipbench import routing_ling

    row = dc.routing_row(routing_ling, CELL, capsys)
    sizes = cell.sizes(rehearse=True)
    total = sizes["per_rank_batch"] * sizes["seq_len"] * sizes["num_experts_per_tok"]
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


def test_the_cut_keeps_the_period_of_mixers_and_no_clipped_layer(cell):
    """What the shared case's table cannot say in numbers: the kinds follow
    from the published indices kept."""
    cfg, sizes = cell.config, cell.config["sizes"]
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
    assert "vision tower is not" in cfg["published"]["described_as"]


def test_the_cell_is_the_manifests(cell):
    assert "19,648" in cell.mix["describes"]
    bench = manifest.load_manifest()
    assert len(bench["configs"]) >= 6 and len(bench["workloads"]) >= 9
    assert bench["workloads"][8]["name"] == CELL and cell.chips == 1
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

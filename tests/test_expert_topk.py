"""The top-k expert layer that is told which experts it holds
(`parallel.expert.route_topk`, `held_topk_experts`): the shares add up to the
uncut layer of the configuration's plain reference, no assignment is lost
when one expert gets every token, gradients reach the router and the experts,
the layer is the parent's prologue to the bit with nothing looked up or
scattered over all the assignments, and the trace-time gauges say what was
built."""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu import telemetry
from bluefog_tpu.parallel import expert as ep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import manifest  # noqa: E402

T, D, F, E, K = 96, 16, 8, 64, 6  # the configuration's 64 experts, top-6


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module(os.path.join(
        REPO, "chipbench", "reference", "smallthinker-21b-a3b.py"))


@pytest.fixture(scope="module")
def layer():
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    return {
        "x": jax.random.normal(ks[0], (T, D)), "m": jax.random.normal(ks[1], (T, D)),
        "router": jax.random.normal(ks[2], (D, E)),
        "wg": 0.3 * jax.random.normal(ks[3], (E, D, F)),
        "wu": 0.3 * jax.random.normal(ks[4], (E, D, F)),
        "wd": 0.3 * jax.random.normal(ks[5], (E, F, D)),
    }


def share(layer, held, rows=None):
    held = list(held)
    experts, weights = ep.route_topk(layer["x"], layer["router"], K)
    stacks = {n: layer[n][jnp.asarray(held)] for n in ("wg", "wu", "wd")}
    return ep.held_topk_experts(layer["m"], experts, weights, stacks, held, E,
                                rows=rows)


def uncut(reference, layer):
    """The plain reference's expert terms with all 64 experts held."""
    sizes = {"moe_num_active_primary_experts": K}
    p = {("l", n): layer[n] for n in ("wg", "wu", "wd")}
    r = layer["x"] @ layer["router"]
    return reference.expert_terms(layer["m"], r, p, "l", sizes, False, tuple(range(E)))


@pytest.mark.parametrize("rows", [None, 16])
def test_the_eight_shares_add_up_to_the_uncut_layer(reference, layer, rows):
    """Each share holds 8 of the 64 experts and routes over all 64; the
    eight shares' expert terms, the residual counted once, are the uncut
    64-expert reference's layer output."""
    shares = [share(layer, range(8 * s, 8 * s + 8), rows) for s in range(8)]
    whole = layer["m"] + sum(shares)  # the residual once
    want = layer["m"] + uncut(reference, layer)
    np.testing.assert_allclose(whole, want, atol=2e-5)
    # a share alone leaves the others' terms out: it is not the whole
    assert float(jnp.max(jnp.abs(shares[0] - uncut(reference, layer)))) > 1e-2


def test_no_assignment_is_lost_when_one_expert_gets_every_token(layer):
    """Identical tokens: all T pile up on the same six experts, four of them
    held here.  Every one of the 4 T = 384 assignments is computed, in six
    passes of 64 rows and in the default buffer alike."""
    piled = dict(layer, x=jnp.broadcast_to(layer["x"][:1], (T, D)),
                 m=jnp.broadcast_to(layer["m"][:1], (T, D)))
    experts, weights = ep.route_topk(piled["x"], piled["router"], K)
    chosen = [int(e) for e in np.asarray(experts[0])]
    assert all((np.asarray(experts) == np.asarray(chosen)).all(axis=1))
    held = chosen[:4] + [e for e in range(E) if e not in chosen][:4]
    m0 = piled["m"][0]
    want = sum(
        weights[0, j] * ((jax.nn.relu(m0 @ layer["wg"][e]) * (m0 @ layer["wu"][e]))
                         @ layer["wd"][e])
        for j, e in enumerate(chosen) if e in held)
    for rows in (None, 64):
        out = share(piled, held, rows)
        np.testing.assert_allclose(out, jnp.broadcast_to(want, (T, D)), atol=1e-5)


def test_chip_smokes_pile_up_witness_walks_several_passes():
    """`chip_smoke.phase_experts_piled` is how the loop's several passes are
    held against the plain reference at the benchmark's sizes on the chip;
    here its rehearsal: tokens that differ, a router pushed towards the
    experts held, seven passes of 64 rows with the last one partly filled,
    output and every gradient within the phase's own tolerance, and the loop
    no further from the reference than one pass of all the rows is."""
    import chip_smoke

    got = chip_smoke.phase_experts_piled(
        chip_smoke.TINY["experts"], 0, chip_smoke._CompileClock())
    assert got["passes"] == 7
    assert sorted(got["rel_l2"]) == ["m", "out", "router", "wd", "wg", "wu"]
    for name, gap in got["rel_l2"].items():
        assert gap <= chip_smoke.EXPERTS_L2_RTOL
        assert abs(gap - got["rel_l2_in_one_pass"][name]) < 1e-4


def test_gradients_reach_router_and_held_experts(reference, layer):
    def ours(layer):
        return jnp.sum(sum(share(layer, range(8 * s, 8 * s + 8), 32)
                           for s in range(8)) ** 2)

    def plain(layer):
        return jnp.sum(uncut(reference, layer) ** 2)

    got, want = jax.grad(ours)(layer), jax.grad(plain)(layer)
    for name in ("router", "m", "wg", "wu", "wd"):
        scale = float(jnp.max(jnp.abs(want[name])))
        assert scale > 0
        np.testing.assert_allclose(got[name], want[name], atol=1e-5 * scale + 1e-7)


def test_a_width_the_lanes_divide_is_added_up_by_lanes(reference):
    """At a hidden size that 128 divides the token sums are kept as
    [T, d / 128, 128] (a row is a tile-aligned block): the same numbers,
    values and gradients, as the plain reference's."""
    d = 256
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    wide = {"x": jax.random.normal(ks[0], (T, d)), "m": jax.random.normal(ks[1], (T, d)),
            "router": jax.random.normal(ks[2], (d, E)),
            "wg": 0.1 * jax.random.normal(ks[3], (E, d, F)),
            "wu": 0.1 * jax.random.normal(ks[4], (E, d, F)),
            "wd": 0.1 * jax.random.normal(ks[5], (E, F, d))}
    assert ep._by_lanes((T, d)) == (T, 2, 128) and ep._by_lanes((T, D)) == (T, D)

    def ours(layer):
        return sum(share(layer, range(8 * s, 8 * s + 8), 40) for s in range(8))

    np.testing.assert_allclose(ours(wide), uncut(reference, wide), atol=2e-5)
    got = jax.grad(lambda l: jnp.sum(ours(l) ** 2))(wide)
    want = jax.grad(lambda l: jnp.sum(uncut(reference, l) ** 2))(wide)
    for name in ("router", "m", "wg", "wu", "wd"):
        scale = float(jnp.max(jnp.abs(want[name])))
        np.testing.assert_allclose(got[name], want[name], atol=2e-5 * scale + 1e-7)


def test_route_topk_weights_are_the_softmax_over_all_renormalised(layer):
    experts, weights = ep.route_topk(layer["x"], layer["router"], K)
    probs = jax.nn.softmax(layer["x"] @ layer["router"], axis=-1)
    top = jnp.take_along_axis(probs, experts, axis=1)
    np.testing.assert_allclose(weights, top / top.sum(-1, keepdims=True), rtol=1e-5)
    assert experts.shape == (T, K) and float(weights.sum(-1).min()) > 0.999


def test_held_must_name_the_stacks(layer):
    experts, weights = ep.route_topk(layer["x"], layer["router"], K)
    stacks = {n: layer[n][:8] for n in ("wg", "wu", "wd")}
    with pytest.raises(ValueError, match="held"):
        ep.held_topk_experts(layer["m"], experts, weights, stacks, [0, 1, 2], E)
    with pytest.raises(ValueError, match="distinct"):
        ep.held_topk_experts(layer["m"], experts, weights, stacks, [0] * 8, E)


def test_gauges_say_what_was_built(monkeypatch, tmp_path):
    """`moe.*` and `attention.*` at trace time, as `gossip.*` are: one trace
    of the rehearsal-sized decoder, nothing compiled."""
    from functools import partial

    from bluefog_tpu.kernels.flash_attention import flash_attention
    from bluefog_tpu.models.transformer import MixedAttentionMoELM

    monkeypatch.setenv("BFTPU_TELEMETRY", str(tmp_path))
    telemetry.reset()
    try:
        model = MixedAttentionMoELM(
            vocab_size=128, hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
            layer_windows=(None, 24, 24, 24), num_experts=16, top_k=3,
            experts_held=(0, 1, 2, 3), expert_dff=32, rope_base=1.5e6, head_chunks=2,
            attention_fn=partial(flash_attention, causal=True, block_q=16, block_k=16))
        ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
        v = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                              jnp.zeros((2, 64), jnp.int32)))
        jax.eval_shape(lambda v, i: model.apply(v, i, labels=i), v, ids)
        gauges = {g["name"]: g["value"] for g in
                  telemetry.get_registry().snapshot()["gauges"]}
    finally:
        telemetry.reset()
    assert gauges == {
        "moe.experts_held": 4, "moe.experts_total": 16, "moe.top_k": 3,
        "moe.buffer_rows": 384,  # 128 tokens x min(3, 4): all the rows there can be
        "moe.rows_expected": 96.0,  # 128 x 3 x 4 / 16: what even routing sends here
        "moe.assignments": 384,  # 128 x 3: what the sort and the comparisons run over
        "attention.window": 24, "attention.layers_window": 3,
        "attention.layers_global": 1}
    # the SmallThinker cells' layer: passes of 16,384 sorted rows, one at even
    # routing (12,288 rows expected), six if every token picks six experts held
    assert ep._pass_rows(16384, 6, 8, 64) == ep.PASS_ROWS == 16384


# a step's tokens, top-k, experts held, experts the router knows; the rows of a pass
CELLS = {"smallthinker": ((16384, 6, 8, 64), 16384), "ling": ((8192, 8, 8, 512), 2048),
         "kanana": ((8192, 6, 16, 128), 8192), "laguna": ((8192, 8, 32, 256), 11264)}


@pytest.mark.parametrize("name", CELLS)
def test_a_pass_is_sized_for_the_load_the_shapes_promise(name):
    """Not for the worst routing: the buffer holds the even load and a third,
    in whole grains.  SmallThinker's stays the 16,384 it was chosen at; of the
    three cells that inherited it Ling's and Kanana's get half of it or less
    and Laguna's, whose even load is half of it, two thirds."""
    (tokens, k, held, total), rows = CELLS[name]
    even = tokens * k * held / total
    assert ep._pass_rows(tokens, k, held, total) == rows <= ep.PASS_ROWS
    assert ep.HEADROOM * even <= rows < ep.HEADROOM * even + ep.ROW_GRAIN
    # never more rows than there can be: few tokens, or fewer held than chosen
    assert ep._pass_rows(96, k, held, total) == 96 * min(k, held)
    assert ep._pass_rows(tokens, k, 1, total) <= tokens


def _pushed(layer, tokens, held, push):
    """`tokens` tokens that differ, a last feature of ones whose row of the
    router leans by `push` on the experts `held`, and their stacks."""
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    x = jnp.concatenate([jax.random.normal(ks[0], (tokens, D)),
                         jnp.ones((tokens, 1))], axis=1)
    lean = jnp.zeros((1, E)).at[0, jnp.asarray(held)].set(push)
    stacks = {n: layer[n][jnp.asarray(held)] for n in ("wg", "wu", "wd")}
    return dict(stacks, x=x, m=jax.random.normal(ks[1], (tokens, D)),
                router=jnp.concatenate([layer["router"], lean]))


@pytest.mark.parametrize("push,passes", [(1.5, 2), (3.5, 3)])
def test_routing_that_overfills_the_default_buffer_takes_more_passes(
        reference, layer, push, passes):
    """1,024 tokens promise 768 rows on the 8 experts held, so a pass is
    1,024 rows; a router pushed towards them sends more: two passes, three,
    the last partly filled, and the plain reference's numbers, output and
    every gradient, as from one pass of all the rows there can be."""
    tokens, held = 1024, list(range(8))
    piled = _pushed(layer, tokens, held, push)
    rows = ep._pass_rows(tokens, K, len(held), E)
    assigned = int(jnp.sum(ep.route_topk(piled["x"], piled["router"], K)[0] < 8))
    assert rows == 1024 and -(-assigned // rows) == passes and assigned % rows

    def ours(a, rows=None):
        return share(a, held, rows)

    def plain(a):
        p = {("l", n): a[n][:8] for n in ("wg", "wu", "wd")}
        return reference.expert_terms(
            a["m"], a["x"] @ a["router"], p, "l",
            {"moe_num_active_primary_experts": K}, False, tuple(held))

    np.testing.assert_allclose(ours(piled), plain(piled), atol=2e-5)
    got = jax.grad(lambda a: jnp.sum(ours(a) ** 2))(piled)
    whole = jax.grad(lambda a: jnp.sum(ours(a, tokens * K) ** 2))(piled)
    want = jax.grad(lambda a: jnp.sum(plain(a) ** 2))(piled)
    for name in ("router", "m", "wg", "wu", "wd"):
        scale = float(jnp.max(jnp.abs(want[name])))
        assert scale > 0
        np.testing.assert_allclose(got[name], want[name], atol=2e-5 * scale + 1e-7)
        np.testing.assert_allclose(got[name], whole[name], atol=2e-5 * scale + 1e-7)


# ---- the prologue: the parent's to the bit, and nothing looked up over T k -------


@partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _parents_passes(m, w_sorted, wg, wu, wd, order, starts, ends, k, rows):
    """`_held_passes` as the parent commit (1cede2d) had it: handed all `T k`
    weights in sorted order, a pass slices its rows of them, and the backward
    pass writes their cotangents back slice by slice."""
    return _parents_passes_fwd(m, w_sorted, wg, wu, wd, order, starts, ends, k, rows)[0]


def _parents_passes_fwd(m, w_sorted, wg, wu, wd, order, starts, ends, k, rows):
    def body(i, out):
        _, tok, valid, sizes = ep._pass(i, rows, k, order, starts, ends)
        w_rows = jax.lax.dynamic_slice_in_dim(w_sorted, i * rows, rows)
        y = ep._pass_rows_out(m[tok], w_rows, wg, wu, wd, sizes, valid, m.dtype,
                              jax.nn.relu)
        return ep._add_rows(out, tok, y)

    out = jax.lax.fori_loop(0, ep._passes(ends, rows), body,
                            jnp.zeros(ep._by_lanes(m.shape), jnp.float32))
    return out.reshape(m.shape), (m, w_sorted, wg, wu, wd, order, starts, ends)


def _parents_passes_bwd(k, rows, res, g):
    m, w_sorted, wg, wu, wd, order, starts, ends = res

    def body(i, acc):
        dm, dw_sorted, dwg, dwu, dwd = acc
        _, tok, valid, sizes = ep._pass(i, rows, k, order, starts, ends)
        _, vjp = jax.vjp(
            lambda xs, w_rows, wg, wu, wd: ep._pass_rows_out(
                xs, w_rows, wg, wu, wd, sizes, valid, m.dtype, jax.nn.relu),
            m[tok], jax.lax.dynamic_slice_in_dim(w_sorted, i * rows, rows), wg, wu, wd)
        dxs, dw_rows, g1, g2, g3 = vjp(g[tok])
        return (ep._add_rows(dm, tok, dxs),
                jax.lax.dynamic_update_slice_in_dim(dw_sorted, dw_rows, i * rows, 0),
                dwg + g1, dwu + g2, dwd + g3)

    zeros = lambda like: jnp.zeros(like.shape, jnp.float32)
    dm, dw_sorted, dwg, dwu, dwd = jax.lax.fori_loop(0, ep._passes(ends, rows), body, (
        jnp.zeros(ep._by_lanes(m.shape), jnp.float32), zeros(w_sorted), zeros(wg),
        zeros(wu), zeros(wd)))
    return (dm.reshape(m.shape).astype(m.dtype), dw_sorted, dwg.astype(wg.dtype),
            dwu.astype(wu.dtype), dwd.astype(wd.dtype), None, None, None)


_parents_passes.defvjp(_parents_passes_fwd, _parents_passes_bwd)


def parents_layer(m, experts, weights, params, held, num_experts, rows=None):
    """The oracle: `held_topk_experts` with the parent commit's prologue: a
    table gather names the experts held, `bincount` counts them, and every
    one of the `T k` weights is gathered into sorted order before the passes."""
    tokens, k, held = m.shape[0], experts.shape[1], tuple(held)
    H, A = len(held), tokens * k
    rows = rows or ep._pass_rows(tokens, k, H, num_experts)
    place = np.full((num_experts,), H, np.int32)
    place[list(held)] = np.arange(H)
    group = jnp.asarray(place)[experts].reshape(A)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=H + 1)[:H].astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    most = -(-tokens * min(k, H) // rows) * rows
    if most > A:
        order = jnp.concatenate([order, jnp.zeros((most - A,), order.dtype)])
    out = _parents_passes(m, weights.reshape(A)[order], params["wg"], params["wu"],
                          params["wd"], order, ends - sizes, ends, k, rows)
    return out.astype(m.dtype)


@pytest.mark.parametrize("held,push,rows,passes", [
    (tuple(range(8)), 0.0, None, 1), (tuple(range(8)), 1.5, None, 2),
    (tuple(range(8)), 3.5, 256, 10), ((5, 1, 9), 0.0, None, 1), ((5, 1, 9), 2.0, 64, 11),
    (tuple(range(E)), 0.0, 1000, 7)],
    ids=["one-pass", "two-passes", "ten-passes", "held-5-1-9", "held-5-1-9-pushed",
         "every-expert-held"])
def test_the_layer_is_the_parents_prologue_to_the_bit(layer, held, push, rows, passes):
    """The weights gathered a pass and the held named by comparison change no
    bit: the loss, the output and the gradients to `m`, to the router (through
    `weights`) and to the three stacks equal those of the parent's prologue,
    at one pass, at several, with held experts that are no leading range and
    with every expert held."""
    a = _pushed(layer, 1024, held, push)
    assigned = int(jnp.isin(ep.route_topk(a["x"], a["router"], K)[0],
                            jnp.asarray(held)).sum())
    assert -(-assigned // (rows or ep._pass_rows(1024, K, len(held), E))) == passes

    def run(fn):
        def loss(a):
            experts, weights = ep.route_topk(a["x"], a["router"], K)
            y = fn(a["m"], experts, weights, a, held, E, rows=rows)
            return jnp.sum(y ** 2), y
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(a)

    (got_loss, got_out), got = run(ep.held_topk_experts)
    (want_loss, want_out), want = run(parents_layer)
    assert float(jnp.max(jnp.abs(want["router"]))) > 0
    np.testing.assert_array_equal(got_loss, want_loss)
    np.testing.assert_array_equal(got_out, want_out)
    for name in ("m", "router", "wg", "wu", "wd"):
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def _outside_the_loops(text):
    """The lines of a lowered module that stand in no `stablehlo.while`, and
    how many `while`s there are."""
    kept, depth, inside, loops = [], 0, None, 0
    for line in text.splitlines():
        if inside is None and "stablehlo.while" in line:
            inside, loops = depth, loops + 1
        if inside is None:
            kept.append(line)
        depth += line.count("{") - line.count("}")
        if inside is not None and depth <= inside and "}" in line:
            inside = None
    return kept, loops


def _count(lines, op):
    return sum(line.count(f'"stablehlo.{op}"(') for line in lines)


@pytest.mark.parametrize("name", ["ling", "laguna"])
def test_nothing_is_gathered_or_scattered_over_all_the_assignments(name):
    """Loss-and-gradient of the layer at a cell's shapes (Ling's 65,536
    assignments for 2,048 rows a pass; Laguna's, whose last pass reaches past
    them): every gather and scatter stands inside one of the two loops over the
    passes, on a pass's rows; over all `T k` there is the one sort.  The
    parent's prologue has two gathers and two scatters before its loops."""
    (tokens, k, H, total), rows = CELLS[name]
    d, f, S = 2560, 768, jax.ShapeDtypeStruct
    stacks = {"wg": S((H, d, f), jnp.float32), "wu": S((H, d, f), jnp.float32),
              "wd": S((H, f, d), jnp.float32)}

    def lowered(fn):
        def loss(m, weights, stacks, experts):
            return jnp.sum(fn(m, experts, weights, stacks, range(H), total)
                           .astype(jnp.float32))
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
            S((tokens, d), jnp.bfloat16), S((tokens, k), jnp.float32), stacks,
            S((tokens, k), jnp.int32)).as_text()

    text = lowered(ep.held_topk_experts)
    ours, loops = _outside_the_loops(text)
    assert loops == 2 and f"{rows}x{d}" in text
    assert [_count(ours, op) for op in ("gather", "scatter", "sort")] == [0, 0, 1]
    # in the loops: m[tok] and w[idx] forward, with g[tok] backward; out; dm and dw
    assert [_count(text.splitlines(), op) for op in ("gather", "scatter")] == [5, 3]
    theirs, loops = _outside_the_loops(lowered(parents_layer))
    assert loops == 2
    assert [_count(theirs, op) for op in ("gather", "scatter", "sort")] == [2, 2, 1]

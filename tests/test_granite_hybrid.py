"""The hybrid state-space decoder (`models.hybrid.HybridMambaLM`, IBM's Granite
4.0-H Micro, chipbench's `granite-4.0-h-micro`) at a small size on the CPU:
the chunked scan's kernels in interpret mode against the recurrence taken token
by token, values and all six gradients; the causal convolution against a loop;
the model's loss and every gradient against the configuration's plain
reference, and three steps of AdamW as the cell's `correct` compares them;
each of Granite's four multipliers, the missing rotary and the tied head shown
to matter; recomputation changing nothing; the gauges; the configuration file
against its published source; the FLOP count against a hand count; the new
readers; the cell's rehearsal through `python -m chipbench` and its controls."""

import collections
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.kernels.ssd import ssd_scan
from bluefog_tpu.models import hybrid
from bluefog_tpu.models.transformer import _rotary
from bluefog_tpu.telemetry import registry as telemetry
from bluefog_tpu.training import make_lm_loss_fns

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import check, control, manifest, optimizers, runner, seeded  # noqa: E402

CELL = "granite-4.0-h-micro-atc-warmup-b1-s8k-1chip"


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(CELL)


@pytest.fixture(scope="module")
def ref(cell):
    return cell.module("reference")


# ---- the scan's kernels against the recurrence --------------------------------


def _scan_inputs(seed, t, heads=4, p=16, groups=1, n=32, batch=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (batch, t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, t, heads)) - 2.0)
    a_log = jnp.log(jax.random.uniform(k[2], (heads,), minval=1.0, maxval=16.0))
    bm = 0.5 * jax.random.normal(k[3], (batch, t, groups, n))
    cm = 0.5 * jax.random.normal(k[4], (batch, t, groups, n))
    skip = jax.random.normal(k[5], (heads,))
    return (x, dt, a_log, bm, cm, skip), jax.random.normal(k[6], x.shape)


def _values_and_grads(fn, args, weight):
    def loss(*a):
        y = fn(*a)
        return jnp.sum(y * weight), y
    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, tuple(range(6)), has_aux=True))(*args)
    return (y,) + grads


NAMES = ("y", "dx", "ddt", "dA_log", "dB", "dC", "dD")


# a length the chunk divides, one it does not, two groups of heads, and a head
# size that puts one head in a step
@pytest.mark.parametrize("t,chunk,groups,p", [
    (64, 16, 1, 16), (56, 16, 1, 16), (64, 8, 2, 16), (32, 16, 1, 128)])
def test_the_scan_kernels_are_the_token_recurrence(ref, t, chunk, groups, p):
    """`ssd_scan` in interpret mode, float32: y and the gradients in x, dt,
    A_log, B, C and D against `ssm_scan` of the plain reference, which takes
    one token after another.  Float32 sums in another order: 1e-5."""
    args, weight = _scan_inputs(3, t, p=p, groups=groups)
    with jax.default_matmul_precision("highest"):
        got = _values_and_grads(lambda *a: ssd_scan(*a, chunk=chunk), args, weight)
        want = _values_and_grads(
            lambda x, dt, a_log, bm, cm, skip: jax.vmap(
                lambda x, dt, bm, cm: ref.ssm_scan(x, dt, a_log, bm, cm, skip))(
                    x, dt, bm, cm), args, weight)
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape, name
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < 1e-5, (name, gap)


def test_the_chunk_changes_nothing_but_the_order_of_sums():
    args, weight = _scan_inputs(5, 64)
    with jax.default_matmul_precision("highest"):
        a = _values_and_grads(lambda *v: ssd_scan(*v, chunk=8), args, weight)
        b = _values_and_grads(lambda *v: ssd_scan(*v, chunk=16), args, weight)
    for name, u, v in zip(NAMES, a, b):
        assert float(jnp.linalg.norm(u - v) / jnp.linalg.norm(v)) < 1e-5, name


def test_the_scan_in_bfloat16_stays_within_its_smoke_tolerance():
    """bfloat16 operands, float32 accumulators: what `chip_smoke.py --only ssd`
    holds the compiled kernels to at the cell's shapes, here its rehearsal."""
    import chip_smoke

    chip_smoke.phase_ssd(chip_smoke.TINY["ssd"], 0, False, chip_smoke._CompileClock())
    assert "ssd" in chip_smoke.PHASES


def test_the_convolutions_kernels_stay_within_their_smoke_tolerance():
    """What `chip_smoke.py --only conv` holds the compiled kernels to at the
    cell's shapes, here its rehearsal: the kernels, the expression and the
    rolled candidate against the reference's float32 expression."""
    import chip_smoke

    chip_smoke.phase_conv(chip_smoke.TINY["conv"], 0, False, chip_smoke._CompileClock())
    assert chip_smoke.PHASES[-2:] == ("ssd", "conv")


def test_b_and_c_of_a_group_count_that_does_not_divide_the_heads_are_refused():
    (x, dt, a_log, bm, cm, skip), _ = _scan_inputs(0, 16, heads=4, groups=3)
    with pytest.raises(ValueError, match="divides"):
        ssd_scan(x, dt, a_log, bm, cm, skip, chunk=8)


def test_the_causal_convolution_is_the_loop(ref):
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k[0], (2, 9, 5))
    kernel, bias = jax.random.normal(k[1], (4, 5)), jax.random.normal(k[2], (5,))
    want = np.zeros((2, 9, 5))
    for t in range(9):
        for tap in range(4):
            if t - 3 + tap >= 0:
                want[:, t] += np.asarray(kernel[tap]) * np.asarray(x[:, t - 3 + tap])
    want += np.asarray(bias)
    np.testing.assert_allclose(hybrid.causal_conv(x, kernel, bias), want, atol=1e-5)
    np.testing.assert_allclose(ref.causal_conv(x[0], kernel, bias), want[0], atol=1e-5)
    # a token sees nothing after it
    later = x.at[:, 5:].set(0.0)
    np.testing.assert_array_equal(hybrid.causal_conv(later, kernel, bias)[:, :5],
                                  hybrid.causal_conv(x, kernel, bias)[:, :5])


# ---- the model against the plain reference ------------------------------------


def _float32_model(cell, sizes, **changed):
    return cell.module("program").build(sizes)["model"].clone(
        dtype=jnp.float32, **changed)


def _loss_and_grads(model, params, x, y):
    apply_fn = make_lm_loss_fns(model)[0]
    return jax.jit(jax.value_and_grad(
        lambda p: apply_fn({"params": seeded.nest(p)}, x, labels=y)))(params)


def _seeded_case(ref, sizes):
    params = seeded.make_weights(ref, sizes, seed=11)[0]
    (x, y), = seeded.make_batches(ref, sizes, 11, ranks=1, pool=1)
    x, y = x[0], y[0]
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, {}, x, y, sizes), has_aux=True))(params)
    return sizes, params, x, y, float(loss), grads


@pytest.fixture(scope="module")
def seeded_case(cell, ref):
    return _seeded_case(ref, cell.sizes(rehearse=True))


def _worst_gap(got, want):
    """The widest relative L2 of a leaf's gradient from the reference's."""
    gaps = {}
    for path in want:
        a, b = np.asarray(got[path], np.float64), np.asarray(want[path], np.float64)
        gaps["/".join(path)] = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def test_loss_and_gradients_match_the_plain_reference(cell, ref, seeded_case):
    """State-space, attention, state-space: 8 scan heads of 16 on one group
    with a state of 32 in chunks of 16, 4 query heads on 2 key-value heads of
    16 with no position and scores times 1/64, the four multipliers, the head
    tied to the embedding, the chunked loss; float32 throughout, so only the
    order of sums differs: 1e-4 on every leaf."""
    sizes, params, x, y, loss, grads = seeded_case
    lp, gp = _loss_and_grads(_float32_model(cell, sizes), params, x, y)
    assert abs(float(lp) - loss) < 1e-5
    assert set(gp) == set(grads) == set(ref.param_shapes(sizes)[0])
    gap, where = _worst_gap(gp, grads)
    assert gap < 1e-4, (where, gap)


def test_the_reference_is_matched_through_the_convolutions_kernels_too(cell, ref):
    """The same comparison with a state of 64 for 32: B and C are then one
    128-lane block, and both state-space layers' convolutions go through the
    kernels of `kernels/causal_conv.py` (the rehearsal's own shapes send them
    down the expression).  The reference has a convolution of its own."""
    sizes, params, x, y, loss, grads = _seeded_case(
        ref, dict(cell.sizes(rehearse=True), mamba_d_state=64))
    assert hybrid.conv_kernels_take(x.shape[1], 128, 64, 4)
    lp, gp = _loss_and_grads(_float32_model(cell, sizes), params, x, y)
    assert abs(float(lp) - loss) < 1e-5
    gap, where = _worst_gap(gp, grads)
    assert gap < 1e-4, (where, gap)


def _rotated(q, k, v, **kw):
    positions = jnp.arange(q.shape[1])
    return flash_attention(_rotary(q, positions), _rotary(k, positions), v, **kw)


# a model with one of them changed is another model: it fails the comparison
@pytest.mark.parametrize("changed", [
    dict(embedding_multiplier=1.0), dict(attention_multiplier=None),
    dict(residual_multiplier=1.0), dict(logits_scaling=1.0),
    dict(attention_fn=lambda q, k, v: _rotated(q, k, v, causal=True)),
    dict(tie_embeddings=False)],
    ids=["embedding_multiplier", "attention_multiplier", "residual_multiplier",
         "logits_scaling", "a_rotary", "an_untied_head"])
def test_each_of_the_models_own_rules_matters(cell, seeded_case, changed):
    sizes, params, x, y, loss, grads = seeded_case
    if changed.get("tie_embeddings") is False:
        # a head of its own that starts as the embedding: the loss is the
        # reference's, the embedding's gradient is one use's and not their sum
        params = {**params, ("head", "kernel"): params[("embed", "embedding")].T}
    lp, gp = _loss_and_grads(_float32_model(cell, sizes, **changed), params, x, y)
    gap, _ = _worst_gap({p: gp[p] for p in grads}, grads)
    assert gap > 1e-2 or abs(float(lp) - loss) > 1e-3
    if changed.get("tie_embeddings") is False:
        assert abs(float(lp) - loss) < 1e-5
        both = gp[("embed", "embedding")] + gp[("head", "kernel")].T
        np.testing.assert_allclose(both, grads[("embed", "embedding")],
                                   rtol=1e-3, atol=1e-7)


def test_the_tied_tensors_gradient_is_the_sum_of_both_uses(cell, seeded_case):
    """The lookup's part (with the head's use held constant) and the head's
    part (with the lookup's held constant) add up to the tied gradient."""
    sizes, params, x, y, _, _ = seeded_case
    model = _float32_model(cell, sizes, tie_embeddings=False)
    tied = _loss_and_grads(_float32_model(cell, sizes), params, x, y)[1]
    table = params[("embed", "embedding")]
    apart = _loss_and_grads(model, {**params, ("head", "kernel"): table.T}, x, y)[1]
    np.testing.assert_allclose(
        apart[("embed", "embedding")] + apart[("head", "kernel")].T,
        tied[("embed", "embedding")], rtol=1e-4, atol=1e-8)
    assert np.linalg.norm(apart[("head", "kernel")]) > 0


def test_recomputing_the_blocks_changes_no_gradient(cell, seeded_case):
    sizes, params, x, y, _, _ = seeded_case
    la, ga = _loss_and_grads(_float32_model(cell, sizes), params, x, y)
    lb, gb = _loss_and_grads(_float32_model(cell, sizes, remat=False), params, x, y)
    assert float(la) == float(lb)
    for path in ga:
        np.testing.assert_allclose(ga[path], gb[path], rtol=1e-5, atol=1e-9,
                                   err_msg="/".join(path))


ALL_FIVE_NAMES = ("attn_out", "attn_lse", "mixer_out", "mlp_gate_up", "ssm_in_proj")


def _count_calls(jaxpr, counts):
    """Every equation of a jaxpr and of the jaxprs inside it by its primitive,
    a Pallas kernel by its name (the whole-sequence flash kernels have none)
    and not by what interpret mode would run in its place."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[f"pallas:{eqn.params['name']}"] += 1
            continue
        counts[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    _count_calls(getattr(sub, "jaxpr", sub), counts)
    return counts


@pytest.fixture(scope="module")
def gradient_calls(cell, ref):
    """`counts(model)`: the kernel calls and products in the jaxpr of the
    gradient of a two-layer model's loss, one layer of each kind."""
    sizes = dict(cell.sizes(rehearse=True), num_hidden_layers=2,
                 layer_types=["mamba", "attention"])
    params = {p: jnp.zeros(s, jnp.float32)
              for p, s in ref.param_shapes(sizes)[0].items()}
    ids = jnp.zeros((1, sizes["seq_len"]), jnp.int32)

    def counts(**changed):
        model = cell.module("program").build(sizes)["model"].clone(**changed)
        apply_fn = make_lm_loss_fns(model)[0]
        grad = jax.grad(lambda p: apply_fn({"params": seeded.nest(p)}, ids, labels=ids))
        found = _count_calls(jax.make_jaxpr(grad)(params).jaxpr, collections.Counter())
        return {"flash": found["pallas:None"], "scan_fwd": found["pallas:ssd_chunk_fwd"],
                "scan_bwd": found["pallas:ssd_chunk_bwd"],
                "products": found["dot_general"]}

    return counts


# what a recomputed block's backward pass makes again, by the names it is
# handed: the tuple as shipped, the tuple cut from its tail name by name (the
# order a larger share drops them in), and nothing named (a bare `nn.remat`)
@pytest.mark.parametrize("names,flash_calls,products_again", [
    pytest.param(None, 3, 4, id="as-shipped"),
    pytest.param(5, 3, 3, id="all-five"),
    pytest.param(4, 3, 4, id="without-ssm_in_proj"),
    pytest.param(3, 3, 6, id="without-mlp_gate_up"),
    pytest.param(2, 3, 8, id="without-mixer_out"),
    pytest.param(1, 4, 8, id="attn_out-alone"),
    pytest.param(0, 4, 8, id="nothing-kept")])
def test_a_recomputed_block_makes_again_only_what_it_is_not_handed(
        gradient_calls, monkeypatch, names, flash_calls, products_again):
    """With the flash forward's output and logsumexp both kept its kernel runs
    three times in the gradient (forward, dK/dV, dQ) and not four; with the
    output alone it runs again for the logsumexp.  Of a block's products all
    five names leave the attention layer's q, k and v to be made again (3);
    `in_proj` (1: the shipped tuple), the two layers' gate-and-up (2) and
    `out_proj` and `o` (2) join them as their names go.  The scan's forward
    runs twice whatever is kept (PERF.md section 7)."""
    if names is not None:
        monkeypatch.setattr(hybrid, "REMAT_KEEPS", ALL_FIVE_NAMES[:names])
    plain, again = gradient_calls(remat=False), gradient_calls()
    assert plain["flash"] == 3 and plain["scan_fwd"] == plain["scan_bwd"] == 1
    assert again["flash"] == flash_calls
    assert (again["scan_fwd"], again["scan_bwd"]) == (2, 1)
    assert again["products"] - plain["products"] == products_again


def test_three_steps_of_adamw_as_the_cells_correct_compares_them(cell, ref):
    """The float32 program's first three steps under the mix's optimizer
    against `check.reference_run`, every number the cell's LIMITS name."""
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


# ---- gauges, the configuration, the FLOP count, the readers ------------------


WANTED_GAUGES = {
    "ssm.layers": 2, "ssm.heads": 8, "ssm.head_dim": 16, "ssm.state": 32,
    "ssm.groups": 1, "ssm.chunk": 16, "ssm.conv_width": 4,
    "attention.layers_global": 1, "attention.heads_global": 4,
    "attention.kv_heads": 2, "attention.scale": 0.015625, "lm.tied_head": 1,
    "lm.remat_blocks": 3,
    # bfloat16 of 32 tokens: an attention layer's [4, 32, 16] and float32
    # [4, 32], three layers' [32, 64] and [32, 192]
    "lm.remat_kept_names": 4, "lm.remat_kept_mb": (4096 + 512 + 12288 + 36864) / 1e6}


# the rehearsal's convolution is 128 + 2 x 32 channels wide: B and C are no
# whole 128-lane block and every layer takes the expression; with a state of 64
# they are one, and with 12 tokens for 32 the tokens are no whole 8-row tiles
@pytest.mark.parametrize("changed,tokens,conv_kernel_layers", [
    pytest.param({}, 32, 0, id="as-rehearsed"),
    pytest.param({"ssm_state": 64}, 32, 2, id="shapes-that-tile"),
    pytest.param({"ssm_state": 64}, 12, 0, id="tokens-that-do-not")])
def test_the_model_sets_its_gauges(cell, monkeypatch, tmp_path, changed, tokens,
                                   conv_kernel_layers):
    monkeypatch.setenv("BFTPU_TELEMETRY", str(tmp_path))
    telemetry.reset()
    try:
        model = cell.module("program").build(cell.sizes(rehearse=True))["model"]
        jax.eval_shape(lambda i: model.clone(**changed).init(jax.random.PRNGKey(0), i),
                       jax.ShapeDtypeStruct((1, tokens), jnp.int32))
        gauges = {g["name"]: g["value"] for g in
                  telemetry.get_registry().snapshot()["gauges"]}
    finally:
        telemetry.reset()
    wanted = {**WANTED_GAUGES, "ssm.conv_kernel_layers": conv_kernel_layers,
              "ssm.state": changed.get("ssm_state", 32),
              # bfloat16 of the tokens: an attention layer's [4, T, 16] and
              # float32 [4, T], three layers' [T, 64] and [T, 192]
              "lm.remat_kept_mb": tokens * (128 + 16 + 384 + 1152) / 1e6}
    assert {k: v for k, v in gauges.items() if k in wanted} == wanted


def test_a_mixer_kind_the_decoder_does_not_have_is_refused(cell):
    model = _float32_model(cell, cell.sizes(rehearse=True),
                           layer_kinds=("mamba", "linear_attention"))
    with pytest.raises(ValueError, match="linear_attention"):
        jax.eval_shape(lambda i: model.init(jax.random.PRNGKey(0), i),
                       jax.ShapeDtypeStruct((1, 32), jnp.int32))


def test_no_width_differs_from_the_source_and_the_cut_is_stated(cell):
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"granite-4.0-h-micro"' in line) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    cfg, cut = cell.config, {"num_hidden_layers": 10, "vocab_size": 12544}
    published = row["config"] if row else PUBLISHED
    assert published == dict(PUBLISHED, layer_types=published["layer_types"])
    for key, value in published.items():
        assert cfg[key] == cut.get(key, value), key
        if key in cfg["sizes"]:
            assert cfg["sizes"][key] == cfg[key], key  # one number, stated twice
    assert cfg["layer_types"] == (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    assert cfg["sizes"]["attention_head_dim"] * cfg["num_attention_heads"] \
        == cfg["hidden_size"]
    assert cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        == cfg["mamba_expand"] * cfg["hidden_size"]
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert set(cfg["reduced"]) < set(cfg["cut"])
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["published"]["vocab_size"] == 100352 == 8 * cfg["vocab_size"]
    assert "eight" in cfg["deployment"] and "period" in cfg["deployment"]
    assumed = " ".join(cfg["assumed"])
    for mark in ("[z, xBC, dt]", "inverse softplus", "log-uniform in [0.001, 0.1]",
                 "A uniform in [1, 16]", "silu(z) first", "residual_multiplier",
                 "nope", "recomputed"):
        assert mark in assumed, mark
    # the parameters of the cut, as the issue's arithmetic has them
    shapes = cell.module("reference").param_shapes(cell.sizes())[0]
    count = lambda pick: sum(int(np.prod(s)) for p, s in shapes.items() if pick(p))
    assert count(lambda p: p[0] == "layer_0") == 76_182_976
    assert count(lambda p: p[0] == "layer_5") == 60_821_504
    assert count(lambda p: True) == 772_160_448
    mix = cell.mix
    assert mix["sizes"] == {"per_rank_batch": 1, "seq_len": 8192}
    standing = manifest.resolve("laguna-xs.2-atc-warmup-b1-s8k-1chip").mix
    assert {k: v for k, v in mix.items() if k != "describes"} == {
        k: v for k, v in standing.items() if k != "describes"}
    assert mix["describes"] != standing["describes"]
    assert mix["optimizer"] == dict(cfg["optimizer"], warmup_steps=2000)
    bench = manifest.load_manifest()
    entry = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert entry["source"] == cfg["source"] and entry["source"].endswith("config.json")
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == ["resnet50-atc-exp2-4chip"]
    named = {p["name"] for p in bench["per_layer"] if CELL in p.get("workloads", [])}
    assert named == {
        "train_step_host_ms_per_step", "attention_ms_per_step",
        "attention_global_ms_per_step", "ssm_scan_ms_per_step",
        "ssd_chunk_fwd_roofline", "ssd_chunk_bwd_roofline",
        # PR 41: the step's split by scope
        "optimizer_ms_per_step", "head_loss_ms_per_step", "mlp_ms_per_step",
        "attention_proj_ms_per_step", "ssm_mixer_ms_per_step",
        "recompute_ms_per_step", "unscoped_ms_per_step"}


PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4}


def test_flops_against_a_hand_count(cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    d, s, f = 2048, 8192, 8192
    visible = 256 * 257 // 2
    scan = 32 * (64 * (visible * 64 + 2 * 256 * 128 * 64) + visible * 128)
    assert flops.scan_macs(sizes) == scan
    mamba = s * (d * 8512 + 4096 * d + 3 * d * f) + scan
    attention = s * (d * 3072 + 2048 * d + 3 * d * f) + 2 * 33_558_528 * 2048
    macs = 9 * mamba + attention + s * d * 12544
    assert flops.forward_macs(sizes) == macs
    assert flops.train_flops_per_sample(sizes) == 6 * macs
    assert 6 * macs == pytest.approx(39.5e12, rel=5e-3)
    assert 6 * 9 * scan == pytest.approx(0.70e12, rel=1e-2)
    # a kernel call: the scan's products, twice them backward; x and y (and
    # theirs) at 2 bytes, the step sizes at 4, a float32 state a head a chunk
    ops, nbytes = flops.kernel_call(sizes, "fwd")
    assert ops == 2 * scan
    assert nbytes == 2 * s * 4096 * 2 + 2 * s * 128 * 2 + s * 64 * 4
    ops_b, nbytes_b = flops.kernel_call(sizes, "bwd")
    assert ops_b == 2 * ops
    assert nbytes_b == (3 * s * 4096 * 2 + 4 * s * 128 * 2 + 2 * s * 64 * 4
                        + 32 * 64 * 64 * 128 * 4)
    assert flops.kernel_calls_per_step(sizes, "fwd") == 18  # the recomputed pass too
    assert flops.kernel_calls_per_step(sizes, "bwd") == 9


def test_the_new_readers_find_the_scan_kernels_by_name(cell):
    flops, sizes = cell.module("flops"), cell.sizes()
    ops = {"%ssd_chunk_fwd.3 = (bf16[...]": 1.0, "%ssd_chunk_fwd.4": 1.5,
           "%ssd_chunk_bwd.1": 3.0, "%attention_global.2": 9.0,
           "%fusion.9": 100.0, "%ssd_chunk_fwd_other": 50.0}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"trace": {"ops_ms_per_step": ops}, "peaks": peaks,
           "flops_per_sample": flops.train_flops_per_sample(sizes)}
    assert cell.reader("ssm_scan_ms_per_step").read(run) == 5.5
    assert cell.reader("attention_global_ms_per_step").read(run) == 9.0
    assert cell.reader("attention_ms_per_step").read(run) == 9.0
    for kernel, ms in (("fwd", 2.5), ("bwd", 3.0)):
        work, nbytes = flops.kernel_call(sizes, kernel)
        ideal = flops.kernel_calls_per_step(sizes, kernel) * max(
            work / 197e12, nbytes / 819e9)
        assert nbytes / 819e9 > work / 197e12  # bound by the bytes, as counted
        assert cell.reader(f"ssd_chunk_{kernel}_roofline").read(run) \
            == pytest.approx(100 * ideal / (ms / 1e3))
    # a program without such kernels, a run without a trace, a rehearsal and a
    # run of another cell: nothing, no raise
    for empty in ({"trace": None}, {"trace": {"ops_ms_per_step": {"%fusion": 1.0}}},
                  dict(run, peaks=None), dict(run, flops_per_sample=1.0)):
        assert cell.reader("ssd_chunk_fwd_roofline").read(empty) is None
        assert cell.reader("ssd_chunk_bwd_roofline").read(empty) is None
    assert cell.reader("ssm_scan_ms_per_step").read({"trace": None}) is None


# ---- the cell's rehearsal: its limits and its controls ------------------------


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


class _Broken:
    """A job in the timed path's place that does one thing wrong."""

    def __init__(self, job, fault):
        self.job, self.fault = job, fault
        self.start = jax.tree_util.tree_map(jnp.copy, job.state)

    def __getattr__(self, name):
        return getattr(self.job, name)

    def step(self, k):
        if self.fault == "unchanged":  # the state is handed back as it came
            out = self.job.step(k)
            self.job.state = jax.tree_util.tree_map(jnp.copy, self.start)
            return out
        # half the batch left out: the second sequence is the first again
        spec = self.job.spec
        x, y = spec.batches[k % len(spec.batches)]
        half = lambda a: jnp.concatenate([a[:, :1], a[:, :1]], axis=1)
        kept, spec.batches = spec.batches, [(half(x), half(y))] * len(spec.batches)
        try:
            return self.job.step(k)
        finally:
            spec.batches = kept


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_step_that_does_one_thing_wrong_is_not_correct(cell, fault):
    """The runner with a broken job in the timed path's place: `correct` has
    to come out false by one of the cell's limits."""
    args = type("Args", (), dict(rehearse=True, seed=2**31 + 99, seconds=0.5, trace=0))
    result = runner.run(args, 0.0, cell, wrap_job=lambda job: _Broken(job, fault))
    assert result["correct"] is False
    over = [k for k, c in result["checks"].items()
            if c["limit"] and c["value"] is not None and c["value"] > c["limit"]]
    assert over, result["checks"]


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

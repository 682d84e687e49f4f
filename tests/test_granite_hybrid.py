"""`granite-4.0-h-micro`'s own (`models.hybrid.HybridMambaLM`): the chunked
scan's kernels in interpret mode against the token-by-token recurrence, values
and all six gradients; the causal convolution against a loop, and the
reference matched through its kernels; the tied head's gradient as the sum of
both uses; what a recomputed block makes again; half of the batch left out.
The cases it shares with the other decoder configurations are in
`tests/test_decoder_cells.py`."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.kernels.ssd import ssd_scan
from bluefog_tpu.models import hybrid
from bluefog_tpu.training import make_lm_loss_fns
from decoder_cells import (GRANITE, _float32_model, _loss_and_grads, _worst_gap,
                           model_matches, not_correct_under, reference_case,
                           values_and_grads)

from chipbench import seeded


@pytest.fixture(scope="module")
def cell():
    return GRANITE.cell


@pytest.fixture(scope="module")
def ref():
    return GRANITE.reference


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
        got = values_and_grads(lambda *a: ssd_scan(*a, chunk=chunk), args, weight)
        want = values_and_grads(
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
        a = values_and_grads(lambda *v: ssd_scan(*v, chunk=8), args, weight)
        b = values_and_grads(lambda *v: ssd_scan(*v, chunk=16), args, weight)
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
    assert chip_smoke.PHASES[-3:-1] == ("ssd", "conv")


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


def test_the_reference_is_matched_through_the_convolutions_kernels_too(cell):
    """The same comparison with a state of 64 for 32: B and C are then one
    128-lane block, and both state-space layers' convolutions go through the
    kernels of `kernels/causal_conv.py` (the rehearsal's own shapes send them
    down the expression).  The reference has a convolution of its own."""
    case = reference_case(cell, dict(cell.sizes(rehearse=True), mamba_d_state=64))
    assert hybrid.conv_kernels_take(case[2].shape[1], 128, 64, 4)
    model_matches(cell, case, GRANITE.float32_gap)


# the one of the model's own rules that needs another tree (the five that do
# not are the shared case's): a head of its own that starts as the embedding
@pytest.mark.parametrize("changed", [dict(tie_embeddings=False)], ids=["an_untied_head"])
def test_each_of_the_models_own_rules_matters(cell, changed):
    """The loss is the reference's, the embedding's gradient is one use's and
    not their sum."""
    sizes, params, x, y, loss, grads = GRANITE.seeded_case
    params = {**params, ("head", "kernel"): params[("embed", "embedding")].T}
    lp, gp = _loss_and_grads(_float32_model(cell, sizes, **changed), params, x, y)
    gap, _ = _worst_gap({p: gp[p] for p in grads}, grads)
    assert gap > 1e-2 and abs(float(lp) - loss) < 1e-5
    both = gp[("embed", "embedding")] + gp[("head", "kernel")].T
    np.testing.assert_allclose(both, grads[("embed", "embedding")],
                               rtol=1e-3, atol=1e-7)


def test_the_tied_tensors_gradient_is_the_sum_of_both_uses(cell):
    """The lookup's part (with the head's use held constant) and the head's
    part (with the lookup's held constant) add up to the tied gradient."""
    sizes, params, x, y, _, _ = GRANITE.seeded_case
    model = _float32_model(cell, sizes, tie_embeddings=False)
    tied = _loss_and_grads(_float32_model(cell, sizes), params, x, y)[1]
    table = params[("embed", "embedding")]
    apart = _loss_and_grads(model, {**params, ("head", "kernel"): table.T}, x, y)[1]
    np.testing.assert_allclose(
        apart[("embed", "embedding")] + apart[("head", "kernel")].T,
        tied[("embed", "embedding")], rtol=1e-4, atol=1e-8)
    assert np.linalg.norm(apart[("head", "kernel")]) > 0


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


def test_the_heads_fill_the_widths_the_source_states(cell):
    cfg = cell.config
    assert cfg["sizes"]["attention_head_dim"] * cfg["num_attention_heads"] \
        == cfg["hidden_size"]
    assert cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        == cfg["mamba_expand"] * cfg["hidden_size"]


# ---- the cell's rehearsal: a broken job in the timed path's place ---------------


def _half_batch(job):
    """A timed path broken underneath: half the batch left out, the second
    sequence the first again.  The reference keeps the whole batch."""
    half = lambda a: jnp.concatenate([a[:, :1], a[:, :1]], axis=1)
    job.spec.batches = [tuple(half(a) for a in batch) for batch in job.spec.batches]
    return job


# the state handed back as it came is the shared case's
@pytest.mark.parametrize("fault", ["half_batch"])
def test_a_step_that_does_one_thing_wrong_is_not_correct(cell, fault):
    not_correct_under(cell, _half_batch)

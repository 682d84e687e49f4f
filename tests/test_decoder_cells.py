"""The cases every decoder configuration of the benchmark has to pass, each
written once and run for every entry of `decoder_cells.TABLE` that has the
fields the case reads.  A configuration's kernels, routers, rotaries and mixers
are in its own file."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu.training import make_lm_loss_fns
from decoder_cells import (OPTIMIZER, Unchanged, _float32_model, _loss_and_grads,
                           _worst_gap, each, each_of, gauges_of, leaf_shapes,
                           model_matches, not_correct_under,
                           rehearse_through_the_command_line)

from chipbench import check, control, manifest, optimizers, runner, seeded


# ---- the model against the plain reference ------------------------------------


@each()
def test_loss_and_every_gradient_match_the_plain_reference(entry):
    """Every kind of layer the rehearsal has, the chunked loss, float32
    throughout, so only the order of sums differs."""
    grads = entry.seeded_case[-1]
    gp = model_matches(entry.cell, entry.seeded_case, entry.float32_gap)
    biases = [p for p in grads if p[-1] == "router_bias"]
    assert len(biases) == entry.router_biases
    for p in biases:  # a leaf, and no gradient reaches it on either side
        assert float(jnp.max(jnp.abs(gp[p]))) == float(jnp.max(jnp.abs(grads[p]))) == 0.0
    assert not [p for p in gp for mark in entry.no_leaf_named if mark in "/".join(p)]


@each()
def test_bfloat16_products_fail_the_float32_tolerance(entry):
    """The program as it trains (bfloat16 products; float32 norms, gates,
    state, router and head) is another number than the float32 reference by
    far more than the tolerance: a comparison that states float32 would catch
    it."""
    sizes, params, x, y, _, grads = entry.seeded_case
    model = entry.cell.module("program").build(sizes)["model"]
    assert model.dtype == jnp.bfloat16
    _, gp = _loss_and_grads(model, params, x, y)
    gap, _ = _worst_gap(gp, grads)
    assert gap > 10 * entry.float32_gap, gap


@each_of("rules")
def test_each_of_the_models_own_rules_matters(entry, key):
    """A model with one of them changed is another model: by its leaves
    alone, by a gradient, or by its loss."""
    sizes, params, x, y, loss, grads = entry.seeded_case
    model = _float32_model(entry.cell, sizes, **entry.rules[key])
    if leaf_shapes(model, jax.ShapeDtypeStruct(x.shape, x.dtype)) \
            != {p: v.shape for p, v in params.items()}:
        return  # another tree
    lp, gp = _loss_and_grads(model, params, x, y)
    assert _worst_gap(gp, grads)[0] > 1e-2 or abs(float(lp) - loss) > 1e-3


@each("remat_off")
def test_recomputing_the_blocks_changes_no_gradient(entry):
    sizes, params, x, y, _, _ = entry.seeded_case
    la, ga = _loss_and_grads(_float32_model(entry.cell, sizes), params, x, y)
    lb, gb = _loss_and_grads(
        _float32_model(entry.cell, sizes, **entry.remat_off), params, x, y)
    assert float(la) == float(lb)
    for path in ga:
        np.testing.assert_allclose(ga[path], gb[path], rtol=1e-5, atol=1e-9,
                                   err_msg="/".join(path))


@each("adamw_seed")
def test_three_steps_of_adamw_as_the_cells_correct_compares_them(entry):
    """The float32 program's first three steps under the mix's optimizer
    against `check.reference_run`, every number the cell's LIMITS name; a leaf
    that no gradient reaches is decayed alike on both sides."""
    cell, ref = entry.cell, entry.reference
    sizes = cell.sizes(rehearse=True)
    seed, M = entry.adamw_seed, np.ones((1, 1))
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
    for leaf in entry.decayed_only:
        moved = np.asarray(p[leaf]) - np.asarray(params0[leaf])
        assert 0 < np.max(np.abs(moved)) < 1e-6 * np.max(np.abs(params0[leaf]))


# ---- the gauges, and what the decoder refuses ------------------------------------


@each_of("gauges")
def test_the_model_sets_its_gauges(entry, key, monkeypatch, tmp_path):
    case = entry.gauges[key]
    sizes = dict(entry.cell.sizes(rehearse=True), **case["sizes"])
    model = entry.cell.module("program").build(sizes)["model"].clone(**case["fields"])
    gauges = gauges_of(model, case["tokens"], monkeypatch, tmp_path)
    assert {k: v for k, v in gauges.items() if k in case["wanted"]} == case["wanted"]
    assert not [k for k in gauges if k.startswith(entry.gauges_absent)]


@each("foreign_kinds")
def test_a_mixer_kind_the_decoder_does_not_have_is_refused(entry):
    kinds, named = entry.foreign_kinds
    model = _float32_model(entry.cell, entry.cell.sizes(rehearse=True), layer_kinds=kinds)
    with pytest.raises(ValueError, match=named):
        jax.eval_shape(lambda i: model.init(jax.random.PRNGKey(0), i),
                       jax.ShapeDtypeStruct((1, 32), jnp.int32))


# ---- the configuration file, the hand counts, the readers --------------------------


@each()
def test_no_width_differs_from_the_source_and_the_cut_is_stated(entry):
    published = entry.published_config()
    if published is None:
        pytest.skip("no catalog of architectures here")
    cell, cfg = entry.cell, entry.cell.config
    for key, value in published.items():
        assert cfg[key] == entry.cut.get(key, value), key
    for key in set(published) & set(cfg["sizes"]) - set(entry.sizes_say):
        assert cfg["sizes"][key] == cfg[key], key  # one number, stated twice
    for key, value in entry.config_says.items():
        assert cfg[key] == value, key
        assert cfg["sizes"].get(key, value) == value, key
    for key, value in entry.sizes_say.items():
        assert cfg["sizes"][key] == value, key
    assert cfg["reduced"] == entry.reduced
    assert set(cfg["cut"]) == {*entry.reduced, *entry.cut_also}
    for key, value in entry.published_stated.items():
        assert cfg["published"][key] == value, key
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    for field, marks in entry.marks.items():
        text = cfg[field] if isinstance(cfg[field], str) else " ".join(cfg[field])
        for mark in marks:
            assert mark in text, (field, mark)
    # the cell: its mix beside a standing cell's, its line of the manifest
    mix = cell.mix
    assert mix["sizes"] == entry.mix_sizes
    assert cfg["optimizer"] == OPTIMIZER
    assert mix["optimizer"] == dict(OPTIMIZER, warmup_steps=2000)
    standing, differs = entry.mix_as
    standing = manifest.resolve(standing).mix
    assert {k: v for k, v in mix.items() if k not in differs} == {
        k: v for k, v in standing.items() if k not in differs}
    for key in differs:
        assert mix[key] != standing[key], key
    bench = manifest.load_manifest()
    listed = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert listed["source"] == cfg["source"] and listed["source"].endswith("config.json")
    assert listed["reduced"] == cfg["reduced"]
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == ["resnet50-atc-exp2-4chip"]
    assert {p["name"] for p in bench["per_layer"]
            if cell.name in p.get("workloads", [])} == entry.per_layer


@each("parameters")
def test_the_parameters_of_the_cut_are_the_issues_arithmetic(entry):
    shapes = entry.reference.param_shapes(entry.cell.sizes())[0]
    for what, pick, count in entry.parameters:
        assert sum(int(np.prod(s)) for p, s in shapes.items() if pick(p)) == count, what


@each("flops")
def test_flops_against_a_hand_count(entry):
    for what, got, want in entry.flops(entry.cell.module("flops"), entry.cell.sizes()):
        assert got == want, what


@each_of("readers")
def test_the_readers_find_their_kernels(entry, key):
    """Each reader on traced names as the device trace writes them, and on
    runs that have nothing for it: None, no raise."""
    for metric, run, want in entry.readers[key](entry.cell):
        got = entry.cell.reader(metric).read(run)
        assert got is None if want is None else got == want, (metric, got)


# ---- the cell's rehearsal: its limits and its controls ------------------------


@each("control_seed")
def test_sound_readings_pass_and_the_float8_control_fails(entry):
    """chipbench.control at the rehearsal sizes, one CPU device, under the
    warm-up (the three steps run at 1.5e-7 to 4.5e-7)."""
    ses = runner.Session(entry.cell, rehearse=True)
    try:
        row = control.readings(ses, entry.control_seed, ["step"])
    finally:
        bf.shutdown()
    limits = ses.reference.LIMITS
    failed = lambda part: [k for k, v in row[part].items()
                           if k in limits and not v <= limits[k]]
    assert failed("sound") == [], row["sound"]
    assert failed("control_step"), row["control_step"]
    assert row["sound"]["change1_rel_l2"] > 0  # the parameters did move


@each("unchanged_seed")
def test_a_step_that_returns_its_state_unchanged_is_not_correct(entry):
    not_correct_under(entry.cell, Unchanged, entry.unchanged_seed)


@each("cli_seed")
def test_the_cell_rehearses_through_the_command_line(entry):
    line = rehearse_through_the_command_line(entry.cell_name, entry.cli_seed)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"train_samples_s_chip", "step_ms_p95", "setup_s"}
    assert set(line["checks"]) >= set(entry.reference.LIMITS) >= {
        "grad_norm_gap", "delta_norm_gap", "change1_rel_l2", "assoc_p_gap"}

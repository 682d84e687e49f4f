"""`chipbench.optimizers`: the optional `warmup_steps` of an optimizer's spec
gives step k (counted from 1) the rate `learning_rate x min(1, k / N)`, in
the program and in the plain reference alike (both take `make`); a spec
without the key builds what it always built, to the bit; and the first
gradient is recovered from the state after one step under the schedule too."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import bluefog_tpu as bf  # noqa: E402

from chipbench import manifest, optimizers, runner  # noqa: E402

RATE, WARMUP = 3e-4, 5

SPECS = {
    "sgd": {"name": "sgd", "learning_rate": RATE},
    "adam": {"name": "adam", "learning_rate": RATE},
    "adamw": {"name": "adamw", "learning_rate": RATE, "weight_decay": 0.0},
}


def _update_sizes(spec, steps, gradient=1.0):
    """The size of a scalar's update in each of `steps` steps under a
    constant gradient: SGD's is rate x g, Adam's is the rate itself (m-hat
    over the root of v-hat is 1 whatever the step)."""
    tx = optimizers.make(spec)
    p = {"w": jnp.zeros(())}
    state, sizes = tx.init(p), []
    for _ in range(steps):
        updates, state = tx.update({"w": jnp.float32(gradient)}, state, p)
        sizes.append(abs(float(updates["w"])))
    return sizes


@pytest.mark.parametrize("k", [1, 2, 3, WARMUP, WARMUP + 1, WARMUP + 4])
@pytest.mark.parametrize("name", sorted(SPECS))
def test_step_k_of_a_warm_up_runs_at_rate_times_k_over_n(name, k):
    sizes = _update_sizes(dict(SPECS[name], warmup_steps=WARMUP), k)
    expected = RATE * min(1.0, k / WARMUP)
    assert sizes[k - 1] == pytest.approx(expected, rel=1e-4)
    assert sizes[0] > 0  # the first update is something: not linear_schedule(0, ...)


@pytest.mark.parametrize("name", sorted(SPECS))
def test_without_the_key_the_transformation_is_todays_to_the_bit(name):
    spec = SPECS[name]
    today = {
        "sgd": lambda: optax.sgd(RATE, momentum=None),
        "adam": lambda: optax.adam(RATE, b1=optimizers.ADAM_B1),
        "adamw": lambda: optax.adamw(RATE, b1=optimizers.ADAM_B1, weight_decay=0.0),
    }[name]()
    made = optimizers.make(spec)
    p = {"w": jnp.linspace(-1.0, 1.0, 7), "b": jnp.ones((2, 3))}
    g = jax.tree_util.tree_map(lambda a: jnp.cos(3.0 * a), p)
    assert jax.tree_util.tree_structure(made.init(p)) \
        == jax.tree_util.tree_structure(today.init(p))  # no schedule's count

    def three_steps(tx):
        def run(p, g):
            state = tx.init(p)
            for _ in range(3):
                updates, state = tx.update(g, state, p)
                p = optax.apply_updates(p, updates)
            return p
        return jax.jit(run)

    assert three_steps(made).lower(p, g).as_text() \
        == three_steps(today).lower(p, g).as_text()
    for a, b in zip(jax.tree_util.tree_leaves(three_steps(made)(p, g)),
                    jax.tree_util.tree_leaves(three_steps(today)(p, g))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("spec", [
    {"name": "adamw", "learning_rate": RATE, "weight_decay": 0.1, "warmup_steps": 2000},
    {"name": "adam", "learning_rate": RATE, "warmup_steps": 2000},
    {"name": "sgd", "learning_rate": RATE, "momentum": 0.9, "warmup_steps": 2000},
], ids=lambda s: s["name"])
def test_first_gradient_is_recovered_under_the_schedule(spec):
    tx = optimizers.make(spec)
    p = {"w": jnp.linspace(-1.0, 1.0, 7), "b": jnp.ones((2, 3))}
    g = jax.tree_util.tree_map(lambda a: jnp.cos(3.0 * a) * 1e-3, p)
    _, state = tx.update(g, tx.init(p), p)
    got = optimizers.first_gradient(spec, state)
    for name in p:
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(g[name]),
                                   rtol=1e-6)


def test_the_decoder_cells_mix_warms_up_over_two_thousand_steps():
    cell = manifest.resolve("smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip")
    ses = runner.Session(cell, rehearse=True)
    bf.shutdown()
    spec = ses.opt_spec  # the mix's optimizer: the configuration's, warmed up
    assert spec == cell.mix["optimizer"] == dict(
        cell.config["optimizer"], warmup_steps=2000)
    # the three checked steps run at 1.5e-7, 3e-7, 4.5e-7
    sizes = _update_sizes(dict(spec, weight_decay=0.0), 3)
    np.testing.assert_allclose(sizes, [1.5e-7, 3e-7, 4.5e-7], rtol=1e-4)
    # and the rate is a traced function of the count: one program for all steps
    rate = optimizers.learning_rate(spec)
    assert float(rate(jnp.int32(1999))) == pytest.approx(3e-4)
    assert float(rate(jnp.int32(10**6))) == pytest.approx(3e-4)
    # the cells that name no warm-up are handed the number itself
    for name in ("bert-base-atc-b128-1chip", "resnet50-atc-1chip",
                 "smallthinker-21b-a3b-atc-b2-s8k-1chip"):
        other = manifest.resolve(name)
        spec = other.mix.get("optimizer", other.config["optimizer"])
        assert optimizers.learning_rate(spec) == spec["learning_rate"]

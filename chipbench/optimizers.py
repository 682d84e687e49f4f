"""The optimizer a configuration names, as the optax transformation both the
program and the plain reference are given, and the first gradient as that
optimizer got it, worked out from its state after one step."""

import jax
import optax

ADAM_B1 = 0.9


def make(spec):
    name = spec["name"]
    if name == "sgd":
        return optax.sgd(spec["learning_rate"], momentum=spec.get("momentum"))
    if name == "adam":
        return optax.adam(spec["learning_rate"], b1=ADAM_B1)
    if name == "adamw":
        return optax.adamw(spec["learning_rate"], b1=ADAM_B1,
                           weight_decay=spec.get("weight_decay", 1e-4))
    raise ValueError(f"unknown optimizer {name!r}")


def first_gradient(spec, opt_state):
    """The gradient of step one from the state after it: SGD's momentum trace
    is g itself, Adam's first moment is (1 - b1) g."""
    for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "trace") or hasattr(x, "mu")):
        if hasattr(s, "trace"):
            return s.trace
        if hasattr(s, "mu"):
            return jax.tree_util.tree_map(lambda m: m / (1.0 - ADAM_B1), s.mu)
    raise ValueError(f"no momentum or first moment in the state of {spec['name']!r}")

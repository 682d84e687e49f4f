"""The optimizer a configuration names, as the optax transformation both the
program and the plain reference are given, and the first gradient as that
optimizer got it, worked out from its state after one step."""

import jax
import jax.numpy as jnp
import optax

ADAM_B1 = 0.9


def learning_rate(spec):
    """The rate as optax takes it.  With `warmup_steps` N, step k (counted
    from 1) runs at learning_rate x min(1, k / N).  Not
    `optax.linear_schedule(0, ...)`: its value at count 0 is 0, the first
    update would be nothing and the check would compare zeros.  Without the
    key it is the number itself, and the optimizer is built as it always was."""
    rate, warmup = spec["learning_rate"], spec.get("warmup_steps")
    if warmup is None:
        return rate
    return lambda count: rate * jnp.minimum(1.0, (count + 1) / warmup)


def make(spec):
    name, rate = spec["name"], learning_rate(spec)
    if name == "sgd":
        return optax.sgd(rate, momentum=spec.get("momentum"))
    if name == "adam":
        return optax.adam(rate, b1=ADAM_B1)
    if name == "adamw":
        return optax.adamw(rate, b1=ADAM_B1,
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

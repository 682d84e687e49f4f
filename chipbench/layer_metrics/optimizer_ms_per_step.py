"""Layer `optimizer + gossip`: device milliseconds a step in the ops traced under
`optimizer_update`: the base optimizer's update (SGD with momentum, AdamW),
`optax.apply_updates`, ATC's `c - p` and the new state's rank axis, where the
compiler runs them as passes of their own.  Where it fuses a leaf's update
into the product or convolution that makes the leaf's gradient (all of
ResNet-50's leaves, half of Granite's elements, PERF.md section 6, PR 41), the
fusion has the product's path and is in the product's group: this number is a
floor on the optimizer's cost, and its bytes over it read near the HBM's peak.
One group of the step's split by scope (`chipbench/step_scopes.py`); `None`
without a device trace or on a program that keeps no record of its step."""

from chipbench import step_scopes


def read(run):
    return step_scopes.group_ms(run, "optimizer")

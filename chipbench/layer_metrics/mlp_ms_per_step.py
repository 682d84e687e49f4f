"""Layer `train step`: device milliseconds a step in the ops traced under `mlp_dense` (a dense gated MLP and, in the hybrid
decoder, its `mlp_norm`), `moe_shared` (the shared expert) or `ffn_norm`.
One group of the step's split by scope (`chipbench/step_scopes.py`); `None`
without a device trace or on a program that keeps no record of its step."""

from chipbench import step_scopes


def read(run):
    return step_scopes.group_ms(run, "mlp")

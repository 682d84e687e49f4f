"""Layer `train step`: device milliseconds a step in the state-space mixer outside the scan's kernels: the ops traced under
`ssm_in_proj`, `ssm_conv`, `ssm_gate_norm`, `ssm_out_proj`, and what
`ssm_scan` holds beside the kernel calls (the step sizes' softplus, the
cumulative sums, reshapes).
One group of the step's split by scope (`chipbench/step_scopes.py`); `None`
without a device trace or on a program that keeps no record of its step."""

from chipbench import step_scopes


def read(run):
    return step_scopes.group_ms(run, "ssm_mixer")

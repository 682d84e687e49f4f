"""Layer `train step`: device milliseconds a step in the ops traced under a batch-norm module (`BatchNorm_<n>`, `bn_init`)
that the compiler did not fuse into a convolution.
One group of the step's split by scope (`chipbench/step_scopes.py`); `None`
without a device trace or on a program that keeps no record of its step."""

from chipbench import step_scopes


def read(run):
    return step_scopes.group_ms(run, "batch_norm")

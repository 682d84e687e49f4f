"""Layer `train step`: device milliseconds a step in the ops traced under a convolution module (flax's `Conv_<n>`,
`conv_init`), forward and backward; the compiler fuses a batch norm's
statistics and a block's ReLU into many of them, and such a fusion is here
where it was built around the convolution.
One group of the step's split by scope (`chipbench/step_scopes.py`); `None`
without a device trace or on a program that keeps no record of its step."""

from chipbench import step_scopes


def read(run):
    return step_scopes.group_ms(run, "conv")

"""Layer `train step`: device milliseconds a step in the attention layer outside its kernels: the ops traced under the `q`,
`k`, `v`, `o` products, `attention_rotary`, `attention_gate`, `attn_norm`, and
what `attention_window` / `attention_global` hold beside the kernel calls
(the backward pass's row sums, reshapes).
One group of the step's split by scope (`chipbench/step_scopes.py`); `None`
without a device trace or on a program that keeps no record of its step."""

from chipbench import step_scopes


def read(run):
    return step_scopes.group_ms(run, "attention_proj")

"""Layer `train step`: device milliseconds a step in the ops traced under `lm_head_loss` (the chunked head and loss of
`models/transformer.chunked_softmax_cross_entropy`: its loop's own time, the
head's product a chunk, forward and again backward, the softmax) and under
the `final_norm` that feeds it.
One group of the step's split by scope (`chipbench/step_scopes.py`); `None`
without a device trace or on a program that keeps no record of its step."""

from chipbench import step_scopes


def read(run):
    return step_scopes.group_ms(run, "head_loss")

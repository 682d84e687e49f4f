"""Layer `optimizer + gossip`: device milliseconds a step in the non-collective ops traced under `gossip_combine` (or
`gradient_allreduce`): packing the leaves into buckets, the slice-and-multiply
of each shift class and the weighted combine; the permutes themselves are
`collective_ms_per_step`'s.
One group of the step's split by scope (`chipbench/step_scopes.py`); `None`
without a device trace or on a program that keeps no record of its step."""

from chipbench import step_scopes


def read(run):
    return step_scopes.group_ms(run, "gossip_combine")

"""Layer `kernels`: device milliseconds a step in the ops traced under `moe_route` or `moe_experts` that are not a
`ragged-dot` (those are `expert_ms_per_step`'s): the router's product and
top-k, the sort, the gathers and scatter-adds, the activation, the loops' own
time.
One group of the step's split by scope (`chipbench/step_scopes.py`); `None`
without a device trace or on a program that keeps no record of its step."""

from chipbench import step_scopes


def read(run):
    return step_scopes.group_ms(run, "expert_dispatch")

"""Layer `train step`: device milliseconds a step in ops the program's record
marks `recomputed`: a `jax.checkpoint` / `nn.remat` block's second forward
pass inside the backward pass, whatever group each op is in, the scan's
kernels run again among them.  The one number of the step's split by scope
(`chipbench/step_scopes.py`) that overlaps the others; `None` without a device
trace, on a program that keeps no record of its step or recomputes nothing."""

from chipbench import step_scopes


def read(run):
    return step_scopes.recomputed_ms(run)

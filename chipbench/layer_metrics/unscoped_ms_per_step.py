"""Layer `device`: device milliseconds a step in what no rule of `step_scopes.RULES` takes: ops whose path names no scope
or module of a group (a block's residual adds, the embedding), ops the
compiler made and gave no path (layout copies, prefetches), and traced ops
the record does not have.  The partition's remainder.
One group of the step's split by scope (`chipbench/step_scopes.py`); `None`
without a device trace or on a program that keeps no record of its step."""

from chipbench import step_scopes


def read(run):
    return step_scopes.group_ms(run, "unscoped")

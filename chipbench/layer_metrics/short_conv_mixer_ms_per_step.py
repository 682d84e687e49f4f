"""Layer `train step`: device milliseconds a step in the gated
short-convolution mixer: everything traced under `short_conv_in_proj`,
`short_conv_gate` (both gates and the convolution: the two kernels, or the
definition where the shapes do not tile) and `short_conv_out_proj`, forward,
recomputed and backward.  `step_scopes.RULES` has no group for these scopes, so
the reader matches them on the partition's list of ops (`scoped_ms` of
kda_kernels_ms_per_step.py, which leaves out the delta rule's kernels alone:
this mixer's own are counted); `None` without a device trace or on a program
that keeps no record of its step."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "kda_kernels_ms_per_step.py"))
    return shared.scoped_ms(run, "short_conv_")

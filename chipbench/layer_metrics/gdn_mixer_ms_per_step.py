"""Layer `train step`: device milliseconds a step in the gated-delta-rule mixer
outside the delta rule's four kernels: the ops traced under `gdn_in_proj`,
`gdn_conv` (the convolution's kernels among them), `gdn_gates`,
`gdn_gate_norm`, `gdn_out_proj`, and what `gdn_chunk` and `gdn_intra` hold
beside the kernel calls (the split into groups of heads, transposes, the
cotangents' sums).  `step_scopes.RULES` has no group for these scopes, so the
reader matches them on the partition's list of ops (`scoped_ms` of
gdn_kernels_ms_per_step.py); `None` without a device trace or on a program that
keeps no record of its step."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "gdn_kernels_ms_per_step.py"))
    return shared.scoped_ms(run, "gdn_")

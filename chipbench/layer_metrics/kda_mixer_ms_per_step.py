"""Layer `train step`: device milliseconds a step in the delta-rule mixer
outside its kernels: the ops traced under `kda_in_proj`, `kda_conv`,
`kda_gates`, `kda_gate_norm`, `kda_out_proj`, and what `kda_chunk` holds
beside the kernel calls (the norms of q and k, the chunk's pairs and its
triangular substitution under `kda_intra`, transposes).  `step_scopes.RULES`
has no group for these scopes, so the reader matches them on the partition's
list of ops (`scoped_ms` of kda_kernels_ms_per_step.py); `None` without a
device trace or on a program that keeps no record of its step."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "kda_kernels_ms_per_step.py"))
    return shared.scoped_ms(run, "kda_")

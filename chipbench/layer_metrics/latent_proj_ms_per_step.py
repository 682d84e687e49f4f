"""Layer `train step`: device milliseconds a step in the latent-attention
layer's own products: the ops traced under `mla_q`, `mla_kv_down`,
`mla_kv_norm`, `mla_kv_up` (the queries, the compression to the latent and
the rotary key, the latent's norm, the expansion to every head's keys and
values and the one rotary head laid beside them).  The kernels, the rotary,
the gate and `o` are `attention_ms_per_step`'s and
`attention_proj_ms_per_step`'s.  `step_scopes.RULES` has no group for these
scopes, so the reader matches them on the partition's list of ops (`scoped_ms`
of kda_kernels_ms_per_step.py); `None` without a device trace or on a program
that keeps no record of its step."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "kda_kernels_ms_per_step.py"))
    return shared.scoped_ms(run, "mla_")

"""Layer `kernels`: the gated delta rule's chunk_bwd kernel's share of its roofline,
in percent: the walk's backward kernel's (`kda_chunk_bwd`, called at this configuration's heads)
(`kernel_roofline` of gdn_kernels_ms_per_step.py)."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "gdn_kernels_ms_per_step.py"))
    return shared.kernel_roofline(run, "chunk_bwd")

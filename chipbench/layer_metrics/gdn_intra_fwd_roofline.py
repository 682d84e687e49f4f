"""Layer `kernels`: the gated delta rule's intra_fwd kernel's share of its roofline,
in percent: the stateless stage's forward kernel's
(`kernel_roofline` of gdn_kernels_ms_per_step.py)."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "gdn_kernels_ms_per_step.py"))
    return shared.kernel_roofline(run, "intra_fwd")

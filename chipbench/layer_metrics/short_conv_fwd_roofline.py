"""Layer `kernels`: the gated short convolution's fwd kernel's share of its
roofline, in percent (`kernel_roofline` of short_conv_kernels_ms_per_step.py)."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "short_conv_kernels_ms_per_step.py"))
    return shared.kernel_roofline(run, "fwd")

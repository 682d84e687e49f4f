"""Layer `kernels`: the whole-sequence flash-attention dQ kernel's share
of its roofline, in percent (`kernel_roofline` of
flash_fwd_global_roofline.py, which says how the three kernels of one name are
told apart)."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "flash_fwd_global_roofline.py"))
    return shared.kernel_roofline(run, "dq")

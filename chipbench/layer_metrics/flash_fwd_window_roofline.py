"""Layer `kernels`: the banded flash-attention fwd kernel's share of its
roofline, in percent (`kernel_roofline` of attention_ms_per_step.py)."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "attention_ms_per_step.py"))
    return shared.kernel_roofline(run, "flash_fwd_window_roofline", "fwd", "%flash_fwd_window")

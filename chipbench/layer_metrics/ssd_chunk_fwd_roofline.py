"""Layer `kernels`: the state-space scan's fwd kernel's share of its roofline,
in percent (`kernel_roofline` of ssm_scan_ms_per_step.py)."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "ssm_scan_ms_per_step.py"))
    return shared.kernel_roofline(run, "ssd_chunk_fwd_roofline", "fwd")

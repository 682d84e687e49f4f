"""Layer `kernels`: device milliseconds a step spends in the banded
flash-attention kernels (forward, dK/dV, dQ of the window layers), found by
their names (`kernel_ms` of attention_ms_per_step.py).  `None` where the
trace has no such kernel."""

import os

from chipbench import manifest

KERNELS = ("%flash_fwd_window", "%flash_bwd_dkv_window", "%flash_bwd_dq_window")


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "attention_ms_per_step.py"))
    found = [ms for ms in (shared.kernel_ms(run, k) for k in KERNELS) if ms is not None]
    return sum(found) if found else None

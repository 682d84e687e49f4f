"""Layer `kernels`: device milliseconds a step spends in the whole-sequence
flash-attention kernels (forward, dK/dV, dQ of the global layers), which the
compiled step names after the scope the model calls them in (`kernel_ms` of
attention_ms_per_step.py).  `None` where the trace has no such kernel."""

import os

from chipbench import manifest


def read(run):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "attention_ms_per_step.py"))
    return shared.kernel_ms(run, "%attention_global")

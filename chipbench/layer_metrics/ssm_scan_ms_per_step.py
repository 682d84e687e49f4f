"""Layer `kernels`: device milliseconds a step spends in the state-space
scan's kernels (`ssd_chunk_fwd`, the forward pass and its recomputation, and
`ssd_chunk_bwd`), found by name among all the ops of a step (`kernel_ms` of
attention_ms_per_step.py).  `None` where the trace has no such kernel, as on
a program without them."""

import os

from chipbench import manifest

KERNELS = {"fwd": "%ssd_chunk_fwd", "bwd": "%ssd_chunk_bwd"}


def _kernel_ms(run, marker):
    shared = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "attention_ms_per_step.py"))
    return shared.kernel_ms(run, marker)


def read(run):
    found = [ms for ms in (_kernel_ms(run, m) for m in KERNELS.values())
             if ms is not None]
    return sum(found) if found else None


def kernel_roofline(run, metric, kernel):
    """A scan kernel's share of its roofline, in percent: over its calls in a
    step, the larger of FLOPs over the chip's bf16 peak and HBM bytes over its
    bandwidth (`kernel_call` and `kernel_calls_per_step` of
    flops/<config>.py), over its device time a step.  The cell is the one of
    the metric's `workloads` whose FLOPs a sample are the run's.  `None` where
    the trace has no such kernel or the run no peaks (a rehearsal)."""
    ms = _kernel_ms(run, KERNELS[kernel])
    if not ms or run.get("peaks") is None:
        return None
    entry = next(m for m in manifest.load_manifest()["per_layer"]
                 if m["name"] == metric)
    for name in entry["workloads"]:
        cell = manifest.resolve(name)
        flops, sizes = cell.module("flops"), cell.sizes()
        if flops.train_flops_per_sample(sizes) == run["flops_per_sample"]:
            break
    else:
        return None
    ops, nbytes = flops.kernel_call(sizes, kernel)
    ideal_s = flops.kernel_calls_per_step(sizes, kernel) * max(
        ops / run["peaks"]["bf16_flops_per_s"], nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * ideal_s / (ms / 1e3)

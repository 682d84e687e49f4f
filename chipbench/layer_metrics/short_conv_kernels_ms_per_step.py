"""Layer `kernels`: device milliseconds a step spends in the gated short
convolution's kernels (`short_conv_fwd`, the forward pass and its
recomputation, and `short_conv_bwd`), found by name among all the ops of a
step (`kernel_ms` of attention_ms_per_step.py).  `None` where the trace has no
such kernel, as on a program without them."""

import os

from chipbench import manifest

KERNELS = {"fwd": "%short_conv_fwd", "bwd": "%short_conv_bwd"}
METRICS = {"fwd": "short_conv_fwd_roofline", "bwd": "short_conv_bwd_roofline"}


def _shared():
    return manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "attention_ms_per_step.py"))


def _is(name, marker):
    """`%short_conv_fwd`, `%short_conv_fwd.3 = ...`, and no longer name."""
    return name.startswith(marker) and name[len(marker):][:1] in ("", ".", " ")


def read(run):
    found = [ms for ms in (_shared().kernel_ms(run, m) for m in KERNELS.values())
             if ms is not None]
    return sum(found) if found else None


def calls_per_step(run, kernel):
    """How often a step runs the kernel, **from the trace**: every traced op
    of its name is one call (a layer's forward pass, its recomputation, its
    backward pass: the layers are unrolled, none stands in a loop)."""
    ops = (run["trace"] or {}).get("ops_ms_per_step") or {}
    return sum(1 for name, ms in ops.items()
               if ms is not None and _is(name, KERNELS[kernel]))


def kernel_roofline(run, kernel):
    """A short-convolution kernel's share of its roofline, in percent: its
    calls in a step times the larger of operations over the chip's bf16 peak
    and HBM bytes over its bandwidth of one call (`kernel_call` of
    flops/<config>.py), over its device time a step.  The cell is the one of
    the metric's `workloads` whose FLOPs a sample are the run's.  `None` where
    the trace has no such kernel or the run no peaks (a rehearsal)."""
    ms = _shared().kernel_ms(run, KERNELS[kernel])
    if not ms or run.get("peaks") is None:
        return None
    entry = next(m for m in manifest.load_manifest()["per_layer"]
                 if m["name"] == METRICS[kernel])
    for name in entry["workloads"]:
        cell = manifest.resolve(name)
        flops, sizes = cell.module("flops"), cell.sizes()
        if flops.train_flops_per_sample(sizes) == run["flops_per_sample"]:
            break
    else:
        return None
    ops, nbytes = flops.kernel_call(sizes, kernel)
    ideal_s = calls_per_step(run, kernel) * max(
        ops / run["peaks"]["bf16_flops_per_s"], nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * ideal_s / (ms / 1e3)

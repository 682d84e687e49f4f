"""Layer `kernels`: device milliseconds a step spends in the flash-attention
kernels (forward, dK/dV, dQ; the banded ones and the whole-sequence ones),
found by name among all the ops of a step.  `None` where the trace has no
such kernel, as on a program without them."""

from chipbench import manifest

# the banded kernels carry names of their own (kernels/flash_attention.py);
# the whole-sequence kernels are Pallas calls without one, which the compiled
# step names after the scope the model calls them in
KERNELS = ("%flash_fwd_window", "%flash_bwd_dkv_window", "%flash_bwd_dq_window",
           "%attention_global")


def kernel_ms(run, marker):
    """Summed device ms a step of the ops whose name begins with `marker`
    (`%flash_fwd_window.3`, `.4`, ...: one a layer), or None."""
    ops = (run["trace"] or {}).get("ops_ms_per_step") or {}
    found = [ms for name, ms in ops.items() if ms is not None
             and name.startswith(marker) and name[len(marker):][:1] in ("", ".", " ")]
    return sum(found) if found else None


def read(run):
    found = [ms for ms in (kernel_ms(run, k) for k in KERNELS) if ms is not None]
    return sum(found) if found else None


def kernel_roofline(run, metric, kernel, marker):
    """A banded kernel's share of its roofline, in percent: over its calls
    in a step, the larger of FLOPs over the chip's bf16 peak and HBM bytes
    over its bandwidth (from shapes, visible pairs only: `kernel_call` of
    flops/<config>.py), over its device time a step.  A reader is handed
    the run and not the cell: the cell is the one of the metric's
    `workloads` whose FLOPs a sample are the run's.  `None` where the trace
    has no such kernel or the run no peaks (a rehearsal)."""
    ms = kernel_ms(run, marker)
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
    ideal_s = 0.0
    for window in flops.windows(sizes):
        if window is not None:  # the whole-sequence layers run unnamed kernels
            ops, nbytes = flops.kernel_call(sizes, kernel, window)
            ideal_s += max(ops / run["peaks"]["bf16_flops_per_s"],
                           nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * ideal_s / (ms / 1e3) if ideal_s else None

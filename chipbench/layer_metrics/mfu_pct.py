"""Layer `kernels`: an end-to-end utilization, not a kernel's roofline share:
forward + backward FLOPs per sample from shapes (no recomputation) times
samples per second per chip, over the chip's published bf16 peak."""


def read(run):
    if run["peaks"] is None:  # a rehearsal has no peak to take a share of
        return None
    return (100.0 * run["flops_per_sample"] * run["window"]["samples_per_s_chip"]
            / run["peaks"]["bf16_flops_per_s"])

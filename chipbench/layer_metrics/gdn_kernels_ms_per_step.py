"""Layer `kernels`: device milliseconds a step spends in the gated delta rule's
four kernels: the stateless stage's `gdn_intra_fwd` (the forward pass and its
recomputation) and `gdn_intra_bwd`, and the walk's `kda_chunk_fwd` and
`kda_chunk_bwd` (Ling's kernels, called), found by name among all the ops of a
step (`kernel_ms` of attention_ms_per_step.py).  `None` where the trace has no
such kernel, as on a program without them."""

import os

from chipbench import manifest, step_scopes

KERNELS = {"intra_fwd": "%gdn_intra_fwd", "intra_bwd": "%gdn_intra_bwd",
           "chunk_fwd": "%kda_chunk_fwd", "chunk_bwd": "%kda_chunk_bwd"}
METRICS = {"intra_fwd": "gdn_intra_fwd_roofline", "intra_bwd": "gdn_intra_bwd_roofline",
           "chunk_fwd": "gdn_chunk_fwd_roofline", "chunk_bwd": "gdn_chunk_bwd_roofline"}


def _shared():
    return manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "attention_ms_per_step.py"))


def read(run):
    found = [ms for ms in (_shared().kernel_ms(run, m) for m in KERNELS.values())
             if ms is not None]
    return sum(found) if found else None


def _is(name, marker):
    """`gdn_intra_fwd`, `gdn_intra_fwd.3`, and no longer name."""
    return name.startswith(marker[1:]) and name[len(marker) - 1:][:1] in ("", ".")


def calls_per_step(run, kernel, loop_trips):
    """How often a step runs the kernel, **from the trace**: every traced op
    of its name is one call, or `loop_trips` calls where the program's record
    of its step places the op inside a `while` (the walk over groups of
    heads).  None without a record."""
    done = step_scopes.partition(run)
    if not done or not done.get("ops"):
        return None
    calls = sum(loop_trips if op.within else 1 for op, _, _ in done["ops"]
                if _is(op.name, KERNELS[kernel]))
    return calls or None


def kernel_roofline(run, kernel):
    """A kernel's share of its roofline, in percent: its calls in a step times
    the larger of FLOPs over the chip's bf16 peak and HBM bytes over its
    bandwidth of one call (`kernel_call` of flops/<config>.py: four value
    heads), over its device time a step.  The cell is the one of the metric's
    `workloads` whose FLOPs a sample are the run's.  `None` where the trace has
    no such kernel, the program no record of its step or the run no peaks."""
    ms = _shared().kernel_ms(run, KERNELS[kernel])
    if not ms or run.get("peaks") is None:
        return None
    entry = next((m for m in manifest.load_manifest()["per_layer"]
                  if m["name"] == METRICS[kernel]), None)
    for name in (entry or {}).get("workloads", ()):
        cell = manifest.resolve(name)
        flops, sizes = cell.module("flops"), cell.sizes()
        if flops.train_flops_per_sample(sizes) == run["flops_per_sample"]:
            break
    else:
        return None
    calls = calls_per_step(
        run, kernel, sizes["linear_num_value_heads"] // flops.HEADS_A_CALL)
    if not calls:
        return None
    ops, nbytes = flops.kernel_call(sizes, kernel)
    ideal_s = calls * max(ops / run["peaks"]["bf16_flops_per_s"],
                          nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * ideal_s / (ms / 1e3)


def scoped_ms(run, prefix):
    """Device ms a step of the ops traced under a scope whose name begins with
    `prefix`, the four kernels left out: read from the partition's list of ops,
    whatever group `step_scopes.RULES` gave them.  None without a trace or a
    record, or where no op is."""
    done = step_scopes.partition(run)
    if not done or not done.get("ops"):
        return None
    found = [own for op, _, own in done["ops"]
             if any(part.startswith(prefix) for part in op.path.split("/"))
             and not any(_is(op.name, m) for m in KERNELS.values())]
    return sum(found) if found else None

"""Layer `kernels`: the whole-sequence flash-attention forward kernel's share
of its roofline, in percent, and `kernel_roofline`, which the dK/dV and dQ
readers share.

The three whole-sequence kernels are Pallas calls without a name of their own,
which the compiled step names after the scope the model calls them in: all
three are `%attention_global.<n>`.  A traced op's name is the start of its HLO
text, and that carries the op's output shape.  Where the query-key head and
the value head differ in size (latent attention: 192 beside 128) the shape
tells the three apart:

    forward   (bf16[.., T, v], f32[.., T, lanes])   a tuple led by the value head's
                                                    size, then the float32 logsumexp
    dK/dV     (bf16[.., T, qk], bf16[.., T, v])     a tuple led by the query-key size
    dQ        bf16[.., T, qk]                       one array, no tuple

An op whose shape fits none of the three is left out, and a cell whose two head
sizes are equal cannot be told apart this way: `None`.  Every traced op of a
kind is one call a step (a layer's kernel is an instruction of its own; the
forward's output and logsumexp are kept, so a recomputed block does not call it
again): the calls are **counted from the trace**, not from the configuration."""

import re

from chipbench import manifest

MARKER = "%attention_global"
METRICS = {"fwd": "flash_fwd_global_roofline", "dkv": "flash_bwd_dkv_global_roofline",
           "dq": "flash_bwd_dq_global_roofline"}
_ARRAY = re.compile(r"([a-z]+\d+)\[([\d,]*)\]")


def kind_of(name, qk, dv):
    """'fwd', 'dkv' or 'dq' of a traced op's name by its output shape, None
    where it is no such kernel or the shape does not say."""
    if not (name.startswith(MARKER) and name[len(MARKER):][:1] in (".", " ")):
        return None
    _, _, result = name.partition(" = ")
    arrays = [(dtype, int(dims.split(",")[-1])) for dtype, dims in
              _ARRAY.findall(result) if dims]
    if qk == dv or not arrays:
        return None
    if not result.startswith("("):
        return "dq" if arrays[0] == ("bf16", qk) else None
    if len(arrays) < 2:  # the name was cut before the second array
        return None
    if arrays[0] == ("bf16", dv) and arrays[1][0] == "f32":
        return "fwd"
    if arrays[0] == ("bf16", qk) and arrays[1][0] == "bf16":
        return "dkv"
    return None


def kernel_roofline(run, kernel):
    """A whole-sequence flash kernel's share of its roofline, in percent: its
    calls in a step (counted from the trace) times the larger of FLOPs over
    the chip's bf16 peak and HBM bytes over its bandwidth of one call
    (`kernel_call` of flops/<config>.py: visible pairs only), over the device
    time of those calls.  The cell is the one of the metric's `workloads`
    whose FLOPs a sample are the run's.  `None` without a trace, without
    peaks (a rehearsal), on a run of another cell, or where no traced op is
    told to be this kernel."""
    ops = (run.get("trace") or {}).get("ops_ms_per_step") or {}
    if not ops or run.get("peaks") is None:
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
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    found = [ms for name, ms in ops.items()
             if ms and kind_of(name, qk, sizes["v_head_dim"]) == kernel]
    if not found:
        return None
    work, nbytes = flops.kernel_call(sizes, kernel)
    ideal_s = len(found) * max(work / run["peaks"]["bf16_flops_per_s"],
                               nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * ideal_s / (sum(found) / 1e3)


def read(run):
    return kernel_roofline(run, "fwd")

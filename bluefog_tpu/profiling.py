"""Intra-step attribution tools (round-2 verdict missing #4).

The reference's timeline stamps per-tensor NEGOTIATING/COMMUNICATING
spans from its background loop (``bluefog/common/timeline.cc`` [U]);
under XLA one jitted step is one opaque span, so attribution works
differently: compare COMPILED COSTS between program variants, and time
program segments with the dispatch-amortized slope protocol.  This
module turns both hand-run techniques (the ResNet fwd/bwd/step
decomposition, the peaks measurement) into tools.

- :func:`slope_time` — per-call wall time as the slope between two call
  counts (per-run sync RTT cancels; per-call dispatch is included — the
  honest number for step-level segments).
- :func:`slope_time_fused` — the microkernel form: iterations inside ONE
  jitted ``fori_loop``, so dispatch amortizes too (peaks methodology).
- :func:`segment_times` — slope-time a dict of named jitted segments
  (e.g. fwd / fwd+bwd / full step) in one sweep: the decomposition that
  pinned the ResNet ceiling.
- :func:`cost_summary` — XLA's compiled cost analysis (flops, bytes
  accessed) for a jitted fn.  NOTE: ``bytes accessed`` counts operand
  bytes per HLO op and OVERCOUNTS real HBM traffic under fusion — valid
  for program-to-program DELTAS, invalid as a roofline floor.
- :func:`cost_delta` — the delta form: what did this change add/remove.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Mapping, Sequence, Tuple

import jax

from bluefog_tpu.ops import device_sync

__all__ = ["slope_time", "slope_time_fused", "segment_times",
           "cost_summary", "cost_delta"]


def slope_time(fn: Callable, args: Sequence = (), *, iters_lo: int = 3,
               iters_hi: int = 13, repeats: int = 2) -> float:
    """Per-call wall seconds of ``fn(*args)`` as the slope
    ``(T(iters_hi) - T(iters_lo)) / (iters_hi - iters_lo)``, each T the
    best of ``repeats`` timed runs (queued async calls, one
    ``device_sync`` at the end).

    What cancels: the per-RUN sync/fetch round-trip (a constant per
    region).  What does NOT cancel: the per-CALL dispatch cost — each
    iteration is a real
    eager call, so the slope measures compute + per-call dispatch.  That
    is the honest number for step-level segments (a training step pays
    dispatch every call); for sub-ms MICROKERNELS it is dispatch-biased
    — use :func:`slope_time_fused`, which loops inside ONE jitted
    program (the benchmarks/peaks.py methodology).  Either way, size the
    span so the compute delta well exceeds per-run noise (a few ms)."""
    if iters_hi <= iters_lo:
        raise ValueError(f"iters_hi ({iters_hi}) must exceed iters_lo "
                         f"({iters_lo})")

    def timed(k: int) -> float:
        out = fn(*args)
        device_sync(out)  # compile + settle outside the timed region
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(k):
                out = fn(*args)
            device_sync(out)
            best = min(best, time.perf_counter() - t0)
        return best

    return (timed(iters_hi) - timed(iters_lo)) / (iters_hi - iters_lo)


def slope_time_fused(body: Callable, x, *, iters_lo: int = 4,
                     iters_hi: int = 24, repeats: int = 2) -> float:
    """Per-iteration seconds of ``x -> body(x)`` with the loop INSIDE one
    jitted ``lax.fori_loop`` — per-call dispatch amortizes to ~0, so this
    is the microkernel form (how benchmarks/peaks.py measures the chip's
    peaks).  ``body`` must be carry-compatible (same shape/dtype out)."""
    from jax import lax

    def make(k):
        @jax.jit
        def run(x):
            return lax.fori_loop(0, k, lambda _, y: body(y), x)

        return run

    lo = slope_time(make(iters_lo), (x,), iters_lo=1, iters_hi=2,
                    repeats=repeats)
    hi = slope_time(make(iters_hi), (x,), iters_lo=1, iters_hi=2,
                    repeats=repeats)
    return (hi - lo) / (iters_hi - iters_lo)


def segment_times(segments: Mapping[str, Tuple[Callable, Sequence]],
                  **slope_kwargs) -> Dict[str, float]:
    """Slope-time every named segment; returns {name: seconds}.

    The intra-step attribution recipe: pass e.g. ``{"fwd": (fwd_fn, a),
    "fwd_bwd": (grad_fn, a), "full_step": (step_fn, b)}`` and read the
    differences — optimizer+gossip+dispatch = full_step − fwd_bwd, etc.
    """
    return {name: slope_time(fn, args, **slope_kwargs)
            for name, (fn, args) in segments.items()}


def _compiled(fn: Callable, args: Sequence):
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return jitted.lower(*args).compile()


def cost_summary(fn: Callable, args: Sequence = ()) -> Dict[str, float]:
    """XLA cost analysis of the compiled program: ``flops`` and
    ``bytes_accessed`` (operand-byte count — see the module docstring
    caveat), plus every other scalar XLA reports."""
    analysis = _compiled(fn, args).cost_analysis()
    return {k: float(v) for k, v in analysis.items()
            if isinstance(v, (int, float))}


def cost_delta(fn_a: Callable, fn_b: Callable, args_a: Sequence = (),
               args_b: Sequence = ()) -> Dict[str, float]:
    """``cost_summary(fn_b) - cost_summary(fn_a)`` per key — the honest
    use of XLA's cost model: attribute what a CHANGE adds (a layer, a
    gossip edge, an optimizer), where the fusion overcount cancels to
    first order."""
    a = cost_summary(fn_a, args_a)
    b = cost_summary(fn_b, args_b)
    return {k: b.get(k, 0.0) - a.get(k, 0.0) for k in sorted(set(a) | set(b))}

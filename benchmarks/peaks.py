"""Measure the chip's REAL peaks with dispatch cost amortized.

Round-2's "99.1 TF/s bf16 peak" was measured as ONE 8192^3 matmul per
dispatch; every dispatch then carried a ~3.5 ms fixed cost,
so that number was dispatch-contaminated (a >100%-of-peak MFU elsewhere in
the repo proved it).  This script measures each peak
as the SLOPE between two inner-iteration counts inside one jitted
``lax.fori_loop`` program:

    t_per_iter = (T(k_hi) - T(k_lo)) / (k_hi - k_lo)

The fixed dispatch/fetch cost appears in both T's and cancels exactly.
Sync is ``bluefog_tpu.ops.device_sync`` (scalar host round-trip — the only
proof of completion on this platform; ``block_until_ready`` returns
immediately here).

Measured quantities:
  - bf16 matmul peak TF/s (MXU), at 4096^3 and 8192^3
  - f32 matmul TF/s
  - HBM stream bandwidth GB/s  (x -> 0.999*x + 0.5: 1 read + 1 write
    per iteration, no pass-through carries, no reuse XLA can fuse)
  - per-dispatch fixed cost (tiny jitted add, one op per dispatch)

Prints one JSON dict.  Parity note: the reference has no equivalent; this
exists because every MFU/roofline claim keys off these
denominators (SURVEY.md section 6).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bluefog_tpu.ops import device_sync


def _time_calls(fn, args, n=3):
    """Min wall time of fn(*args) over n calls, device_sync'd."""
    out = fn(*args)
    device_sync(out)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(*args)
        device_sync(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _slope(make_fn, args, k_lo, k_hi, n=3):
    """Per-iteration time via the two-point slope (dispatch cancels)."""
    t_lo = _time_calls(make_fn(k_lo), args, n)
    t_hi = _time_calls(make_fn(k_hi), args, n)
    return (t_hi - t_lo) / (k_hi - k_lo), t_lo, t_hi


def matmul_peak(dim, dtype, k_lo=4, k_hi=24, n=3):
    """Chained y = (y @ w) * s inside one jit; returns TF/s per matmul."""

    def make(k):
        @jax.jit
        def run(y, w):
            def body(_, y):
                # 0.02 keeps the chain from saturating to inf in bf16;
                # the scale fuses into the matmul epilogue (no extra pass)
                return (y @ w) * jnp.asarray(0.02, dtype)

            return jax.lax.fori_loop(0, k, body, y)

        return run

    key = jax.random.PRNGKey(0)
    y = jax.random.normal(key, (dim, dim), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (dim, dim), jnp.float32).astype(dtype)
    per_iter, t_lo, t_hi = _slope(make, (y, w), k_lo, k_hi, n)
    flops = 2.0 * dim**3
    return {
        "tflops": round(flops / per_iter / 1e12, 2),
        "ms_per_matmul": round(per_iter * 1e3, 3),
        "t_lo_s": round(t_lo, 4),
        "t_hi_s": round(t_hi, 4),
    }


def hbm_stream(mb=1024, k_lo=4, k_hi=24, n=3):
    """Sustained HBM bandwidth: x -> 0.999*x + 0.5 (1 read + 1 write).

    A STREAM-triad formulation (carry (a,b) -> (b, a*s+b)) measures ~40%
    lower here because the pass-through carry element costs XLA an extra
    copy per iteration; the single-array recurrence has no pass-through,
    no cross-iteration reuse a compiler could exploit, and its 2*bytes
    traffic count is exact.  Returns effective GB/s.
    """
    elems = int(mb * 1e6 / 4)

    def make(k):
        @jax.jit
        def run(x):
            return jax.lax.fori_loop(0, k, lambda _, x: x * 0.999 + 0.5, x)

        return run

    x = jnp.ones((elems,), jnp.float32)
    per_iter, t_lo, t_hi = _slope(make, (x,), k_lo, k_hi, n)
    gbytes = 2.0 * elems * 4 / 1e9
    return {
        "gbs": round(gbytes / per_iter, 1),
        "ms_per_iter": round(per_iter * 1e3, 3),
        "array_mb": round(elems * 4 / 1e6, 1),
    }


def dispatch_cost(n=10):
    """Fixed cost of one tiny dispatch (4 KB add)."""

    @jax.jit
    def add(x):
        return x + 1.0

    x = jnp.ones((1024,), jnp.float32)
    return {"ms": round(_time_calls(add, (x,), n) * 1e3, 2)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true", help="small sizes (CPU/CI)")
    args = p.parse_args()

    if args.quick:
        out = {"platform": jax.devices()[0].platform, "dispatch": dispatch_cost()}
        out["bf16_matmul_256"] = matmul_peak(256, jnp.bfloat16, 2, 6)
        out["f32_matmul_256"] = matmul_peak(256, jnp.float32, 2, 6)
        out["hbm_stream"] = hbm_stream(8, 2, 6)
        print(json.dumps(out))
        return out

    # k spans sized so the t_hi - t_lo delta is >= ~100 ms of pure compute:
    # the slope must dominate per-call noise (RTT has varied
    # 3.5-200 ms across sessions, a few ms within one)
    out = {"platform": jax.devices()[0].platform, "dispatch": dispatch_cost()}
    out["bf16_matmul_4096"] = matmul_peak(4096, jnp.bfloat16, 8, 200)
    out["bf16_matmul_8192"] = matmul_peak(8192, jnp.bfloat16, 2, 20)
    out["f32_matmul_4096"] = matmul_peak(4096, jnp.float32, 8, 100)
    out["hbm_stream"] = hbm_stream(1024, 4, 40)
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()

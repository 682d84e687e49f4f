"""Attention kernel benchmark: the Pallas flash kernel vs dense XLA
attention across sequence lengths (the hot op of the transformer configs —
BASELINE configs #3/#5; kernel in ``bluefog_tpu/kernels/flash_attention.py``).

Run (TPU):      python benchmarks/attention.py
Run (CPU mesh): JAX_PLATFORMS=cpu python benchmarks/attention.py --seqs 256

Prints ONE JSON line: value = flash fwd+bwd TFLOP/s at the largest
sequence, vs_baseline = dense time / flash time there (>1: flash faster).
"""

import argparse
import json
import os
import sys
import time

import jax

if os.environ.get("JAX_PLATFORMS"):
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _sync, measure_rtt, paired_slope, use_compile_cache
from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models.transformer import dense_attention


def timed(f, args, iters):
    """Per-call via the shared paired-slope estimator (bench.paired_slope):
    the constant per-region cost — fetch RTT AND pipeline fill — cancels
    in the difference of the two regions, where the previous RTT-only
    subtraction left the fill share in and pulled small-S ratios toward
    1 (see the r4 STATUS estimator note)."""
    out = f(*args)
    first = out[0] if isinstance(out, tuple) else out
    _sync(first)

    def region(k):
        o = None
        t0 = time.perf_counter()
        for _ in range(k):
            o = f(*args)
        _sync(o[0] if isinstance(o, tuple) else o)
        return time.perf_counter() - t0

    return paired_slope(region, iters, "attention",
                        lambda: measure_rtt(first))


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--seqs", type=int, nargs="*", default=None)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    on_tpu = jax.devices()[0].platform == "tpu"
    seqs = args.seqs or ([1024, 2048, 4096, 8192] if on_tpu else [256])
    B, H, D = args.batch, args.heads, args.head_dim
    dtype = jnp.bfloat16 if on_tpu else jnp.float32

    # [B, T, H, D] layout (the models' convention)
    def qkv(S):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        return tuple(jax.random.normal(k, (B, S, H, D), dtype) for k in ks)

    def flash_loss(q, k, v):
        # found a TPU: the kernel is compiled, and says so if it cannot be
        return jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=False if on_tpu else None,
        ).astype(jnp.float32))

    def dense_loss(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True,
                                       dtype=dtype).astype(jnp.float32))

    flash_g = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))
    dense_g = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))

    result = None
    for S in seqs:
        # size the region so the slope's compute DELTA — the difference
        # between the iters and iters//2 regions, i.e. ~iters/2 calls —
        # is ~0.5 s (peaks.py's rule: the estimator is only as good as
        # the delta it differences; at fixed iters the small-S deltas
        # are a few ms and drown in region noise).  ~50 TF/s estimate.
        flops_s = 12 * B * H * S * S * D * 0.5
        iters = args.iters
        if on_tpu:
            est = flops_s / 50e12
            iters = max(args.iters, min(int(1.0 / est), 2000))
        try:
            tf, tf_fb = timed(flash_g, qkv(S), iters)
        except AssertionError:  # _sync's finiteness check: a real kernel bug
            raise
        except Exception as e:  # keep earlier lengths' result on OOM
            print(f"# S={S}: flash failed ({type(e).__name__}); stopping",
                  file=sys.stderr)
            break
        try:
            td, td_fb = timed(dense_g, qkv(S), iters)
        except AssertionError:  # _sync's finiteness check: a real bug
            raise
        except Exception:  # dense OOMs first at long S — that's the point
            td, td_fb = float("inf"), False
        # causal fwd+bwd useful FLOPs: (4 qk/pv + 2x4 bwd) * 0.5 causal
        flops = flops_s
        print(
            f"# S={S}: flash {tf * 1e3:8.2f} ms  dense {td * 1e3:8.2f} ms  "
            f"({flops / tf / 1e12:5.1f} TF/s, dense/flash {td / tf:4.2f}x)",
            file=sys.stderr,
        )
        result = {
            "metric": f"flash attention fwd+bwd TFLOP/s "
                      f"(B{B} H{H} S{S} D{D} causal {jnp.dtype(dtype).name})",
            "value": round(flops / tf / 1e12, 2),
            "unit": "TFLOP/s",
            "vs_baseline": round(td / tf, 4) if np.isfinite(td) else None,
            # paired_slope's contract: flag figures that fell back to
            # the RTT-subtracted estimator (never mix them up with
            # slope-timed records)
            "estimator_fallbacks": int(tf_fb) + int(td_fb),
        }
    print(json.dumps(result))


if __name__ == "__main__":
    main()

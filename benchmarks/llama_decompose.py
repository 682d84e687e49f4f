"""Where does the Llama step's time go? — per-block / embed+head
decomposition by layer-count slope (the methodology that pinned the
ResNet ceiling in round 3).

Protocol: slope-time (``profiling.slope_time``: queued async calls, one
sync, RTT cancels) the jitted fwd+bwd loss at two layer counts in
INTERLEAVED rounds — lo and hi measured back to back inside each round,
so the per-round delta cancels session drift the way ``paired_slope``
cancels the region constant (r4 verdict #8: the sequential protocol's
slope/intercept split moved 7.8/45.4 -> 11.25/18.75 ms between re-runs).
The delta is the marginal cost of ``hi - lo`` decoder blocks, free of
embed/head/dispatch; the intercept (min lo time minus ``lo`` blocks) is
embed + head + harness.  Each piece is compared against its
MXU-ideal time (6·flops at the measured 197 TF/s bf16 peak / 155 TF/s
for f32-emulation matmuls) so the gap — memory-bound norms/rotary/
softmax and scheduling — is measured, not guessed.

Run (TPU):      python benchmarks/llama_decompose.py
Run (CPU mesh): JAX_PLATFORMS=cpu python benchmarks/llama_decompose.py --preset tiny
"""

import argparse
import json
import os
import sys

import jax

if os.environ.get("JAX_PLATFORMS"):
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import conservative_delta, robust_min, use_compile_cache
from bluefog_tpu import profiling
from bluefog_tpu.kernels import make_flash_attention_fn
from bluefog_tpu.models.transformer import LlamaLM

PRESETS = {
    "small": dict(vocab=32000, hidden=768, heads=12, dff=2048,
                  seq=2048, batch=8, layers_lo=6, layers_hi=12,
                  head_chunks=8),
    "tiny": dict(vocab=256, hidden=64, heads=4, dff=128,
                 seq=128, batch=2, layers_lo=1, layers_hi=2,
                 head_chunks=4),
}


def build_grad_fn(cfg, layers, on_tpu, head_bf16, attn):
    attention_fn = {
        # off-TPU there is no Pallas path: fall back to dense and SAY so
        # in the JSON (effective_attn) instead of mislabeling a dense run
        # as flash (r3 advisor finding)
        "flash": make_flash_attention_fn(interpret=False) if on_tpu else None,
        "dense": None,
        # shape-correct pass-through: measures the block with the
        # attention OP deleted (projections/rotary/norms/FFN remain),
        # so flash-share = per_block(flash) - per_block(none)
        "none": lambda q, k, v: v,
    }[attn]
    effective_attn = attn if (attn != "flash" or on_tpu) else "dense"
    model = LlamaLM(
        vocab_size=cfg["vocab"], hidden_size=cfg["hidden"],
        num_layers=layers, num_heads=cfg["heads"], dff=cfg["dff"],
        head_chunks=cfg["head_chunks"],
        head_dtype=jnp.bfloat16 if head_bf16 else jnp.float32,
        attention_fn=attention_fn,
    )
    ids = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg["vocab"], size=(cfg["batch"], cfg["seq"])),
        jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    @jax.jit
    def grad_step(p, x):
        return jax.grad(
            lambda p_: model.apply({"params": p_}, x, labels=x))(p)

    # warm the cache so slope_time measures execution, not compilation
    jax.block_until_ready(grad_step(params, ids))
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(params))
    return grad_step, params, ids, n_params, effective_attn


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    on_tpu = jax.devices()[0].platform == "tpu"
    ap.add_argument("--preset", default="small" if on_tpu else "tiny",
                    choices=sorted(PRESETS))
    ap.add_argument("--head-bf16", action="store_true")
    ap.add_argument("--attn", default="flash",
                    choices=["flash", "dense", "none"],
                    help="attention inside the blocks (none = "
                    "pass-through, isolates the attention share)")
    ap.add_argument("--rounds", type=int, default=3,
                    help="interleaved lo/hi measurement rounds")
    args = ap.parse_args()
    cfg = PRESETS[args.preset]
    lo, hi = cfg["layers_lo"], cfg["layers_hi"]

    # Build and warm BOTH layer-count programs first, then measure them in
    # INTERLEAVED rounds (r4 verdict #8: the r4 protocol measured lo fully
    # before hi, so the slope/intercept split absorbed whatever the
    # session drifted between the two phases — re-runs read 7.8/45.4 vs
    # 11.25/18.75 ms.  A paired round shares one session window, so the
    # per-round delta cancels the drift the way paired_slope cancels the
    # region constant).
    built = {}
    meta = {}
    effective_attn = args.attn
    for layers in (lo, hi):
        fn, params, ids, n_params, effective_attn = build_grad_fn(
            cfg, layers, on_tpu, args.head_bf16, args.attn)
        built[layers] = (fn, (params, ids))
        meta[layers] = n_params

    t_los, t_his = [], []
    for _ in range(max(args.rounds, 1)):
        t_los.append(profiling.slope_time(*built[lo]))
        t_his.append(profiling.slope_time(*built[hi]))

    toks = cfg["batch"] * cfg["seq"]
    # bench.conservative_delta across rounds: per-round deltas are
    # drift-paired, the floors guard stall-deflated rounds
    delta = conservative_delta(t_los, t_his)
    if delta is None:
        print("llama_decompose: all paired layer-count deltas "
              "non-positive — session too noisy, rerun", file=sys.stderr)
        sys.exit(1)
    per_block = delta / (hi - lo)
    deltas = [(th - tl) / (hi - lo) for tl, th in zip(t_los, t_his)]
    # robust_min, not min: a stall deflating one round's lo reading would
    # deflate the intercept (embed_head could even print negative)
    embed_head = robust_min(t_los, "decompose-lo") - lo * per_block
    per_block_spread_pct = (
        (max(deltas) - min(deltas)) / per_block * 100 if len(deltas) > 1
        else 0.0)

    # MXU-ideal milliseconds: 6 flops/param/token fwd+bwd at the measured
    # 197 TF/s bf16 peak; the head's f32 3-pass emulation runs ~155
    block_params = (meta[hi] - meta[lo]) / (hi - lo)
    head_params = cfg["vocab"] * cfg["hidden"]  # embed lookup is ~free
    head_rate = 197e12 if args.head_bf16 else 155e12
    # head flops: fwd + chunked recompute + 2x backward = 8·N_head/token
    ideal_block_ms = 6 * block_params * toks / 197e12 * 1e3
    ideal_head_ms = 8 * head_params * toks / head_rate * 1e3

    print(json.dumps({
        "metric": f"Llama-{args.preset} fwd+bwd decomposition "
                  f"(layer-count slope {lo}->{hi})",
        "per_block_ms": round(per_block * 1e3, 2),
        "per_block_mxu_ideal_ms": round(ideal_block_ms, 2),
        "per_block_gap_x": round(per_block * 1e3 / max(ideal_block_ms, 1e-9), 2),
        "embed_head_ms": round(embed_head * 1e3, 2),
        "head_mxu_ideal_ms": round(ideal_head_ms, 2),
        "step_ms_at_hi": round(robust_min(t_his, "decompose-hi") * 1e3, 2),
        # interleaved-round transparency (r4 verdict #8): the per-round
        # paired deltas and the spread the conservative pick came from
        "per_block_rounds_ms": [round(d * 1e3, 2) for d in deltas],
        "per_block_spread_pct": round(per_block_spread_pct, 1),
        "n_rounds": len(deltas),
        "estimator": "interleaved paired rounds (two-statistic)",
        "head_bf16": bool(args.head_bf16),
        "attn": args.attn,
        "effective_attn": effective_attn,
        "unit": "ms",
    }))


if __name__ == "__main__":
    main()

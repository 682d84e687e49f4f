"""Llama-style decentralized pretraining throughput, tokens/sec/chip —
evidence for BASELINE config #5 (Llama gossip pretraining) at a
single-chip-sized model.  Same harness conventions as bench.py (the driver
metric): decentralized ATC step with the exp-2 plan, global-allreduce
baseline phase for vs_baseline, one JSON line.

Run (TPU):      python benchmarks/llama.py            (~125M params, S=2048)
Run (CPU mesh): JAX_PLATFORMS=cpu python benchmarks/llama.py --preset tiny
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
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (
    _sync, measure_rtt, paired_slope, robust_min, throughput_range,
    use_compile_cache,
)
import bluefog_tpu as bf
from bluefog_tpu import topology_util
from bluefog_tpu.core import basics
from bluefog_tpu.kernels import make_flash_attention_fn
from bluefog_tpu.models.transformer import LlamaLM
from bluefog_tpu.optim import CommunicationType
from bluefog_tpu.training import (
    make_decentralized_train_step,
    make_lm_loss_fns,
    replicate_for_mesh,
)

PRESETS = {
    # ~125M-class: GPT-2-small-shaped Llama, flash attention.
    # head_chunks: chunked LM loss measured FASTER here too (+3.9%
    # same-session, 72.1k vs 69.4k tok/s) — the freed [B,T,32k] f32
    # logits traffic outweighs the head recompute even at 134M
    "small": dict(vocab=32000, hidden=768, layers=12, heads=12, dff=2048,
                  seq=2048, batch=8, head_chunks=8),
    # ~1.05B (BASELINE config #5 feasibility on one 16 GB chip): bf16
    # compute, per-block remat, momentum-SGD with a bf16 momentum trace
    # (optax accumulator_dtype; AdamW's extra state would not fit
    # single-chip regardless of trace dtype)
    # scan_layers: one block body in the HLO — 24 unrolled 1B-scale blocks
    # failed to compile (round 2)
    # head_chunks: chunked LM loss — the full [B,T,32k] f32 logits (+their
    # backward cotangent) never materialize.
    # Batch/optimizer history: under the old 512^2 flash blocks B=4+f32
    # sgdm and B=8+sgdm_bf16 were throughput-NEUTRAL (13.08k vs 13.11k
    # tok/s) so exact-f32 momentum stayed default; the r4 1024^2 block
    # retune flipped that — B=8+sgdm_bf16 measured 15.44k vs B=4's
    # 15.03k (+2.7%, reproduced 15,440/15,449) and is now the preset.
    # B=4+f32 momentum remains available via --batch 4 --optimizer sgdm
    # (B=8+f32 OOMs: 12.6 GB of f32 state; B=16 OOMs even bf16;
    # B=6 measured 12% slower — non-power-of-2 MXU tiling).
    "1b": dict(vocab=32000, hidden=1792, layers=24, heads=14, dff=4864,
               seq=2048, batch=8, remat=True, scan_layers=True,
               optimizer="sgdm_bf16", head_chunks=8),
    "tiny": dict(vocab=256, hidden=64, layers=2, heads=4, dff=128,
                 seq=128, batch=2),
}


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    on_tpu = jax.devices()[0].platform == "tpu"
    ap.add_argument("--preset", default="small" if on_tpu else "tiny",
                    choices=sorted(PRESETS))
    ap.add_argument("--iters", type=int, default=10 if on_tpu else 3)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--passes", type=int, default=3 if on_tpu else 1,
                    help="paired-slope passes for the headline phase; the "
                    "value is the stall-guarded min (bench.robust_min) and "
                    "the JSON carries the full range (r4 verdict #7)")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "xla", "pallas", "dense"],
                    help="flash-attention implementation (dense = model's "
                    "built-in softmax attention)")
    ap.add_argument("--batch", type=int, default=0,
                    help="override the preset's per-rank batch (A/B sweeps)")
    ap.add_argument("--blocks", type=int, default=0,
                    help="override the flash block_q=block_k size (A/B sweeps)")
    ap.add_argument("--remat-policy", default=None,
                    choices=[None, "dots", "dots_no_batch", "attn"],
                    help="checkpoint policy under remat presets (A/B sweeps)")
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped-query attention: kv head count "
                    "(0 = MHA; must divide the preset's heads)")
    ap.add_argument("--head-chunks", type=int, default=-1,
                    help="chunked LM loss: sequence chunks for the head "
                    "(-1 = preset default, 0/1 = full logits)")
    ap.add_argument("--head-bf16", action="store_true",
                    help="LM head matmul with bf16 operands / f32 "
                    "accumulation (custom-VJP path; measured NEUTRAL "
                    "at 1B and -3%% at 134M on the v5e, where default "
                    "f32 matmul already runs near the bf16 rate)")
    ap.add_argument("--seq", type=int, default=0,
                    help="override the preset sequence length (long-context "
                    "runs; pair with --batch to keep tokens/step sane)")
    ap.add_argument("--optimizer", default=None,
                    choices=[None, "adamw", "sgdm", "sgdm_bf16",
                             "adafactor"],
                    help="override the preset optimizer (sgdm_bf16 = "
                    "bf16 momentum trace, frees 2.1 GB at 1B; "
                    "adafactor = factored second moment, adaptive "
                    "updates at ~zero state cost)")
    args = ap.parse_args()
    cfg = dict(PRESETS[args.preset])
    if args.batch:
        cfg["batch"] = args.batch
    if args.seq:
        cfg["seq"] = args.seq
    if args.optimizer:
        cfg["optimizer"] = args.optimizer
    if args.remat_policy and not cfg.get("remat"):
        # LlamaLM only consults remat_policy under remat=True; silently
        # attributing a number to a policy that never applied would
        # poison the A/B sweep
        ap.error(f"--remat-policy requires a remat preset "
                 f"(preset {args.preset!r} has remat=False)")

    bf.init()
    n = bf.size()
    bf.set_topology(topology_util.ExponentialTwoGraph(n))
    ctx = basics.context()

    head_chunks = (cfg.get("head_chunks", 0) if args.head_chunks < 0
                   else args.head_chunks)
    # explicit pallas/xla is honored everywhere (interpret mode off TPU);
    # only "dense" and the off-TPU auto default skip flash, and the JSON
    # line names what ran.  Having found a TPU, the kernel is asked for
    # compiled: it fails there rather than run interpreted.
    if args.attn_impl == "dense" or (args.attn_impl == "auto" and not on_tpu):
        attn_ran, attention_fn = "dense", None
    else:
        attn_ran = args.attn_impl
        attention_fn = make_flash_attention_fn(
            impl=args.attn_impl, interpret=False if on_tpu else None,
            block_q=args.blocks or None, block_k=args.blocks or None,
        )
    model = LlamaLM(
        vocab_size=cfg["vocab"], hidden_size=cfg["hidden"],
        num_layers=cfg["layers"], num_heads=cfg["heads"], dff=cfg["dff"],
        head_chunks=head_chunks,
        head_dtype=jnp.bfloat16 if args.head_bf16 else jnp.float32,
        remat=cfg.get("remat", False),
        remat_policy=args.remat_policy,
        num_kv_heads=args.kv_heads or None,
        scan_layers=cfg.get("scan_layers", False),
        attention_fn=attention_fn,
    )
    B, T = cfg["batch"], cfg["seq"]
    ids0 = jnp.ones((B, T), jnp.int32)
    # keep the pristine copy on HOST: at 1B params a device-resident extra
    # copy alongside params+momentum+grads blows the 16 GB budget
    params_host = jax.tree_util.tree_map(
        np.asarray,
        replicate_for_mesh(model.init(jax.random.PRNGKey(0), ids0)["params"], n),
    )
    n_params = sum(
        np.prod(a.shape) for a in jax.tree_util.tree_leaves(params_host)
    ) // n
    rng = np.random.default_rng(0)
    # placed over the mesh where it is made: one rank's rows per chip
    ids = jax.device_put(
        rng.integers(0, cfg["vocab"], size=(n, B, T)).astype(np.int32),
        basics.rank_major_sharding(ctx))

    lm_apply, lm_loss = make_lm_loss_fns(model)

    opt = {
        "adamw": lambda: optax.adamw(3e-4),
        "sgdm": lambda: optax.sgd(3e-4, momentum=0.9),
        # mixed-precision momentum (optax's own accumulator_dtype): the
        # f32 trace is 4.2 GB at 1B — halving it is what admits batch 8
        # on a 16 GB chip.  Opt-in: bf16 accumulation changes numerics.
        "sgdm_bf16": lambda: optax.sgd(
            3e-4, momentum=0.9, accumulator_dtype=jnp.bfloat16),
        # the idiomatic TPU big-model optimizer (T5/PaLM lineage): the
        # second moment is FACTORED (row+col accumulators, ~KB per
        # matrix instead of a param-sized f32 copy), so at 1B the
        # optimizer state is ~8 MB where AdamW needs 8.4 GB — adaptive
        # learning rates at momentum-SGD's memory cost
        "adafactor": lambda: optax.adafactor(3e-4),
    }[cfg.get("optimizer", "adamw")]()

    def timed(comm, plan, passes=1):
        init_fn, step_fn = make_decentralized_train_step(
            lm_apply, opt, ctx.mesh,
            communication_type=comm, plan=plan, loss_fn=lm_loss,
        )
        p = jax.device_put(params_host, basics.rank_major_sharding(ctx))
        opt_state = init_fn(p)
        loss = None
        for _ in range(args.warmup):
            p, _, opt_state, loss, _ = step_fn(p, {}, opt_state, ids, ids)
        _sync(loss)

        def region(k):
            nonlocal p, opt_state, loss
            t0 = time.perf_counter()
            for _ in range(k):
                p, _, opt_state, loss, _ = step_fn(p, {}, opt_state, ids, ids)
            _sync(loss)
            return time.perf_counter() - t0

        # shared paired-slope estimator (bench.paired_slope — rationale
        # there): cancels the constant per-region cost, fetch RTT AND
        # pipeline fill, where the previous (T - rt)/iters left the fill
        # share in (~5% at 134M's ~20 ms steps with iters=10)
        nonlocal fallbacks
        ts = []
        for _ in range(passes):
            t, fb = paired_slope(region, args.iters, "llama",
                                 lambda: measure_rtt(loss))
            fallbacks += int(fb)
            ts.append(t)
        return ts

    fallbacks = 0
    dec_times = timed(CommunicationType.neighbor_allreduce, ctx.plan,
                      passes=args.passes)
    t_dec = robust_min(dec_times, "llama-dec")
    if n == 1 and cfg.get("remat"):
        # single-chip 1B: the exp2 plan has no edges so both phases run the
        # same program — skip the redundant (and memory-hungry) recompile
        t_ar = t_dec
    else:
        t_ar = min(timed(CommunicationType.allreduce, None))

    toks = B * T / t_dec
    # MFU convention (PaLM et al.): 6N flops/token fwd+bwd, NOT counting
    # remat recompute (that would be HFU); vs the v5e's 197 TFLOP/s bf16
    # peak (measured 188-207 by dispatch-amortized slope, benchmarks/
    # peaks.py — round 2's "99" was dispatch-contaminated).  Attention
    # flops excluded (standard approximation), so this slightly
    # understates true utilization.
    flops_per_tok = 6 * float(n_params)
    # attention-inclusive utilization: causal QK+PV fwd+bwd add
    # 6·L·T·d_model flops/token (2·T²·d per matmul pair, halved causal,
    # ×3 for fwd+bwd) — negligible at S=2048 but the dominant term at
    # long context, where the 6N lens badly understates real work
    attn_per_tok = 6.0 * cfg["layers"] * T * cfg["hidden"]
    out = {
        "metric": f"Llama-{args.preset} ({n_params/1e6:.0f}M) tokens/sec/chip "
                  f"(neighbor_allreduce exp2, S={T})",
        "value": round(toks, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(t_ar / t_dec, 4),
        "mfu_vs_197tf_bf16": round(toks * flops_per_tok / 197e12, 3),
        "mfu_attn_incl": round(
            toks * (flops_per_tok + attn_per_tok) / 197e12, 3),
        # paired_slope's contract: surface when a phase fell back to the
        # RTT-subtracted estimator (0 = every figure is slope-timed)
        "estimator": "paired-slope",
        "estimator_fallbacks": fallbacks,
        # per-headline uncertainty in the contract (r4 verdict #7)
        "range": throughput_range(dec_times, B * T),
        "n_runs": len(dec_times),
        "attn_impl": attn_ran,
        "platform": jax.devices()[0].platform,
    }
    stats = getattr(jax.local_devices()[0], "memory_stats", lambda: None)()
    if stats and stats.get("peak_bytes_in_use"):
        out["peak_hbm_gb"] = round(stats["peak_bytes_in_use"] / 2**30, 2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

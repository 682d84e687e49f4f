"""Counted roofline for the flash-attention forward (r4 verdict #5).

The r4 claim "the remaining 134M attention gap is the D=64 MXU-lane
penalty plus irreducible softmax VPU work" was directional arithmetic.
This makes it a MODEL: measure the per-component rates on THIS chip —
the two MXU matmuls at the kernel's exact shapes ([Bq,D]x[D,Bk] scores,
[Bq,Bk]x[Bk,D] PV) and the VPU online-softmax chain at tile size
(max, subtract, exp2, sum, alpha rescale — the ops `_fwd_kernel._body`
executes) — then predict the per-layer forward time as

    tiles x (serial | overlapped) component times,

where ``serial`` (sum of components — Mosaic issues them in order but
the MXU/VPU can overlap across iterations) is the upper bound and
``overlapped`` (max of MXU and VPU totals) the lower.  Compare against
the MEASURED kernel forward (same interleaved session) and print the
unexplained gap — the number that decides whether more kernel work can
pay (>=10% unexplained => there is headroom somewhere; less => the wall
is component throughput, stop).

Components are timed with an in-kernel fused-loop slope at a fixed
(2048, 16384)-rep pair — 35-80 ms deltas for the us-scale bodies, well
above post-warmup pairing jitter but not above a full host stall, so
the rounds run through ``bench.conservative_delta`` (stall-guarded,
fails loudly rather than reporting a clamped near-zero component); the
measured forward chains the kernel inside one jitted scan so
per-dispatch cost amortizes.

Run (TPU): python benchmarks/attention_roofline.py
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

from bench import measure_rtt, paired_slope, use_compile_cache
from bluefog_tpu.kernels import flash_attention
from bluefog_tpu.ops import device_sync

SHAPES = {
    # the shipped bench configs (llama.py presets); blocks = the r4-tuned
    # 1024^2 (clipped to T)
    "134m": dict(B=8, H=12, T=2048, D=64, block=1024),
    "1b": dict(B=8, H=14, T=2048, D=128, block=1024),
}


def _tile_counts(T, block):
    """(interior, diagonal) tile counts per (batch, head) for the aligned
    causal grid: nq = nk = T/block; interior = tiles strictly below the
    diagonal, diagonal = nq."""
    nq = T // block
    return nq * (nq - 1) // 2, nq


def _pallas_component(make_kernel, inputs, out_shape,
                      reps_pair=(2048, 16384)):
    """Per-repetition seconds of a component looped IN-KERNEL
    (``lax.fori_loop`` inside one Pallas program over VMEM-resident
    operands) — the only honest way to time a tile component: a
    standalone XLA op round-trips its [Bq,Bk] f32 result through HBM
    (measured ~5 us/tile of pure bandwidth), which is exactly the
    traffic the flash kernel exists to avoid.  The loop body carries a
    data dependency on the accumulator so Mosaic cannot hoist the
    invariant compute.  Two rep counts, slope cancels dispatch + RTT;
    sync is a SCALAR FETCH (``device_sync``): where this was written
    ``block_until_ready`` did not actually block (40960 queued matmuls
    "completed" in 0.05 ms); ``chip_smoke.py`` times both today."""
    import time as _t

    from jax.experimental import pallas as pl

    def make(reps):
        return jax.jit(pl.pallas_call(
            make_kernel(reps), out_shape=out_shape))

    from bench import conservative_delta

    r1, r2 = reps_pair
    f1, f2 = make(r1), make(r2)
    device_sync(f1(*inputs))
    device_sync(f2(*inputs))
    t_smalls, t_bigs = [], []
    for _ in range(3):
        t0 = _t.perf_counter()
        device_sync(f1(*inputs))
        t1 = _t.perf_counter()
        device_sync(f2(*inputs))
        t2 = _t.perf_counter()
        t_smalls.append(t1 - t0)
        t_bigs.append(t2 - t1)
    delta = conservative_delta(t_smalls, t_bigs)
    if delta is None:
        # a silently-clamped near-zero component would collapse the
        # predicted bounds and flip the go/no-go verdict — fail loudly
        print("attention_roofline: component slope non-positive in all "
              "rounds — host too noisy, rerun", file=sys.stderr)
        return float("nan")
    return delta / (r2 - r1)


def component_times(Bq, Bk, D, dtype=jnp.bfloat16):
    """VMEM-resident per-tile component times via Pallas microkernels:

    - ``qk``: the scores matmul [Bq,D]x[D,Bk] -> f32 (the D<128
      contraction-lane penalty shows up as its effective rate);
    - ``pv``: [Bq,Bk]bf16 x [Bk,D] -> f32 (output-lane penalty);
    - ``vpu``: the online-softmax chain exactly as ``_fwd_kernel._body``
      runs it — row max, subtract, exp2, row sum, cast to bf16.

    Each body adds a small dependency pass (feeding a slice of the
    accumulator back into an operand) so the loop cannot be hoisted;
    that pass rides in the reading (conservative, <5%)."""
    from jax import lax

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (Bq, D), dtype)
    k = jax.random.normal(key, (D, Bk), dtype)
    p16 = jax.random.normal(key, (Bq, Bk), dtype)
    v = jax.random.normal(key, (Bk, D), dtype)
    s0 = jax.random.normal(key, (Bq, Bk), jnp.float32) * 0.1

    def qk_make(reps):
        def kernel(q_ref, k_ref, o_ref):
            def body(i, acc):
                qi = q_ref[...] + acc[0:1, 0:D].astype(dtype)
                s = jax.lax.dot_general(
                    qi, k_ref[...], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return acc * 0.5 + s

            o_ref[...] = lax.fori_loop(
                0, reps, body, jnp.zeros((Bq, Bk), jnp.float32))

        return kernel

    def pv_make(reps):
        def kernel(p_ref, v_ref, o_ref):
            def body(i, acc):
                # dep via the V operand: [1,D] -> [Bk,D] is a sublane-only
                # broadcast (Mosaic rejects [1,1] -> both dims)
                vi = v_ref[...] + acc[0:1, :].astype(dtype)
                return acc * 0.5 + jax.lax.dot_general(
                    p_ref[...], vi, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            o_ref[...] = lax.fori_loop(
                0, reps, body, jnp.zeros((Bq, D), jnp.float32))

        return kernel

    def vpu_make_rows(rows):
        def vpu_make(reps):
            def kernel(s_ref, o_ref):
                def body(i, acc):
                    s = s_ref[...] + acc[0:1, :]  # sublane-only broadcast
                    m = jnp.max(s, axis=-1, keepdims=True)
                    p = jnp.exp2(s - m)
                    l = jnp.sum(p, axis=-1, keepdims=True)
                    return (acc * 0.5
                            + p.astype(jnp.bfloat16).astype(jnp.float32)
                            + (m + l))

                o_ref[...] = lax.fori_loop(
                    0, reps, body, jnp.zeros((rows, Bk), jnp.float32))

            return kernel

        return vpu_make

    f32 = jnp.float32
    qk = _pallas_component(qk_make, (q, k),
                           jax.ShapeDtypeStruct((Bq, Bk), f32))
    pv = _pallas_component(pv_make, (p16, v),
                           jax.ShapeDtypeStruct((Bq, D), f32))
    vpu = _rows_scaled_vpu(vpu_make_rows, (s0,), Bq, Bk)
    return dict(qk=qk, pv=pv, vpu=vpu)


def _rows_scaled_vpu(make_rows, inputs, Bq, Bk):
    """Measure a [rows, Bk] VPU chain at rows = min(Bq, 512) and scale to
    Bq rows — elementwise/row-reduce cost is per-element, and the full
    tile plus the harness accumulator overflows the 16 MB VMEM scope
    (shared by the fwd and bwd chain harnesses)."""
    rows = min(Bq, 512)
    half = _pallas_component(
        make_rows(rows), tuple(x[:rows] for x in inputs),
        jax.ShapeDtypeStruct((rows, Bk), jnp.float32))
    return half * (Bq / rows)


def bwd_component_times(Bq, Bk):
    """Backward-kernel per-tile VPU chains (``_bwd_dkv_kernel`` /
    ``_bwd_dq_kernel``): p = exp2(s - lse); ds = p*(dp + corr); then the
    dkv kernel casts BOTH p (for dv) and ds to bf16 while the dq kernel
    casts only ds (its p is consumed in f32) — so the two kernels get
    separately-measured chains.  The matmul classes reduce to the two
    the forward already measured (contraction-D and contraction-Bq).
    Returns ``(vpu_dkv, vpu_dq)`` seconds/tile."""
    from jax import lax

    key = jax.random.PRNGKey(0)
    s0 = jax.random.normal(key, (Bq, Bk), jnp.float32) * 0.1
    dp0 = jax.random.normal(key, (Bq, Bk), jnp.float32) * 0.1

    def make_rows(rows, cast_p):
        def vpu_make(reps):
            def kernel(s_ref, dp_ref, o_ref):
                def body(i, acc):
                    s = s_ref[...] + acc[0:1, :]  # sublane-only broadcast
                    p = jnp.exp2(s - 1.7)  # lse rides as a row const
                    ds = p * (dp_ref[...] + 0.3)
                    out = acc * 0.5 + ds.astype(jnp.bfloat16).astype(
                        jnp.float32)
                    if cast_p:
                        out = out + p.astype(jnp.bfloat16).astype(
                            jnp.float32)
                    else:
                        out = out + p
                    return out

                o_ref[...] = lax.fori_loop(
                    0, reps, body, jnp.zeros((rows, Bk), jnp.float32))

            return kernel

        return vpu_make

    vpu_dkv = _rows_scaled_vpu(lambda r: make_rows(r, True), (s0, dp0),
                               Bq, Bk)
    vpu_dq = _rows_scaled_vpu(lambda r: make_rows(r, False), (s0, dp0),
                              Bq, Bk)
    return vpu_dkv, vpu_dq


def measured_grad(cfg, iters=10, chain=48):
    """fwd + full backward (dq + dkv kernels + the corr pass) per call,
    chained inside one jitted scan like ``measured_forward``."""
    import time as _t

    from jax import lax

    B, H, T, D, blk = (cfg["B"], cfg["H"], cfg["T"], cfg["D"], cfg["block"])
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    blk = min(blk, T)

    def loss(qq, kk, vv):
        o = flash_attention(qq, kk, vv, causal=True, block_q=blk,
                            block_k=blk)
        return jnp.sum(o.astype(jnp.float32) * 1e-3), o

    @jax.jit
    def chained(q):
        def body(carry, _):
            # all three cotangents kept live — grad w.r.t. q alone would
            # let jit DCE the dkv kernel out of the custom-vjp bwd
            (_, o), (dq, dk, dv) = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(carry, k, v)
            nxt = (0.5 * o + dq + 0.1 * dk + 0.1 * dv).astype(jnp.bfloat16)
            return nxt, ()

        out, _ = lax.scan(body, q, None, length=chain)
        return out

    out = chained(q)
    device_sync(out)

    def region(n):
        t0 = _t.perf_counter()
        o = q
        for _ in range(n):
            o = chained(o)
        device_sync(o)
        return _t.perf_counter() - t0

    t, fb = paired_slope(region, iters, "roofline-grad",
                         lambda: measure_rtt(out))
    return t / chain, fb


def measured_forward(cfg, iters=10, chain=64):
    """The real kernel's fwd time, slope-timed this session.

    ``chain`` attention calls run inside ONE jitted ``lax.scan`` so the
    per-dispatch cost (~3.5 ms when written) amortizes to <6% of a call (the
    attention_fwd_ab protocol; an eager per-call region measured 8.3 ms
    for a ~0.9 ms kernel — 8x dispatch bias)."""
    import time as _t

    from jax import lax

    B, H, T, D, blk = (cfg["B"], cfg["H"], cfg["T"], cfg["D"], cfg["block"])
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, T, H, D), jnp.bfloat16)
    blk = min(blk, T)

    @jax.jit
    def chained(q):
        def body(carry, _):
            o = flash_attention(carry, k, v, causal=True, block_q=blk,
                                block_k=blk)
            return o.astype(jnp.bfloat16), ()

        out, _ = lax.scan(body, q, None, length=chain)
        return out

    out = chained(q)
    device_sync(out)

    def region(n):
        t0 = _t.perf_counter()
        o = q
        for _ in range(n):
            o = chained(o)
        device_sync(o)
        return _t.perf_counter() - t0

    t, fb = paired_slope(region, iters, "roofline-fwd",
                         lambda: measure_rtt(out))
    return t / chain, fb


def _band_gap(meas, overlap, serial):
    """How far the measurement sits OUTSIDE the [overlap, serial] band
    (0 if inside)."""
    if meas > serial:
        return (meas - serial) / serial
    if meas < overlap:
        return (meas - overlap) / overlap
    return 0.0


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=["134m", "1b"],
                    choices=sorted(SHAPES))
    ap.add_argument("--bwd", action="store_true",
                    help="also model + measure the BACKWARD kernels (dkv: "
                    "2 contraction-D + 2 contraction-Bq matmuls + chain; "
                    "dq: 2 + 1 + chain); measured via a grad-chained scan "
                    "minus the forward")
    args = ap.parse_args()
    if args.bwd and os.environ.get("BLUEFOG_FLASH_BWD_BLOCKS"):
        # the knob overrides the BACKWARD kernels' blocks only
        # (flash_attention._BWD_BLOCKS): measured_grad would run at the
        # overridden tiling while the model counts tiles at the forward
        # blocks — the comparison would be silently meaningless
        sys.exit("attention_roofline --bwd refuses to run with "
                 "BLUEFOG_FLASH_BWD_BLOCKS set: the model counts tiles at "
                 "the forward blocks, the measurement would use the "
                 "override")
    rows = []
    for name in args.shapes:
        cfg = SHAPES[name]
        B, H, T, D = cfg["B"], cfg["H"], cfg["T"], cfg["D"]
        blk = min(cfg["block"], T)
        comp = component_times(blk, blk, D)
        if any(np.isnan(v) for v in comp.values()):
            rows.append({"shape": name, "invalid": True,
                         "reason": "component slope non-positive (host "
                                   "stall in every round) — rerun"})
            continue
        interior, diag = _tile_counts(T, blk)
        per_bh = interior + diag  # diagonal tiles do the same dominant work
        tiles = B * H * per_bh
        mxu = comp["qk"] + comp["pv"]
        vpu = comp["vpu"]
        serial = tiles * (mxu + vpu)
        overlap = tiles * max(mxu, vpu)
        meas, fb = measured_forward(cfg)
        row = {
            "shape": name,
            "tiles": tiles,
            "qk_us": round(comp["qk"] * 1e6, 2),
            "pv_us": round(comp["pv"] * 1e6, 2),
            "vpu_us": round(comp["vpu"] * 1e6, 2),
            "pred_overlap_ms": round(overlap * 1e3, 3),
            "pred_serial_ms": round(serial * 1e3, 3),
            "measured_ms": round(meas * 1e3, 3),
            "unexplained_pct": round(_band_gap(meas, overlap, serial) * 100,
                                     1),
            "estimator_fallbacks": int(fb),
        }
        if args.bwd:
            vpu_dkv, vpu_dq = bwd_component_times(blk, blk)
            if np.isnan(vpu_dkv) or np.isnan(vpu_dq):
                row["bwd_invalid"] = True
            else:
                # per tile: dkv = 2 contraction-D (s, dp) + 2
                # contraction-Bq (dv, dk) matmuls; dq = 2 + 1; each
                # kernel with its OWN chain (dkv casts p AND ds, dq
                # only ds)
                dkv_mxu = 2 * comp["qk"] + 2 * comp["pv"]
                dq_mxu = 2 * comp["qk"] + comp["pv"]
                bwd_serial = tiles * (dkv_mxu + vpu_dkv + dq_mxu + vpu_dq)
                bwd_overlap = tiles * (max(dkv_mxu, vpu_dkv)
                                       + max(dq_mxu, vpu_dq))
                grad_meas, gfb = measured_grad(cfg)
                bwd_meas = grad_meas - meas
                row.update({
                    "bwd_vpu_dkv_us": round(vpu_dkv * 1e6, 2),
                    "bwd_vpu_dq_us": round(vpu_dq * 1e6, 2),
                    "bwd_pred_overlap_ms": round(bwd_overlap * 1e3, 3),
                    "bwd_pred_serial_ms": round(bwd_serial * 1e3, 3),
                    "grad_measured_ms": round(grad_meas * 1e3, 3),
                    "bwd_measured_ms": round(bwd_meas * 1e3, 3),
                    "bwd_unexplained_pct": round(
                        _band_gap(bwd_meas, bwd_overlap, bwd_serial) * 100,
                        1),
                    "bwd_estimator_fallbacks": int(gfb),
                    # bwd_measured carries harness work the band does not
                    # model: the corr pass (sum(do*o) over D), the loss
                    # reduction, and the grad-chain's 4-tensor combine —
                    # ~0.2-0.4 ms of HBM-bound time at the 134M shape, so
                    # the comparison is biased HIGH on the measured side
                    # (conservative for a "no unexplained overhead" read)
                    "bwd_measured_includes_harness": True,
                })
        rows.append(row)
    print(json.dumps({
        "metric": "flash counted roofline (component rates x tile "
                  "counts vs measured, same session)",
        "rows": rows,
        "reading": ("measured inside [overlap, serial] band = the time "
                    "is accounted for by component throughput (no "
                    "recoverable scheduling headroom); measured above "
                    "serial = unexplained overhead worth hunting; below "
                    "overlap = the model under-counts"),
    }))


if __name__ == "__main__":
    main()

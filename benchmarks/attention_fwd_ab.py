"""Forward-only A/B: Pallas flash kernel vs the XLA blockwise forward.

Settles (and re-pins, whenever the kernel changes) the question the
flash_attention.py header history tracks: which forward is faster
*forward-only*, independent of the backward-schedule effects that decide
the end-to-end default.

Protocol: the N forward calls are chained inside ONE jitted `lax.scan`
(each iteration's q depends on the previous output, so XLA can neither
hoist nor dedupe them), and each timed region runs a GROUP of those
dispatches back-to-back with one sync at the end (bench.py's
dispatch-amortizing shape).  The two impls' repeats are INTERLEAVED
(p,x,p,x,...) so a session-window throughput shift lands on both sides
of the ratio — the drift mode that invalidates sequential sweeps (see
the r4 STATUS protocol note).  min over repeats per impl.

NO RTT subtraction — deliberately, unlike the sibling benchmarks, and
the measured reason is written down because two plausible protocols
failed first: (1) sync-per-dispatch timing + one subtracted RTT
under-amortizes (each fresh dispatch after a sync pays its own
round-trip: +12.8 ms/call observed in a 255 ms RTT window); (2)
subtracting a measured RTT from the grouped region OVER-corrects,
because dispatch is async (1-2 ms for a whole group) and the sync's
round-trip OVERLAPS the device compute it waits on — a diagnostic with
per-round raw totals read pallas~341-351 / xla~466-474 ms for 60 calls,
stable across rt samples of 207-259 ms, i.e. the region is pure device
time + a small exposed tail; subtracting rt produced an impossible
4.3 ms/call XLA reading (faster than its fast-window floor).  Final
protocol: the SLOPE estimator (as in benchmarks/peaks.py) — each impl's
region timed at `group` and `2*group` dispatches, per-call =
(T_big - T_small)/(group*chain), so whatever constant per-region cost
exists (exposed sync tail, dispatch setup, fetch) cancels exactly
rather than being estimated; the session RTT range rides in the JSON as
context.

History:
- r3 (512^2 blocks, pre-aligned-path): XLA blockwise won forward-only by
  ~25-35% — recorded in the kernel header as the largest known
  recoverable perf item (r3 verdict weak #2).
- r4 continuation (1024^2 blocks + aligned fast path + packed scalar
  tiles, this script): the gap is not just closed but REVERSED — with
  the slope estimator, Pallas is 4.8-6.2x faster at B4/H12/T2048/D64
  (134M dims: 0.51-0.58 ms/call, 44-50 TF/s), 4.29-4.52x at
  B4/H16/T2048/D128 (1B dims: 0.84-0.88 ms, 78-82 TF/s), 4.07-4.11x at
  B2/H12/T8192/D64 (long context: 3.88-3.90 ms, 53 TF/s).  Single-region
  variants of this protocol read the ratio compressed to 1.3-3x —
  ~60-350 ms of constant per-region host overhead (NOT device time)
  sat on both sides of the division until the slope cancelled it.  The
  headroom the verdict flagged was recovered by the r4 kernel work;
  `impl="auto"` = Pallas is the right default on BOTH the forward-only
  and end-to-end lenses.

No reference sibling (the reference has no attention code, SURVEY.md
SS2.3); this guards the rebuild's hot-op default.
"""
import argparse
import json
import os
import sys
import time

import jax

import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _sync, conservative_delta, measure_rtt, use_compile_cache
from bluefog_tpu.kernels.flash_attention import flash_attention


def make_run(impl, q0, k0, v0, n_chain):
    """Compile the n_chain-scan program for one impl and warm it."""

    @jax.jit
    def run(q, k, v):
        def body(carry, _):
            o = flash_attention(carry, k, v, causal=True, impl=impl)
            # dependency chain: next q depends on this o, so the scan
            # body cannot be hoisted or deduped
            return (q0 + 0.001 * o).astype(q0.dtype), None

        out, _ = lax.scan(body, q, None, length=n_chain)
        return out

    _sync(run(q0, k0, v0))
    return run


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--chain", type=int, default=20,
                    help="forward calls chained per dispatch")
    ap.add_argument("--group", type=int, default=3,
                    help="back-to-back dispatches per timed region, one "
                         "sync at the end (bench.py-style dispatch "
                         "amortization; see module docstring for why no "
                         "RTT is subtracted)")
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()
    b, h, t, d = args.batch, args.heads, args.seq, args.head_dim

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q0 = jax.random.normal(kq, (b, t, h, d), jnp.bfloat16)
    k0 = jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
    v0 = jax.random.normal(kv, (b, t, h, d), jnp.bfloat16)

    runs = {impl: make_run(impl, q0, k0, v0, args.chain)
            for impl in ("pallas", "xla")}
    def region(run, n_disp):
        t0 = time.perf_counter()
        out = None
        for _ in range(n_disp):
            out = run(q0, k0, v0)
        _sync(out)
        return time.perf_counter() - t0

    # Slope protocol (benchmarks/peaks.py's dispatch-amortized timing):
    # per repeat, time each impl's region at `group` and `2*group`
    # dispatches BACK-TO-BACK and keep the PAIRED delta, so the
    # constant per-region cost — the sync tail however much of it is
    # exposed, dispatch setup, fetch — cancels within the same session
    # window it occurred in (mins taken independently across repeats
    # could pair a fast-window small region with a slow-window big one
    # and inflate or negate the slope — review finding).  per-call =
    # bench.conservative_delta(smalls, bigs)/(group*chain).  Repeats
    # stay impl-interleaved; rt is sampled per round purely as context.
    smalls = {impl: [] for impl in runs}
    big = {impl: [] for impl in runs}
    rts = []
    for _ in range(args.repeats):
        rts.append(measure_rtt(q0, n=2))
        for impl, run in runs.items():
            smalls[impl].append(region(run, args.group))
            big[impl].append(region(run, 2 * args.group))
    n_delta = args.chain * args.group
    per_call = {}
    fallbacks = []
    for impl in runs:
        # THE shared two-statistic rule (bench.conservative_delta; its
        # docstring records why an inline re-implementation here had
        # already drifted once — r4 advisor finding)
        delta = conservative_delta(smalls[impl], big[impl])
        if delta is None:
            # noise exceeded the compute delta in every round —
            # conservative fallback, flagged in the JSON so a consumer
            # of the one-line contract sees the estimators differ
            print(
                f"fwd_ab:{impl}: all paired slopes non-positive — raise "
                "--chain/--group; falling back to the MIN big region "
                "(carries the constant per-region overhead the slope "
                "would have cancelled)",
                file=sys.stderr,
            )
            fallbacks.append(impl)
            per_call[impl] = min(big[impl]) / (2 * n_delta)
        else:
            per_call[impl] = delta / n_delta
    tp, tx = per_call["pallas"], per_call["xla"]
    flops = 2 * 2 * b * h * t * t * d * 0.5  # qk+pv matmuls, causal half
    print(json.dumps({
        "metric": f"flash fwd-only Pallas-vs-XLA speedup "
                  f"(B{b} H{h} T{t} D{d}, {args.chain}-chain scan, "
                  f"interleaved x{args.repeats})",
        "value": round(tx / tp, 3),
        "unit": "x (xla_time/pallas_time, >1 = Pallas faster)",
        "vs_baseline": round(tx / tp, 3),
        "pallas_ms": round(tp * 1e3, 3),
        "xla_ms": round(tx * 1e3, 3),
        "pallas_tf_s": round(flops / tp / 1e12, 1),
        "session_rtt_ms": round(min(rts) * 1e3, 2),
        "session_rtt_max_ms": round(max(rts) * 1e3, 2),
        # impls whose slope collapsed to the overhead-carrying fallback
        # estimator (ratio not slope-vs-slope when non-empty)
        "fallback": fallbacks,
    }))


if __name__ == "__main__":
    main()

"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

One process, the entry points a user calls (``bf.init`` → topology/plan →
eager gossip and window ops → ``make_decentralized_train_step``), full
widths, random weights from ``--seed``.  Every phase prints one JSON line;
the LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and the exit code is 0 only if JAX found a TPU and every phase passed.  A
phase that fails raises: the traceback goes to stderr, the last line says
``"ok": false`` and the exit code is 1.

    python chip_smoke.py             one chip: ops/windows parity, ResNet-50
                                     b128 224² ATC + allreduce steps, a
                                     decoder step with the compiled Pallas
                                     flash kernel vs dense attention, the
                                     dropless expert layer under a pile-up
                                     vs the benchmark's plain reference, the
                                     flash kernels reading 8 shared key-value
                                     heads for 64 query heads vs the heads
                                     repeated, the backward kernels' cut tiles
                                     walked in sub-tiles vs computed whole, the
                                     chunked scan's kernels vs the recurrence
                                     taken token by token and the flash
                                     kernels at heads of 64 vs dense attention,
                                     the gated delta rule's kernels vs the
                                     recurrence at a decay near 0 and at -30 a
                                     token and the flash kernels at heads of
                                     256 vs dense attention,
                                     the causal convolution's kernels and the
                                     gated short convolution's vs the
                                     references' expressions in float32, a
                                     latent-attention mixer with no gate and
                                     interleaved rotary pairs vs its dense
                                     float32 oracle
    python chip_smoke.py --chips 4   four chips, only what exists across
                                     chips: parity on exp2(4), placement,
                                     ResNet-50 ATC vs allreduce, contraction,
                                     the optimizer's part with bucketed
                                     gossip against per-leaf gossip
    python chip_smoke.py --chips 4 --only buckets_vs_per_leaf
                                     that phase alone
    python chip_smoke.py --rehearse  control-flow rehearsal at tiny sizes on
                                     whatever backend there is; never "ok"

Times printed here are observations of a smoke run, not performance.
"""

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu import models, native, ops_spmd, optim, topology_util
from bluefog_tpu.core import basics
from bluefog_tpu.kernels import make_flash_attention_fn
from bluefog_tpu.kernels.flash_attention import _default_interpret
from bluefog_tpu.models.transformer import LlamaLM
from bluefog_tpu.optim import CommunicationType
from bluefog_tpu.training import (
    make_decentralized_train_step,
    make_lm_loss_fns,
    replicate_for_mesh,
)
from chipbench.compile_cache import use_compile_cache


# ---------------------------------------------------------------------------
# sizes: what a user runs (FULL) and what the CPU can rehearse (TINY)
# ---------------------------------------------------------------------------

FULL = dict(
    gossip_elems=1 << 20,
    resnet=dict(model="ResNet50", classes=1000, img=224, batch=128),
    # a small decoder: hidden 768, 12 heads of 64
    decoder=dict(vocab=32000, hidden=768, layers=12, heads=12, dff=2048,
                 seq=2048, batch=8, head_chunks=8, logits_rows=2),
    # one layer of chipbench's `smallthinker-21b-a3b` at its cell's 2 x 8192
    # tokens; rows None: the pass the program chooses (16,384 sorted rows)
    experts=dict(tokens=16384, hidden=2560, dff=768, experts=64, top_k=6,
                 held=8, rows=None),
    # a window layer of chipbench's `laguna-xs.2` at its cell's 8192 tokens,
    # and the same heads over the whole sequence; None: the kernels' own blocks
    shared_heads=dict(seq=8192, heads=64, kv_heads=8, head_dim=128, window=512,
                      block=None),
    # the three attention shapes of the decoder cells (heads on key-value
    # heads, window, block; None: the kernels' own) and the edges swept
    subtiles=dict(seq=8192, head_dim=128, edges=(128, 256, 512), calls=20, shapes=(
        ("smallthinker_window", 2, 28, 4, 4096, None),
        ("laguna_window", 1, 64, 8, 512, None),
        ("laguna_global", 1, 48, 8, None, None),
        # as the SmallThinker cell runs them: `_MixedBlock` repeats its heads
        ("smallthinker_window_repeated", 2, 28, 28, 4096, None),
        ("smallthinker_global_repeated", 2, 28, 28, None, None))),
    # a state-space layer's scan and the attention layer of chipbench's
    # `granite-4.0-h-micro` at its cell's 8192 tokens
    ssd=dict(seq=8192, heads=64, head_dim=64, state=128, chunks=(128, 256),
             calls=20, att_heads=32, att_kv_heads=8, att_head_dim=64),
    # a state-space layer's convolution of that cell: the 8,512-wide product
    # of its input projection, 4,096 channels of x and 128 each of B and C
    conv=dict(seq=8192, inner=4096, states=128, heads=64, width=4, calls=20),
    # a conv layer's gated short convolution of the `lfm2-24b-a2b` cell: [B, C, x]
    # the 6,144 channels of the input projection's product
    short_conv=dict(seq=8192, channels=2048, width=3, calls=20),
    # one linear layer's delta rule and one latent-attention layer's kernel
    # call of the `ling-3.0-flash-vl` cell: 1 x 8192 x 32 heads of 128, chunks
    # of 64; 128 + 64 rotary query-key channels beside values of 128
    kda=dict(seq=8192, heads=32, head_dim=128, chunk=64, lower=-5.0, calls=10),
    # one linear layer's gated delta rule of the `qwen3-next-80b-a3b` cell: 1 x
    # 8192, 32 value heads on 16 key heads of 128, chunks of 64, the log-decay
    # near 0 and down to -30 a token; and its attention layer's kernel call: 16
    # query heads on 2 key-value heads of 256
    gdn=dict(seq=8192, heads=32, key_heads=16, head_dim=128, chunk=64,
             decays=(-0.1, -30.0), calls=10, att_heads=16, att_kv_heads=2,
             att_head_dim=256, block=None),
    mla=dict(seq=8192, heads=32, nope=128, rope=64, v_dim=128, calls=10),
    # a layer's whole mixer of the `kanana-2-30b-a3b` cell: hidden 2048, a
    # latent of 512, no head gate, the rotary over interleaved pairs
    mla_mixer=dict(seq=8192, hidden=2048, heads=32, rank=512, nope=128, rope=64,
                   v_dim=128, theta=1e6, calls=10),
    probe=dict(dim=4096, iters=512),
)
TINY = dict(
    gossip_elems=256,
    resnet=dict(model="ResNet18", classes=10, img=16, batch=2),
    decoder=dict(vocab=256, hidden=64, layers=2, heads=4, dff=128,
                 seq=128, batch=2, head_chunks=2, logits_rows=1),
    experts=dict(tokens=96, hidden=128, dff=8, experts=64, top_k=6, held=8,
                 rows=64),
    shared_heads=dict(seq=128, heads=6, kv_heads=2, head_dim=16, window=40,
                      block=16),
    subtiles=dict(seq=128, head_dim=16, edges=(8, 16), calls=2, shapes=(
        ("window", 2, 4, 2, 40, 32), ("global", 1, 4, 2, None, 32))),
    ssd=dict(seq=64, heads=4, head_dim=16, state=32, chunks=(8, 16), calls=2,
             att_heads=4, att_kv_heads=2, att_head_dim=16),
    conv=dict(seq=64, inner=128, states=64, heads=8, width=4, calls=2),
    short_conv=dict(seq=64, channels=128, width=3, calls=2),
    kda=dict(seq=128, heads=4, head_dim=16, chunk=32, lower=-5.0, calls=2),
    gdn=dict(seq=64, heads=2, key_heads=1, head_dim=128, chunk=32,
             decays=(-0.1, -30.0), calls=2, att_heads=4, att_kv_heads=2,
             att_head_dim=32, block=16),
    mla=dict(seq=128, heads=4, nope=16, rope=8, v_dim=16, calls=2),
    mla_mixer=dict(seq=128, hidden=64, heads=4, rank=32, nope=16, rope=8, v_dim=16,
                   theta=1e6, calls=2),
)

# flash-vs-dense agreement at bf16 compute on seeded weights.  The two paths
# round the attention output in a different order (one bf16 ulp is 0.4-0.8%)
# and every later block carries that on, so single logits may part by a few
# percent of the largest logit while the whole tensor agrees to about a
# percent (first v5e run: 1.4e-2 relative L2, 1.6 % of the largest logit,
# over 12 blocks).  A wrong mask, scale or block offset moves both by O(1).
LOGITS_L2_RTOL = 3e-2
LOGITS_MAX_RTOL = 5e-2
LOSS_ATOL = 5e-3

# the expert layer (bf16 operands, float32 accumulators) against the plain
# reference (float32, `highest`) on tokens and stacks that bf16 holds exactly:
# relative L2 of the output and of each gradient.  What is left is the
# layer's own rounding of the hidden units and the cotangents: 2.3e-3 to
# 3.3e-3 on the v5e (PERF.md section 6, PR 29).  A pass that is left out, counted twice or
# scattered to the wrong tokens moves them by 0.2 or more.
EXPERTS_L2_RTOL = 1.5e-2

# the flash kernels handed 8 key-value heads for 64 query heads against the
# same kernels handed the heads repeated: the output and dQ are the same
# arithmetic on the same blocks (equal to the bit on the v5e and on the CPU);
# dK and dV are summed over the group in float32 inside the kernel and rounded
# to bfloat16 once, where the repeated call rounds each head's and XLA sums the
# eight: a few bfloat16 ulps of 0.4-0.8 % over the whole tensor, the kernels'
# standing tolerance against dense attention being 3e-2 (LOGITS_L2_RTOL).  A
# wrong head, a group left out of the sum or a block offset moves them by O(1).
SHARED_HEADS_L2_RTOL = 1e-2

# the bucketed gossip against the per-leaf gossip, the widest gap of a leaf
# over its largest value.  The two are the same sums written the same way;
# what parts them is the compiler's (a common weight factored out on the TPU,
# a multiply fused into an add on the CPU): a few roundings of 6e-8 an
# element.  Not yet read on the chip as a gap (PERF.md section 7).
BUCKETS_GAP_RTOL = 1e-6


# the static-offset backward kernels walking a cut tile in sub-tiles against the
# same kernels computing it whole and masking: every visible pair is computed
# once in both, the sums inside a tile taken in another order.  What PR 35
# read between two programs of one arithmetic on the v5e (2.3e-3 to 2.6e-3).
SUBTILES_L2_RTOL = 4e-3

# the chunked scan's kernels (bfloat16 operands, float32 accumulators, step
# sizes and state in float32) against the recurrence taken one token after
# another in float32 at `highest`, on the same bfloat16 inputs: relative L2 of
# the output and of the six gradients.  What is left is the kernels' rounding
# of the decay-weighted scores and of the carried state to bfloat16 before each
# product (2^-9 an operand); a chunk's state dropped, a decay applied twice or
# a head reading another group moves them by 0.1 or more.
SSD_L2_RTOL = 2e-2

# the convolution's kernels (`kernels/causal_conv.py`: bfloat16 in and out,
# float32 between) against the plain reference's expression and a SiLU in
# float32 on the same bfloat16 input: what is left is the one rounding of the
# output and of dx to bfloat16 (2^-9 an element); a tap off by a row or a block
# that does not see its neighbour moves them by 0.1 or more.
CONV_L2_RTOL = 2e-2

# the chunked delta rule (`kernels/kda.py`: bfloat16 q, k and v, the pairs, the
# triangle and the carried state in float32, the kernels' products on bfloat16
# operands) against the recurrence taken one token after another in float32 on
# the same bfloat16 inputs: relative L2 of the output and of the five
# gradients.  What is left is the rounding of W, of q exp G, of U and of the
# state to bfloat16 before each product; a chunk's state dropped, a decay
# applied twice or a sub-block's reference misplaced moves them by 0.1 or more.
KDA_L2_RTOL = 2e-2

# the gated delta rule (`kernels/gdn.py`: the same operands and the same
# roundings as the delta rule above, one decay a head) against the recurrence
# the same way, with one difference in what is compared: at -30 a token the
# gradient of `g` is 1e-4 of the other gradients (what a token before decays to
# is e^-30 of it) and is held against the largest of them, not against itself.
GDN_L2_RTOL = 2e-2

PHASES = ("ops_windows", "resnet_atc", "resnet_allreduce", "contraction",
          "buckets_vs_per_leaf", "decoder", "experts_piled", "kda_vs_recurrence",
          "gdn_vs_recurrence", "mla_two_head_sizes", "mla_mixer_no_gate", "shared_heads", "subtiles", "ssd",
          "conv", "short_conv")


class _CompileClock:
    """Seconds XLA spent compiling (or fetching from the persistent cache)
    since the last ``take()`` — JAX's own backend-compile duration events,
    which do not nest the way its trace and lowering events do."""

    def __init__(self):
        self._secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._secs += secs

    def take(self):
        s, self._secs = self._secs, 0.0
        return s


def _emit(phase, t0, clock, **fields):
    print(json.dumps({
        "phase": phase,
        "seconds": round(time.perf_counter() - t0, 3),
        "compile_seconds": round(clock.take(), 3),
        **fields,
    }), flush=True)


def _max_abs_diff(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _rel_l2(got, want):
    """Relative L2 of each named array of ``got`` against ``want``'s."""
    return {n: float(jnp.linalg.norm((got[n] - want[n]).astype(jnp.float32))
                     / jnp.linalg.norm(want[n].astype(jnp.float32)))
            for n in got}


def _ms_a_call(fn, args, calls):
    """Host clock over ``calls`` calls of a compiled ``fn``, ms a call."""
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(calls):
        last = fn(*args)
    jax.block_until_ready(last)
    return round((time.perf_counter() - t) / calls * 1e3, 3)


def _exp2_mixing_matrix(n):
    """W for ExponentialTwoGraph(n) written from its definition, not read
    from the code under test: rank i hears from (i - 2^j) % n, every
    in-edge and the self-loop weigh 1/(in_degree + 1)."""
    W = np.zeros((n, n))
    for i in range(n):
        srcs = {(i - (1 << j)) % n for j in range(max(n - 1, 0).bit_length())}
        srcs.discard(i)
        for s in srcs | {i}:
            W[i, s] = 1.0 / (len(srcs) + 1)
    return W


def _distinct_devices(tree):
    return min(len({s.device for s in leaf.addressable_shards})
               for leaf in jax.tree_util.tree_leaves(tree))


def _lowered_text(step_fn, *args):
    """StableHLO of a train step as the user-facing ``step_fn`` lowers it."""
    return jax.jit(step_fn).lower(*args).as_text()


# ---------------------------------------------------------------------------
# phase: eager gossip + window ops against W @ x
# ---------------------------------------------------------------------------


def phase_ops_windows(n, elems, seed, clock):
    t0 = time.perf_counter()
    W = _exp2_mixing_matrix(n)
    topo_W = topology_util.GetWeightMatrix(bf.load_topology())
    assert np.allclose(W, topo_W), f"installed topology is not exp2({n})"
    rng = np.random.default_rng(seed)
    # per-rank seeded values: a wrong edge or weight shows as a wrong row
    x, y, z, u = (rng.normal(size=(n, elems)).astype(np.float32)
                  for _ in range(4))
    diffs = {}

    diffs["neighbor_allreduce"] = _max_abs_diff(bf.neighbor_allreduce(x), W @ x)

    bf.win_create(x, "smoke")
    bf.win_put(y, "smoke")
    diffs["win_put+win_update"] = _max_abs_diff(bf.win_update("smoke"), W @ y)
    diffs["win_put_update"] = _max_abs_diff(bf.win_put_update(z, "smoke"), W @ z)
    bf.win_free("smoke")

    # one push-sum round: column-stochastic sends, the associated weight p
    # rides the same mailbox.  exp2 is regular, so the column-stochastic
    # matrix is W again and p must stay 1.
    out_nbrs = [[r for r in range(n) if r != s and W[r, s] > 0] for s in range(n)]
    in_nbrs = [[s for s in range(n) if s != r and W[r, s] > 0] for r in range(n)]
    keep = [1.0 / (len(o) + 1) for o in out_nbrs]
    bf.turn_on_win_ops_with_associated_p()
    try:
        bf.win_create(u, "smoke_ps", zero_init=True)
        bf.win_accumulate(
            u, "smoke_ps",
            dst_weights=[{d: keep[s] for d in out_nbrs[s]} for s in range(n)])
        got = bf.win_update(
            "smoke_ps", self_weight=keep,
            neighbor_weights=[{s: 1.0 for s in in_nbrs[r]} for r in range(n)],
            reset=True)
        diffs["push_sum_round"] = _max_abs_diff(got, W @ u)
        diffs["push_sum_p"] = _max_abs_diff(
            bf.win_associated_p("smoke_ps"), W @ np.ones(n))
        bf.win_free("smoke_ps")
    finally:
        bf.turn_off_win_ops_with_associated_p()

    jax.block_until_ready(got)
    worst = max(diffs.values())
    _emit("ops_windows", t0, clock, ranks=n, elems_per_rank=elems,
          compared="eager op vs NumPy W @ x, W = exp2 mixing matrix",
          max_abs_diff=diffs, tolerance=1e-5)
    assert worst <= 1e-5, f"gossip result differs from W @ x: {diffs}"


# ---------------------------------------------------------------------------
# phase: ResNet train steps through make_decentralized_train_step
# ---------------------------------------------------------------------------


def _resnet_model(cfg):
    if cfg["model"] == "ResNet50":
        return models.ResNet50(num_classes=cfg["classes"])
    # rehearsal only
    return models.ResNet18(
        num_classes=cfg["classes"], num_filters=8, small_images=True)


class _ResNetJob:
    """Seeded ResNet variables and one rank-major batch on ``ctx.mesh``."""

    def __init__(self, cfg, seed):
        self.cfg = cfg
        self.ctx = ctx = basics.context()
        n, b, img = ctx.size, cfg["batch"], cfg["img"]
        self.model = _resnet_model(cfg)
        self.variables = jax.jit(
            lambda key, x: self.model.init(key, x, train=True)
        )(jax.random.PRNGKey(seed), jnp.ones((b, img, img, 3), jnp.float32))
        rng = np.random.default_rng(seed)
        sharding = basics.rank_major_sharding(ctx)
        self.batch = jax.device_put(
            rng.normal(size=(n, b, img, img, 3)).astype(np.float32), sharding)
        self.labels = jax.device_put(
            rng.integers(0, cfg["classes"], size=(n, b)).astype(np.int32),
            sharding)

    def build(self, comm_type, plan):
        """(step_fn, params, batch_stats, opt_state), each phase its own
        copies because the step donates them."""
        n = self.ctx.size
        init_fn, step_fn = make_decentralized_train_step(
            self.model.apply, optax.sgd(0.1, momentum=0.9), self.ctx.mesh,
            communication_type=comm_type, plan=plan, has_batch_stats=True,
            donate=True)
        params = replicate_for_mesh(self.variables["params"], n)
        batch_stats = replicate_for_mesh(self.variables["batch_stats"], n)
        return step_fn, params, batch_stats, init_fn(params)


def _all_finite(tree):
    return bool(jax.jit(lambda t: jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(a)) for a in jax.tree_util.tree_leaves(t)])))(tree))


def _run_steps(step_fn, state, batch, labels, steps):
    """``steps`` train steps, each timed on the host clock around
    ``jax.block_until_ready``.  Returns (state, losses[steps][ranks], secs)."""
    losses, secs = [], []
    for _ in range(steps):
        t = time.perf_counter()
        *state, loss, _acc = step_fn(*state, batch, labels)
        jax.block_until_ready((state, loss))
        secs.append(time.perf_counter() - t)
        losses.append(np.asarray(loss, np.float64))
    assert np.isfinite(losses).all(), f"non-finite loss: {losses}"
    return state, losses, secs


def _peak_bytes(ctx):
    stats = ctx.devices[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_resnet(job, name, comm_type, plan, clock, steps=3):
    t0 = time.perf_counter()
    step_fn, params, batch_stats, opt_state = job.build(comm_type, plan)
    placed = _distinct_devices((params, batch_stats, opt_state,
                                job.batch, job.labels))
    assert placed == job.ctx.size, (
        f"a leaf of params/batch_stats/opt_state/batch sits on {placed} "
        f"device(s) before step one, not {job.ctx.size}")
    # gossip is collective-permutes, and only where there are neighbours:
    # exp2(1) has no edge, and the allreduce step never needs one
    permutes = "collective_permute" in _lowered_text(
        step_fn, params, batch_stats, opt_state, job.batch, job.labels)
    want = (job.ctx.size > 1
            and comm_type == CommunicationType.neighbor_allreduce)
    assert permutes == want, (
        f"{name}: collective-permute "
        f"{'missing from' if want else 'found in'} the lowered step")
    probe_leaf = jax.tree_util.tree_leaves(params)[-1]
    before = np.asarray(probe_leaf)
    state, losses, secs = _run_steps(
        step_fn, (params, batch_stats, opt_state), job.batch, job.labels, steps)
    after = np.asarray(jax.tree_util.tree_leaves(state[0])[-1])
    moved = _max_abs_diff(after, before)
    assert moved > 0, "parameters did not change"
    assert _all_finite(state[0]), "non-finite parameters"
    cfg = job.cfg
    _emit(name, t0, clock, model=cfg["model"], ranks=job.ctx.size,
          per_rank_batch=cfg["batch"], image=cfg["img"],
          classes=cfg["classes"],
          distinct_devices_per_leaf_before_step_one=placed,
          collective_permute_in_lowered_step=permutes,
          loss_per_step=[float(l.mean()) for l in losses],
          step_seconds=[round(s, 4) for s in secs],
          param_max_abs_change=moved,
          peak_bytes_in_use=_peak_bytes(job.ctx))


def phase_contraction(job, clock):
    """Four chips: per-rank parameters differ after a local step, one
    gossip round is W @ them, the spread shrinks by what W predicts, and
    the ATC step from the same start lands on the same point."""
    t0 = time.perf_counter()
    n = job.ctx.size
    W = _exp2_mixing_matrix(n)
    step_fn, *state = job.build(CommunicationType.empty, None)
    (local, _, _), _, _ = _run_steps(step_fn, tuple(state), job.batch,
                                     job.labels, 1)
    local_np = [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(local)]
    mixed_np = [np.asarray(a, np.float64) for a in
                jax.tree_util.tree_leaves(bf.neighbor_allreduce(local))]

    def spread(leaves):
        return float(np.sqrt(sum(
            np.sum((a - a.mean(axis=0, keepdims=True)) ** 2) for a in leaves)))

    s_local, s_mixed = spread(local_np), spread(mixed_np)
    assert s_local > 0, "ranks hold identical parameters after a local step"
    # W's action off the consensus direction: its largest singular value
    # there bounds the contraction; for exp2(4) every such mode has |λ| = 1/3
    sv = np.linalg.svd(W - np.full((n, n), 1.0 / n), compute_uv=False)
    predicted = float(sv[0])
    ratio = s_mixed / s_local
    gossip_diff = max(
        _max_abs_diff(m, np.tensordot(W, a, axes=1))
        for m, a in zip(mixed_np, local_np))

    atc_step, *atc_state = job.build(
        CommunicationType.neighbor_allreduce, job.ctx.plan)
    (atc, _, _), _, _ = _run_steps(atc_step, tuple(atc_state), job.batch,
                                   job.labels, 1)
    atc_diff = max(
        _max_abs_diff(a, m) for a, m in
        zip(jax.tree_util.tree_leaves(atc), mixed_np))
    scale = max(float(np.max(np.abs(a))) for a in local_np)
    _emit("contraction", t0, clock, ranks=n,
          compared="local step then gossip vs NumPy W @ params; ATC step vs both",
          spread_after_local_step=s_local, spread_after_gossip=s_mixed,
          contraction=ratio, predicted_by_W=predicted,
          gossip_vs_W_max_abs_diff=gossip_diff,
          atc_step_vs_W_max_abs_diff=atc_diff, param_max_abs=scale,
          tolerance={"contraction": 1e-3, "gossip": 1e-5 * scale,
                     "atc": 1e-3 * scale})
    assert gossip_diff <= 1e-5 * scale, "gossiped parameters are not W @ params"
    assert ratio <= predicted + 1e-3, (
        f"spread contracted by {ratio}, W allows at most {predicted}")
    assert atc_diff <= 1e-3 * scale, "ATC step is not local step then W"


def phase_buckets_vs_per_leaf(ctx, cfg, seed, clock, steps=2):
    """Four chips: what the ATC step does once it has its gradients, alone
    (SGD with momentum, the gossip over exp2(4), ``p + (c - p)``), on seeded
    parameters, gradients and momentum that differ on every rank, at the
    shapes of the model's leaves and at exp2(4)'s own weights, which are
    thirds and round.  Once with the leaves' order, as the step passes it
    (two buckets, a permute per bucket and shift class, every leaf combined
    out of the buckets that arrive), once with the order withheld (a permute
    per leaf and class); ``steps`` times, each from what the last gave.

    Both programs are written as ``sw * a + w1 * r1 + w2 * r2`` an element,
    and no leaf may part from its twin by more than ``BUCKETS_GAP_RTOL`` of
    its largest value: a wrong element, weight or order moves it by a tenth
    and more.  **Not bit for bit**, and the phase says how many leaves do
    differ: where the three weights fold to one constant (exp2's uniform
    weights) the TPU compiler factors it out, ``(a + r1 + r2) * c``, wherever
    it sees the three products together: in the per-leaf path, in a combine
    at the bucket's size, and for the bucket's vectors, but not for a leaf
    of two or more dimensions, whose product it moves in front of the
    unpack's reshapes.  On four v5e chips 55 of 322 leaves differed in half
    their elements (the first of them convolution kernels, 64 columns and
    256; PERF.md section 6, PR 30); on one chip with self-permutes none.
    XLA's CPU contracts one
    multiply of an add into a fused multiply-add instead, by what else
    shares the kernel, and parts a few leaves of a whole step in the last
    place.

    Not the whole step: the TPU compiler lays out the forward and backward
    pass of two programs differently, so two steps that differ anywhere give
    other gradients from the first step on (the first loss of the bucketed
    and of the per-leaf ResNet-50 step read 6.970607 and 6.970569 on four
    chips), whatever the gossip does."""
    t0 = time.perf_counter()
    n = ctx.size
    tx = optim.adapt_then_combine_spmd(
        optax.sgd(0.1, momentum=0.9),
        optim.make_spmd_comm_fn(CommunicationType.neighbor_allreduce,
                                plan=ctx.plan))
    rng = np.random.default_rng(seed)
    sharding = basics.rank_major_sharding(ctx)
    img = cfg["img"]
    shapes = jax.tree_util.tree_map(
        lambda a: a.shape, jax.eval_shape(
            lambda: _resnet_model(cfg).init(
                jax.random.PRNGKey(0), jnp.ones((1, img, img, 3)), train=True)
        )["params"])
    is_shape = lambda x: isinstance(x, tuple)

    def seeded(scale):
        return jax.tree_util.tree_map(
            lambda shape: jax.device_put(
                scale * rng.standard_normal((n,) + shape, dtype=np.float32),
                sharding), shapes, is_leaf=is_shape)

    params, grads, trace = seeded(0.05), seeded(0.01), seeded(0.01)
    # ready in reverse flatten order, about what a backward pass gives
    count = len(jax.tree_util.tree_leaves(params))
    ready = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), range(count, 0, -1))
    first = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
    again = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)

    def run(**order):
        def local(p, g, m):
            p, g, m = first(p), first(g), first(m)
            state = optax.tree_utils.tree_set(tx.init(p), trace=m)
            updates, state = tx.update(g, state, p, **order)
            return (again(optax.apply_updates(p, updates)),
                    again(optax.tree_utils.tree_get(state, "trace")))

        fn = jax.jit(jax.shard_map(
            local, mesh=ctx.mesh, in_specs=P(basics.NODES_AXIS),
            out_specs=P(basics.NODES_AXIS)))
        permutes = fn.lower(params, grads, trace).as_text().count(
            "collective_permute")
        p, m = params, trace
        for _ in range(steps):
            p, m = fn(p, grads, m)
        return permutes, [np.asarray(a)
                          for a in jax.tree_util.tree_leaves((p, m))]

    n_bucketed, bucketed = run(grad_order=ready)
    n_per_leaf, per_leaf = run()
    bits = lambda a: np.asarray(a).view(np.uint32)
    moved = sum(int((bits(a) != bits(b)).sum()) for a, b in
                zip(bucketed, jax.tree_util.tree_leaves((params, trace))))
    differ = [(i, int((bits(a) != bits(b)).sum()), a.size)
              for i, (a, b) in enumerate(zip(bucketed, per_leaf))
              if not np.array_equal(bits(a), bits(b))]
    # the widest gap as a share of its leaf's largest value: one unit of the
    # last place is 6e-8 to 1.2e-7 of the value it belongs to
    gap = max((float(np.abs(bucketed[i].astype(np.float64) - per_leaf[i]).max()
                     / np.abs(per_leaf[i]).max())
               for i, _, _ in differ), default=0.0)
    elements = int(sum(a.size for a in bucketed))
    _emit("buckets_vs_per_leaf", t0, clock, ranks=n, steps=steps,
          self_weight=float(ctx.plan.self_weights[0]),
          compared="every leaf of parameters and momentum, as bits, after "
                   "SGD with momentum, gossip and p + (c - p)",
          leaves=len(bucketed), elements=elements,
          elements_moved_from_the_start=moved,
          permutes_bucketed=n_bucketed, permutes_per_leaf=n_per_leaf,
          leaves_that_differ=len(differ), first_that_differ=differ[:5],
          largest_gap_over_leaf_max=gap, gap_limit=BUCKETS_GAP_RTOL)
    classes = len(ctx.plan.classes)
    buckets = (ops_spmd.MAX_PERMUTES_OUTSTANDING - 1) // classes
    assert (n_bucketed, n_per_leaf) == (buckets * classes, count * classes), (
        n_bucketed, n_per_leaf, count)
    assert moved > 0.99 * elements, "the part left its inputs as they were"
    assert gap <= BUCKETS_GAP_RTOL, (
        f"a leaf of the bucketed gossip is {gap:.3g} of its largest value "
        f"from the per-leaf gossip's; {len(differ)} differ: {differ[:5]}")
    return len(differ), gap


# ---------------------------------------------------------------------------
# phase: decoder step, compiled Pallas flash kernel vs dense attention
# ---------------------------------------------------------------------------


def phase_decoder(cfg, seed, on_tpu, clock, steps=3):
    t0 = time.perf_counter()
    ctx = basics.context()
    n, B, T = ctx.size, cfg["batch"], cfg["seq"]
    # on the chip the kernel is compiled, and says so; interpret mode is
    # only how the CPU rehearsal walks the same control flow
    interpret = not on_tpu
    assert _default_interpret() is interpret, (
        f"on platform {jax.devices()[0].platform!r} the flash kernel would "
        f"default to interpret={_default_interpret()}")
    flash = make_flash_attention_fn(impl="pallas", interpret=interpret)

    def lm(attention_fn, head_chunks):
        return LlamaLM(
            vocab_size=cfg["vocab"], hidden_size=cfg["hidden"],
            num_layers=cfg["layers"], num_heads=cfg["heads"], dff=cfg["dff"],
            head_chunks=head_chunks, attention_fn=attention_fn)

    model = lm(flash, cfg["head_chunks"])
    rng = np.random.default_rng(seed)
    ids_np = rng.integers(0, cfg["vocab"], size=(n, B, T)).astype(np.int32)
    ids = jax.device_put(ids_np, basics.rank_major_sharding(ctx))
    params0 = jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.asarray(ids_np[0]))["params"]
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params0))

    # the reference: same weights, same batch, the model's dense attention
    row = jnp.asarray(ids_np[0])
    dense_apply, dense_loss = make_lm_loss_fns(lm(None, cfg["head_chunks"]))
    loss_dense = float(jax.jit(
        lambda p, x: dense_loss(dense_apply({"params": p}, x, labels=x), x)
    )(params0, row))
    few = row[:cfg["logits_rows"]]
    logits = {
        name: jax.jit(lambda p, x, m=lm(fn, 0): m.apply({"params": p}, x))(
            params0, few)
        for name, fn in (("flash", flash), ("dense", None))}
    delta = logits["flash"] - logits["dense"]
    logits_diff = float(jnp.max(jnp.abs(delta)))
    logits_max = float(jnp.max(jnp.abs(logits["dense"])))
    logits_rel = float(jnp.linalg.norm(delta) / jnp.linalg.norm(logits["dense"]))
    assert np.isfinite(logits_diff) and logits_max > 0
    del logits, delta

    apply_fn, loss_fn = make_lm_loss_fns(model)
    init_fn, step_fn = make_decentralized_train_step(
        apply_fn, optax.adamw(3e-4), ctx.mesh,
        communication_type=CommunicationType.neighbor_allreduce,
        plan=ctx.plan, loss_fn=loss_fn)
    params = replicate_for_mesh(params0, n)
    opt_state = init_fn(params)
    hlo = _lowered_text(step_fn, params, {}, opt_state, ids, ids)
    custom_calls = hlo.count("tpu_custom_call")
    if on_tpu:
        assert custom_calls > 0, "no tpu_custom_call in the lowered decoder step"
    state, losses, secs = _run_steps(
        step_fn, (params, {}, opt_state), ids, ids, steps)
    loss_flash = float(losses[0][0])
    loss_diff = abs(loss_flash - loss_dense)
    _emit("decoder_flash_vs_dense", t0, clock, params=int(n_params),
          hidden=cfg["hidden"], heads=cfg["heads"],
          head_dim=cfg["hidden"] // cfg["heads"], layers=cfg["layers"],
          seq=T, per_rank_batch=B, interpret=interpret,
          tpu_custom_calls_in_lowered_step=custom_calls,
          compared="first-step loss (train step, Pallas flash) and logits of "
                   f"{cfg['logits_rows']} sequences vs the same weights and "
                   "batch through dense attention",
          loss_flash=loss_flash, loss_dense=loss_dense,
          loss_abs_diff=loss_diff, loss_atol=LOSS_ATOL,
          logits_max_abs_diff=logits_diff, logits_max_abs=logits_max,
          logits_max_abs_tol=LOGITS_MAX_RTOL * logits_max,
          logits_rel_l2_err=logits_rel, logits_rel_l2_tol=LOGITS_L2_RTOL,
          loss_per_step=[float(l.mean()) for l in losses],
          step_seconds=[round(s, 4) for s in secs],
          peak_bytes_in_use=_peak_bytes(ctx))
    assert _all_finite(state[0]), "non-finite parameters"
    assert loss_diff <= LOSS_ATOL, f"flash loss differs from dense by {loss_diff}"
    assert logits_rel <= LOGITS_L2_RTOL, (
        f"flash logits differ from dense by {logits_rel} in relative L2")
    assert logits_diff <= LOGITS_MAX_RTOL * logits_max, (
        f"flash logits differ from dense by {logits_diff} (max |logit| {logits_max})")


# ---------------------------------------------------------------------------
# phase: the dropless expert layer under a pile-up vs the plain reference
# ---------------------------------------------------------------------------


def phase_experts_piled(cfg, seed, clock):
    """`held_topk_experts` where the benchmark's decoder cell spends its
    window and its three checked steps never go: several passes of the
    loop, forward and in the backward rule, the last one partly filled.  The
    router is pushed towards the experts held (as it learns to be in that
    cell), the tokens differ; output and gradients (tokens, router, the
    three stacks) against `expert_terms` of chipbench's plain reference,
    which applies every expert held to every token.  Also the same layer in
    one pass of every row there could be: what the loop adds, alone."""
    from bluefog_tpu.parallel import expert as ep
    from chipbench import manifest

    t0 = time.perf_counter()
    reference = manifest.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chipbench", "reference",
        "smallthinker-21b-a3b.py"))
    T, d, f = cfg["tokens"], cfg["hidden"], cfg["dff"]
    E, k, H = cfg["experts"], cfg["top_k"], cfg["held"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    # logits of unit spread; the experts held 2.5 higher, through a direction
    # `u` that every token shares, so that the push has a gradient too
    u = jax.random.normal(ks[0], (d,)) / d ** 0.5
    lean = 0.6 * d ** 0.5
    x = jax.random.normal(ks[1], (T, d)) + lean * u
    router = jax.random.normal(ks[2], (d, E)) / d ** 0.5
    router = router.at[:, :H].add((2.5 / lean / jnp.sum(u * u)) * u[:, None])
    # Both sides get tokens and stacks that bf16 holds exactly, so the layer's
    # own cast rounds nothing.  With float32 stacks 0.1 % of the gates' signs
    # flip under that cast and ReLU's derivative is a step: `wg` then reads
    # 3.2e-2 and the tokens 2.3e-2, in one pass as in five (first v5e run).
    def held_exactly(key, shape, std):
        a = std * jax.random.normal(key, shape)
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    args = {"m": held_exactly(ks[3], (T, d), 1.0),
            "router": router,
            "wg": held_exactly(ks[4], (H, d, f), d ** -0.5),
            "wu": held_exactly(ks[5], (H, d, f), d ** -0.5),
            "wd": held_exactly(ks[6], (H, f, d), f ** -0.5)}
    cot = jax.random.normal(ks[7], (T, d))

    def ours(a, x, rows):
        experts, weights = ep.route_topk(x, a["router"], k)
        y = ep.held_topk_experts(
            a["m"].astype(jnp.bfloat16), experts, weights,
            {n: a[n] for n in ("wg", "wu", "wd")}, range(H), E, rows=rows)
        return y.astype(jnp.float32)

    def plain(a, x):
        r = jnp.einsum("td,de->te", x, a["router"], precision="highest")
        p = {("l", n): a[n] for n in ("wg", "wu", "wd")}
        return reference.expert_terms(
            a["m"], r, p, "l", {"moe_num_active_primary_experts": k}, False,
            tuple(range(H)))

    def out_and_grads(fn):
        # tokens and cotangent are arguments: closed over, each is a 170 MB
        # constant in every program
        @jax.jit
        def run(a, x, cot):
            y, vjp = jax.vjp(lambda a: fn(a, x), a)
            return dict(vjp(cot)[0], out=y)
        return run(args, x, cot)

    rows = cfg["rows"] or ep._pass_rows(T, k, H, E)
    assigned = int(jnp.sum(ep.route_topk(x, router, k)[0] < H))
    passes = -(-assigned // rows)
    assert passes > 1 and assigned % rows, (
        f"{assigned} rows in passes of {rows}: not the loop's several passes "
        "with a last one partly filled")
    want = out_and_grads(plain)

    def gaps(got):
        return {n: float(jnp.linalg.norm(got[n] - want[n])
                         / jnp.linalg.norm(want[n])) for n in sorted(want)}

    looped = out_and_grads(lambda a, x: ours(a, x, rows))
    one_pass = out_and_grads(lambda a, x: ours(a, x, T * min(k, H)))
    rel, rel_one = gaps(looped), gaps(one_pass)
    stats = jax.devices()[0].memory_stats() or {}
    _emit("experts_piled_vs_reference", t0, clock, tokens=T, hidden=d, dff=f,
          experts=E, top_k=k, held=H, rows_per_pass=rows,
          rows_assigned_here=assigned, rows_at_even_routing=T * k * H // E,
          passes=passes,
          compared="output and gradients (tokens, router, wg, wu, wd) of "
                   "held_topk_experts, bf16 operands, against expert_terms "
                   "of chipbench/reference/smallthinker-21b-a3b.py, float32 "
                   "highest: relative L2",
          rel_l2=rel, rel_l2_in_one_pass=rel_one, rel_l2_tol=EXPERTS_L2_RTOL,
          peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    for name, gap in rel.items():
        assert gap <= EXPERTS_L2_RTOL, (
            f"{name}: {gap} from the plain reference in relative L2 over "
            f"{passes} passes ({rel_one[name]} in one pass)")
    return {"passes": passes, "rel_l2": rel, "rel_l2_in_one_pass": rel_one}


# ---------------------------------------------------------------------------
# phase: shared key-value heads read in place vs the heads repeated
# ---------------------------------------------------------------------------


def phase_shared_heads(cfg, seed, on_tpu, clock):
    """The banded and the whole-sequence flash kernels (forward, dK/dV, dQ)
    with fewer key-value heads than query heads, at the sizes of a window
    layer of the benchmark's `laguna-xs.2` cell: the index maps fetch head
    ``h // group`` where it lies and the dK/dV kernel sums over the group.
    Against the same kernels handed ``jnp.repeat``-ed heads, whose dK and dV
    are summed back afterwards: values and all three gradients."""
    from bluefog_tpu.kernels.flash_attention import flash_attention

    t0 = time.perf_counter()
    T, H, KV, D = cfg["seq"], cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, g = (jax.random.normal(k, (1, T, H, D), jnp.bfloat16) for k in keys[:2])
    k, v = (jax.random.normal(k, (1, T, KV, D), jnp.bfloat16) for k in keys[2:])

    def run(window, repeat):
        def loss(q, k, v):
            if repeat:
                k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
            out = flash_attention(
                q, k, v, causal=True, window=window, impl="pallas",
                interpret=not on_tpu, block_q=cfg["block"], block_k=cfg["block"])
            return jnp.sum((out * g).astype(jnp.float32)), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True))(q, k, v)
        return dict(zip(("out", "dq", "dk", "dv"), (out,) + grads))

    rel = {}
    for name, window in (("window", cfg["window"]), ("global", None)):
        got, want = run(window, False), run(window, True)
        assert got["dk"].shape == k.shape and got["dq"].shape == q.shape
        rel[name] = _rel_l2(got, want)
    _emit("shared_heads_vs_repeated", t0, clock, seq=T, heads=H, kv_heads=KV,
          head_dim=D, window=cfg["window"], interpret=not on_tpu,
          compared="output, dQ, dK, dV of flash_attention handed the shared "
                   "heads against the same call handed them repeated, "
                   "bfloat16: relative L2",
          rel_l2=rel, rel_l2_tol=SHARED_HEADS_L2_RTOL)
    for name, gaps in rel.items():
        for n, gap in gaps.items():
            assert gap <= SHARED_HEADS_L2_RTOL, (
                f"{name} {n}: {gap} from the repeated call in relative L2")
    return rel


# ---------------------------------------------------------------------------
# phase: cut tiles walked in sub-tiles vs computed whole, and the edge's sweep
# ---------------------------------------------------------------------------


def phase_subtiles(cfg, seed, on_tpu, clock):
    """The backward kernels' builder at the decoder cells' attention shapes,
    a cut tile walked in sub-tiles of each swept edge against the tile
    computed whole (``sub=0``): dQ, dK and dV in relative L2, and the host
    clock over ``calls`` calls of each kernel, which is what `_sub_edge`'s
    edge is chosen from.  The bring-up proof of the sub-tiled kernels; no
    cell runs it."""
    from bluefog_tpu.kernels.flash_attention import (
        _default_blocks, _flash_bwd_pallas, _flash_fwd, _sub_edge)

    T, D = cfg["seq"], cfg["head_dim"]
    for name, B, H, KV, window, block in cfg["shapes"]:
        t0 = time.perf_counter()
        keys = jax.random.split(jax.random.PRNGKey(seed), 4)
        q, g = (jax.random.normal(k, (B * H, T, D), jnp.bfloat16) for k in keys[:2])
        k, v = (jax.random.normal(k, (B * KV, T, D), jnp.bfloat16) for k in keys[2:])
        kw = dict(scale=D ** -0.5, causal=True, block_q=block, block_k=block,
                  interpret=not on_tpu, tri_delta=0, window=window)
        out, lse = jax.jit(lambda q, k, v: _flash_fwd(q, k, v, 0, 0, **kw))(q, k, v)
        corr = -jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), -1)
        args = (q, k, v, lse, corr, g)

        def run(sub):
            def bwd(*a):
                return _flash_bwd_pallas(*a[:5], 0, 0, a[5], sub=sub, **kw)

            got = dict(zip(("dq", "dk", "dv"), jax.jit(bwd)(*args)))
            ms = {}
            # a kernel whose outputs are dropped is not in the compiled program
            for kind, fn in (("dkv", jax.jit(lambda *a: bwd(*a)[1:])),
                             ("dq", jax.jit(lambda *a: bwd(*a)[0]))):
                jax.block_until_ready((got, fn(*args)))
                t = time.perf_counter()
                for _ in range(cfg["calls"]):
                    last = fn(*args)
                jax.block_until_ready(last)
                ms[kind] = round((time.perf_counter() - t) / cfg["calls"] * 1e3, 3)
            return got, ms

        blocks = _default_blocks(T, T, block, block, window)
        whole, ms = run(0)
        sweep, rel = {"whole": ms}, {}
        for edge in (e for e in cfg["edges"] if e < min(blocks)):
            got, sweep[str(edge)] = run(edge)
            rel[str(edge)] = _rel_l2(got, whole)
        _emit("subtiles_vs_whole_tiles", t0, clock, shape=name, batch=B, seq=T,
              heads=H, kv_heads=KV, head_dim=D, window=window, blocks=blocks,
              interpret=not on_tpu, the_kernels_own_edge=_sub_edge(*blocks),
              compared="dQ, dK, dV of the backward kernels' builder, a cut tile "
                       "walked in sub-tiles of each edge against the tile "
                       "computed whole, bfloat16: relative L2; ms a call of each "
                       "kernel, host clock",
              ms_per_call=sweep, rel_l2=rel, rel_l2_tol=SUBTILES_L2_RTOL)
        for edge, gaps in rel.items():
            for n, gap in gaps.items():
                assert gap <= SUBTILES_L2_RTOL, (
                    f"{name} edge {edge} {n}: {gap} from the whole-tile kernels "
                    "in relative L2")


# ---------------------------------------------------------------------------
# phase: the chunked scan's kernels vs the token recurrence; flash at heads of 64
# ---------------------------------------------------------------------------


def phase_ssd(cfg, seed, on_tpu, clock):
    """`ssd_scan` (forward and backward Pallas kernels) at the sizes of a
    state-space layer of the benchmark's `granite-4.0-h-micro` cell, one
    group, against the recurrence of chipbench's plain reference taken token
    by token: the output and all six gradients in relative L2, and the host
    clock over ``calls`` calls of the forward alone and of forward and
    backward, for each chunk swept.  Then the whole-sequence flash kernels at
    that cell's attention shape (heads of 64, no cell had run them there)
    against dense attention, a key-value head's group at a time."""
    from bluefog_tpu.kernels.flash_attention import flash_attention
    from bluefog_tpu.kernels.ssd import ssd_scan
    from bluefog_tpu.models.transformer import dense_attention
    from chipbench import manifest

    reference = manifest.load_module(os.path.join(
        manifest.REPO, "chipbench", "reference", "granite-4.0-h-micro.py"))
    t0 = time.perf_counter()
    T, H, P, N = cfg["seq"], cfg["heads"], cfg["head_dim"], cfg["state"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x, g = (jax.random.normal(k, (1, T, H, P), jnp.bfloat16) for k in keys[:2])
    bm, cm = (0.5 * jax.random.normal(k, (1, T, 1, N), jnp.bfloat16) for k in keys[2:4])
    # step sizes and rates spread as the configuration's seeded weights spread them
    dt = jax.nn.softplus(jax.random.normal(keys[4], (1, T, H)) + jnp.log(jnp.expm1(
        jnp.exp(jax.random.uniform(keys[5], (H,), minval=np.log(1e-3), maxval=np.log(0.1))))))
    a_log = jnp.log(jax.random.uniform(keys[6], (H,), minval=1.0, maxval=16.0))
    skip = jnp.ones((H,), jnp.float32)
    args = (x, dt, a_log, bm, cm, skip)
    names = ("x", "dt", "A_log", "B", "C", "D")

    def values(fn):
        def loss(*a):
            y = fn(*a)
            return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32)), y
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, tuple(range(6)), has_aux=True))(*args)
        return dict(zip(("y",) + tuple("d" + n for n in names), (y,) + grads))

    def recurrence(x, dt, a_log, bm, cm, skip):
        f32 = lambda a: a[0].astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            return reference.ssm_scan(f32(x), dt[0], a_log, f32(bm), f32(cm), skip)[None]

    want = values(recurrence)
    rel, ms = {}, {}
    for chunk in cfg["chunks"]:
        scan = lambda *a: ssd_scan(*a, chunk=chunk, interpret=not on_tpu)
        rel[str(chunk)] = _rel_l2(values(scan), want)
        fwd = jax.jit(scan)
        both = jax.jit(jax.grad(lambda *a: jnp.sum(
            scan(*a).astype(jnp.float32) * g.astype(jnp.float32)), tuple(range(6))))
        ms[str(chunk)] = {kind: _ms_a_call(fn, args, cfg["calls"])
                          for kind, fn in (("fwd", fwd), ("fwd_bwd", both))}
    _emit("ssd_vs_recurrence", t0, clock, seq=T, heads=H, head_dim=P, state=N,
          groups=1, interpret=not on_tpu,
          compared="y and the six gradients of ssd_scan (bfloat16 x, B, C) against "
                   "the float32 recurrence taken token by token: relative L2; ms a "
                   "call, host clock, by chunk",
          rel_l2=rel, ms_per_call=ms, rel_l2_tol=SSD_L2_RTOL)
    for chunk, gaps in rel.items():
        for n, gap in gaps.items():
            assert gap <= SSD_L2_RTOL, (
                f"chunk {chunk} {n}: {gap} from the recurrence in relative L2")

    t0 = time.perf_counter()
    HQ, KV, D = cfg["att_heads"], cfg["att_kv_heads"], cfg["att_head_dim"]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    q, go = (jax.random.normal(k, (1, T, HQ, D), jnp.bfloat16) for k in keys[:2])
    k_, v_ = (jax.random.normal(k, (1, T, KV, D), jnp.bfloat16) for k in keys[2:])

    def dense(q, k, v):  # a key-value head with its query heads at a time
        group = HQ // KV
        qg = q.reshape(1, T, KV, group, D).transpose(2, 0, 1, 3, 4)
        one = jax.checkpoint(lambda a: dense_attention(
            a[0], jnp.repeat(a[1], group, 2), jnp.repeat(a[2], group, 2),
            causal=True, dtype=jnp.bfloat16))
        out = jax.lax.map(one, (qg, k.transpose(2, 0, 1, 3)[:, :, :, None],
                                v.transpose(2, 0, 1, 3)[:, :, :, None]))
        return out.transpose(1, 2, 0, 3, 4).reshape(q.shape)

    def att(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum((out * go).astype(jnp.float32)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1, 2), has_aux=True))(q, k_, v_)
        return dict(zip(("out", "dq", "dk", "dv"), (out,) + grads))

    block = None if T >= 2048 else T // 4
    rel = _rel_l2(att(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl="pallas", interpret=not on_tpu,
        block_q=block, block_k=block)), att(dense))
    _emit("flash_heads_of_64_vs_dense", t0, clock, seq=T, heads=HQ, kv_heads=KV,
          head_dim=D, interpret=not on_tpu,
          compared="output, dQ, dK, dV of the whole-sequence flash kernels "
                   "against dense attention, bfloat16: relative L2",
          rel_l2=rel, rel_l2_tol=LOGITS_L2_RTOL)
    for n, gap in rel.items():
        assert gap <= LOGITS_L2_RTOL, f"{n}: {gap} from dense attention in relative L2"


# ---------------------------------------------------------------------------
# phase: the causal convolution's kernels vs the expression, and a plain candidate
# ---------------------------------------------------------------------------


def _rolled_conv(x, kernel, bias):
    """`hybrid.causal_conv` with no pad and no slice off the tile: the input
    widened, rolled along the tokens a tap at a time, the rows that came
    round masked.  The plain candidate the kernels are timed against."""
    w = kernel.shape[0]
    wide = x.astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[1], 1), 1)
    taps = (wide if k == w - 1 else
            jnp.where(row >= w - 1 - k, jnp.roll(wide, w - 1 - k, axis=1), 0.0)
            for k in range(w))
    return bias + sum(kernel[k] * rows for k, rows in enumerate(taps))


def phase_conv(cfg, seed, on_tpu, clock):
    """`hybrid.conv_silu` (the causal convolution, its bias and SiLU through
    the forward and backward Pallas kernels) at the sizes of a state-space
    layer of the benchmark's `granite-4.0-h-micro` cell, on the input
    projection's whole output, against the plain reference's `causal_conv` and
    a SiLU in float32: the output and the three gradients in relative L2, and
    the host clock over ``calls`` calls of the forward alone and of forward and
    backward, for the kernels, for `hybrid.causal_conv`'s expression and for
    the same sum written with rolls."""
    from bluefog_tpu.models import hybrid
    from chipbench import manifest

    reference = manifest.load_module(os.path.join(
        manifest.REPO, "chipbench", "reference", "granite-4.0-h-micro.py"))
    t0 = time.perf_counter()
    T, inner, states, W = cfg["seq"], cfg["inner"], cfg["states"], cfg["width"]
    conv = inner + 2 * states
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    # [z, xBC, dt], its columns rounded up to whole 128-lane blocks: an argument
    # of 8,512 columns is laid out tokens-minor, and every path would be timed
    # with a copy that a product inside a step does not need
    wide = -(-(inner + conv + cfg["heads"]) // 128) * 128
    zxbcdt = jax.random.normal(keys[0], (1, T, wide), jnp.bfloat16)
    g = jax.random.normal(keys[1], (1, T, conv), jnp.bfloat16)
    taps = 0.5 * jax.random.normal(keys[2], (W, conv))
    bias = 0.5 * jax.random.normal(keys[3], (conv,))
    args = (zxbcdt, taps, bias)
    assert hybrid.conv_kernels_take(T, inner, states, W)

    def expression(conv_fn):  # as `hybrid.conv_silu` where the kernels do not tile
        def fn(zxbcdt, taps, bias):
            xbc = jax.nn.silu(conv_fn(
                zxbcdt[..., inner:inner + conv], taps, bias)).astype(zxbcdt.dtype)
            return xbc[..., :inner], xbc[..., inner:]
        return fn

    def in_float32(zxbcdt, taps, bias):
        with jax.default_matmul_precision("highest"):
            xbc = jax.nn.silu(reference.causal_conv(
                zxbcdt[0, :, inner:inner + conv].astype(jnp.float32), taps, bias))[None]
        return xbc[..., :inner], xbc[..., inner:]

    paths = {"kernels": lambda *a: hybrid.conv_silu(*a, inner),
             "expression": expression(hybrid.causal_conv),
             "rolled": expression(_rolled_conv)}

    def loss(fn):  # x and [B, C] each against its part of g, as the scan takes them
        def of(*a):
            x, bc = fn(*a)
            return (jnp.sum(x.astype(jnp.float32) * g[..., :inner].astype(jnp.float32))
                    + jnp.sum(bc.astype(jnp.float32) * g[..., inner:].astype(jnp.float32)))
        return of

    def values(fn):
        y, grads = jax.jit(lambda *a: (fn(*a), jax.grad(loss(fn), (0, 1, 2))(*a)))(*args)
        return dict(zip(("y", "dx", "dkernel", "dbias"),
                        (jnp.concatenate(y, axis=-1),
                         grads[0][..., inner:inner + conv]) + grads[1:]))

    want = values(in_float32)
    rel, ms = {}, {}
    for name, fn in paths.items():
        rel[name] = _rel_l2(values(fn), want)
        ms[name] = {kind: _ms_a_call(jax.jit(timed), args, cfg["calls"]) for kind, timed in (
            ("fwd", fn), ("fwd_bwd", jax.grad(loss(fn), (0, 1, 2))))}
    _emit("conv_vs_expression", t0, clock, seq=T, channels=conv, of=zxbcdt.shape[-1],
          width=W, interpret=not on_tpu,
          compared="silu(causal_conv) and its gradients in the input, the taps and "
                   "the bias (bfloat16 in and out) against the reference's "
                   "expression in float32: relative L2; ms a call, host clock; the "
                   "kernels, hybrid.causal_conv's expression, the same with rolls",
          rel_l2=rel, ms_per_call=ms, rel_l2_tol=CONV_L2_RTOL)
    for name, gaps in rel.items():
        for n, gap in gaps.items():
            assert gap <= CONV_L2_RTOL, (
                f"{name} {n}: {gap} from the float32 expression in relative L2")


# ---------------------------------------------------------------------------


def phase_short_conv(cfg, seed, on_tpu, clock):
    """`kernels.causal_conv.short_conv` (LFM2's gated short convolution, `C *
    conv(B * x)`, through the `short_conv_fwd` / `short_conv_bwd` kernels) at
    the sizes of a conv layer of the benchmark's `lfm2-24b-a2b` cell, on the
    input projection's whole `[B, C, x]` product, against the plain
    reference's three shifted multiply-adds in float32: the output and the two
    gradients in relative L2, and the host clock over ``calls`` calls of the
    forward alone and of forward and backward, for the kernels and for
    `hybrid.gated_short_conv`'s expression."""
    from bluefog_tpu.kernels.causal_conv import short_conv
    from bluefog_tpu.models import hybrid
    from chipbench import manifest

    reference = manifest.load_module(os.path.join(
        manifest.REPO, "chipbench", "reference", "lfm2-24b-a2b.py"))
    t0 = time.perf_counter()
    T, d, W = cfg["seq"], cfg["channels"], cfg["width"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcx = jax.random.normal(keys[0], (1, T, 3 * d), jnp.bfloat16)
    g = jax.random.normal(keys[1], (1, T, d), jnp.bfloat16).astype(jnp.float32)
    taps = 0.5 * jax.random.normal(keys[2], (W, d))
    args = (bcx, taps)
    assert hybrid.short_conv_kernels_take(T, d, W)

    def in_float32(bcx, taps):
        gate_b, gate_c, x = (bcx[0, :, i * d:(i + 1) * d].astype(jnp.float32)
                             for i in range(3))
        return (gate_c * reference.short_conv(gate_b * x, taps))[None]

    paths = {"kernels": short_conv, "expression": hybrid.gated_short_conv}
    loss = lambda fn: lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * g)

    def values(fn):
        y, grads = jax.jit(lambda *a: (fn(*a), jax.grad(loss(fn), (0, 1))(*a)))(*args)
        return dict(zip(("y", "dbcx", "dkernel"), (y,) + grads))

    want = values(in_float32)
    rel, ms = {}, {}
    for name, fn in paths.items():
        rel[name] = _rel_l2(values(fn), want)
        ms[name] = {kind: _ms_a_call(jax.jit(timed), args, cfg["calls"]) for kind, timed in (
            ("fwd", fn), ("fwd_bwd", jax.grad(loss(fn), (0, 1))))}
    _emit("short_conv_vs_expression", t0, clock, seq=T, channels=d, of=3 * d, width=W,
          interpret=not on_tpu,
          compared="C * conv(B * x) and its gradients in [B, C, x] and the taps "
                   "(bfloat16 in and out) against the reference's shifted "
                   "multiply-adds in float32: relative L2; ms a call, host clock; the "
                   "kernels, hybrid.gated_short_conv's expression",
          rel_l2=rel, ms_per_call=ms, rel_l2_tol=CONV_L2_RTOL)
    for name, gaps in rel.items():
        for n, gap in gaps.items():
            assert gap <= CONV_L2_RTOL, (
                f"{name} {n}: {gap} from the float32 expression in relative L2")


def phase_kda(cfg, seed, on_tpu, clock):
    """`kda_chunked` (the chunk's stateless stage through `kda_intra_fwd` /
    `kda_intra_bwd`, which since PR 47 take the unit vectors of q and k
    themselves, the walk through `kda_chunk_fwd` / `kda_chunk_bwd`: four
    Pallas kernels; 7.96 ms a forward call and 21.03 with backward, beside
    8.03 and 20.89 of the parent's kernels handed unit vectors made outside
    them, my chip runs, PR 47) at the sizes of a linear layer of the
    benchmark's `ling-3.0-flash-vl` cell, handed q and k raw in bfloat16 as
    the convolution leaves them, against the recurrence of
    chipbench's plain reference taken token by token, eight heads at a time,
    on the reference's own unit vectors of the same q and k in float32: the
    output and all five gradients in relative L2, and the host clock over
    ``calls`` calls of the forward alone and of forward and backward.  The
    decays are drawn over their whole range, a quarter of the channels within
    1e-2 of the bound."""
    from bluefog_tpu.kernels.kda import kda_chunked
    from chipbench import manifest

    reference = manifest.load_module(os.path.join(
        manifest.REPO, "chipbench", "reference", "ling-3.0-flash-vl.py"))
    t0 = time.perf_counter()
    T, H, K = cfg["seq"], cfg["heads"], cfg["head_dim"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    q, k, v, go = (jax.random.normal(r, (1, T, H, K), jnp.bfloat16) for r in keys[:4])
    shift = jnp.where(jax.random.uniform(keys[4], (K,)) < 0.25, 9.0, -4.0)
    g = cfg["lower"] * jax.nn.sigmoid(shift + jax.random.normal(keys[5], (1, T, H, K)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[6], (1, T, H)))
    args, names = (q, k, v, g, beta), ("q", "k", "v", "g", "beta")

    def values(fn):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(o.astype(jnp.float32) * go.astype(jnp.float32)), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, tuple(range(5)), has_aux=True))(*args)
        return dict(zip(("o",) + tuple("d" + n for n in names), (o,) + grads))

    def recurrence(q, k, v, g, beta):
        f32 = lambda a: a[0].astype(jnp.float32)
        groups = max(1, H // 8)
        split = lambda a: jnp.moveaxis(
            f32(a).reshape((T, groups, H // groups) + a.shape[3:]), 1, 0)

        def one(a):  # a head's vector over its length, the query over sqrt(K)
            q, k, *rest = a
            return reference.kda_scan(reference._unit(q) * K ** -0.5,
                                      reference._unit(k), *rest)

        o = jax.lax.map(jax.checkpoint(one), tuple(map(split, (q, k, v, g, beta))))
        return jnp.moveaxis(o, 0, 1).reshape(1, T, H, K)

    chunked = lambda *a: kda_chunked(*a, chunk=cfg["chunk"], interpret=not on_tpu)
    rel = _rel_l2(values(chunked), values(recurrence))
    both = jax.jit(jax.grad(lambda *a: jnp.sum(
        chunked(*a).astype(jnp.float32) * go.astype(jnp.float32)), tuple(range(5))))
    ms = {kind: _ms_a_call(fn, args, cfg["calls"])
          for kind, fn in (("fwd", jax.jit(chunked)), ("fwd_bwd", both))}
    _emit("kda_vs_recurrence", t0, clock, seq=T, heads=H, head_dim=K,
          chunk=cfg["chunk"], interpret=not on_tpu,
          compared="o and the five gradients of kda_chunked (raw bfloat16 q, k; v) "
                   "against the float32 recurrence taken token by token on the "
                   "unit vectors of the same q and k: relative L2; ms a call, host "
                   "clock",
          rel_l2=rel, ms_per_call=ms, rel_l2_tol=KDA_L2_RTOL)
    for n, gap in rel.items():
        assert gap <= KDA_L2_RTOL, f"{n}: {gap} from the recurrence in relative L2"


def phase_gdn(cfg, seed, on_tpu, clock):
    """`gdn_chunked` (the chunk's stateless stage through `gdn_intra_fwd` /
    `gdn_intra_bwd`, one decay a head and the shared key heads read in place,
    the walk through Ling's `kda_chunk_fwd` / `kda_chunk_bwd`) at the sizes of a
    linear layer of the benchmark's `qwen3-next-80b-a3b` cell, handed q and k
    raw in bfloat16 as the convolution leaves them, against the recurrence of
    chipbench's plain reference taken token by token, eight value heads at a
    time, on the reference's own unit vectors of the same q and k repeated to
    the value heads: the output and all five gradients in relative L2 at each of
    ``decays`` (the log-decay drawn between a fifth of it and it: near 0, and
    down to -30 a token, which the channel-decay kernels cannot take), and the
    host clock over ``calls`` calls.  Then the whole-sequence flash kernels at
    the cell's attention layer (heads of ``att_head_dim``, ``att_heads`` on
    ``att_kv_heads``) against dense softmax in float32, forward and backward."""
    from bluefog_tpu.kernels.flash_attention import flash_attention
    from bluefog_tpu.kernels.gdn import gdn_chunked
    from chipbench import manifest

    reference = manifest.load_module(os.path.join(
        manifest.REPO, "chipbench", "reference", "qwen3-next-80b-a3b.py"))
    t0 = time.perf_counter()
    T, H, Hk, K = cfg["seq"], cfg["heads"], cfg["key_heads"], cfg["head_dim"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    q, k = (jax.random.normal(r, (1, T, Hk, K), jnp.bfloat16) for r in keys[:2])
    v, go = (jax.random.normal(r, (1, T, H, K), jnp.bfloat16) for r in keys[2:4])
    spread = jax.random.uniform(keys[4], (1, T, H), minval=0.2, maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, T, H)))

    def values(fn, args, weight, names=("q", "k", "v", "g", "beta")):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(o.astype(jnp.float32) * weight.astype(jnp.float32)), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, tuple(range(len(args))), has_aux=True))(*args)
        return dict(zip(("o",) + tuple("d" + n for n in names), (o,) + grads))

    def recurrence(q, k, v, g, beta):
        f32 = lambda a: a[0].astype(jnp.float32)
        unit = lambda a, scale: jnp.repeat(reference.unit(f32(a)) * scale, H // Hk, axis=1)
        groups = max(1, H // 8)
        split = lambda a: jnp.moveaxis(
            a.reshape((T, groups, H // groups) + a.shape[2:]), 1, 0)
        o = jax.lax.map(jax.checkpoint(lambda a: reference.gdn_scan(*a)), tuple(map(
            split, (unit(q, K ** -0.5), unit(k, 1.0), f32(v), f32(g), f32(beta)))))
        return jnp.moveaxis(o, 0, 1).reshape(1, T, H, K)

    chunked = lambda *a: gdn_chunked(*a, chunk=cfg["chunk"], interpret=not on_tpu)
    rel, ms = {}, {}
    for decay in cfg["decays"]:
        args = (q, k, v, decay * spread, beta)
        got, want = values(chunked, args, go), values(recurrence, args, go)
        floor = max(float(jnp.linalg.norm(want[n].astype(jnp.float32)))
                    for n in want if n != "o")
        rel[str(decay)] = {
            n: float(jnp.linalg.norm((got[n] - want[n]).astype(jnp.float32))
                     / max(float(jnp.linalg.norm(want[n].astype(jnp.float32))),
                           1e-2 * floor)) for n in got}
        both = jax.jit(jax.grad(lambda *a: jnp.sum(
            chunked(*a).astype(jnp.float32) * go.astype(jnp.float32)), tuple(range(5))))
        ms[str(decay)] = {kind: _ms_a_call(fn, args, cfg["calls"])
                          for kind, fn in (("fwd", jax.jit(chunked)), ("fwd_bwd", both))}
    # the attention layer's kernels at its head size
    Ha, Hkv, D, block = (cfg["att_heads"], cfg["att_kv_heads"], cfg["att_head_dim"],
                         cfg["block"])
    aq, ago = (jax.random.normal(r, (1, T, Ha, D), jnp.bfloat16) for r in keys[5:7])
    ak, av = (jax.random.normal(r, (1, T, Hkv, D), jnp.bfloat16) for r in keys[:2])

    def dense(q, k, v):  # a query head at a time against its group's key-value head
        heads_first = lambda a: jnp.moveaxis(a[0].astype(jnp.float32), 1, 0)
        group = jnp.arange(Ha) // (Ha // Hkv)
        one = jax.checkpoint(lambda a: reference.causal_softmax_head(*a, False))
        o = jax.lax.map(one, (heads_first(q), heads_first(k)[group], heads_first(v)[group]))
        return jnp.moveaxis(o, 0, 1)[None]

    fast = lambda *a: flash_attention(*a, causal=True, block_q=block, block_k=block,
                                      interpret=not on_tpu)
    att_rel = _rel_l2(values(fast, (aq, ak, av), ago), values(dense, (aq, ak, av), ago))
    att_both = jax.jit(jax.grad(lambda *a: jnp.sum(
        fast(*a).astype(jnp.float32) * ago.astype(jnp.float32)), (0, 1, 2)))
    att_ms = {kind: _ms_a_call(fn, (aq, ak, av), cfg["calls"])
              for kind, fn in (("fwd", jax.jit(fast)), ("fwd_bwd", att_both))}
    _emit("gdn_vs_recurrence", t0, clock, seq=T, heads=H, key_heads=Hk, head_dim=K,
          chunk=cfg["chunk"], interpret=not on_tpu,
          compared="o and the five gradients of gdn_chunked (raw bfloat16 q, k on "
                   "shared key heads; v) against the float32 recurrence taken token by "
                   "token, at each log-decay: relative L2 (a gradient under a hundredth "
                   "of the largest against that); then o, dq, dk, dv of the flash "
                   "kernels at the attention layer's head size against dense float32 "
                   "softmax; ms a call, host clock",
          rel_l2=rel, ms_per_call=ms, rel_l2_tol=GDN_L2_RTOL,
          attention=dict(heads=Ha, kv_heads=Hkv, head_dim=D, rel_l2=att_rel,
                         ms_per_call=att_ms, rel_l2_tol=LOGITS_L2_RTOL))
    for decay, gaps in rel.items():
        for n, gap in gaps.items():
            assert gap <= GDN_L2_RTOL, f"g to {decay}, {n}: {gap} from the recurrence"
    for n, gap in att_rel.items():
        assert gap <= LOGITS_L2_RTOL, f"{n}: {gap} from dense softmax in relative L2"


def phase_mla(cfg, seed, on_tpu, clock):
    """The whole-sequence flash kernels handed a query-key head of ``nope +
    rope`` beside a value head of ``v_dim`` (a latent-attention layer of the
    `ling-3.0-flash-vl` cell: 192 beside 128, the rotary key channels one head
    laid beside every head's own) against dense softmax in float32 blocks, a
    head and 2,048 query rows at a time: the output and the three gradients in
    relative L2, and ms a call."""
    from bluefog_tpu.kernels.flash_attention import flash_attention
    from chipbench import manifest

    reference = manifest.load_module(os.path.join(
        manifest.REPO, "chipbench", "reference", "ling-3.0-flash-vl.py"))
    t0 = time.perf_counter()
    T, H, dq, dv = cfg["seq"], cfg["heads"], cfg["nope"] + cfg["rope"], cfg["v_dim"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (1, T, H, dq), jnp.bfloat16)
    k_n = jax.random.normal(keys[1], (1, T, H, cfg["nope"]), jnp.bfloat16)
    k_r = jax.random.normal(keys[2], (1, T, 1, cfg["rope"]), jnp.bfloat16)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (1, T, H, cfg["rope"]))], axis=-1)
    v, go = (jax.random.normal(r, (1, T, H, dv), jnp.bfloat16) for r in keys[3:])
    block = None if T >= 4096 else 32

    def values(fn):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(o.astype(jnp.float32) * go.astype(jnp.float32)), o
        (_, o), grads = jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(q, k, v)
        return dict(zip(("o", "dq", "dk", "dv"), (o,) + grads))

    def dense(q, k, v):  # a head at a time
        heads_first = lambda a: jnp.moveaxis(a[0].astype(jnp.float32), 1, 0)
        one = jax.checkpoint(lambda a: reference._attention(*a, dq ** -0.5, False))
        with jax.default_matmul_precision("highest"):
            o = jax.lax.map(one, tuple(map(heads_first, (q, k, v))))
        return jnp.moveaxis(o, 0, 1)[None]

    fast = lambda *a: flash_attention(*a, causal=True, block_q=block, block_k=block,
                                      interpret=not on_tpu)
    rel = _rel_l2(values(fast), values(dense))
    both = jax.jit(jax.grad(lambda *a: jnp.sum(
        fast(*a).astype(jnp.float32) * go.astype(jnp.float32)), (0, 1, 2)))
    ms = {kind: _ms_a_call(fn, (q, k, v), cfg["calls"])
          for kind, fn in (("fwd", jax.jit(fast)), ("fwd_bwd", both))}
    _emit("mla_two_head_sizes", t0, clock, seq=T, heads=H, qk_dims=dq, v_dims=dv,
          interpret=not on_tpu,
          compared="o, dq, dk, dv of the flash kernels at two head sizes (bfloat16) "
                   "against dense float32 softmax: relative L2; ms a call, host clock",
          rel_l2=rel, ms_per_call=ms, rel_l2_tol=LOGITS_L2_RTOL)
    for n, gap in rel.items():
        assert gap <= LOGITS_L2_RTOL, f"{n}: {gap} from dense softmax in relative L2"


def phase_mla_mixer(cfg, seed, on_tpu, clock):
    """A latent-attention layer's whole mixer as the `kanana-2-30b-a3b` cell
    calls it (`hybrid.LatentAttentionMixer` with no head gate and the rotary
    over interleaved pairs put evens first, bfloat16 products, the flash
    kernels at 192 beside 128) against the dense oracle of chipbench's plain
    reference (float32, the pairs turned in place, a head and 2,048 query rows
    at a time): the output and the gradients of the input and of the five
    leaves in relative L2, and ms a call."""
    from bluefog_tpu.kernels.flash_attention import flash_attention
    from bluefog_tpu.models.hybrid import LatentAttentionMixer
    from bluefog_tpu.models.transformer import rotary_frequencies
    from chipbench import manifest, seeded

    reference = manifest.load_module(os.path.join(
        manifest.REPO, "chipbench", "reference", "kanana-2-30b-a3b.py"))
    t0 = time.perf_counter()
    sizes = dict(hidden_size=cfg["hidden"], num_attention_heads=cfg["heads"],
                 kv_lora_rank=cfg["rank"], qk_nope_head_dim=cfg["nope"],
                 qk_rope_head_dim=cfg["rope"], v_head_dim=cfg["v_dim"],
                 rope_theta=cfg["theta"], rms_norm_eps=1e-6)
    block = None if cfg["seq"] >= 4096 else 32
    mixer = LatentAttentionMixer(
        cfg["heads"], cfg["rank"], cfg["nope"], cfg["rope"], cfg["v_dim"],
        rotary_frequencies(cfg["rope"], cfg["theta"]), 1e-6, jnp.bfloat16,
        functools.partial(flash_attention, causal=True, block_q=block, block_k=block,
                          interpret=not on_tpu),
        head_gate=False, rotary_interleaved=True)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    u = jax.random.normal(keys[0], (1, cfg["seq"], cfg["hidden"]), jnp.float32)
    go = jax.random.normal(keys[1], u.shape, jnp.float32)
    leaves = {path: (jnp.ones(a.shape) if path[-1] == "scale" else 0.02 * jax.random.normal(
        jax.random.fold_in(keys[2], i), a.shape)) for i, (path, a) in enumerate(sorted(
            seeded.flatten(jax.eval_shape(mixer.init, keys[2], u)["params"]).items()))}
    assert ("gate", "kernel") not in leaves and len(leaves) == 5

    def fast(p, u_):
        return mixer.apply({"params": seeded.nest(p)}, u_.astype(jnp.bfloat16))

    def dense(p, u_):
        full = {("mixer",) + path: a for path, a in p.items()}
        with jax.default_matmul_precision("highest"):
            return reference.latent_attention(u_[0], full, ("mixer",), sizes, False)[None]

    def values(fn):
        def loss(p, u_):
            o = fn(p, u_)
            return jnp.sum(o.astype(jnp.float32) * go), o
        (_, o), (dp, du) = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(leaves, u)
        return {"o": o, "du": du, **{"d_" + "/".join(path): a for path, a in dp.items()}}

    rel = _rel_l2(values(fast), values(dense))
    both = jax.jit(jax.grad(lambda p, u_: jnp.sum(fast(p, u_).astype(jnp.float32) * go),
                            (0, 1)))
    ms = {kind: _ms_a_call(fn, (leaves, u), cfg["calls"])
          for kind, fn in (("fwd", jax.jit(fast)), ("fwd_bwd", both))}
    _emit("mla_mixer_no_gate", t0, clock, seq=cfg["seq"], hidden=cfg["hidden"],
          heads=cfg["heads"], qk_dims=cfg["nope"] + cfg["rope"], v_dims=cfg["v_dim"],
          interpret=not on_tpu,
          compared="o, du and the five leaves' gradients of the latent-attention mixer "
                   "(no gate, interleaved pairs, bfloat16) against the dense float32 "
                   "oracle that turns the pairs in place: relative L2; ms a call, host clock",
          rel_l2=rel, ms_per_call=ms, rel_l2_tol=LOGITS_L2_RTOL)
    for n, gap in rel.items():
        assert gap <= LOGITS_L2_RTOL, f"{n}: {gap} from the dense oracle in relative L2"


def _rebuild_native():
    """The plan compiler on this path loads the native library when it is
    there; the chip tool copies the disk, so build it from the committed
    sources instead of trusting whatever binary travelled."""
    if os.path.exists(native._LIB_PATH):
        os.remove(native._LIB_PATH)
    if not native.build() or native.get_lib() is None:
        raise RuntimeError(
            "could not build bluefog_tpu/native/libbluefog_native.so from "
            "the committed sources (make/g++ failed)")


def run(args, device):
    on_tpu = device["platform"] == "tpu"
    sizes = TINY if args.rehearse else FULL
    clock = _CompileClock()
    t0 = time.perf_counter()
    _rebuild_native()
    # the rehearsal's CPU programs are no use to the chip and are not kept
    cache_dir = use_compile_cache() if on_tpu else None
    n = args.chips
    bf.init(devices=jax.devices()[:n])
    bf.set_topology(topology_util.ExponentialTwoGraph(n))
    ctx = basics.context()
    _emit("init", t0, clock, ranks=n, compile_cache_dir=cache_dir,
          jax=jax.__version__, mesh_devices=[str(d) for d in ctx.devices])

    want = lambda phase: args.only in (None, phase)
    if want("ops_windows"):
        phase_ops_windows(n, sizes["gossip_elems"], args.seed, clock)
    if n > 1 and want("buckets_vs_per_leaf"):
        # the cell's weights, which round
        assert set(np.float32(ctx.plan.self_weights)) == {np.float32(1 / 3)}
        phase_buckets_vs_per_leaf(ctx, sizes["resnet"], args.seed, clock)
    if any(map(want, ("resnet_atc", "resnet_allreduce", "contraction"))):
        t0 = time.perf_counter()
        job = _ResNetJob(sizes["resnet"], args.seed)
        jax.block_until_ready((job.variables, job.batch, job.labels))
        _emit("resnet_setup", t0, clock, made="seeded variables and batch")
        if want("resnet_atc"):
            phase_resnet(job, "resnet_atc",
                         CommunicationType.neighbor_allreduce, ctx.plan, clock)
        if want("resnet_allreduce"):
            phase_resnet(job, "resnet_allreduce", CommunicationType.allreduce,
                         None, clock)
        if n > 1 and want("contraction"):
            phase_contraction(job, clock)
        del job
    if n == 1:
        if want("decoder"):
            phase_decoder(sizes["decoder"], args.seed, on_tpu, clock)
        if want("experts_piled"):
            phase_experts_piled(sizes["experts"], args.seed, clock)
        if want("kda_vs_recurrence"):
            phase_kda(sizes["kda"], args.seed, on_tpu, clock)
        if want("gdn_vs_recurrence"):
            phase_gdn(sizes["gdn"], args.seed, on_tpu, clock)
        if want("mla_two_head_sizes"):
            phase_mla(sizes["mla"], args.seed, on_tpu, clock)
        if want("mla_mixer_no_gate"):
            phase_mla_mixer(sizes["mla_mixer"], args.seed, on_tpu, clock)
        if want("shared_heads"):
            phase_shared_heads(sizes["shared_heads"], args.seed, on_tpu, clock)
        if want("subtiles"):
            phase_subtiles(sizes["subtiles"], args.seed, on_tpu, clock)
        if want("ssd"):
            phase_ssd(sizes["ssd"], args.seed, on_tpu, clock)
        if want("conv"):
            phase_conv(sizes["conv"], args.seed, on_tpu, clock)
        if want("short_conv"):
            phase_short_conv(sizes["short_conv"], args.seed, on_tpu, clock)
    bf.shutdown()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never prints ok: true")
    ap.add_argument("--only", choices=PHASES,
                    help="run this phase alone, with the set-up it needs")
    args = ap.parse_args()

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": args.chips}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              "device(s)", file=sys.stderr)
        return 2

    ok = False
    try:
        run(args, device)
        ok = on_tpu and not args.rehearse
    finally:
        sys.stderr.flush()
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

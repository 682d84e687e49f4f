"""Flash attention: blockwise XLA forward/backward + a Pallas TPU kernel.

**What the chip records hold (my chip runs, PR 36, `PERF.md` sections 5
and 6; TPU v5e, one chip, a traced run of the cell
`smallthinker-21b-a3b-atc-warmup-b2-s8k-1chip`: B2 S8192, 28 heads of 128,
bfloat16, 1024 x 1024 blocks, device time a call, visible query-key pairs
only counted as work):** the banded kernels (``window=4096``, 30 of the 64
tiles visited): forward 7.42 ms (97 TF/s, 49 % of the bf16 peak), dK/dV
10.46 ms (138 TF/s, 70 %), dQ 7.61 ms (142 TF/s, 72 %); the whole-sequence
causal kernels (``window=None``, 36 tiles): forward 9.10 ms (106 TF/s),
dK/dV 13.76 ms (140 TF/s), dQ 10.49 ms (138 TF/s).  Before the backward
kernels walked their cut tiles in sub-tiles (PR 29 and PR 34): 10.87 and
8.22, 13.93 and 10.81 ms.  **No other speed is stated in this file as
today's**: where a choice rests on a 2026-07 reading whose record is
deleted, the comment says so and says "not measured".

Two interchangeable forwards behind one ``impl`` switch ("auto" default =
the Pallas kernel): a hand Pallas kernel and an online-softmax blockwise
computation in plain XLA (``impl="xla"``).  The Pallas kernels against the
XLA path, forward alone or in training: not measured (no cell runs
``impl="xla"``; the 2026-07 readings that chose "auto" — the Pallas forward
several times faster, training with the XLA forward an order of magnitude
slower because the unrolled blockwise forward inside the custom-vjp
recompute wrecks the backward schedule under jit — went with their
records).
Both share the custom-VJP blockwise backward and produce identical
(o, lse) contracts; interpret mode always runs the Pallas logic so CPU
tests exercise the kernel.

No sibling in the reference — it has no attention at all (SURVEY.md §2.3) —
but the rebuild's transformer workloads (BERT push-sum fine-tune, Llama
gossip pretraining; BASELINE configs #3/#5) spend their FLOPs here, so the
hot op gets a hand kernel the way the reference hand-codes its hot combine
loops in native code (``nccl_controller.cc`` [U]).

Forward: the standard online-softmax blocking (Dao et al., arXiv:2205.14135;
blockwise form as in Liu et al., arXiv:2310.01889): grid over
``(batch*heads, q_blocks, k_blocks)`` with the k axis innermost, carrying
running max ``m``, normalizer ``l`` and the output accumulator in VMEM
scratch across k iterations — O(T·block) memory instead of O(T²), q/k block
matmuls on the MXU, fp32 accumulation regardless of input dtype.  Causal
masking works on *global* positions: the query/key start offsets ride in as
SMEM scalars, so the same compiled kernel serves the single-device case
(offsets 0) and one hop of ring attention (offsets = rotating block
positions, including fully-masked hops, which predicate away at runtime).

Backward: custom VJP that recomputes per-k-block probabilities from the
saved logsumexp (the flash trick — no O(T²) residuals).  The default is
a PAIR OF PALLAS KERNELS (dK/dV accumulated over q blocks, dQ over k
blocks, probability tiles live only in VMEM): the earlier XLA
``fori_loop`` backward materialized `[BH, T, block_k]` f32 tiles in HBM
per k-block and was memory-bound (a 2026-07 reading whose record is
deleted; the Pallas backward against it: not measured by any cell, the
decoder cells time the Pallas backward alone).
The XLA backward remains behind ``impl="xla"``.  The lse output is
itself differentiable (its cotangent folds into the dS term), which is
what lets ring attention's logsumexp *merge* train end-to-end.

A causal *band* (``window=``, PR 29): query ``i`` sees key ``j`` iff ``0 <=
i - j < window``.  With static equal offsets the three kernels' inner grid
axis runs over the blocks the band touches and no others (:class:`_Band`:
the index maps start at the band's first block, clamped, so a block
outside the band is neither visited nor fetched); edge tiles are masked
by one broadcast subtract and two compares, interior tiles not at all.
With traced offsets (a ring hop) the dynamic-offset kernels mask on global
positions and skip tiles outside the band at run time.  ``window=None``
traces what it always traced (the interpret-mode lowering is the parent's
byte for byte; the compiled kernel's serialized form carries source line
numbers, which moved).

*Cut tiles in sub-tiles* (PR 36): with static offsets and blocks of 1024,
the two backward kernels do not compute whole and mask a tile that the
band's edge or the causal diagonal cuts.  One rolled ``lax.fori_loop`` walks
its four square sub-tiles of 512 (:func:`_sub_edge`, :func:`_walk_cut_tile`:
slices of the blocks already in VMEM, ``pl.ds`` at a multiple of the edge):
a sub-tile that sees nothing is skipped, one that sees everything runs the
unmasked body, the rest the masked one, three of four on the diagonal and on
the band's lower edge.  The blocks, the grid and what is fetched are as they
were.  Rolled, not unrolled: a kernel carries two bodies of a sub-tile in
place of one of a tile and less generated code than before, where sixteen
unrolled bodies a kernel multiplied what every run traces, lowers and loads
(PR 32's 7.7 s of `setup_s`).  The forward kernel, blocks under 1024 and the
dynamic-offset kernels lower to what they lowered to; :func:`_sub_edge` says
what the chip read.

On non-TPU platforms the same kernel runs in Pallas interpret mode (tests
exercise the real kernel logic on the CPU mesh).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.parallel._util import vma_full
from bluefog_tpu.telemetry import registry as _telemetry

__all__ = ["flash_attention", "flash_attention_with_lse", "make_flash_attention_fn"]

_NEG_INF = -1e30  # finite mask sentinel (real scores can never reach it)
_MASK_THRESH = -0.5e30  # "was this entry masked" test after sentinel fill
_LANES = 128
# Total lane width of the per-row-scalar tiles.  The forward's lse
# output uses the full width; the backward packs BOTH scalars (lse, corr)
# into one tile of this width — each gets _SCALAR_LANES/2 lanes — and
# re-reads one such tile per (q-block, k-block) pair.  History of the
# choice (2026-07, records deleted; the lane width against another: not
# measured by any cell, the decoder cells run at 8):
# - 512^2 blocks: 8 lanes lost to one packed 128-lane tile (the narrow
#   512x8 f32 DMA cost more than the fat reads, which fwd+bwd overlap hid).
# - 1024^2 blocks (the default since): the conclusion flipped; a 1024-row
#   scalar tile amortizes the narrow DMA that the 512-row tile could not,
#   and 16x fewer scalar bytes win.  8 ships.
_SCALAR_LANES = 8
_MAX_UNROLL = 64  # triangular fast paths unroll at most this many k blocks
_SUB_EDGE = 512  # see _sub_edge


def _score_operand(q, dtype, scale):
    """The q matmul operand with the softmax scale folded where possible.

    Returns ``(q_operand, scale_scores)``: an exact power-of-two scale
    folds losslessly; any other scale stays on the f32 scores
    (``scale_scores=True``) — shared by the forward and both backward
    kernels."""
    if _scale_folds_exactly(scale):
        return q * jnp.asarray(scale, dtype), False
    return q, True


def _use_triangular(causal, tri_delta, tq, tk, num_k):
    """Shared gate for the fwd/bwd triangular fast paths: static offsets
    with a small non-negative key-ahead delta (0 = aligned; 1 = the striped
    ring's strict-lower-triangle hops), square shapes, bounded unroll."""
    return (causal and tri_delta is not None and tq == tk
            and num_k <= _MAX_UNROLL)


def _tri_mask(rows, block_k, delta=0):
    """Causal mask for a q-row slice starting exactly at the k block, with
    keys shifted ``delta`` positions ahead (visible iff col + delta <= row)."""
    return jnp.arange(rows)[:, None] >= jnp.arange(block_k)[None, :] + delta


def _default_interpret() -> bool:
    # interpret mode is how the CPU suite runs the kernel; on a TPU the
    # kernel is compiled.  Entry points that found a TPU pass
    # ``interpret=False`` themselves, so a wrong guess here cannot turn
    # their chip run into an interpreted one in silence.
    return jax.devices()[0].platform != "tpu"


def _block_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _default_blocks(tq, tk, block_q, block_k, window=None):
    """Sequence-adaptive block defaults: 1024 x 1024 whenever the sequence
    admits it, 512 below, and 512 under a band narrower than 1024 keys,
    whatever the sequence.

    What chose them.  1024 over 512 from 2,048 tokens: at 8,192 tokens the
    whole-sequence kernels (48 query heads on 8 of 128; forward and backward
    together, host clock over 20 calls, my chip runs, PR 35) read 29.5 ms at
    1024x1024 against 44.2 at 512x512; at 2,048 tokens: not measured by any
    cell (the 2026-07 readings that moved the default there, on invented
    widths, went with their records).  2048x2048 fails to compile (a
    [2048, 2048] f32 score tile plus accumulators exceeds what Mosaic will
    carry).  512 under a narrow band: a window layer of the `laguna-xs.2`
    cell (64 query heads on 8 of 128, 8,192 tokens, 512 keys; same clock,
    PR 35) read 13.8 ms at 512x512 (2.0 pairs computed for one seen), 17.3
    at 1024x1024 (3.9), 17.9 at 1024x512 (3.0; the forward alone 7.2
    against 5.3), 15.9 at 256x512, 20.8 at 256x256 (1.5) and at 512x256.
    Those were whole tiles; the backward kernels have since walked the
    tiles a mask cuts in sub-tiles (:func:`_sub_edge` says what the edge
    was chosen from)."""
    big = max(tq, tk) >= 2048 and (window is None or window >= 1024)
    if block_q is None:
        block_q = 1024 if big else 512
    if block_k is None:
        block_k = 1024 if big else 512
    return block_q, block_k


def _sub_edge(block_q, block_k):
    """The edge of the square sub-tiles in which the static-offset backward
    kernels walk a tile that the mask cuts (a band's edge, the causal
    diagonal), or None where they compute it whole and mask: 512 under blocks
    of 1024, nothing under smaller ones.

    Chosen from a sweep on the chip (`chip_smoke.py --only subtiles`, my chip
    runs, PR 36; TPU v5e, 8,192 tokens, heads of 128, host clock over 20
    calls, ms a call whole / 512 / 256 / 128).  A SmallThinker window layer
    (2 x 28 heads, window 4096, 1024 x 1024): dK/dV 11.37 / 10.95 / 14.52 /
    18.25, dQ 8.71 / 8.10 / 9.02 / 13.70, the forward 8.87 / 9.28 / 12.82 /
    17.81.  Its whole-sequence layer: dK/dV 14.90 / 14.73 / 17.18 / 19.64, dQ
    11.22 / 10.90 / 11.56 / 14.64, the forward 9.54 / 11.01 / 13.29 / 16.57.
    A Laguna window layer (64 on 8 heads, window 512, 512 x 512): dK/dV 4.49
    / - / 7.28 / 9.83, dQ 3.25 / - / 3.83 / 7.40, the forward 5.19 / - / 7.95
    / 11.65.  So the backward kernels take 512, and **the forward kernel
    walks nothing**: a rolled loop's body is scheduled alone, and a sub-tile
    of the forward (its row maxima, its accumulator rescaled once a
    sub-tile) ran 2.4 to 4 times slower a pair than the same pairs inside
    the whole tile's body, which no skipped quarter pays for.  256 computes
    10 sub-tiles of 16 where 512 computes 3 of 4, and loses more to that than
    it skips.  Mosaic unrolls a loop whole or not at all
    (``fori_loop(unroll=)`` takes 1 or the trip count), and whole is sixteen
    bodies a kernel (PR 32's form: 22.6 MB of code, 7.7 s of every run's
    set-up)."""
    if min(block_q, block_k) < 2 * _SUB_EDGE or block_q % _SUB_EDGE or block_k % _SUB_EDGE:
        return None
    return _SUB_EDGE


def _fit_block(t, b):
    """Largest power-of-two shrink of ``b`` that divides sequence length
    ``t`` (capped at ``t`` itself), so default block sizes adapt to short or
    odd shards instead of raising.  Lengths whose largest fitting block is
    degenerate (< 8 sublanes, e.g. odd primes) still raise loudly — a
    near-1-row Pallas grid would be pathologically slow or fail Mosaic
    layout opaquely."""
    b = min(b, t)
    while t % b and b > 1:
        b = max(b // 2, 1)
    if b < 8 and b < t:
        raise ValueError(
            f"no block size >= 8 divides sequence length {t} (best fit {b}); "
            f"pad the sequence/shard to a multiple of 8"
        )
    return b


def _out_struct(shape, dtype, operands):
    """ShapeDtypeStruct whose varying-mesh-axes set is the union of the
    operands' (required under shard_map's vma checking; empty outside)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _scale_folds_exactly(scale: float) -> bool:
    """True when ``scale`` is a power of two — folding it into a bf16
    operand is then an exact exponent shift (head dim a power of 4, e.g.
    D=64 -> 1/8).  Otherwise folding would round q*scale to bf16 and the
    scale stays on the f32 scores."""
    m, _ = math.frexp(scale)
    return scale > 0 and m == 0.5


def _aligned_mask(s, block_q, block_k, delta):
    """Cheap diagonal-tile causal mask for the aligned (static-offset) fast
    path: one broadcast compare of a [bq,1] row iota against a [1,bk]
    column iota, instead of two full-tile 2D iotas + add + compare.
    Visible iff col + delta <= row (delta 0 = aligned; 1 = the striped
    ring's strict-lower-triangle hops)."""
    row = lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return jnp.where(col + delta <= row, s, _NEG_INF)


def _visible(qpos, kpos, window):
    """Causal visibility on global positions, banded when ``window`` is set
    (``window=None`` traces exactly the compare it always did)."""
    if window is None:
        return kpos <= qpos
    return (kpos <= qpos) & (qpos - kpos < window)


def _and_in_window(seen, first_k, last_q, block_q, block_k, window):
    """The dynamic-offset kernels' runtime skip, narrowed to the band: a
    tile under the diagonal (``seen``) runs only if its first query is
    within ``window`` of its last key."""
    if window is None:
        return seen
    return seen & (last_q - (block_q - 1) - (first_k + block_k - 1) < window)


def _band_mask(s, block_q, block_k, offset, window):
    """Causal band mask of one tile: visible iff ``0 <= qpos - kpos <
    window``, with ``offset`` = (first row's position - first column's), a
    traced scalar.  One [bq,1] - [1,bk] broadcast subtract, two compares."""
    row = lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    d = offset + row - col
    return jnp.where((d >= 0) & (d < window), s, _NEG_INF)


class _Band:
    """Which blocks a causal band ``0 <= i - j < window`` touches, for
    statically aligned offsets (query i and key i at the same position).
    The kernels' inner grid axis runs over the ``steps`` blocks a block of
    the outer axis can touch, not over all of them: blocks wholly outside
    the band are never visited, and never fetched.  All methods take a
    program id (traced int32) or a Python int."""

    def __init__(self, block_q, block_k, window, num_q, num_k):
        self.bq, self.bk, self.w = block_q, block_k, window
        self.num_q, self.num_k = num_q, num_k
        # inner-axis lengths: the most blocks any outer block touches
        self.k_steps = max(self.k_hi(i) - self.k_lo(i) + 1 for i in range(num_q))
        self.q_steps = max(self.q_hi(j) - self.q_lo(j) + 1 for j in range(num_k))

    # k blocks seen by q block i: positions [i*bq - w + 1, (i+1)*bq - 1]
    def k_lo(self, i):
        first = i * self.bq - self.w + 1
        if isinstance(first, int):
            return max(first, 0) // self.bk
        return lax.div(jnp.maximum(first, 0), self.bk)

    def k_hi(self, i):
        last = (i + 1) * self.bq - 1
        if isinstance(last, int):
            return min(last // self.bk, self.num_k - 1)
        return jnp.minimum(lax.div(last, self.bk), self.num_k - 1)

    # q blocks that see k block j: positions [j*bk, (j+1)*bk - 1 + w - 1]
    def q_lo(self, j):
        first = j * self.bk
        return first // self.bq if isinstance(first, int) else lax.div(first, self.bq)

    def q_hi(self, j):
        last = (j + 1) * self.bk + self.w - 2
        if isinstance(last, int):
            return min(last // self.bq, self.num_q - 1)
        return jnp.minimum(lax.div(last, self.bq), self.num_q - 1)

    def tile(self, iq, jk):
        """(runs, interior, offset) of tile (q block iq, k block jk) known
        to lie inside [lo, lo + steps): ``runs`` is false for the padding
        steps past ``hi``; an interior tile needs no mask."""
        r0, c0 = iq * self.bq, jk * self.bk
        runs = ((c0 <= r0 + self.bq - 1) & (r0 - (c0 + self.bk - 1) < self.w)
                & (iq < self.num_q) & (jk < self.num_k))
        interior = (c0 + self.bk - 1 <= r0) & (r0 + self.bq - 1 - c0 < self.w)
        return runs, interior, r0 - c0

    def when(self, iq, jk, body, cut=None):
        """``body(False)`` on an interior tile; on a tile the band cuts
        ``cut()``, by default the masked body over the whole tile."""
        runs, interior, _ = self.tile(iq, jk)
        pl.when(runs & interior)(lambda: body(False))
        pl.when(runs & jnp.logical_not(interior))(cut or (lambda: body(True)))

    def pairs(self, sub):
        """(visible, computed) query-key pairs of one head over the whole
        sequence: what the mask lets through, and what the kernels compute
        for it when a cut tile is walked in sub-tiles of edge ``sub`` (falsy:
        computed whole)."""
        visible = sum(min(r + 1, self.w) for r in range(self.num_q * self.bq))
        computed = 0
        for i in range(self.num_q):
            for j in range(self.k_lo(i), self.k_hi(i) + 1):
                _, interior, offset = self.tile(i, j)
                if interior or not sub:
                    computed += self.bq * self.bk
                    continue
                computed += sub * sub * sum(
                    _seen_and_full(offset + (a - b) * sub, sub, 0, self.w)[0]
                    for a in range(self.bq // sub) for b in range(self.bk // sub))
        return visible, computed


def _seen_and_full(offset, edge, lo, hi):
    """Whether a square of ``edge``, its first row ``offset`` positions past
    its first column, sees anything of the mask ``lo <= qpos - kpos < hi``
    (``hi`` None: no upper edge), and whether it sees all of it.  Python
    ints or traced scalars."""
    d_min, d_max = offset - (edge - 1), offset + (edge - 1)
    seen, full = d_max >= lo, d_min >= lo
    if hi is not None:
        seen, full = seen & (d_min < hi), full & (d_max < hi)
    return seen, full


class _Part(NamedTuple):
    """One sub-tile of a cut tile: where its rows and columns lie in the
    blocks held in VMEM, and its first row's position less its first
    column's."""
    rows: object
    cols: object
    offset: object


def _walk_cut_tile(block_q, block_k, sub, offset, lo, hi, body):
    """A tile that the mask cuts, as square sub-tiles of edge ``sub`` in one
    rolled loop: ``body(masked, part)`` once for each sub-tile that sees
    anything (``lo <= qpos - kpos < hi`` somewhere in it; ``hi`` None: no
    upper edge), unmasked where it sees everything.  A sub-tile that sees
    nothing is skipped.  Two traced bodies, whatever the count of sub-tiles."""
    nk = block_k // sub

    def step(t, carry):
        a, b = lax.div(t, nk), lax.rem(t, nk)
        part = _Part(pl.ds(pl.multiple_of(a * sub, sub), sub),
                     pl.ds(pl.multiple_of(b * sub, sub), sub),
                     offset + (a - b) * sub)
        seen, full = _seen_and_full(part.offset, sub, lo, hi)
        pl.when(full)(lambda: body(False, part))
        pl.when(seen & jnp.logical_not(full))(lambda: body(True, part))
        return carry

    lax.fori_loop(0, (block_q // sub) * nk, step, 0)


def _span(part, block_q, block_k, sub):
    """(rows, cols, bq, bk) of what one traced body computes: the whole
    tile (``part`` None) or one sub-tile of it."""
    if part is None:
        return slice(None), slice(None), block_q, block_k
    return part.rows, part.cols, sub, sub


def _cut_tile(body, block_q, block_k, sub, band, aligned_delta, iq, jk):
    """What a static-offset kernel runs on a tile its mask cuts: the masked
    body over the whole tile, or with an edge ``sub`` the walk in sub-tiles
    (of the diagonal tile those that are cut lie on the diagonal themselves,
    so the bodies' ``_aligned_mask`` holds for them as it stands).  The
    dynamic-offset paths never call it."""
    if not sub:
        return lambda: body(True)
    if band is not None:
        return lambda: _walk_cut_tile(
            block_q, block_k, sub, band.tile(iq, jk)[2], 0, band.w, body)
    return lambda: _walk_cut_tile(
        block_q, block_k, sub, 0, aligned_delta, None, body)


def _fwd_kernel(qs_ref, ks_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc, m_ref, l_ref,
                *, scale: float, block_q: int, block_k: int, causal: bool,
                num_k: int, aligned_delta, window=None, band=None):
    """One (bh, iq, jk) program: fold k-block jk into the online softmax.

    ``aligned_delta`` (static int or None) enables the aligned fast path:
    offsets are statically equal (+delta), so interior tiles (jk < iq) run
    with NO mask VPU work at all, diagonal tiles get the cheap broadcast
    mask, and the sentinel-row fixup exists only when a fully-masked row is
    actually possible (delta > 0).  The earlier uniform-kernel note ("a
    lax.cond skipping the mask measured slower") held for a runtime-offset
    cond inside one body; the static split compiles two bodies and measured
    faster (see module docstring history).
    """
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    step = jk  # place on the inner grid axis
    if band is not None:
        jk = band.k_lo(iq) + step  # the k block this step holds

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc[...] = jnp.zeros_like(acc)

    def _body(masked):
        # operands stay in their storage dtype (bf16 on TPU — full-rate MXU
        # passes); fp32 happens only in the accumulator via
        # preferred_element_type.  Casting to fp32 first would force the
        # MXU's slow fp32 path and make the kernel slower than dense XLA.
        # Scale folding: see _score_operand.
        q, scale_scores = _score_operand(q_ref[0], q_ref.dtype, scale)
        k = k_ref[0]  # [block_k, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k] fp32
        if scale_scores:
            s = s * scale
        sentinel_rows = False
        if masked:
            if band is not None:
                s = _band_mask(s, block_q, block_k, band.tile(iq, jk)[2],
                               band.w)
                # a row of the band's lower-edge tile may see none of its keys
                sentinel_rows = True
            elif aligned_delta is None:
                qpos = qs_ref[0, 0] + iq * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                kpos = ks_ref[0, 0] + jk * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1
                )
                s = jnp.where(_visible(qpos, kpos, window), s, _NEG_INF)
                sentinel_rows = True  # dynamic offsets: fully-masked rows possible
            else:
                s = _aligned_mask(s, block_q, block_k, aligned_delta)
                # delta == 0: every row of a diagonal tile sees >= 1 key,
                # masked entries underflow to 0 through exp(s - m_new)
                sentinel_rows = aligned_delta > 0
        m_prev = m_ref[:, :1]  # [block_q, 1] (replicated columns)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # [block_q, 1]
        p = jnp.exp(s - m_new)  # [block_q, block_k]
        if sentinel_rows:
            # fully-masked rows have m_new == sentinel and would otherwise
            # contribute exp(0) == 1 per entry
            p = jnp.where(s > _MASK_THRESH, p, 0.0)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if band is not None:
        band.when(iq, jk, _body)
    elif causal and aligned_delta is not None:
        pl.when(jk < iq)(lambda: _body(False))
        pl.when(jk == iq)(lambda: _body(True))
    elif causal:
        # predicate away k blocks entirely above the diagonal (runtime skip:
        # the offsets are dynamic, so this can't prune at compile time)
        first_k = ks_ref[0, 0] + jk * block_k
        last_q = qs_ref[0, 0] + (iq + 1) * block_q - 1
        pl.when(_and_in_window(first_k <= last_q, first_k, last_q, block_q,
                               block_k, window))(lambda: _body(True))
    else:
        _body(False)

    @pl.when(step == num_k - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse = m_ref[:, :_SCALAR_LANES] + jnp.log(
            jnp.maximum(l_ref[:, :_SCALAR_LANES], 1e-30))
        lse_ref[0] = lse.astype(jnp.float32)


def _aligned_or_none(tri_delta, causal, tq, tk, block_q, block_k):
    """The Pallas aligned fast path needs: causal, statically-equal offsets
    (+delta <= 1), square shapes, and equal block sizes (tile (i, j) sits
    exactly on the diagonal iff i == j).  delta <= 1 is load-bearing: the
    path leaves interior tiles (jk < iq) UNMASKED, which is exactly valid
    for delta 0 (aligned) and 1 (the striped ring's strict lower
    triangle); at delta >= 2 the last key of tile iq-1 would be a future
    position for the first row of q block iq.  Larger static deltas fall
    back to the general masked path."""
    if (causal and tri_delta is not None
            and tri_delta <= 1 and tq == tk and block_q == block_k):
        return tri_delta
    return None


def _gauge_pairs(kind, band, sub):
    """`attention.pairs_visible_<kind>` and `.pairs_computed_<kind>` at trace
    time, a head and a sequence: what the mask lets through and what each
    backward kernel computes for it (the forward computes its tiles whole,
    ``band.pairs(0)``).  XProf and tests read them."""
    reg = _telemetry.get_registry()
    if reg.enabled:
        visible, computed = band.pairs(sub)
        reg.gauge(f"attention.pairs_visible_{kind}").set(visible)
        reg.gauge(f"attention.pairs_computed_{kind}").set(computed)


def _kv_head_of(q, k):
    """Folded query head ``b`` (of ``[B * H, T, D]``) -> the folded key-value
    head it reads (of ``[B * KV, T, D]``): ``b // (H / KV)``, since query head
    ``h`` reads key-value head ``h // (H / KV)``.  The kernels' index maps
    fetch the shared head where it lies; with equal head counts the map is
    the identity and traces nothing."""
    group = q.shape[0] // k.shape[0]
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _band_or_none(window, tri_delta, causal, tq, tk, block_q, block_k):
    """The banded grid needs what the aligned fast path needs, at delta 0:
    statically equal offsets and square shapes.  Otherwise a window is
    masked on global positions by the dynamic-offset kernels, which skip
    tiles outside the band at run time."""
    if window is None or not causal or tri_delta != 0 or tq != tk:
        return None
    return _Band(block_q, block_k, window, tq // block_q, tk // block_k)


def _flash_fwd(q, k, v, q_start, k_start, *, scale, causal, block_q, block_k,
               interpret, tri_delta=None, window=None):
    """q,k,v: [BH, T, D]; q_start/k_start: int32 scalars (global offsets).

    Returns (o [BH, Tq, D], lse [BH, Tq]).
    """
    bh, tq, d = q.shape
    tk, vd = k.shape[1], v.shape[2]  # values may be of another head size
    block_q, block_k = _default_blocks(tq, tk, block_q, block_k, window)
    block_q = _fit_block(tq, block_q)
    block_k = _fit_block(tk, block_k)
    num_q, num_k = tq // block_q, tk // block_k
    kv_of = _kv_head_of(q, k)

    qs = jnp.asarray(q_start, jnp.int32).reshape(1, 1)
    ks = jnp.asarray(k_start, jnp.int32).reshape(1, 1)
    kv_map = lambda b, i, j: (kv_of(b), j, 0)
    kernel_kw, call_kw, aligned = {}, {}, None
    if window is None:
        aligned = _aligned_or_none(tri_delta, causal, tq, tk, block_q, block_k)
    else:
        band = _band_or_none(window, tri_delta, causal, tq, tk, block_q, block_k)
        kernel_kw = dict(window=window, band=band)
        call_kw = dict(name="flash_fwd_window")  # as the device trace names it
        if band is not None:
            num_k = band.k_steps  # the inner axis visits the band only
            kv_map = lambda b, i, j: (
                kv_of(b), jnp.minimum(band.k_lo(i) + j, band.k_hi(i)), 0)
    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        num_k=num_k,
        aligned_delta=aligned,
        **kernel_kw,
    )
    smem = pl.BlockSpec((1, 1), lambda b, i, j: (0, 0),
                        memory_space=pltpu.SMEM)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_k),
        in_specs=[
            smem,
            smem,
            _block_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _block_spec((1, block_k, d), kv_map),
            _block_spec((1, block_k, vd), kv_map),
        ],
        out_specs=[
            _block_spec((1, block_q, vd), lambda b, i, j: (b, i, 0)),
            _block_spec((1, block_q, _SCALAR_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((bh, tq, vd), q.dtype, (q, k, v)),
            _out_struct((bh, tq, _SCALAR_LANES), jnp.float32, (q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, vd), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        **call_kw,
    )(qs, ks, q, k, v)
    return o, lse[:, :, 0]


def _blockwise_fwd_xla(q, k, v, q_start, k_start, *, scale, causal, block_k,
                       tri_delta, window=None):
    """Online-softmax blockwise forward in plain XLA; same math and
    (o, lse) contract as the Pallas kernel.

    Selectable via ``impl="xla"``; not the auto default (its speed
    against the Pallas kernel: not measured, see the module docstring).
    Kept as the independent same-contract implementation (numerics
    cross-check, non-Mosaic fallback).
    """
    bh, tq, d = q.shape
    tk = k.shape[1]
    _, block_k = _default_blocks(tq, tk, None, block_k)
    block_k = _fit_block(tk, block_k)
    num_k = tk // block_k
    f32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    # a window is masked in the general loop below: every block runs (this
    # is the fall-back; the Pallas kernels skip what the band leaves out)
    if window is None and _use_triangular(causal, tri_delta, tq, tk, num_k):
        # triangular unroll: k block j touches only q rows >= j*block_k
        o = vma_full(q, (bh, tq, v.shape[2]), jnp.float32)
        m = vma_full(q, (bh, tq, 1), jnp.float32, _NEG_INF)
        l = vma_full(q, (bh, tq, 1), jnp.float32)
        for j in range(num_k):
            r0 = j * block_k
            kb, vb = k[:, r0:r0 + block_k], v[:, r0:r0 + block_k]
            s = f32("bqd,bkd->bqk", q[:, r0:], kb) * scale
            s = jnp.where(_tri_mask(tq - r0, block_k, tri_delta)[None], s,
                          _NEG_INF)
            m_new = jnp.maximum(m[:, r0:], s.max(-1, keepdims=True))
            alpha = jnp.exp(m[:, r0:] - m_new)
            p = jnp.exp(s - m_new)  # masked entries underflow to 0...
            if tri_delta:
                # ...except on fully-masked rows (rows < delta), where
                # m_new is the sentinel and exp(0) would be 1
                p = jnp.where(s > _MASK_THRESH, p, 0.0)
            l = l.at[:, r0:].set(l[:, r0:] * alpha + p.sum(-1, keepdims=True))
            o = o.at[:, r0:].set(
                o[:, r0:] * alpha + f32("bqk,bkd->bqd", p.astype(v.dtype), vb)
            )
            m = m.at[:, r0:].set(m_new)
    else:
        qpos = q_start + jnp.arange(tq)

        def body(j, carry):
            o, m, l = carry
            kb = lax.dynamic_slice_in_dim(k, j * block_k, block_k, axis=1)
            vb = lax.dynamic_slice_in_dim(v, j * block_k, block_k, axis=1)
            s = f32("bqd,bkd->bqk", q, kb) * scale
            if causal:
                kpos = k_start + j * block_k + jnp.arange(block_k)
                s = jnp.where(
                    _visible(qpos[:, None], kpos[None, :], window)[None], s,
                    _NEG_INF)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            if causal:
                # fully-masked rows: m_new is the sentinel, exp(0) would be 1
                p = jnp.where(s > _MASK_THRESH, p, 0.0)
            l = l * alpha + p.sum(-1, keepdims=True)
            o = o * alpha + f32("bqk,bkd->bqd", p.astype(v.dtype), vb)
            return o, m_new, l

        o, m, l = lax.fori_loop(
            0, num_k,
            body,
            (vma_full(q, (bh, tq, v.shape[2]), jnp.float32),
             vma_full(q, (bh, tq, 1), jnp.float32, _NEG_INF),
             vma_full(q, (bh, tq, 1), jnp.float32)),
        )

    out = (o / jnp.maximum(l, 1e-30)).astype(q.dtype)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    return out, lse


def _bwd_dkv_kernel(qs_ref, ks_ref, q_ref, g_ref, aux_ref,
                    k_ref, v_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale: float, block_q: int, block_k: int,
                    causal: bool, num_q: int, aligned_delta, half: int,
                    window=None, band=None, group: int = 1, sub=None):
    """One (bh, jk, iq) program: fold q-block iq into dK/dV of k-block jk.

    Same recompute-from-lse trick as the XLA backward, but the
    [block_q, block_k] probability/score tiles live and die in VMEM —
    the XLA path materializes them per k-block in HBM.
    ``aligned_delta``: see :func:`_fwd_kernel`.  ``aux_ref`` packs the two
    per-row scalars in one tile (lse in lanes [:half], corr in [half:]) —
    one scalar DMA per grid step instead of two.  ``group`` > 1: the grid is
    (key-value head, jk, query head of the group, iq) and the accumulators
    run over the group's query heads too, so dK/dV of a shared head leave
    the kernel summed.
    """
    jk = pl.program_id(1)
    iq = pl.program_id(2 if group == 1 else 3)
    step = iq  # place on the inner grid axis
    if band is not None:
        iq = band.q_lo(jk) + step  # the q block this step holds

    def at(inner, member):  # this step, of this member of the group
        if group == 1:
            return step == inner
        return (step == inner) & (pl.program_id(2) == member)

    @pl.when(at(0, 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body(masked, part=None):
        rows, cols, bq, bk = _span(part, block_q, block_k, sub)
        q = q_ref[0, rows]  # [bq, D]
        g = g_ref[0, rows]  # [bq, D]
        k = k_ref[0, cols]  # [bk, D]
        v = v_ref[0, cols]  # [bk, D]
        lse = aux_ref[0, rows][:, :1]  # [bq, 1]
        corr = aux_ref[0, rows][:, half:half + 1]
        qk, scale_scores = _score_operand(q, q_ref.dtype, scale)
        s = jax.lax.dot_general(
            qk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk] fp32
        if scale_scores:
            s = s * scale
        if masked:
            if band is not None:
                s = _band_mask(
                    s, bq, bk,
                    band.tile(iq, jk)[2] if part is None else part.offset, band.w)
            elif aligned_delta is None:
                qpos = qs_ref[0, 0] + iq * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                kpos = ks_ref[0, 0] + jk * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(_visible(qpos, kpos, window), s, _NEG_INF)
            else:
                s = _aligned_mask(s, bq, bk, aligned_delta)
            # masked entries (and whole sentinel-lse rows) exp to exactly 0
            p = jnp.exp(jnp.where(s > _MASK_THRESH, s - lse, _NEG_INF))
        else:
            # interior tile: nothing is masked and (aligned path) no
            # sentinel-lse row can appear here — plain recompute
            p = jnp.exp(s - lse)
        dv_acc[cols] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # ds stays UNSCALED per tile; scale multiplies the f32 accumulator
        # once at _finish (a [block_k, D] pass instead of a
        # [block_q, block_k] pass per tile — exact, any scale)
        ds = (p * (dp + corr)).astype(q.dtype)
        dk_acc[cols] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    cut = _cut_tile(_body, block_q, block_k, sub, band, aligned_delta, iq, jk)
    if band is not None:
        band.when(iq, jk, _body, cut)
    elif causal and aligned_delta is not None:
        pl.when(iq > jk)(lambda: _body(False))
        pl.when(iq == jk)(cut)
    elif causal:
        # skip q blocks entirely above the diagonal (they reach no k row)
        last_q = qs_ref[0, 0] + (iq + 1) * block_q - 1
        first_k = ks_ref[0, 0] + jk * block_k
        pl.when(_and_in_window(last_q >= first_k, first_k, last_q, block_q,
                               block_k, window))(lambda: _body(True))
    else:
        _body(False)

    @pl.when(at(num_q - 1, group - 1))
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(qs_ref, ks_ref, q_ref, g_ref, aux_ref,
                   k_ref, v_ref, dq_ref, dq_acc,
                   *, scale: float, block_q: int, block_k: int,
                   causal: bool, num_k: int, aligned_delta, half: int,
                   window=None, band=None, sub=None):
    """One (bh, iq, jk) program: fold k-block jk into dQ of q-block iq.
    ``aligned_delta``: see :func:`_fwd_kernel`; ``aux_ref``/``half``: see
    :func:`_bwd_dkv_kernel`."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    step = jk
    if band is not None:
        jk = band.k_lo(iq) + step

    @pl.when(step == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body(masked, part=None):
        rows, cols, bq, bk = _span(part, block_q, block_k, sub)
        q = q_ref[0, rows]
        g = g_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        lse = aux_ref[0, rows][:, :1]
        corr = aux_ref[0, rows][:, half:half + 1]
        qk, scale_scores = _score_operand(q, q_ref.dtype, scale)
        s = jax.lax.dot_general(
            qk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if scale_scores:
            s = s * scale
        if masked:
            if band is not None:
                s = _band_mask(
                    s, bq, bk,
                    band.tile(iq, jk)[2] if part is None else part.offset, band.w)
            elif aligned_delta is None:
                qpos = qs_ref[0, 0] + iq * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                kpos = ks_ref[0, 0] + jk * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(_visible(qpos, kpos, window), s, _NEG_INF)
            else:
                s = _aligned_mask(s, bq, bk, aligned_delta)
            p = jnp.exp(jnp.where(s > _MASK_THRESH, s - lse, _NEG_INF))
        else:
            p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # unscaled ds; scale applied once to the accumulator at _finish
        ds = (p * (dp + corr)).astype(q.dtype)
        dq_acc[rows] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    cut = _cut_tile(_body, block_q, block_k, sub, band, aligned_delta, iq, jk)
    if band is not None:
        band.when(iq, jk, _body, cut)
    elif causal and aligned_delta is not None:
        pl.when(jk < iq)(lambda: _body(False))
        pl.when(jk == iq)(cut)
    elif causal:
        first_k = ks_ref[0, 0] + jk * block_k
        last_q = qs_ref[0, 0] + (iq + 1) * block_q - 1
        pl.when(_and_in_window(first_k <= last_q, first_k, last_q, block_q,
                               block_k, window))(lambda: _body(True))
    else:
        _body(False)

    @pl.when(step == num_k - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, lse, corr, q_start, k_start, g,
                      *, scale, causal, block_q, block_k, interpret,
                      tri_delta=None, window=None, sub=None):
    """dQ/dK/dV via two Pallas kernels; all [BH, T, D].

    ``corr`` is ``g_lse − rowsum(o·g)`` per q row (f32, [BH, Tq]) — the
    dS correction term, precomputed once in XLA.  ``sub``: the edge of a cut
    tile's sub-tiles, for tests and the edge's sweep (None:
    :func:`_sub_edge`'s; 0: cut tiles computed whole).
    """
    bh, tq, d = q.shape
    tk, vd = k.shape[1], v.shape[2]  # values may be of another head size
    block_q, block_k = _default_blocks(tq, tk, block_q, block_k, window)
    block_q = _fit_block(tq, block_q)
    block_k = _fit_block(tk, block_k)
    num_q, num_k = tq // block_q, tk // block_k
    kv_of, group = _kv_head_of(q, k), bh // k.shape[0]
    if sub is None:
        sub = _sub_edge(block_q, block_k)
    # the inner grid axes and which block each of their steps holds: all of
    # them in order, or with a band only those the outer block can touch
    steps_q, steps_k = num_q, num_k
    q_of = lambda j, i: i  # dK/dV kernel: grid (bh, k block, step)
    k_of = lambda i, j: j  # dQ kernel: grid (bh, q block, step)
    kernel_kw, dkv_kw, dq_kw, aligned = {}, {}, {}, None
    if window is None:
        aligned = _aligned_or_none(tri_delta, causal, tq, tk, block_q, block_k)
        if aligned == 0:
            _gauge_pairs("global", _Band(block_q, block_k, tq, num_q, num_k), sub)
    else:
        band = _band_or_none(window, tri_delta, causal, tq, tk, block_q, block_k)
        kernel_kw = dict(window=window, band=band)
        dkv_kw = dict(name="flash_bwd_dkv_window")  # the device trace's names
        dq_kw = dict(name="flash_bwd_dq_window")
        if band is not None:
            _gauge_pairs("window", band, sub)
            steps_q, steps_k = band.q_steps, band.k_steps
            q_of = lambda j, i: jnp.minimum(band.q_lo(j) + i, band.q_hi(j))
            k_of = lambda i, j: jnp.minimum(band.k_lo(i) + j, band.k_hi(i))

    qs = jnp.asarray(q_start, jnp.int32).reshape(1, 1)
    ks = jnp.asarray(k_start, jnp.int32).reshape(1, 1)
    # per-row scalars ride lane-replicated, PACKED in one array (lse in
    # lanes [:half], corr in [half:]): the packed tile is the SAME width
    # as ONE of the old separate lse/corr tiles, so each (q-block,
    # k-block) grid step reads half the scalar bytes in one DMA instead
    # of two
    half = max(_SCALAR_LANES // 2, 1)
    aux = jnp.concatenate(
        [jnp.broadcast_to(lse[..., None], (bh, tq, half)),
         jnp.broadcast_to(corr[..., None], (bh, tq, half))], axis=-1)

    smem = pl.BlockSpec((1, 1), lambda *_: (0, 0), memory_space=pltpu.SMEM)

    def rowspec(index):  # q/g/aux blocks, selected by the q index
        return [
            _block_spec((1, block_q, d), index),
            _block_spec((1, block_q, vd), index),
            _block_spec((1, block_q, 2 * half), index),
        ]

    def kvspec(index):  # k/v blocks, selected by the k index
        return [_block_spec((1, block_k, d), index),
                _block_spec((1, block_k, vd), index)]

    # the dK/dV grid: (head, k block, step), or where a group of query heads
    # shares the head, (key-value head, k block, member of the group, step)
    kernel_group = {}
    if group == 1:
        dkv_grid = (bh, num_k, steps_q)
        dkv_rows = lambda b, j, i: (b, q_of(j, i), 0)
    else:
        dkv_grid = (bh // group, num_k, group, steps_q)
        dkv_rows = lambda b, j, m, i: (b * group + m, q_of(j, i), 0)
        kernel_group = dict(group=group)
    dkv_keys = lambda b, j, *_: (b, j, 0)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
            causal=causal, num_q=steps_q, aligned_delta=aligned, half=half,
            sub=sub, **kernel_kw, **kernel_group),
        grid=dkv_grid,
        in_specs=[smem, smem, *rowspec(dkv_rows), *kvspec(dkv_keys)],
        out_specs=kvspec(dkv_keys),
        out_shape=[
            _out_struct(k.shape, k.dtype, (q, k, v, g)),
            _out_struct(v.shape, v.dtype, (q, k, v, g)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, vd), jnp.float32),
        ],
        interpret=interpret,
        **dkv_kw,
    )(qs, ks, q, g, aux, k, v)

    dq, = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
            causal=causal, num_k=steps_k, aligned_delta=aligned, half=half,
            sub=sub, **kernel_kw),
        grid=(bh, num_q, steps_k),
        in_specs=[smem, smem,
                  *rowspec(lambda b, i, j: (b, i, 0)),
                  *kvspec(lambda b, i, j: (kv_of(b), k_of(i, j), 0))],
        out_specs=[
            _block_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((bh, tq, d), q.dtype, (q, k, v, g)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        **dq_kw,
    )(qs, ks, q, g, aux, k, v)
    return dq, dk, dv


def _blockwise_bwd(q, k, v, o, lse, q_start, k_start, g, g_lse,
                   *, scale, causal, block_k, tri_delta=None, window=None):
    """dQ/dK/dV via per-k-block recompute from lse; all [BH, T, D].

    ``g_lse`` is the lse output's cotangent: d lse/d s is the normalized
    probability row, so it folds into dS as ``p * g_lse`` (used by ring
    attention's merge; zeros for plain attention).  ``tri_delta`` (static
    int or None) asserts static offsets with key-ahead delta and tq == tk,
    enabling the triangular fast path.
    """
    bh, tq, d = q.shape
    tk = k.shape[1]
    _, block_k = _default_blocks(tq, tk, None, block_k)
    block_k = _fit_block(tk, block_k)  # must cover tk exactly, like forward
    num_k = tk // block_k
    # matmul operands stay in their storage dtype (bf16 on TPU) with fp32
    # accumulators — casting up first would force the MXU's slow fp32 path;
    # only elementwise softmax math runs in fp32
    f32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, Tq, 1]
    corr = g_lse.astype(jnp.float32)[..., None] - delta  # [BH, Tq, 1]

    if window is None and _use_triangular(causal, tri_delta, tq, tk, num_k):
        # Triangular fast path: with zero offsets, k block j only reaches q
        # rows >= j*block_k — static slicing halves the causal bwd FLOPs
        # that the dynamic fori_loop below must spend on fully-masked rows.
        dq = q.astype(jnp.float32) * 0.0
        dks, dvs = [], []
        for j in range(num_k):
            r0 = j * block_k
            kb, vb = k[:, r0:r0 + block_k], v[:, r0:r0 + block_k]
            qj, gj = q[:, r0:], g[:, r0:]
            s = f32("bqd,bkd->bqk", qj, kb) * scale
            s = jnp.where(_tri_mask(tq - r0, block_k, tri_delta)[None], s,
                          _NEG_INF)
            p = jnp.exp(s - lse[:, r0:, None])  # masked entries underflow to 0
            if tri_delta:
                # fully-masked rows have sentinel lse: exp would explode
                p = jnp.where(s > _MASK_THRESH, p, 0.0)
            dvs.append(f32("bqk,bqd->bkd", p.astype(gj.dtype), gj))
            dp = f32("bqd,bkd->bqk", gj, vb)
            ds = (p * (dp + corr[:, r0:]) * scale).astype(q.dtype)
            dq = dq.at[:, r0:].add(f32("bqk,bkd->bqd", ds, kb))
            dks.append(f32("bqk,bqd->bkd", ds, qj))
        dk = jnp.concatenate(dks, axis=1)
        dv = jnp.concatenate(dvs, axis=1)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    qpos = q_start + jnp.arange(tq)

    def body(j, carry):
        dq, dk, dv = carry
        kb = lax.dynamic_slice_in_dim(k, j * block_k, block_k, axis=1)
        vb = lax.dynamic_slice_in_dim(v, j * block_k, block_k, axis=1)
        s = f32("bqd,bkd->bqk", q, kb) * scale
        if causal:
            kpos = k_start + j * block_k + jnp.arange(block_k)
            mask = _visible(qpos[:, None], kpos[None, :], window)
            s = jnp.where(mask[None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])  # normalized probs [BH, Tq, block_k]
        if causal:
            p = jnp.where(s[...] > _MASK_THRESH, p, 0.0)
        dvb = f32("bqk,bqd->bkd", p.astype(g.dtype), g)
        dp = f32("bqd,bkd->bqk", g, vb)
        ds = (p * (dp + corr) * scale).astype(q.dtype)
        dq = dq + f32("bqk,bkd->bqd", ds, kb)
        dkb = f32("bqk,bqd->bkd", ds, q)
        dk = lax.dynamic_update_slice_in_dim(dk, dkb, j * block_k, axis=1)
        dv = lax.dynamic_update_slice_in_dim(dv, dvb, j * block_k, axis=1)
        return dq, dk, dv

    # fp32 carries vma-typed like the operands
    init = tuple(vma_full(x, x.shape, jnp.float32) for x in (q, k, v))
    dq, dk, dv = lax.fori_loop(0, num_k, body, init)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _repeat_heads(q, x):
    """The XLA fall-back's way with shared key-value heads: each repeated
    for its group of query heads (the Pallas kernels read them in place)."""
    group = q.shape[0] // x.shape[0]
    return x if group == 1 else jnp.repeat(x, group, axis=0)


def _fwd_dispatch(q, k, v, q_start, k_start, *, scale, causal, block_q,
                  block_k, interpret, tri_delta, impl, window=None):
    """Choose the forward implementation (static): "pallas", "xla", or
    "auto" (= Pallas kernel; "xla" remains selectable).

    Auto = Pallas: under jit the XLA path's unrolled per-block forward
    inside the custom-vjp recompute blows up the backward's schedule (a
    2026-07 reading; the two against each other: not measured by any
    cell, see the module docstring).  Callers can still pass
    impl="xla"."""
    use_xla = impl == "xla"
    if use_xla:
        k, v = _repeat_heads(q, k), _repeat_heads(q, v)
        return _blockwise_fwd_xla(
            q, k, v, q_start, k_start,
            scale=scale, causal=causal, block_k=block_k, tri_delta=tri_delta,
            window=window,
        )
    return _flash_fwd(
        q, k, v, q_start, k_start,
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, tri_delta=tri_delta, window=window,
    )


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash_core(q, k, v, q_start, k_start, scale, causal, block_q, block_k,
                interpret, tri_delta, impl, window):
    """(o, lse) with offsets as float32 scalars (zero-cotangent slots)."""
    return _fwd_dispatch(
        q, k, v, q_start.astype(jnp.int32), k_start.astype(jnp.int32),
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, tri_delta=tri_delta, impl=impl, window=window,
    )


def _flash_core_fwd(q, k, v, q_start, k_start, scale, causal, block_q,
                    block_k, interpret, tri_delta, impl, window):
    o, lse = _fwd_dispatch(
        q, k, v, q_start.astype(jnp.int32), k_start.astype(jnp.int32),
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, tri_delta=tri_delta, impl=impl, window=window,
    )
    # named for a recomputed block's policy: with the output alone kept the
    # forward kernel runs again for the logsumexp; outside a checkpoint a
    # name lowers to nothing
    o, lse = checkpoint_name(o, "attn_out"), checkpoint_name(lse, "attn_lse")
    return (o, lse), (q, k, v, o, lse, q_start, k_start)


def _flash_core_bwd(scale, causal, block_q, block_k, interpret, tri_delta,
                    impl, window, res, cts):
    q, k, v, o, lse, q_start, k_start = res
    g, g_lse = cts
    if impl == "xla":
        dq, dk, dv = _blockwise_bwd(
            q, _repeat_heads(q, k), _repeat_heads(q, v), o, lse,
            q_start.astype(jnp.int32), k_start.astype(jnp.int32), g, g_lse,
            scale=scale, causal=causal, block_k=block_k, tri_delta=tri_delta,
            window=window,
        )
        if dk.shape != k.shape:  # the fall-back repeated the shared heads
            dk, dv = (x.reshape(k.shape[0], -1, *k.shape[1:]).sum(1).astype(k.dtype)
                      for x in (dk, dv))
    else:
        # Pallas backward (default): probability/score tiles stay in VMEM;
        # the XLA blockwise backward materializes them per k-block in HBM.
        delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32),
                        axis=-1)  # [BH, Tq]
        corr = g_lse.astype(jnp.float32) - delta
        dq, dk, dv = _flash_bwd_pallas(
            q, k, v, lse, corr,
            q_start.astype(jnp.int32), k_start.astype(jnp.int32), g,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, tri_delta=tri_delta, window=window,
        )
    return dq, dk, dv, jnp.zeros_like(q_start), jnp.zeros_like(k_start)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_start=0,
    k_start=0,
    causal: bool = True,
    block_q: Optional[int] = None,  # None: sequence-adaptive (see _default_blocks)
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    impl: str = "auto",
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(out, lse) for q, k, v of shape ``[B, T, H, D]``; lse ``[B, H, T]``.

    ``q_start``/``k_start`` are *global* sequence offsets (may be traced),
    letting causal masking span sequence shards — one hop of ring attention
    calls this with the rotating key-block offset.  Rows with no visible
    keys return out=0, lse≈-1e30, which merge correctly.

    ``impl``: "auto" (default = the Pallas kernel, see the module
    docstring), "xla", or "pallas".  ``block_q`` only affects the Pallas kernel; the XLA path
    blocks on ``block_k`` alone.

    ``window`` (static int, causal only): a causal band, query ``i`` sees
    key ``j`` iff ``0 <= i - j < window`` on global positions.  With static
    equal offsets and square shapes the Pallas kernels' inner grid axis
    visits only the blocks the band touches; ``None`` is today's lowering.

    ``k`` and ``v`` may have fewer heads than ``q`` (``[B, T, KV, D]``, KV
    dividing H): query head ``h`` reads head ``h // (H / KV)`` where it
    lies, through the kernels' index maps, and dK/dV come back ``[B, T, KV,
    D]``, summed over each group inside the dK/dV kernel.  Nothing is
    repeated, kept for the backward pass or summed afterwards; equal head
    counts lower to what they always did.

    ``v`` may be of another head size than ``q`` and ``k`` (a latent-attention
    layer's 128 beside 128 + 64 rotary): the scores are scaled by the
    query-key size, the output has the values'.  One size lowers to what it
    always did.
    """
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"impl must be auto/xla/pallas, got {impl!r}")
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window!r} needs causal=True and window >= 1")
    if interpret is None:
        interpret = _default_interpret()
    b, tq, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    if k.shape[2] != v.shape[2] or h % k.shape[2]:
        raise ValueError(
            f"{h} query heads on {k.shape[2]} key and {v.shape[2]} value heads: "
            "keys and values share a head count that divides the queries'")
    if k.shape[3] != d:
        raise ValueError(f"query heads of {d} on key heads of {k.shape[3]}: "
                         "queries and keys share a head size (the values' is their own)")

    def fold(x):  # [B, T, H, D] -> [B*H, T, D]
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], x.shape[1], x.shape[3])

    # static offsets with a small key-ahead delta + square shapes unlock
    # the triangular fast paths (delta 0 = aligned; delta 1 = the striped
    # ring's strict-lower-triangle hops)
    tri_delta = None
    if (isinstance(q_start, int) and isinstance(k_start, int)
            and 0 <= k_start - q_start <= 8 and q.shape[1] == k.shape[1]):
        tri_delta = k_start - q_start
    o, lse = _flash_core(
        fold(q), fold(k), fold(v),
        jnp.asarray(q_start, jnp.float32), jnp.asarray(k_start, jnp.float32),
        scale, causal, block_q, block_k, interpret, tri_delta, impl, window,
    )
    o = o.reshape(b, h, tq, v.shape[3]).transpose(0, 2, 1, 3)
    return o, lse.reshape(b, h, tq)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: Optional[int] = None,  # None: sequence-adaptive (see _default_blocks)
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    impl: str = "auto",
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Memory-efficient exact attention; q, k, v: ``[B, T, H, D]``.

    Drop-in for :func:`bluefog_tpu.models.transformer.dense_attention`
    (same layout/semantics, fp32 softmax), O(T·block) memory.
    """
    o, _ = flash_attention_with_lse(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, impl=impl, window=window,
    )
    return o


def make_flash_attention_fn(
    causal: bool = True,
    block_q: Optional[int] = None,  # None: sequence-adaptive (see _default_blocks)
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    impl: str = "auto",
    window: Optional[int] = None,
) -> Callable:
    """``attention_fn`` for :class:`bluefog_tpu.models.transformer.LlamaLM`."""
    return functools.partial(
        flash_attention,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        impl=impl,
        window=window,
    )

"""The state-space scan of a Mamba-2 layer (Dao and Gu, arXiv:2405.21060) in
its chunked form, forward and backward, as two Pallas TPU kernels.

For every head ``h`` (``P`` channels, a state ``S`` of ``P x N``) and token
``t``, with one step size ``dt[t, h] > 0`` and one decay rate a head::

    a[t, h] = exp(-exp(A_log[h]) dt[t, h])
    S[t, h] = a[t, h] S[t-1, h] + dt[t, h] x[t, h] B[t]^T        S[-1] = 0
    y[t, h] = S[t, h] C[t] + D[h] x[t, h]

``B[t]`` and ``C[t]`` (``N`` numbers each) are shared by the heads of a group.
The chunked form walks the sequence ``chunk`` tokens at a time.  Inside a
chunk, with ``cum[i]`` the running sum of ``log a`` from the chunk's first
token to token ``i``::

    Y = (L * (C B^T)) (dt * X) + exp(cum) * (C S_in^T)
    L[i, j] = exp(cum[i] - cum[j]) for i >= j, else 0
    S_out = exp(cum[last]) S_in + ((dt * X) * exp(cum[last] - cum))^T B

Step sizes, ``log a``, their running sums and the carried state are float32;
the matrix products take operands in ``x``'s type (bfloat16 in training) and
accumulate in float32.

**The kernels** (``ssd_chunk_fwd``, ``ssd_chunk_bwd``: the names the device
trace shows).  Grid ``(batch, chunk, heads / hb)``, the heads innermost, the
chunks in order (the backward kernel walks them last to first); ``hb`` heads
a step, as many as fill 128 lanes (two of 64), so that ``x`` and ``y`` are
read and written where they lie, ``[batch, T, H * P]``, and nothing is
transposed in HBM.  A step holds in VMEM: its heads' ``[chunk, hb * P]``
block of ``x`` (and of ``dy``), the group's ``[chunk, N]`` blocks of ``B``
and ``C``, which are fetched once a chunk because the heads that share them
follow one another, and the per-token scalars ``dt`` and ``cum`` twice, since
the decay matrix needs them down the rows and along the lanes and a transpose
in the kernel costs more than 4 KB of traffic: down the rows every head's at
once (``[chunk, 2 H]``, 128 lanes at 64 heads, fetched once a chunk; a step
rolls the lanes so that its own heads' columns stand first, and the backward
kernel rolls its cotangents back; a block a step of ``2 hb`` lanes would be
padded to 128 in HBM, 32 times its size), along the lanes a step's own
(``[hb, chunk]``).  Scratch: ``C B^T`` (``[chunk, chunk]`` float32, computed at a
group's first step and shared by its heads), and the state of every head
(``[H, P, N]`` float32, 2 MB at 64 heads of 64 x 128), carried from chunk to
chunk, so the recurrence over chunks costs no pass over HBM; the backward
kernel carries the state's cotangent the same way and sums over a group's
heads, in scratch, the cotangent of ``C B^T`` and of ``B`` and ``C`` before
it multiplies once a chunk.  The decay matrix ``L``, the scores and their
cotangents live and die in VMEM: neither pass writes a ``[chunk, chunk]``
matrix to HBM.  The forward pass that is differentiated writes each chunk's
incoming state (``[batch, chunks, H, P, N]`` float32) for the backward pass;
the one that is not, as the first pass under ``jax.checkpoint``, writes
none.  What XLA does around the kernels: ``log a`` and its running sum
within a chunk (``[batch, T, H]`` float32, 2 MB at the cell's shapes), their
transposes, the reverse running sum that turns the kernels' cotangent of
``cum`` into that of ``dt`` and ``A_log``, and ``D x``.

On a platform that is not a TPU the same kernels run in Pallas' interpret
mode, as the flash kernels do.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.kernels.flash_attention import (
    _block_spec, _default_interpret, _out_struct)

__all__ = ["ssd_scan"]

_NEG = -1e30  # log of a masked entry of the decay matrix: exp gives exactly 0
_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a b^T
_TN = (((0,), (0,)), ((), ()))  # a^T b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _heads_per_step(heads_per_group, p):
    """As many heads as fill 128 lanes, and divide a group."""
    hb = max(1, min(_LANES // p, heads_per_group))
    while heads_per_group % hb:
        hb -= 1
    return hb


def _own_columns(cols_ref, step, hb):
    """The [chunk, 2 H] tile of dt and cum turned so that this step's heads
    stand first in each half: lane k is dt of head k of the step, lane H + k
    its cum."""
    lanes = cols_ref.shape[-1]
    return pltpu.roll(cols_ref[0], (lanes - step * hb) % lanes, 1)


def _decay(cols, rows_ref, k, tri):
    """Head k of the step: dt and cum down the rows ([Q, 1]), the decay
    matrix L [Q, Q], cum of the chunk's last token ([1, 1])."""
    half = cols.shape[-1] // 2
    dt, cum = cols[:, k:k + 1], cols[:, half + k:half + k + 1]
    across = rows_ref[0, 0, 0, k:k + 1, :]
    L = jnp.exp(jnp.where(tri, cum - across, _NEG))
    return dt, cum, L, cum[-1:, :]


def _over_lanes(one, like):
    """[1, 1] -> [1, lanes of `like`].  Mosaic broadcasts over lanes or over
    sublanes, not over both at once, and two `broadcast_to` in a row are
    folded into one; a sum is not."""
    return one + jnp.zeros((1, like.shape[-1]), one.dtype)


def _lower_triangle(q):
    return (lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _fwd_kernel(c, step, x_ref, cols_ref, rows_ref, b_ref, c_ref, y_ref, *rest,
                hb, p, steps_per_group, save):
    if save:
        s_out_ref, g_scr, s_scr = rest
    else:
        g_scr, s_scr = rest
    q, dtype = x_ref.shape[1], x_ref.dtype
    b, cm = b_ref[0], c_ref[0]  # [Q, N]

    @pl.when(step % steps_per_group == 0)
    def _scores():
        g_scr[...] = _dot(cm, b, _NT)

    tri, cols = _lower_triangle(q), _own_columns(cols_ref, step, hb)
    for k in range(hb):
        head = step * hb + k

        @pl.when(c == 0)
        def _start():
            s_scr[head] = jnp.zeros(s_scr.shape[1:], jnp.float32)

        dt, cum, L, last = _decay(cols, rows_ref, k, tri)
        xd = x_ref[0, :, k * p:(k + 1) * p].astype(jnp.float32) * dt  # [Q, P]
        s_in = s_scr[head]  # [P, N]
        if save:
            s_out_ref[0, 0, k] = s_in
        y = _dot((L * g_scr[...]).astype(dtype), xd.astype(dtype))
        y += jnp.exp(cum) * _dot(cm, s_in.astype(dtype), _NT)
        y_ref[0, :, k * p:(k + 1) * p] = y.astype(y_ref.dtype)
        s_scr[head] = _over_lanes(jnp.exp(last), s_in) * s_in + _dot(
            (xd * jnp.exp(last - cum)).astype(dtype), b, _TN)


def _bwd_kernel(c, step, x_ref, dy_ref, cols_ref, rows_ref, b_ref, c_ref, s_in_ref,
                dx_ref, dcols_ref, drows_ref, db_ref, dc_ref,
                g_scr, dg_scr, db_scr, dc_scr, ds_scr,
                *, hb, p, steps_per_group):
    q, dtype = x_ref.shape[1], x_ref.dtype
    b, cm = b_ref[0], c_ref[0]  # [Q, N]

    @pl.when(step % steps_per_group == 0)
    def _scores():
        g_scr[...] = _dot(cm, b, _NT)
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    @pl.when(step == 0)
    def _columns():
        dcols_ref[...] = jnp.zeros_like(dcols_ref)

    tri, cols = _lower_triangle(q), _own_columns(cols_ref, step, hb)
    is_last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    lane = lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    dcols = jnp.zeros(cols.shape, jnp.float32)  # laid out as `cols` is
    for k in range(hb):
        head = step * hb + k

        @pl.when(c == 0)
        def _start():
            ds_scr[head] = jnp.zeros(ds_scr.shape[1:], jnp.float32)

        dt, cum, L, last = _decay(cols, rows_ref, k, tri)
        x = x_ref[0, :, k * p:(k + 1) * p].astype(jnp.float32)
        dy = dy_ref[0, :, k * p:(k + 1) * p]
        s_in, ds_out = s_in_ref[0, 0, k], ds_scr[head]  # [P, N]
        xd, w, a_chunk = x * dt, jnp.exp(last - cum), jnp.exp(last)
        m = L * g_scr[...]
        dm = _dot(dy, xd.astype(dtype), _NT)  # [Q, Q]
        dg_scr[...] += dm * L
        pulled = dm * m  # L's cotangent times L: what cum gets
        # the two products with a state, each used twice: for B's and C's
        # cotangent, and, times B and C, for cum's
        dy_e = (dy.astype(jnp.float32) * jnp.exp(cum)).astype(dtype)
        dc_in = _dot(dy_e, s_in.astype(dtype))                     # [Q, N]
        db_in = _dot((xd * w).astype(dtype), ds_out.astype(dtype))  # [Q, N]
        dc_scr[...] += dc_in
        db_scr[...] += db_in
        to_b = jnp.sum(b.astype(jnp.float32) * db_in, axis=1, keepdims=True)
        to_c = jnp.sum(cm.astype(jnp.float32) * dc_in, axis=1, keepdims=True)
        # cum of the chunk's last token also scales the whole outgoing state
        whole = a_chunk * jnp.sum(ds_out * s_in) + jnp.sum(to_b)
        dcum = (jnp.sum(pulled, axis=1, keepdims=True) + to_c - to_b
                + jnp.where(is_last, whole, 0.0))
        dxd = _dot(m.astype(dtype), dy, _TN) + w * _dot(
            b, ds_out.astype(dtype), _NT)  # [Q, P]
        dx_ref[0, :, k * p:(k + 1) * p] = (dxd * dt).astype(dx_ref.dtype)
        dcols += jnp.where(lane == k, jnp.sum(dxd * x, axis=1, keepdims=True), 0.0)
        dcols += jnp.where(lane == cols.shape[-1] // 2 + k, dcum, 0.0)
        drows_ref[0, 0, 0, k:k + 1, :] = jnp.sum(pulled, axis=0, keepdims=True)
        ds_scr[head] = _over_lanes(a_chunk, ds_out) * ds_out + _dot(dy_e, cm, _TN)
    dcols_ref[0] += pltpu.roll(dcols, step * hb, 1)  # back to the heads' own lanes

    @pl.when(step % steps_per_group == steps_per_group - 1)
    def _finish():
        dg = dg_scr[...].astype(dtype)
        dc_ref[0] = (dc_scr[...] + _dot(dg, b)).astype(dc_ref.dtype)
        db_ref[0] = (db_scr[...] + _dot(dg, cm, _TN)).astype(db_ref.dtype)


def _scalars(dt, a_log, chunk, hb):
    """log a, and dt and its running sum within a chunk laid out for the
    kernels: down the rows, every head's [b, T, 2 H] (dt, then cum), and
    cum along the lanes, a step's heads together [b, H / hb, chunks, hb,
    chunk]."""
    bsz, t, h = dt.shape
    la = -jnp.exp(a_log.astype(jnp.float32)) * dt
    cum = jnp.cumsum(la.reshape(bsz, t // chunk, chunk, h), axis=2)
    cols = jnp.concatenate([dt, cum.reshape(bsz, t, h)], axis=-1)
    rows = cum.reshape(bsz, t // chunk, chunk, h // hb, hb).transpose(0, 3, 1, 4, 2)
    return la, cols, rows


class _Layout(NamedTuple):
    """The sizes both calls are built from."""
    bsz: int
    t: int
    h: int
    p: int
    n: int
    groups: int
    hb: int       # heads a grid step
    q: int        # tokens a chunk
    chunks: int
    steps: int    # grid steps a chunk: h / hb
    steps_per_group: int


def _layout(x, b, chunk):
    bsz, t, h, p = x.shape
    groups, n = b.shape[2:]
    hb = _heads_per_step(h // groups, p)
    return _Layout(bsz, t, h, p, n, groups, hb, chunk, t // chunk, h // hb,
                   h // groups // hb)


def _program(kernel, interpret):
    """The kernel handed its chunk's and its step's program ids.  In
    interpret mode its body is one branch that is always taken: under
    `shard_map`'s checking of the axes a value varies over, Pallas'
    interpreter evaluates a kernel's equations one by one and refuses a block
    that varies over the mesh beside an index that does not (a constant, a
    program id), while a branch's equations it takes whole, as it takes the
    flash kernels', whose bodies all lie in `pl.when`s.  The compiled kernel
    is as written."""
    def program(*refs):
        c, step = pl.program_id(1), pl.program_id(2)
        if interpret:
            pl.when(c >= 0)(lambda: kernel(c, step, *refs))
        else:
            kernel(c, step, *refs)
    return program


def _specs(lay, chunk_of):
    """Block specs of what both kernels read: x (or dy), the scalars down
    the rows and along the lanes, a group's B (or C), a chunk's states."""
    hb, p, n, q, spg = lay.hb, lay.p, lay.n, lay.q, lay.steps_per_group
    return dict(
        x=_block_spec((1, q, hb * p), lambda i, c, s: (i, chunk_of(c), s)),
        cols=_block_spec((1, q, 2 * lay.h), lambda i, c, s: (i, chunk_of(c), 0)),
        rows=_block_spec((1, 1, 1, hb, q), lambda i, c, s: (i, s, chunk_of(c), 0, 0)),
        bc=_block_spec((1, q, n), lambda i, c, s: (i, chunk_of(c), s // spg)),
        states=_block_spec((1, 1, hb, p, n),
                           lambda i, c, s: (i, chunk_of(c), s, 0, 0)))


def _fwd(x, dt, a_log, b, c, chunk, interpret, save):
    lay = _layout(x, b, chunk)
    bsz, t, h, p, n, hb, q = lay.bsz, lay.t, lay.h, lay.p, lay.n, lay.hb, lay.q
    _, cols, rows = _scalars(dt, a_log, chunk, hb)
    spec = _specs(lay, lambda ci: ci)
    operands = (x, cols, b, c)
    y_shape = _out_struct((bsz, t, h * p), x.dtype, operands)
    s_shape = _out_struct((bsz, lay.chunks, h, p, n), jnp.float32, operands)
    kernel = functools.partial(_fwd_kernel, hb=hb, p=p, save=save,
                               steps_per_group=lay.steps_per_group)
    out = pl.pallas_call(
        _program(kernel, interpret),
        grid=(bsz, lay.chunks, lay.steps),
        in_specs=[spec["x"], spec["cols"], spec["rows"], spec["bc"], spec["bc"]],
        out_specs=[spec["x"], spec["states"]] if save else [spec["x"]],
        out_shape=[y_shape, s_shape] if save else [y_shape],
        scratch_shapes=[pltpu.VMEM((q, q), jnp.float32),
                        pltpu.VMEM((h, p, n), jnp.float32)],
        interpret=interpret, name="ssd_chunk_fwd",
    )(x.reshape(bsz, t, h * p), cols, rows,
      b.reshape(bsz, t, -1), c.reshape(bsz, t, -1))
    return out[0].reshape(x.shape), (out[1] if save else None)


def _bwd(x, dt, a_log, b, c, states, dy, chunk, interpret):
    lay = _layout(x, b, chunk)
    bsz, t, h, p, n, hb, q = lay.bsz, lay.t, lay.h, lay.p, lay.n, lay.hb, lay.q
    chunks = lay.chunks
    la, cols, rows = _scalars(dt, a_log, chunk, hb)
    spec = _specs(lay, lambda ci: chunks - 1 - ci)
    operands = (x, cols, b, c, dy)
    bc_shape = _out_struct((bsz, t, lay.groups * n), b.dtype, operands)
    kernel = functools.partial(_bwd_kernel, hb=hb, p=p,
                               steps_per_group=lay.steps_per_group)
    dx, dcols, drows, db, dc = pl.pallas_call(
        _program(kernel, interpret),
        grid=(bsz, chunks, lay.steps),
        in_specs=[spec["x"], spec["x"], spec["cols"], spec["rows"], spec["bc"],
                  spec["bc"], spec["states"]],
        out_specs=[spec["x"], spec["cols"], spec["rows"], spec["bc"], spec["bc"]],
        out_shape=[_out_struct((bsz, t, h * p), x.dtype, operands),
                   _out_struct(cols.shape, jnp.float32, operands),
                   _out_struct(rows.shape, jnp.float32, operands),
                   bc_shape, bc_shape],
        scratch_shapes=[pltpu.VMEM((q, q), jnp.float32),
                        pltpu.VMEM((q, q), jnp.float32),
                        pltpu.VMEM((q, n), jnp.float32),
                        pltpu.VMEM((q, n), jnp.float32),
                        pltpu.VMEM((h, p, n), jnp.float32)],
        interpret=interpret, name="ssd_chunk_bwd",
    )(x.reshape(bsz, t, h * p), dy.reshape(bsz, t, h * p), cols, rows,
      b.reshape(bsz, t, -1), c.reshape(bsz, t, -1), states)
    ddt = dcols[..., :h]
    dcum = dcols[..., h:] - drows.transpose(0, 2, 4, 1, 3).reshape(bsz, t, h)
    # cum is a running sum within a chunk: its cotangent runs the other way
    dla = jnp.flip(jnp.cumsum(jnp.flip(
        dcum.reshape(bsz, chunks, chunk, h), axis=2), axis=2), axis=2).reshape(bsz, t, h)
    ddt = ddt - jnp.exp(a_log.astype(jnp.float32)) * dla
    da_log = jnp.sum(dla * la, axis=(0, 1)).astype(a_log.dtype)
    return (dx.reshape(x.shape), ddt.astype(dt.dtype), da_log,
            db.reshape(b.shape), dc.reshape(c.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_core(x, dt, a_log, b, c, chunk, interpret):
    return _fwd(x, dt, a_log, b, c, chunk, interpret, save=False)[0]


def _ssd_core_fwd(x, dt, a_log, b, c, chunk, interpret):
    y, states = _fwd(x, dt, a_log, b, c, chunk, interpret, save=True)
    return y, (x, dt, a_log, b, c, states)


def _ssd_core_bwd(chunk, interpret, res, dy):
    return _bwd(*res, dy, chunk, interpret)


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def ssd_scan(x, dt, A_log, B, C, D, *, chunk=256, interpret=None):
    """``y[t] = S[t] C[t] + D x[t]`` of the recurrence in the module's
    docstring, differentiable in all six arguments.

    ``x``: ``[batch, T, H, P]``; ``dt``: ``[batch, T, H]``, the step sizes
    as the recurrence takes them (after the softplus), float32; ``A_log``,
    ``D``: ``[H]``; ``B``, ``C``: ``[batch, T, G, N]``, ``G`` dividing ``H``
    (head ``h`` reads group ``h // (H / G)``).  ``chunk`` is how many tokens
    the kernels take at a time; a ``T`` that it does not divide is padded
    with tokens that leave the state as it is.  Returns ``[batch, T, H, P]``
    in ``x``'s type."""
    if interpret is None:
        interpret = _default_interpret()
    t, h = x.shape[1], x.shape[2]
    if h % B.shape[2] or B.shape != C.shape:
        raise ValueError(f"{h} heads on B {B.shape} and C {C.shape}: B and C "
                         "share a group count that divides the heads'")
    pad = -t % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    y = _ssd_core(x, dt.astype(jnp.float32), A_log, B, C, chunk, interpret)
    y = y + (D.astype(jnp.float32)[:, None] * x).astype(x.dtype)
    return y[:, :t] if pad else y

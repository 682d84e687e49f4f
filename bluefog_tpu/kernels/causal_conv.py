"""The depth-wise causal convolution of a Mamba-2 layer with its bias and
SiLU, forward and backward, as two Pallas TPU kernels: one pass over the
input projection's output each way.

For every channel ``c`` and token ``t``, ``W`` taps (4 in Mamba-2)::

    pre[t, c] = bias[c] + sum_k kernel[k, c] x[t - (W - 1) + k, c]     x[< 0] = 0
    y[t, c]   = silu(pre[t, c])

which is ``jax.nn.silu(models.hybrid.causal_conv(x, kernel, bias))``, the
definition the tests hold the kernels to, in the same arithmetic: ``x``
widened to float32, float32 taps and bias, the taps summed in their order,
the bias added last, the SiLU in float32, one rounding to ``x``'s type.

**The kernels** (``causal_conv_fwd``, ``causal_conv_bwd``: the names the
device trace shows).  Grid ``(channels / cb, batch, tokens / tb)``, the tokens
innermost, ``cb`` a multiple of 128 lanes.  A step loads its ``[tb, cb]``
block of ``x`` in its own type and widens it in VMEM; the rows before it are
the last 8 of the block before, carried in a float32 scratch that a
sequence's first step zeroes; a tap's rows are that stack rolled along the
sublanes.  Nothing of ``[T, C]`` in float32 is written to HBM.  ``x`` may be a
wider array whose channels ``offset .. offset + C`` are convolved (the
``[z, xBC, dt]`` product of a Mamba-2 layer): the block index starts there
and the slice is never made.

The backward kernel walks a sequence's blocks last to first.  It makes the
pre-activation again from ``x`` (the three rows before the block from an
8- or 16-row halo block of the same array), ``dpre = dy silu'(pre)``, and
``dx[t] = sum_k kernel[k] dpre[t + (W - 1) - k]``, the rows after the block
being the first 8 of the block after it, carried in scratch.  The taps' and
the bias's gradients are summed over tokens and batch in one float32 output
block of 8 rows (a row a tap, then the bias's) that every step of a channel
block revisits.  The ``custom_vjp`` keeps ``x``, the taps and the bias, and
nothing a kernel made.

**The gated short convolution** of an LFM2 layer (``short_conv_fwd``,
``short_conv_bwd``) walks the same blocks with the same halo and scratch.  Its
input is the ``[T, 3 C]`` product ``[B, C, x]`` of the layer's input
projection, read at three channel offsets of the one array::

    z[t, c] = B[t, c] x[t, c]
    y[t, c] = C[t, c] sum_k kernel[k, c] z[t - (W - 1) + k, c]          z[< 0] = 0

no bias, no activation: ``C * models.hybrid.causal_conv(B * x, kernel, 0)`` with
both gates in float32 and one rounding to the product's type.  The backward
kernel makes ``z`` and the convolution again, and writes ``dB``, ``dC`` and
``dx`` where ``B``, ``C`` and ``x`` stood, into one ``[T, 3 C]`` cotangent: its
grid has a fourth, innermost axis of three steps whose output block is the
chunk's; the first computes all three and keeps ``dC`` and ``dx`` in VMEM, the
other two hand them over (the inputs' blocks do not move between the three, so
nothing is read twice).

On a platform that is not a TPU the same kernels run in Pallas' interpret
mode, as the flash and the scan kernels do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.kernels.flash_attention import (
    _block_spec, _default_interpret, _out_struct)
from bluefog_tpu.kernels.ssd import _program  # a kernel handed the ids of grid axes 1 and 2

__all__ = ["causal_conv_silu", "short_conv", "tiles"]

_LANES = 128
_ROWS = 8       # a float32 tile's rows: what a block borrows of its neighbour
_BLOCK_T = 512   # tokens a step
_BLOCK_C = 256   # channels a step, at most


def tiles(tokens: int, channels: int, width: int, offset: int = 0) -> bool:
    """Whether the kernels take a sequence of ``tokens`` by ``channels``
    under ``width`` taps, ``offset`` channels into its array: whole 128-lane
    blocks, whole 8-row tiles, and the taps and the bias in one."""
    return (channels % _LANES == 0 and offset % _LANES == 0 and tokens % _ROWS == 0
            and 0 < width <= _ROWS - 1)


def _blocks(tokens, channels, offset):
    tb = min(_BLOCK_T, tokens)
    cb = _BLOCK_C
    while channels % cb or offset % cb:
        cb //= 2
    return tb, cb


def _taps_sum(stack, k_ref):
    """``sum_k kernel[k] x[t - (W - 1) + k]`` over the rows after the first 8
    of ``stack`` (those 8: the rows before the block), and each tap's rows for
    whoever needs them again."""
    w = k_ref.shape[0]
    rows = [stack[_ROWS:] if k == w - 1 else pltpu.roll(stack, w - 1 - k, 0)[_ROWS:]
            for k in range(w)]
    acc = k_ref[0:1, :] * rows[0]
    for k in range(1, w):
        acc = acc + k_ref[k:k + 1, :] * rows[k]
    return acc, rows


def _pre_activation(stack, k_ref, b_ref):
    """``bias +`` :func:`_taps_sum`, the bias added last."""
    acc, rows = _taps_sum(stack, k_ref)
    return b_ref[...] + acc, rows


def _taps_back(dpre, after_scr, k_ref):
    """``sum_k kernel[k] dpre[t + (W - 1) - k]``, the rows after the block
    being ``after_scr``'s, which is left holding this block's first 8 for the
    block before it."""
    tb, w = dpre.shape[0], k_ref.shape[0]
    stack = jnp.concatenate([dpre, after_scr[...]], axis=0)
    after_scr[...] = dpre[:_ROWS]
    n = stack.shape[0]
    dx = k_ref[w - 1:w, :] * dpre
    for k in range(w - 2, -1, -1):
        dx = dx + k_ref[k:k + 1, :] * pltpu.roll(stack, n - (w - 1 - k), 0)[:tb]
    return dx


def _fwd_kernel(b, i, x_ref, k_ref, b_ref, y_ref, before_scr):
    @pl.when(i == 0)
    def _start():
        before_scr[...] = jnp.zeros_like(before_scr)

    x = x_ref[0].astype(jnp.float32)  # [tb, cb]
    stack = jnp.concatenate([before_scr[...], x], axis=0)
    before_scr[...] = x[-_ROWS:]
    pre, _ = _pre_activation(stack, k_ref, b_ref)
    y_ref[0] = jax.nn.silu(pre).astype(y_ref.dtype)


def _bwd_kernel(b, i, dy_ref, x_ref, halo_ref, k_ref, b_ref, dx_ref, dw_ref,
                after_scr, *, tokens):
    tb, w = x_ref.shape[1], k_ref.shape[0]
    block = pl.num_programs(2) - 1 - i  # last to first

    @pl.when(i == 0)
    def _start():
        after_scr[...] = jnp.zeros_like(after_scr)

    @pl.when((b == 0) & (i == 0))
    def _sums():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    x, dy = x_ref[0].astype(jnp.float32), dy_ref[0].astype(jnp.float32)
    if tokens % tb:  # the last block's rows past the sequence hold anything
        row = block * tb + lax.broadcasted_iota(jnp.int32, x.shape, 0)
        x, dy = jnp.where(row < tokens, x, 0.0), jnp.where(row < tokens, dy, 0.0)
    before = jnp.where(block > 0, halo_ref[0].astype(jnp.float32)[-_ROWS:], 0.0)
    pre, rows = _pre_activation(jnp.concatenate([before, x], axis=0), k_ref, b_ref)
    sig = jax.nn.sigmoid(pre)
    dpre = dy * (sig * (1.0 + pre * (1.0 - sig)))
    dx_ref[0] = _taps_back(dpre, after_scr, k_ref).astype(dx_ref.dtype)
    for k in range(w):
        dw_ref[k:k + 1, :] += jnp.sum(dpre * rows[k], axis=0, keepdims=True)
    dw_ref[w:w + 1, :] += jnp.sum(dpre, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _fwd(x, kernel, bias, offset, interpret):
    bsz, t, _ = x.shape
    w, c = kernel.shape
    tb, cb = _blocks(t, c, offset)
    off = offset // cb
    return pl.pallas_call(
        _program(_fwd_kernel, interpret),
        grid=(c // cb, bsz, pl.cdiv(t, tb)),
        in_specs=[_block_spec((1, tb, cb), lambda j, b, i: (b, i, j + off)),
                  _block_spec((w, cb), lambda j, b, i: (0, j)),
                  _block_spec((1, cb), lambda j, b, i: (0, j))],
        out_specs=_block_spec((1, tb, cb), lambda j, b, i: (b, i, j)),
        out_shape=_out_struct((bsz, t, c), x.dtype, (x, kernel, bias)),
        scratch_shapes=[pltpu.VMEM((_ROWS, cb), jnp.float32)],
        interpret=interpret, name="causal_conv_fwd",
    )(x, kernel.astype(jnp.float32), bias.astype(jnp.float32).reshape(1, c))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _bwd(x, kernel, bias, dy, offset, interpret):
    bsz, t, _ = x.shape
    w, c = kernel.shape
    tb, cb = _blocks(t, c, offset)
    off, nt = offset // cb, pl.cdiv(t, tb)
    # the halo in whole tiles of x's type (16 rows of bfloat16), so that both
    # views of x ask for one layout; a sequence of one block has none to read
    halo = min(tb, _ROWS * max(1, 4 // x.dtype.itemsize))
    at = lambda i: nt - 1 - i
    operands = (x, kernel, bias, dy)
    dx, dw = pl.pallas_call(
        _program(functools.partial(_bwd_kernel, tokens=t), interpret),
        grid=(c // cb, bsz, nt),
        in_specs=[_block_spec((1, tb, cb), lambda j, b, i: (b, at(i), j)),
                  _block_spec((1, tb, cb), lambda j, b, i: (b, at(i), j + off)),
                  _block_spec((1, halo, cb), lambda j, b, i: (
                      b, jnp.maximum(at(i) * (tb // halo) - 1, 0), j + off)),
                  _block_spec((w, cb), lambda j, b, i: (0, j)),
                  _block_spec((1, cb), lambda j, b, i: (0, j))],
        out_specs=[_block_spec((1, tb, cb), lambda j, b, i: (b, at(i), j)),
                   _block_spec((_ROWS, cb), lambda j, b, i: (0, j))],
        out_shape=[_out_struct((bsz, t, c), x.dtype, operands),
                   _out_struct((_ROWS, c), jnp.float32, operands)],
        scratch_shapes=[pltpu.VMEM((_ROWS, cb), jnp.float32)],
        interpret=interpret, name="causal_conv_bwd",
    )(dy, x, x, kernel.astype(jnp.float32), bias.astype(jnp.float32).reshape(1, c))
    return dx, dw[:w].astype(kernel.dtype), dw[w].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _core(x, kernel, bias, offset, interpret):
    return _fwd(x, kernel, bias, offset, interpret)


def _core_fwd(x, kernel, bias, offset, interpret):
    return _fwd(x, kernel, bias, offset, interpret), (x, kernel, bias)


def _core_bwd(offset, interpret, res, dy):
    x, kernel, bias = res
    dx, dk, db = _bwd(x, kernel, bias, dy, offset, interpret)
    c = kernel.shape[1]
    if x.shape[-1] != c:  # the channels that were not convolved got nothing
        dx = jnp.pad(dx, ((0, 0), (0, 0), (offset, x.shape[-1] - offset - c)))
    return dx, dk, db


_core.defvjp(_core_fwd, _core_bwd)


def causal_conv_silu(x, kernel, bias, *, offset=0, interpret=None):
    """``silu(bias + sum_k kernel[k] x[:, t - (W - 1) + k])`` of the module's
    docstring over the channels ``offset .. offset + C`` of ``x``,
    differentiable in ``x``, the taps and the bias.

    ``x``: ``[batch, T, >= offset + C]``; ``kernel``: ``[W, C]``; ``bias``:
    ``[C]``.  The shapes must be ones :func:`tiles` takes.  Returns ``[batch,
    T, C]`` in ``x``'s type."""
    w, c = kernel.shape
    if not tiles(x.shape[1], c, w, offset) or x.shape[-1] < offset + c:
        raise ValueError(
            f"x {x.shape} under taps {kernel.shape} at channel {offset}: the "
            "kernels take whole 128-lane blocks of channels, whole 8-row tiles "
            f"of tokens and at most {_ROWS - 1} taps")
    if interpret is None:
        interpret = _default_interpret()
    return _core(x, kernel, bias, offset, interpret)


# ---- the gated short convolution: y = C * conv(B * x), [B, C, x] one array ----


def _short_fwd_kernel(b, i, g_ref, c_ref, x_ref, k_ref, y_ref, before_scr):
    @pl.when(i == 0)
    def _start():
        before_scr[...] = jnp.zeros_like(before_scr)

    z = g_ref[0].astype(jnp.float32) * x_ref[0].astype(jnp.float32)  # [tb, cb]
    stack = jnp.concatenate([before_scr[...], z], axis=0)
    before_scr[...] = z[-_ROWS:]
    conv, _ = _taps_sum(stack, k_ref)
    y_ref[0] = (c_ref[0].astype(jnp.float32) * conv).astype(y_ref.dtype)


def _program_of_parts(kernel, interpret):
    """:func:`bluefog_tpu.kernels.ssd._program` for a grid with a fourth axis,
    whose id the kernel is handed too."""
    def program(*refs):
        b, i, part = pl.program_id(1), pl.program_id(2), pl.program_id(3)
        if interpret:
            pl.when(b >= 0)(lambda: kernel(b, i, part, *refs))
        else:
            kernel(b, i, part, *refs)
    return program


def _short_bwd_kernel(b, i, part, dy_ref, g_ref, c_ref, x_ref, g_halo_ref, x_halo_ref,
                      k_ref, d_ref, dw_ref, after_scr, dc_scr, dx_scr, *, tokens):
    """``part``: whose cotangent this step's output block is, B's, C's or x's."""
    tb, w = x_ref.shape[1], k_ref.shape[0]
    block = pl.num_programs(2) - 1 - i  # last to first

    @pl.when((i == 0) & (part == 0))
    def _start():
        after_scr[...] = jnp.zeros_like(after_scr)

    @pl.when((b == 0) & (i == 0) & (part == 0))
    def _sums():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(part == 0)
    def _all_three():
        gate, x = g_ref[0].astype(jnp.float32), x_ref[0].astype(jnp.float32)
        dy = dy_ref[0].astype(jnp.float32)
        z, dconv = gate * x, dy * c_ref[0].astype(jnp.float32)
        if tokens % tb:  # the last block's rows past the sequence hold anything
            row = block * tb + lax.broadcasted_iota(jnp.int32, z.shape, 0)
            z, dconv = jnp.where(row < tokens, z, 0.0), jnp.where(row < tokens, dconv, 0.0)
        before = jnp.where(block > 0, (g_halo_ref[0].astype(jnp.float32)
                                       * x_halo_ref[0].astype(jnp.float32))[-_ROWS:], 0.0)
        conv, rows = _taps_sum(jnp.concatenate([before, z], axis=0), k_ref)
        dz = _taps_back(dconv, after_scr, k_ref)
        d_ref[0] = (dz * x).astype(d_ref.dtype)
        dc_scr[...] = (dy * conv).astype(dc_scr.dtype)
        dx_scr[...] = (dz * gate).astype(dx_scr.dtype)
        for k in range(w):
            dw_ref[k:k + 1, :] += jnp.sum(dconv * rows[k], axis=0, keepdims=True)

    @pl.when(part == 1)
    def _dc():
        d_ref[0] = dc_scr[...]

    @pl.when(part == 2)
    def _dx():
        d_ref[0] = dx_scr[...]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _short_fwd(bcx, kernel, offset, interpret):
    bsz, t, _ = bcx.shape
    w, c = kernel.shape
    tb, cb = _blocks(t, c, offset)
    off, per = offset // cb, c // cb
    chunk = lambda n: _block_spec((1, tb, cb), lambda j, b, i: (b, i, j + off + n * per))
    return pl.pallas_call(
        _program(_short_fwd_kernel, interpret),
        grid=(per, bsz, pl.cdiv(t, tb)),
        in_specs=[chunk(0), chunk(1), chunk(2),
                  _block_spec((w, cb), lambda j, b, i: (0, j))],
        out_specs=_block_spec((1, tb, cb), lambda j, b, i: (b, i, j)),
        out_shape=_out_struct((bsz, t, c), bcx.dtype, (bcx, kernel)),
        scratch_shapes=[pltpu.VMEM((_ROWS, cb), jnp.float32)],
        interpret=interpret, name="short_conv_fwd",
    )(bcx, bcx, bcx, kernel.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _short_bwd(bcx, kernel, dy, offset, interpret):
    bsz, t, _ = bcx.shape
    w, c = kernel.shape
    tb, cb = _blocks(t, c, offset)
    off, per, nt = offset // cb, c // cb, pl.cdiv(t, tb)
    halo = min(tb, _ROWS * max(1, 4 // bcx.dtype.itemsize))  # as `_bwd`'s
    at = lambda i: nt - 1 - i
    chunk = lambda n: _block_spec(
        (1, tb, cb), lambda j, b, i, p: (b, at(i), j + off + n * per))
    before = lambda n: _block_spec((1, halo, cb), lambda j, b, i, p: (
        b, jnp.maximum(at(i) * (tb // halo) - 1, 0), j + off + n * per))
    operands = (bcx, kernel, dy)
    d, dw = pl.pallas_call(
        _program_of_parts(functools.partial(_short_bwd_kernel, tokens=t), interpret),
        grid=(per, bsz, nt, 3),
        in_specs=[_block_spec((1, tb, cb), lambda j, b, i, p: (b, at(i), j)),
                  chunk(0), chunk(1), chunk(2), before(0), before(2),
                  _block_spec((w, cb), lambda j, b, i, p: (0, j))],
        out_specs=[_block_spec((1, tb, cb), lambda j, b, i, p: (b, at(i), j + p * per)),
                   _block_spec((_ROWS, cb), lambda j, b, i, p: (0, j))],
        out_shape=[_out_struct((bsz, t, 3 * c), bcx.dtype, operands),
                   _out_struct((_ROWS, c), jnp.float32, operands)],
        scratch_shapes=[pltpu.VMEM((_ROWS, cb), jnp.float32),
                        pltpu.VMEM((tb, cb), bcx.dtype), pltpu.VMEM((tb, cb), bcx.dtype)],
        interpret=interpret, name="short_conv_bwd",
    )(dy, bcx, bcx, bcx, bcx, bcx, kernel.astype(jnp.float32))
    return d, dw[:w].astype(kernel.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _short_core(bcx, kernel, offset, interpret):
    return _short_fwd(bcx, kernel, offset, interpret)


def _short_core_fwd(bcx, kernel, offset, interpret):
    return _short_fwd(bcx, kernel, offset, interpret), (bcx, kernel)


def _short_core_bwd(offset, interpret, res, dy):
    bcx, kernel = res
    d, dk = _short_bwd(bcx, kernel, dy, offset, interpret)
    if bcx.shape[-1] != d.shape[-1]:  # the channels beside the three chunks got nothing
        d = jnp.pad(d, ((0, 0), (0, 0), (offset, bcx.shape[-1] - offset - d.shape[-1])))
    return d, dk


_short_core.defvjp(_short_core_fwd, _short_core_bwd)


def short_conv(bcx, kernel, *, offset=0, interpret=None):
    """``C * conv(B * x)`` of the module's docstring, ``[B, C, x]`` the
    channels ``offset .. offset + 3 C`` of ``bcx``, differentiable in ``bcx``
    and the taps.

    ``bcx``: ``[batch, T, >= offset + 3 C]``; ``kernel``: ``[W, C]``.  The
    shapes must be ones :func:`tiles` takes.  Returns ``[batch, T, C]`` in
    ``bcx``'s type."""
    w, c = kernel.shape
    if not tiles(bcx.shape[1], c, w, offset) or bcx.shape[-1] < offset + 3 * c:
        raise ValueError(
            f"[B, C, x] {bcx.shape} under taps {kernel.shape} at channel {offset}: "
            "the kernels take whole 128-lane blocks of channels, whole 8-row tiles "
            f"of tokens and at most {_ROWS - 1} taps")
    if interpret is None:
        interpret = _default_interpret()
    return _short_core(bcx, kernel, offset, interpret)

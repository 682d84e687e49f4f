"""Pallas TPU kernels for the hot ops.

The reference keeps its hot paths in hand-written native code (CUDA stream
combines in ``bluefog/common/nccl_controller.cc`` [U], fused MPI combine
loops in ``mpi_controller.cc`` [U]); the TPU-native analogue is Pallas —
kernels compiled straight to Mosaic for the MXU/VPU, fused with XLA around
them.

``flash_attention``: causal attention, whole-sequence and banded, with the
shared key-value heads read in place and a value head of another size than the
query-key head's.  ``kda``: the chunked delta rule with a decay a channel of a
Kimi Delta Attention layer (:func:`bluefog_tpu.kernels.kda.kda_chunked`).
``gdn``: the gated delta rule with one decay a head of any size and value
heads on fewer key heads (:func:`bluefog_tpu.kernels.gdn.gdn_chunked`: a
stateless stage of its own over ``kda``'s walk).
``ssd``: the chunked state-space scan
of a Mamba-2 layer (:func:`bluefog_tpu.kernels.ssd.ssd_scan`).
``causal_conv``: that layer's depth-wise causal convolution with its bias and
SiLU as one pass each way
(:func:`bluefog_tpu.kernels.causal_conv.causal_conv_silu`).  Each has a
``custom_vjp`` whose backward pass is a kernel too, and runs in Pallas'
interpret mode off the TPU.
"""

from bluefog_tpu.kernels.flash_attention import (
    flash_attention,
    flash_attention_with_lse,
    make_flash_attention_fn,
)

__all__ = [
    "flash_attention",
    "flash_attention_with_lse",
    "make_flash_attention_fn",
]

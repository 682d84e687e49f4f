"""Compile the main path's device programs for a described TPU v5e.

No chip is attached here; the TPU compiler is installed, and it compiles
for a topology that is described, not attached.  That refuses what
interpret mode cannot see: a slice off the tiling, too much fast memory, a
kernel that cannot be partitioned.  Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
describing it loads libtpu, which one process at a time may hold, and a
module that decides at import whether its tests exist gives xdist workers
different collections.  All TPU compiles live in this one file so that a
single worker loads the library.
"""

import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

import optax

import bluefog_tpu as bf
from bluefog_tpu import models, ops_spmd, optim, topology_util
from bluefog_tpu.common.hlo_inspect import entry_schedule, most_outstanding
from bluefog_tpu.core import basics
from bluefog_tpu.core.basics import NODES_AXIS
from bluefog_tpu.kernels.flash_attention import flash_attention
from bluefog_tpu.models.resnet import BottleneckBlock
from bluefog_tpu.training import make_decentralized_train_step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# [B, T, H, D] of three decoder presets ("small", "1b", a long-context run)
# plus a short sequence and f32
FLASH_SHAPES = [
    pytest.param((8, 2048, 12, 64), jnp.bfloat16, id="small-B8-T2048-H12-D64-bf16"),
    pytest.param((8, 2048, 14, 128), jnp.bfloat16, id="1b-B8-T2048-H14-D128-bf16"),
    pytest.param((4, 8192, 8, 128), jnp.bfloat16, id="long-B4-T8192-H8-D128-bf16"),
    pytest.param((8, 128, 12, 64), jnp.bfloat16, id="short-B8-T128-H12-D64-bf16"),
    pytest.param((8, 2048, 12, 64), jnp.float32, id="small-B8-T2048-H12-D64-f32"),
]


def _flash(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False, impl="pallas")


@pytest.mark.parametrize("shape,dtype", FLASH_SHAPES)
def test_flash_forward_compiles_for_v5e(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(_flash).lower(x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("shape,dtype", FLASH_SHAPES)
def test_flash_backward_compiles_for_v5e(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile()
    # forward (recomputed for the residuals), dkv and dq kernels
    assert compiled.as_text().count("tpu_custom_call") == 3


# the benchmark's window layer (smallthinker-21b-a3b: B2 S8192, 28 heads of
# 128, window 4096), a window shorter than a block and one past the sequence.
# The first carries a budget: the bytes of generated code the three kernels
# had while a cut tile was one masked body of 1024 x 1024 (PR 35's tree).  With
# the two backward kernels' rolled loop over sub-tiles of 512 they carry
# 2,743,808; sixteen unrolled bodies a kernel read 22.6 MB, and what is traced,
# compiled and loaded is paid in every run's set-up (PR 32: 7.7 s).
@pytest.mark.parametrize("shape,window,code_budget", [
    pytest.param((2, 8192, 28, 128), 4096, 3_202_560, id="B2-T8192-H28-D128-W4096"),
    pytest.param((2, 2048, 4, 128), 256, None, id="B2-T2048-H4-D128-W256"),
    pytest.param((2, 2048, 4, 128), 4096, None, id="B2-T2048-H4-D128-W4096"),
])
def test_banded_flash_kernels_compile_for_v5e(one_chip, shape, window, code_budget):
    """Forward, dK/dV and dQ with a causal band: the inner grid axis and the
    block index maps are the band's (clamped `lax.div` arithmetic on program
    ids), and the backward kernels walk a cut tile of 1024 in sub-tiles by a
    rolled loop (`pl.ds` at a traced multiple of the edge), which interpret
    mode cannot refuse and Mosaic can."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=False, impl="pallas",
            window=window).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("flash_fwd_window", "flash_bwd_dkv_window", "flash_bwd_dq_window"):
        assert name in text  # the names the benchmark's readers look up
    if code_budget is not None:
        assert compiled.memory_analysis().generated_code_size_in_bytes <= code_budget


# the laguna-xs.2 cell's two kinds of layer (B1 S8192, 8 key-value heads of
# 128): 64 query heads in a 512-key band at the blocks its program passes and
# at the kernels' own, 48 over the whole sequence
@pytest.mark.parametrize("heads,window,blocks", [
    pytest.param(64, 512, (512, 512), id="H64-KV8-W512-512x512"),
    pytest.param(64, 512, (None, None), id="H64-KV8-W512-default"),
    pytest.param(48, None, (1024, 1024), id="H48-KV8-global"),
])
def test_flash_kernels_read_shared_heads_in_place_for_v5e(one_chip, heads, window,
                                                          blocks):
    """Key and value heads fewer than the query heads: the index maps divide
    the head's program id by the group, and the dK/dV grid gains the group as
    an axis of its own over which the accumulators run."""
    q = jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=False, impl="pallas", window=window,
            block_q=blocks[0], block_k=blocks[1]).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert [tuple(o.shape) for o in compiled.out_info] == [
        q.shape, kv.shape, kv.shape]  # dK and dV leave summed over the group


# a state-space layer of the granite-4.0-h-micro cell (B1 S8192, 64 heads of 64
# on one group, state 128) at the configuration's chunk and at half of it.  The
# first carries a budget: the bytes of generated code the scan's program had at
# its first compile (2,742,272: two kernels and the XLA around them), since
# what a Pallas kernel carries is traced and lowered in every run's set-up
# (PR 32).
@pytest.mark.parametrize("chunk,code_budget", [
    pytest.param(256, 3_000_000, id="B1-T8192-H64-P64-N128-chunk256"),
    pytest.param(128, None, id="B1-T8192-H64-P64-N128-chunk128"),
])
def test_scan_kernels_compile_for_v5e(one_chip, chunk, code_budget):
    """The chunked scan, forward and backward: two heads of 64 a step in one
    128-lane block of x, a head's state found in the scratch by its program
    id, per-token scalars down the rows and along the lanes, transposed
    products, which interpret mode cannot refuse and Mosaic can.  No matrix of
    chunk x chunk leaves a kernel for HBM."""
    from bluefog_tpu.kernels.ssd import ssd_scan

    T, H, P, N = 8192, 64, 64, 128
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (spec((1, T, H, P), jnp.bfloat16), spec((1, T, H), jnp.float32),
            spec((H,), jnp.float32), spec((1, T, 1, N), jnp.bfloat16),
            spec((1, T, 1, N), jnp.bfloat16), spec((H,), jnp.float32))

    def loss(*a):
        return jnp.sum(ssd_scan(*a, chunk=chunk, interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for name in ("ssd_chunk_fwd", "ssd_chunk_bwd"):
        assert name in text  # the names the benchmark's readers look up
    assert not re.search(rf"f32\[[0-9,]*{chunk},{chunk}\]", text)
    assert [tuple(o.shape) for o in compiled.out_info] == [a.shape for a in args]
    if code_budget is not None:
        assert compiled.memory_analysis().generated_code_size_in_bytes <= code_budget


# a state-space layer's convolution of the granite-4.0-h-micro cell (B1 S8192):
# the product of the input projection, of which the 4,352 channels from 4,096
# on are convolved.  Its 8,512 columns are rounded up to whole 128-lane blocks
# here, 8,576: as an argument of a program, and not a product inside it, an
# array of 8,512 is laid out tokens-minor and copied into the kernels' layout.
# The budget: the bytes of generated code read at the first compile (462,336:
# the two kernels and the sum of the taps' rows) plus 10 %.
def test_causal_conv_kernels_compile_for_v5e(one_chip):
    """The convolution's two kernels: sublane rolls of a float32 stack, a
    scratch carried along the tokens, a 16-row halo block of bfloat16 and an
    output block of sums that every step of a channel block revisits, which
    interpret mode cannot refuse and Mosaic can.  The input is read inside the
    wider array and nothing of [T, C] in float32 is written."""
    from bluefog_tpu.kernels import causal_conv
    from bluefog_tpu.models import hybrid

    T, inner, conv = 8192, 4096, 4352
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (spec((1, T, inner + conv + 128), jnp.bfloat16), spec((4, conv), jnp.float32),
            spec((conv,), jnp.float32))
    assert hybrid.conv_kernels_take(T, inner, (conv - inner) // 2, 4)

    def loss(x, taps, bias):  # the output too: its gradient alone needs no forward
        y = causal_conv.causal_conv_silu(x, taps, bias, offset=inner, interpret=False)
        return jnp.sum(y.astype(jnp.float32)), y

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True)).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for name in ("causal_conv_fwd", "causal_conv_bwd"):
        assert name in text  # the names the device trace shows
    assert "f32[1,8192,4352]" not in text and "f32[1,8195,4352]" not in text
    assert [tuple(o.shape) for o in jax.tree_util.tree_leaves(compiled.out_info)] == [
        a.shape for a in args] + [(1, T, conv)]
    assert compiled.memory_analysis().generated_code_size_in_bytes <= 508_500


# The lfm2-24b-a2b cell's gated short convolution at its size: 8,192 tokens,
# [B, C, x] the 6,144 channels of the input projection's product.  The budget:
# the bytes of generated code read at the first compile (320,512, PR 49) plus 10 %.
def test_short_conv_kernels_compile_for_v5e(one_chip):
    """The gated short convolution's two kernels: the three chunks read at
    their offsets of the one product, the backward pass's fourth grid axis
    whose output block walks the cotangent's three chunks while the inputs'
    blocks stand, two whole blocks kept in VMEM between its steps.  One
    [T, 6144] cotangent comes out, no float32 [T, C] and no slice is made."""
    from bluefog_tpu.kernels import causal_conv
    from bluefog_tpu.models import hybrid

    T, d = 8192, 2048
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (spec((1, T, 3 * d), jnp.bfloat16), spec((3, d), jnp.float32))
    assert hybrid.short_conv_kernels_take(T, d, 3)

    def loss(bcx, taps):  # the output too: its gradient alone needs no forward
        y = causal_conv.short_conv(bcx, taps, interpret=False)
        return jnp.sum(y.astype(jnp.float32)), y

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for name in ("short_conv_fwd", "short_conv_bwd"):
        assert name in text  # the names the device trace shows
    assert "f32[1,8192,2048]" not in text and "f32[1,8194,2048]" not in text
    assert "bf16[1,8192,2048]{2,1,0:T(8,128)(2,1)} slice" not in text
    assert [tuple(o.shape) for o in jax.tree_util.tree_leaves(compiled.out_info)] == [
        a.shape for a in args] + [(1, T, d)]
    assert compiled.memory_analysis().generated_code_size_in_bytes <= 352_600


# blocks of the granite-4.0-h-micro cell (B1 S8192, hidden 2048, MLP 8192; 64
# scan heads of 64 with a state of 128; 32 query heads on 8 of 64) recomputed
# under the model's own policy: one of each kind, and two state-space blocks,
# where the first one's kept values stand while the second's backward pass
# runs.  The budgets are the temporaries' bytes read at PR 42 (574,669,312,
# 803,047,936 and 880,642,048) plus 5 %; at PR 40, with the convolution as
# XLA's expression over a padded float32 copy, the state-space ones read
# 851,702,272 and 1,090,421,760.  One block alone hardly feels the tuple; the
# pair read 1,231,030,784 at PR 40 with "ssm_in_proj" kept too and
# 1,288,187,392 without "mlp_gate_up" (a block's working set grows as it is
# handed less): a change to `hybrid.REMAT_KEEPS`, or to what a name is put on,
# shows here in bytes before it shows on the chip as an out-of-memory.
@pytest.mark.parametrize("kinds,kernel_calls,temp_budget", [
    pytest.param(("mamba",), 9, 603_400_000, id="state-space-block"),
    pytest.param(("attention",), 3, 843_200_000, id="attention-block"),
    pytest.param(("mamba", "mamba"), 18, 924_600_000, id="two-state-space-blocks"),
])
def test_recomputed_granite_blocks_keep_what_their_policy_names_for_v5e(
        one_chip, monkeypatch, kinds, kernel_calls, temp_budget):
    """Three kernel calls in an attention block's gradient: the flash forward,
    dK/dV and dQ, the forward not run again because its output and logsumexp
    are both kept.  Nine in a state-space block's: the scan's forward, its
    forward again and its backward, because the scan's own residuals are not
    among the names, and the same three of the convolution, twice: x's
    channels, and B's with C's (`hybrid.conv_silu`)."""
    from bluefog_tpu.models.hybrid import HybridMambaLM

    # the model calls its kernels with their defaults, which ask the backend:
    # the CPU here.  Steered in the test, not through an option of the program
    for module in ("flash_attention", "ssd", "causal_conv"):
        monkeypatch.setattr(importlib.import_module(f"bluefog_tpu.kernels.{module}"),
                            "_default_interpret", lambda: False)
    model = HybridMambaLM(
        vocab_size=256, hidden_size=2048, layer_kinds=kinds, dff=8192,
        num_heads=32, num_kv_heads=8, head_dim=64, ssm_heads=64, ssm_head_dim=64,
        ssm_state=128, attention_multiplier=1 / 64, residual_multiplier=0.22)
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0), ids))
    compiled = jax.jit(jax.grad(
        lambda p, i: model.apply(p, i, labels=i))).lower(params, ids).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernel_calls
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= temp_budget, temp


# a linear layer's delta rule of the ling-3.0-flash-vl cell (B1 S8192, 32 heads
# of 128, chunks of 64), four heads a group as the mixer calls it and all at
# once.  The budgets are the temporaries' bytes read at PR 44 (504,187,904 and
# 1,275,390,976; 1,041,389,568 and 3,572,845,056 at PR 43, with the stateless
# stage an XLA expression) plus 5 %: what is left is what the stage's kernels
# hand the walk's and take back from them, six arrays a head each way.  They
# stand since PR 47 put the unit vectors of q and k into the stage's kernels.
# The group's case carries a budget for those two kernels alone, a group's call
# of each: the bytes of generated code read at PR 47 (1,355,776; 1,326,592
# before the norms went in) plus 5 %, since what is traced, compiled and
# loaded is paid in every run's set-up (PR 32).
@pytest.mark.parametrize("at_once,temp_budget,stage_code_budget", [
    pytest.param(4, 529_400_000, 1_423_500, id="B1-T8192-H32-K128-four-heads-a-group"),
    pytest.param(32, 1_339_200_000, None, id="B1-T8192-H32-K128-the-layer-at-once")])
def test_delta_rule_kernels_compile_for_v5e(one_chip, at_once, temp_budget,
                                            stage_code_budget):
    """The chunked delta rule, forward and backward: the state carried
    transposed in VMEM scratch, a [1, 128] decay broadcast down its rows,
    transposed products on bfloat16 operands, which interpret mode cannot
    refuse and Mosaic can; the stateless stage's two kernels beside them:
    float32 products at `Precision.HIGHEST`, plain and transposed, 16-lane
    slices and concatenations of the triangle's diagonal blocks, a block of
    four lanes of `beta`, rolls down the sublanes."""
    from bluefog_tpu.kernels import kda

    T, H, K = 8192, 32, 128
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (spec((1, T, H, K), jnp.bfloat16),) * 3 + (
        spec((1, T, H, K), jnp.float32), spec((1, T, H), jnp.float32))

    def loss(*a):
        return jnp.sum(kda.kda_chunked(*a, chunk=64, heads_at_once=at_once,
                                       interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(5)))).lower(*args).compile()
    text = compiled.as_text()
    for name in ("kda_chunk_fwd", "kda_chunk_bwd", "kda_intra_fwd", "kda_intra_bwd"):
        assert name in text  # the names the benchmark's readers and the trace look up
    assert [tuple(o.shape) for o in compiled.out_info] == [a.shape for a in args]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= temp_budget, temp
    if stage_code_budget is not None:
        group = (spec((1, T, at_once * K), jnp.bfloat16),) * 3 + (
            spec((1, T, at_once * K), jnp.float32), spec((1, T, at_once), jnp.float32))

        def through_the_stage(*a):  # the outputs too: their gradient alone needs no forward
            made = kda._intra(*a, 64, False)
            return sum(jnp.sum(o.astype(jnp.float32)) for o in made), made

        stage = jax.jit(jax.grad(through_the_stage, argnums=tuple(range(5)),
                                 has_aux=True)).lower(*group).compile()
        assert stage.as_text().count("tpu_custom_call") == 2
        code = stage.memory_analysis().generated_code_size_in_bytes
        assert code <= stage_code_budget, code


# The gated delta rule of the qwen3-next-80b-a3b cell: 32 value heads on 16 key
# heads of 128, 8,192 tokens, four value heads a group.  Temporaries read at PR
# 52: 260,917,760 (a group's six arrays and their cotangents, the states a
# chunk, and the eight groups' stacked inputs and gradients) plus 5 %.
def test_gated_delta_rule_kernels_compile_for_v5e(one_chip):
    """The stage's two kernels beside Ling's walk: a `[chunk, chunk]` decay
    matrix from masked sums down the sublanes, a block of two key heads read at
    the index of four value heads, `[1, chunk]` rows of dg and dbeta, one
    product each for the pairs at `Precision.HIGHEST`: what interpret mode
    cannot refuse and Mosaic can."""
    from bluefog_tpu.kernels import gdn

    T, Hk, H, K = 8192, 16, 32, 128
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (spec((1, T, Hk, K), jnp.bfloat16),) * 2 + (
        spec((1, T, H, K), jnp.bfloat16), spec((1, T, H), jnp.float32),
        spec((1, T, H), jnp.float32))

    def loss(*a):
        return jnp.sum(gdn.gdn_chunked(*a, chunk=64, interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(5)))).lower(*args).compile()
    text = compiled.as_text()
    for name in ("gdn_intra_fwd", "gdn_intra_bwd", "kda_chunk_fwd", "kda_chunk_bwd"):
        assert name in text  # the names the benchmark's readers and the trace look up
    assert "kda_intra" not in text
    assert [tuple(o.shape) for o in compiled.out_info] == [a.shape for a in args]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 274_000_000, temp


def test_flash_kernels_take_heads_of_256_for_v5e(one_chip):
    """The qwen3-next-80b-a3b cell's attention layer: 16 query heads on 2
    key-value heads of 256 at 8,192 tokens, the default 1024 x 1024 tiles: a
    256-deep q, k, v and a float32 accumulator of [1024, 256] in VMEM."""
    T = 8192
    spec = lambda h: jax.ShapeDtypeStruct((1, T, h, 256), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=False)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec(16), spec(2), spec(2)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3
    assert [tuple(o.shape)[-2:] for o in compiled.out_info] == [(16, 256), (2, 256), (2, 256)]


def test_flash_kernels_take_a_wider_query_key_head_for_v5e(one_chip):
    """The latent-attention layer's call of the ling-3.0-flash-vl cell: 32
    heads, queries and keys of 128 + 64 rotary, values of 128, 8,192 tokens.
    A block of 192 lanes is one and a half tiles, which interpret mode cannot
    refuse and Mosaic can."""
    T, H = 8192, 32
    spec = lambda d: jax.ShapeDtypeStruct((1, T, H, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=False)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec(192), spec(192), spec(128)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3
    assert [tuple(o.shape)[-1] for o in compiled.out_info] == [192, 192, 128]


# the kanana-2-30b-a3b cell's step at the timed size (B1 S8192, six layers of
# latent attention at its published widths, 687.5 M parameters) and the step of
# its plain reference, which chipbench/check.py runs on the same chip after the
# window: loss and gradient, then AdamW, parameters and optimizer state donated.
# Bytes: arguments + results - aliased + temporaries, read at PR 46 as
# 12,872,735,232 (8.25 GB of weights and moments standing, 4.62 GB of
# temporaries of which 2.75 GB are the gradient; 13,280,411,648 at PR 45, when
# an expert layer's pass held 16,384 rows for a load of 6,144 and not 8,192)
# and at PR 45 as 12,661,027,328 (the reference hands its gradient back: 11.0
# GB of results), plus 3 %.  The chip has 16.909 GB; the floor for a cell is a
# quarter of it.  The reference's case is `slow` (`make test-slow`): 319 s, the
# longest test of the timed run by 2.3 times, to guard a benchmark file that no
# PR but a `benchmark` one may edit, and the chip runs that very step after
# every window of the cell.  The program's case holds the bytes of the code
# that `perf_opt` PRs edit, and stays.
#
# The lfm2-24b-a2b cell's step (B1 S8192, published layers 1-8: six gated short
# convolutions, two attention layers of 32 heads on 8 of 64, 740.2 M parameters)
# and its reference's, the same way.  Bytes read at PR 49: 14,215,708,672 (8.88
# GB of weights and moments standing, the gradient's 2.96 among the
# temporaries) and 13,287,420,416 (11.84 GB of results), plus 3 %.
#
# The qwen3-next-80b-a3b cell's step (B1 S8192, published layers 0-3: three
# gated-delta-rule layers, one gated attention layer of 16 heads on 2 of 256,
# 625.7 M parameters) and its reference's, the same way.  Bytes read at PR 52:
# 11,621,391,872 (7.51 GB of weights and moments standing, 4.11 GB of
# temporaries, the gradient's 2.50 among them) and 15,307,039,232 (10.01
# GB of results), plus 3 %.  `gdn_intra_bwd` is counted twice a layer: the call
# and the fusion the compiler wraps it in to write dq into the groups' stack.
_KANANA, _LFM2, _QWEN = ("kanana-2-30b-a3b-atc-warmup-b1-s8k-1chip",
                         "lfm2-24b-a2b-atc-warmup-b1-s8k-1chip",
                         "qwen3-next-80b-a3b-atc-warmup-b1-s8k-1chip")
_QWEN_KERNELS = ("attention_global", "gdn_intra_fwd", "gdn_intra_bwd", "kda_chunk_fwd",
                 "kda_chunk_bwd")


@pytest.mark.parametrize("cell_name,which,kernel_calls,budget", [
    pytest.param(_KANANA, "program", {"attention_global": 18}, 13_259_000_000,
                 id="program-B1-T8192-six-MLA-layers"),
    pytest.param(_KANANA, "reference", {"attention_global": 0}, 13_041_000_000,
                 id="reference-float32-in-pieces", marks=pytest.mark.slow),
    pytest.param(_LFM2, "program",
                 {"attention_global": 6, "short_conv_fwd": 12, "short_conv_bwd": 6},
                 14_642_000_000, id="lfm2-program-B1-T8192-six-conv-two-attention"),
    pytest.param(_LFM2, "reference",
                 {"attention_global": 0, "short_conv_fwd": 0, "short_conv_bwd": 0},
                 13_686_000_000, id="lfm2-reference-float32-in-pieces",
                 marks=pytest.mark.slow),
    pytest.param(_QWEN, "program", dict(zip(_QWEN_KERNELS, (3, 6, 6, 6, 3))),
                 11_970_000_000, id="qwen3-next-program-B1-T8192-three-gdn-one-attention"),
    pytest.param(_QWEN, "reference", dict.fromkeys(_QWEN_KERNELS, 0),
                 15_766_000_000, id="qwen3-next-reference-float32-in-pieces",
                 marks=pytest.mark.slow)])
def test_a_decoder_cells_step_fits_a_v5e(one_chip, monkeypatch, cell_name, which,
                                         kernel_calls, budget):
    """Kanana: eighteen flash kernel calls in the program's step: forward,
    dK/dV and dQ of six layers at a query-key head of 192 beside a value head
    of 128, the forward not run again because its output and logsumexp are
    kept.  LFM2: the same three of two attention layers at heads of 64, and of
    six short-convolution layers the forward kernel twice (the recomputed
    block runs it again) and the backward kernel once.  Qwen3-Next: the three
    flash kernels of one attention layer at heads of 256, and of three
    gated-delta-rule layers the stage's and the walk's forward kernels twice
    (the delta rule's output is kept; a group of heads under its own checkpoint
    runs them again before its backward) and their backward kernels once."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from chipbench import manifest, optimizers, seeded

    for module in ("flash_attention", "causal_conv", "gdn"):
        monkeypatch.setattr(importlib.import_module(f"bluefog_tpu.kernels.{module}"),
                            "_default_interpret", lambda: False)
    cell = manifest.resolve(cell_name)
    sizes, ref = cell.sizes(), cell.module("reference")
    spec = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    params = {p: spec(s) for p, s in ref.param_shapes(sizes)[0].items()}
    ids = spec((sizes["per_rank_batch"], sizes["seq_len"]), jnp.int32)
    tx = optimizers.make(cell.mix["optimizer"])
    opt = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype),
                                 jax.eval_shape(tx.init, params))
    if which == "program":
        apply_fn = cell.module("program").build(sizes)["apply_fn"]
        loss_of = lambda p, x, y: apply_fn({"params": seeded.nest(p)}, x, labels=y)
    else:
        loss_of = lambda p, x, y: ref.loss_fn(p, {}, x, y, sizes)[0]

    def step(p, o, x, y):
        loss, g = jax.value_and_grad(loss_of)(p, x, y)
        updates, o = tx.update(g, o, p)
        out = (optax.apply_updates(p, updates), o, loss)
        return out + (g,) if which == "reference" else out  # check.local_step_fn's

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(params, opt, ids, ids).compile()
    # beside XLA's own grouped products of the expert layers
    text = compiled.as_text()
    for kernel, calls in kernel_calls.items():
        assert len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text)) == calls, kernel
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
             + m.temp_size_in_bytes)
    assert 0.25 * 16.909e9 < total <= budget, total


def test_grouped_expert_products_compile_to_xlas_kernel_for_v5e(one_chip):
    """`held_topk_experts` at the benchmark's sizes: the three grouped
    products of a pass and their transposes are XLA's own grouped-matmul
    kernel (`ragged-dot`), not a dense product per expert."""
    from bluefog_tpu.parallel.expert import held_topk_experts, route_topk

    T, d, f, E, held = 16384, 2560, 768, 64, 8

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, m, router, wg, wu, wd):
        experts, weights = route_topk(x, router, 6)
        y = held_topk_experts(m, experts, weights, {"wg": wg, "wu": wu, "wd": wd},
                              range(held), E)
        return jnp.sum(y.astype(jnp.float32))

    args = (spec((T, d), jnp.bfloat16), spec((T, d), jnp.bfloat16),
            spec((d, E), jnp.float32), spec((held, d, f), jnp.float32),
            spec((held, d, f), jnp.float32), spec((held, f, d), jnp.float32))
    compiled = jax.jit(jax.grad(loss, argnums=(1, 2, 3, 4, 5))).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("ragged-dot-none") >= 9  # 3 forward, 3 recomputed... and 6 transposes
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


def test_exp2_neighbor_allreduce_compiles_to_permutes_on_2x2(topo):
    """The gossip collective over the real four-chip mesh: ``bf.init`` takes
    the described devices, the exp2(4) plan lowers to collective-permutes."""
    bf.init(devices=topo.devices)
    try:
        bf.set_topology(topology_util.ExponentialTwoGraph(4))
        ctx = basics.context()
        assert ctx.size == 4
        gossip = jax.jit(jax.shard_map(
            lambda x: ops_spmd.neighbor_allreduce(x, ctx.plan, NODES_AXIS),
            mesh=ctx.mesh, in_specs=P(NODES_AXIS), out_specs=P(NODES_AXIS)))
        x = jax.ShapeDtypeStruct(
            (4, 1 << 20), jnp.float32,
            sharding=NamedSharding(ctx.mesh, P(NODES_AXIS)))
        text = gossip.lower(x).compile().as_text()
    finally:
        bf.shutdown()
    # exp2(4): every rank hears from i-1 and i-2
    assert "collective-permute" in text
    assert "all-reduce" not in text


def _atc_step_on_2x2(topo):
    """The ATC train step over exp2(4) on the described mesh, at the sizes of
    chipbench/configs/resnet50.json's ``rehearsal`` (compiles in seconds):
    (compiled text, number of shift classes, number of leaves)."""
    model = models.ResNet(stage_sizes=[1, 1], block_cls=BottleneckBlock,
                          num_classes=10, num_filters=8)
    bf.init(devices=topo.devices)
    try:
        bf.set_topology(topology_util.ExponentialTwoGraph(4))
        ctx = basics.context()
        ranks = NamedSharding(ctx.mesh, P(NODES_AXIS))
        every = NamedSharding(ctx.mesh, P())
        rank_major = lambda t: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((4,) + a.shape, a.dtype, sharding=ranks), t)
        v = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))))
        init_fn, step_fn = make_decentralized_train_step(
            model.apply, optax.sgd(0.1, momentum=0.9), ctx.mesh, plan=ctx.plan,
            has_batch_stats=True)
        params, stats = rank_major(v["params"]), rank_major(v["batch_stats"])
        opt = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=ranks if a.ndim else every),
            jax.eval_shape(init_fn, params))
        x = jax.ShapeDtypeStruct((4, 4, 32, 32, 3), jnp.float32, sharding=ranks)
        y = jax.ShapeDtypeStruct((4, 4), jnp.int32, sharding=ranks)
        text = jax.jit(step_fn, donate_argnums=(0, 1, 2)).lower(
            params, stats, opt, x, y).compile().as_text()
        return text, len(ctx.plan.classes), len(jax.tree_util.tree_leaves(params))
    finally:
        bf.shutdown()


def test_atc_step_starts_its_first_bucket_under_the_backward_pass(topo):
    """As many permutes as the scheduler will hold, and the bucket that is
    ready first starts before the last convolution of the scheduled program:
    its bytes travel while the backward pass still runs."""
    text, classes, _ = _atc_step_on_2x2(topo)
    buckets = (ops_spmd.MAX_PERMUTES_OUTSTANDING - 1) // classes
    marks = entry_schedule(text)
    assert marks.count("S") == marks.count("D") == classes * buckets == 4
    assert marks[:marks.rfind("C")].count("S") == classes  # the first bucket's
    assert "all-reduce" not in text


def test_per_leaf_step_parks_its_permutes_behind_the_backward_pass(
        topo, monkeypatch):
    """What the grouping is for, and the limit its constant is read from:
    with a permute per leaf the scheduler keeps ``MAX_PERMUTES_OUTSTANDING``
    in flight and no more, so only that many starts precede the last
    convolution.  A compiler that lifts the limit fails here."""
    real = ops_spmd.neighbor_allreduce
    monkeypatch.setattr(ops_spmd, "neighbor_allreduce",
                        lambda *a, order=None, **k: real(*a, **k))
    text, classes, leaves = _atc_step_on_2x2(topo)
    marks = entry_schedule(text)
    assert marks.count("S") == marks.count("D") == classes * leaves
    assert most_outstanding(marks) == ops_spmd.MAX_PERMUTES_OUTSTANDING
    early = marks[:marks.rfind("C")].count("S")
    assert 0 < early <= ops_spmd.MAX_PERMUTES_OUTSTANDING


def _flat_pack(leaves):
    return jnp.concatenate([a.ravel() for a in leaves])


def _flat_unpack(flat, leaves):
    ends = np.cumsum([a.size for a in leaves])
    return [flat[end - a.size:end].reshape(a.shape) for a, end in zip(leaves, ends)]


def _gossip_part_on_2x2(topo, leaves):
    """What the ATC step does once it has its gradients, alone: SGD with
    momentum, the bucketed gossip over exp2(4), ``p + (c - p)``; parameters,
    gradients and momentum are arguments.  ``leaves`` is a flat dict of
    per-rank shapes, ready in reverse key order.  The compiled text."""
    bf.init(devices=topo.devices)
    try:
        bf.set_topology(topology_util.ExponentialTwoGraph(4))
        ctx = basics.context()
        ranks = NamedSharding(ctx.mesh, P(NODES_AXIS))
        tx = optim.adapt_then_combine_spmd(
            optax.sgd(0.1, momentum=0.9),
            optim.make_spmd_comm_fn(optim.CommunicationType.neighbor_allreduce,
                                    plan=ctx.plan))
        order = {k: len(leaves) - i for i, k in enumerate(leaves)}
        first = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
        again = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)

        def local(p, g, trace):
            p, g, trace = first(p), first(g), first(trace)
            state = optim.GossipState(
                base=(optax.TraceState(trace=trace), optax.EmptyState()),
                step=jnp.zeros((), jnp.int32))
            updates, state = tx.update(g, state, p, grad_order=order)
            return again(optax.apply_updates(p, updates)), again(state.base[0].trace)

        tree = {k: jax.ShapeDtypeStruct((4,) + a.shape, a.dtype, sharding=ranks)
                for k, a in leaves.items()}
        fn = jax.jit(jax.shard_map(local, mesh=ctx.mesh, in_specs=P(NODES_AXIS),
                                   out_specs=P(NODES_AXIS)),
                     donate_argnums=(0, 2))
        return fn.lower(tree, tree, tree).compile().as_text()
    finally:
        bf.shutdown()


def _resnet50_leaves():
    model = models.ResNet50(num_classes=1000)
    v = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))))
    return {jax.tree_util.keystr(k): a for k, a in
            jax.tree_util.tree_leaves_with_path(v["params"])}


def _bf16_decoder_leaves():
    # two layers' worth of a bf16 transformer's matrices and vectors
    shapes = {"qkv": (1024, 3072), "out": (1024, 1024), "up": (1024, 4096),
              "down": (4096, 1024), "norm": (1024,), "embed": (32000, 1024)}
    return {f"{k}{i}": jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for i in range(2) for k, s in shapes.items()}


_INSTRUCTION_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\(([^)]*)\)")
_ARRAY_RE = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4}


def _entry_instructions(text):
    """(name, elements and bytes of its largest result, opcode, operand names)
    of each instruction of the ENTRY computation, in program order."""
    start = text.index("\nENTRY ")
    for line in text[start + 1:text.index("\n}", start)].splitlines()[1:]:
        name, result, opcode, operands = _INSTRUCTION_RE.match(line).groups()
        n, nbytes = max(
            (int(np.prod([int(d) for d in dims.split(",") if d])), _BYTES[dtype])
            for dtype, dims in _ARRAY_RE.findall(result))
        yield name, n, n * nbytes, opcode, re.findall(r"%([\w.\-]+)", operands)


@pytest.mark.parametrize("make_leaves,pack", [
    pytest.param(_resnet50_leaves, "tiles", id="resnet50-f32-tiles"),
    pytest.param(_resnet50_leaves, "flat", id="resnet50-f32-flat"),
    pytest.param(_bf16_decoder_leaves, "tiles", id="decoder-bf16-tiles"),
])
def test_gossip_buckets_pack_and_unpack_as_bitcasts(
        topo, monkeypatch, make_leaves, pack):
    """The optimizer-and-gossip part on real leaf shapes, where every ``copy``,
    ``reshape`` or ``transpose`` instruction of the compiled program is a
    relayout pass around a bucket (there is nothing else in it): a leaf that
    is whole 8 x 128 tiles goes into the bucket that is sent and comes out of
    each bucket that arrives with none over 1 MB; only leaves that are not
    whole tiles pay (of ResNet-50's: the head [2048, 1000], 8.2 of the 9.0 MB
    that are not).  2-byte leaves too: the compiler keeps them in 8-row
    tiles.  The flat pack of PR 27 (``ravel`` and a 1-D bucket), put back by
    hand, pays a pass each way for every large leaf: what the tile order is
    for, and the first thing to look at if this fails.  Two buckets on two
    shift classes: 4 permutes.

    What the step's gain rests on besides (PERF.md section 6, PR 30): nothing
    that reads a bucket a permute brought writes a buffer of the bucket's
    size.  A combine at the bucket's size writes one, as large as the
    parameters, and on the chip it cost more than the relayouts saved."""
    leaves = make_leaves()
    if pack == "flat":
        monkeypatch.setattr(ops_spmd, "_pack_bucket", _flat_pack)
        monkeypatch.setattr(ops_spmd, "_unpack_bucket", _flat_unpack)
    text = _gossip_part_on_2x2(topo, leaves)
    assert text.count(" collective-permute-start(") == 4
    sizes = lambda keep: sorted(
        int(np.prod(a.shape)) for a in leaves.values()
        if keep(a) and a.size * a.dtype.itemsize > 1 << 20)
    whole, ragged = sizes(ops_spmd._tileable), sizes(lambda a: not ops_spmd._tileable(a))
    entry = list(_entry_instructions(text))
    moved = sorted(n for _, n, nbytes, opcode, _ in entry
                   if opcode in ("copy", "reshape", "transpose")
                   and nbytes >= 1 << 20)
    if pack == "tiles":
        assert set(moved) <= set(ragged), moved
        arrived = {name: n for name, n, _, opcode, _ in entry
                   if opcode == "collective-permute-done"}
        assert len(arrived) == 4
        mixed = [(name, n) for name, n, _, _, operands in entry
                 for o in operands if n >= arrived.get(o, n + 1)]
        assert not mixed, mixed
    else:
        assert len(moved) >= len(whole)  # at least a pass per large leaf

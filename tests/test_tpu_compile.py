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

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

import bluefog_tpu as bf
from bluefog_tpu import ops_spmd, topology_util
from bluefog_tpu.core import basics
from bluefog_tpu.core.basics import NODES_AXIS
from bluefog_tpu.kernels.flash_attention import flash_attention


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


# [B, T, H, D] of the decoder presets the benchmarks run (benchmarks/llama.py
# "small", "1b", the long-context run) plus a short sequence and f32
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

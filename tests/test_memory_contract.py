"""Per-device MEMORY contracts for the flagship programs (r4 verdict #1b).

``compiled.memory_analysis()`` is XLA's own buffer accounting for the
per-device SPMD module — asserting it turns the memory story from a hand
table into a tripwire: a jax upgrade, a plan change, or a model edit that
re-resolves shardings (the round-5 8B campaign caught FOUR such
resolutions: dense-W mixing gathers, take-induced batch replication,
tensor-parallel activation drift, replicated head-kernel cotangents)
fails here instead of OOMing on a pod.

Arguments are asserted TIGHTLY (state bytes are deterministic: a dtype or
sharding drift moves them immediately); temps get a measured envelope
with headroom — they are scheduler-dependent, and the envelope documents
the value the design was validated at.

All programs are AOT-compiled from ShapeDtypeStructs with explicit
NamedShardings — nothing is materialized, so the 1B-state program
compiles on this host in seconds.  The full-8B compile (32 virtual
devices) runs in ``test_8b_full_compile_fits_16gb`` via subprocess.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu import topology_util as tu
from bluefog_tpu.common.hlo_inspect import memory_bytes
from bluefog_tpu.core import basics
from bluefog_tpu.core.basics import NODES_AXIS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GB = 1e9


@pytest.fixture(autouse=True)
def no_persistent_compile_cache():
    # an entry point's main() may have turned the persistent compilation
    # cache on in this process (use_compile_cache), and executables
    # deserialized from that cache report alias_size_in_bytes == 0 —
    # every aliasing assertion below would fail in-suite while passing
    # in isolation.  These contracts need a real compile.  Clearing the
    # config alone is not enough: is_cache_used() memoizes its verdict
    # per process, so once any compile ran with the cache on, the dir
    # change is ignored until reset_cache() drops the memo.
    from jax._src import compilation_cache as _cc
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    _cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    _cc.reset_cache()


@pytest.fixture(autouse=True)
def fresh_context(devices):
    bf.init()
    bf.set_topology(tu.ExponentialTwoGraph(8))
    yield
    bf.shutdown()


def _rank_major_structs(tree, mesh):
    """ShapeDtypeStructs with the rank-major sharding the train step uses
    (leading rank axis over the mesh; scalars replicated)."""

    def struct(a):
        if getattr(a, "ndim", 0) >= 1:
            sh = NamedSharding(
                mesh, P(NODES_AXIS, *([None] * (a.ndim - 1))))
        else:
            sh = NamedSharding(mesh, P())
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

    return jax.tree_util.tree_map(struct, tree)


def _state_bytes(tree):
    """Per-RANK bytes of a rank-major tree (leading axis divides away)."""
    total = 0
    for l in jax.tree_util.tree_leaves(tree):
        n = int(np.prod(l.shape[1:])) if l.ndim >= 1 else 1
        total += n * l.dtype.itemsize
    return total


def _compile_step(step_fn, *structs):
    # donate the train state like the benchmarks do — without it the
    # aliasing column reads 0 and every state output double-counts
    return jax.jit(step_fn, donate_argnums=(0, 1, 2)).lower(
        *structs).compile()


def test_llama_134m_train_step_memory():
    """The driver-benchmark 134M config (a small decoder's shapes,
    blockwise attention standing in for the Pallas kernel — same O(T)
    memory class; Pallas does not compile on CPU)."""
    from bluefog_tpu.kernels import make_flash_attention_fn
    from bluefog_tpu.models.transformer import LlamaLM
    from bluefog_tpu.optim import CommunicationType
    from bluefog_tpu.training import (
        make_decentralized_train_step,
        make_lm_loss_fns,
        replicate_for_mesh,
    )

    ctx = basics.context()
    n = 8
    model = LlamaLM(vocab_size=32000, hidden_size=768, num_layers=12,
                    num_heads=12, dff=2048, head_chunks=8,
                    attention_fn=make_flash_attention_fn(impl="xla"))
    B, T = 8, 2048
    ids0 = jnp.ones((B, T), jnp.int32)
    p_shapes = jax.eval_shape(
        lambda: replicate_for_mesh(
            model.init(jax.random.PRNGKey(0), ids0)["params"], n))
    lm_apply, lm_loss = make_lm_loss_fns(model)
    init_fn, step_fn = make_decentralized_train_step(
        lm_apply, optax.sgd(3e-4, momentum=0.9,
                            accumulator_dtype=jnp.bfloat16),
        ctx.mesh, communication_type=CommunicationType.neighbor_allreduce,
        plan=ctx.plan, loss_fn=lm_loss, donate=True)
    os_shapes = jax.eval_shape(init_fn, p_shapes)
    mesh = ctx.mesh
    p_s = _rank_major_structs(p_shapes, mesh)
    os_s = _rank_major_structs(os_shapes, mesh)
    ids_s = jax.ShapeDtypeStruct(
        (n, B, T), jnp.int32,
        sharding=NamedSharding(mesh, P(NODES_AXIS)))
    mem = memory_bytes(_compile_step(step_fn, p_s, None, os_s, ids_s, ids_s))

    # state: 134.1M params f32 + bf16 momentum = 804 MB/device (+ ids) —
    # TIGHT: a momentum-dtype drift or a gossip path that stops sharding
    # the rank axis moves this immediately
    state = _state_bytes(p_s) + _state_bytes(os_s)
    assert abs(mem["arguments"] - state) < 0.05 * GB + 2 * B * T * 4, mem
    # donation aliases the whole state in place
    assert mem["aliased"] >= 0.95 * state, mem
    # temps: ORDER-OF-MAGNITUDE envelope only.  Measured 42.7 GB on
    # XLA:CPU — the blockwise-attention stand-in's unrolled backward
    # keeps f32 [B,H,T,K] buffers live that the Pallas kernel holds in
    # VMEM on chip (the real 134M step runs in <6 GB of HBM, proven by
    # the bench itself on a 16 GB chip).  The envelope still trips on
    # multiplicative regressions: batch-axis replication across the 8
    # ranks (the failure mode the 8B campaign caught) is x8 here.
    assert mem["temps"] < 60 * GB, mem


def test_llama_1b_train_step_memory():
    """The 1B preset (scan+remat, bf16 momentum, chunked head): the
    single-chip 16 GB budget that B=8 was tuned against — state 6.3 GB,
    temps must leave the rest free."""
    from bluefog_tpu.kernels import make_flash_attention_fn
    from bluefog_tpu.models.transformer import LlamaLM
    from bluefog_tpu.optim import CommunicationType
    from bluefog_tpu.training import (
        make_decentralized_train_step,
        make_lm_loss_fns,
        replicate_for_mesh,
    )

    ctx = basics.context()
    n = 8
    model = LlamaLM(vocab_size=32000, hidden_size=1792, num_layers=24,
                    num_heads=14, dff=4864, head_chunks=8, remat=True,
                    scan_layers=True,
                    attention_fn=make_flash_attention_fn(impl="xla"))
    B, T = 8, 2048
    ids0 = jnp.ones((B, T), jnp.int32)
    p_shapes = jax.eval_shape(
        lambda: replicate_for_mesh(
            model.init(jax.random.PRNGKey(0), ids0)["params"], n))
    lm_apply, lm_loss = make_lm_loss_fns(model)
    init_fn, step_fn = make_decentralized_train_step(
        lm_apply, optax.sgd(3e-4, momentum=0.9,
                            accumulator_dtype=jnp.bfloat16),
        ctx.mesh, communication_type=CommunicationType.neighbor_allreduce,
        plan=ctx.plan, loss_fn=lm_loss, donate=True)
    os_shapes = jax.eval_shape(init_fn, p_shapes)
    mesh = ctx.mesh
    p_s = _rank_major_structs(p_shapes, mesh)
    os_s = _rank_major_structs(os_shapes, mesh)
    ids_s = jax.ShapeDtypeStruct(
        (n, B, T), jnp.int32,
        sharding=NamedSharding(mesh, P(NODES_AXIS)))
    mem = memory_bytes(_compile_step(step_fn, p_s, None, os_s, ids_s, ids_s))

    state = _state_bytes(p_s) + _state_bytes(os_s)
    # 1.05B f32 + bf16 momentum = 6.3 GB/device
    assert 6.0 * GB < state < 6.6 * GB, state
    assert abs(mem["arguments"] - state) < 0.05 * GB + 2 * B * T * 4, mem
    assert mem["aliased"] >= 0.95 * state, mem
    # temps: measured 16.0 GB on XLA:CPU — scan+remat keep one layer
    # live, but the attention stand-in's unrolled backward still carries
    # f32 score-class buffers that Pallas holds in VMEM on chip (the
    # real 1B step fits B=8 on a 16 GB chip, proven by the bench).
    # Envelope = 1.5x measured: trips on replication-class regressions.
    assert mem["temps"] < 24 * GB, mem


def test_resnet50_train_step_memory():
    """The driver benchmark's exact program (ResNet-50, B=128@224, sgdm,
    exp2 gossip, donated state)."""
    from bluefog_tpu.models import ResNet50
    from bluefog_tpu.optim import CommunicationType
    from bluefog_tpu.training import (
        make_decentralized_train_step,
        replicate_for_mesh,
    )

    ctx = basics.context()
    n = 8
    model = ResNet50(num_classes=1000)
    B, img = 128, 224
    x0 = jnp.ones((B, img, img, 3), jnp.float32)
    var_shapes = jax.eval_shape(
        lambda: replicate_for_mesh(
            model.init(jax.random.PRNGKey(0), x0), n))
    p_shapes = var_shapes["params"]
    bs_shapes = var_shapes["batch_stats"]
    init_fn, step_fn = make_decentralized_train_step(
        model.apply, optax.sgd(0.1, momentum=0.9), ctx.mesh,
        communication_type=CommunicationType.neighbor_allreduce,
        plan=ctx.plan, has_batch_stats=True, donate=True)
    os_shapes = jax.eval_shape(init_fn, p_shapes)
    mesh = ctx.mesh
    p_s = _rank_major_structs(p_shapes, mesh)
    bs_s = _rank_major_structs(bs_shapes, mesh)
    os_s = _rank_major_structs(os_shapes, mesh)
    x_s = jax.ShapeDtypeStruct(
        (n, B, img, img, 3), jnp.float32,
        sharding=NamedSharding(mesh, P(NODES_AXIS)))
    y_s = jax.ShapeDtypeStruct(
        (n, B), jnp.int32, sharding=NamedSharding(mesh, P(NODES_AXIS)))
    mem = memory_bytes(_compile_step(step_fn, p_s, bs_s, os_s, x_s, y_s))

    state = (_state_bytes(p_s) + _state_bytes(bs_s) + _state_bytes(os_s))
    data = B * img * img * 3 * 4
    assert abs(mem["arguments"] - state - data - B * 4) < 0.05 * GB, mem
    assert mem["aliased"] >= 0.9 * state, mem
    # measured 11.5 GB of temps on XLA:CPU (f32 conv activations at
    # B=128 dominate; the chip runs the same config inside 16 GB).
    # Envelope = 1.3x measured: trips on replication-class regressions
    # (batch-axis replication across the 8 ranks would be x8).
    assert mem["temps"] < 15 * GB, mem


def test_8b_adamw_full_compile_fits_16gb_at_2x16():
    """The Adam family at 8B: mu bf16 + nu f32 (nu's 0.1%/step EMA decay
    is sub-ulp in bf16 — it would freeze; ``_make_update_rule`` pins it
    f32) + count push the 4x8 state to 10.04 GB/device (19.7 live — over
    budget), but at local=16 the shards halve: validated 12.01 GB live at
    the 2x16 mesh.  The contract pins the deployment answer: sgdm ships
    at 4x8, adamw at 2x16."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
        ZERO8B_MESH="2x16",
        XLA_FLAGS="--xla_force_host_platform_device_count=32",
        JAX_COMPILATION_CACHE_DIR="",  # fresh compile: see no_persistent_compile_cache
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "zero_8b.py"),
         "--compile", "--optimizer", "adamw"],
        env=env, capture_output=True, text=True, timeout=560, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["fits_16gb"] is True, out
    assert out["optimizer"] == "adamw" and out["layers"] == 32, out


def test_8b_full_compile_fits_16gb():
    """BASELINE config #5 (r4 verdict #1c/#4): the FULL 32-layer
    Llama-3-8B FSDP+gossip program at its deployment sharding (4 machines
    x 8 local = 32 virtual devices) must COMPILE and fit 16 GB/device by
    XLA's own accounting.  Subprocess: needs its own 32-device platform.
    Validated at 15.64 GB live (args 6.02 = f32 master shard + bf16
    momentum shard, temps 9.62)."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
        ZERO8B_MESH="4x8",
        XLA_FLAGS="--xla_force_host_platform_device_count=32",
        JAX_COMPILATION_CACHE_DIR="",  # fresh compile: see no_persistent_compile_cache
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "zero_8b.py"),
         "--compile"],
        env=env, capture_output=True, text=True, timeout=560, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["fits_16gb"] is True, out
    assert out["per_device_gb"]["live_peak_upper_bound"] < 16.0, out
    assert out["layers"] == 32 and out["params_b"] > 7.9, out

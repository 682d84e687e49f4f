"""Synthetic throughput benchmark — JAX twin of the reference's
``examples/pytorch_benchmark.py`` [U] (SURVEY.md §5.5: img/sec with warmup,
the number BASELINE's metric refers to), with selectable model, topology
and communication mode.

Run (CPU mesh): JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/jax_benchmark.py --model tiny --iters 3
Run (TPU):      python examples/jax_benchmark.py --model resnet50
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import bluefog_tpu as bf
from bluefog_tpu import topology_util
from bluefog_tpu.core import basics
from bluefog_tpu.models import ResNet18, ResNet50
from bluefog_tpu.optim import CommunicationType
from bluefog_tpu.training import make_decentralized_train_step, replicate_for_mesh

TOPOS = {
    "exp2": topology_util.ExponentialTwoGraph,
    "ring": topology_util.RingGraph,
    "full": topology_util.FullyConnectedGraph,
    "mesh2d": topology_util.MeshGrid2DGraph,
}
MODES = {
    "neighbor_allreduce": CommunicationType.neighbor_allreduce,
    "allreduce": CommunicationType.allreduce,
    "hierarchical": CommunicationType.hierarchical_neighbor_allreduce,
    "empty": CommunicationType.empty,
}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="resnet50",
                        choices=["resnet50", "resnet18", "tiny"])
    parser.add_argument("--batch-size", type=int, default=0, help="per rank (0=auto)")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=5)
    parser.add_argument("--topology", default="exp2", choices=sorted(TOPOS))
    parser.add_argument("--mode", default="neighbor_allreduce", choices=sorted(MODES))
    parser.add_argument("--loader", default="host", choices=["host", "native"],
                        help="native = C++ prefetching data pipeline")
    args = parser.parse_args()

    bf.init()
    n = bf.size()
    bf.set_topology(TOPOS[args.topology](n))
    ctx = basics.context()
    on_tpu = jax.devices()[0].platform == "tpu"

    if args.model == "resnet50":
        model, img = ResNet50(num_classes=1000), 224
    elif args.model == "resnet18":
        model, img = ResNet18(num_classes=1000), 224
    else:
        model, img = ResNet18(num_classes=10, num_filters=8, small_images=True), 16
    bsz = args.batch_size or (64 if on_tpu else 2)

    variables = model.init(
        jax.random.PRNGKey(0), jnp.ones((bsz, img, img, 3)), train=True
    )
    params = replicate_for_mesh(variables["params"], n)
    bstats = replicate_for_mesh(variables["batch_stats"], n)
    rng = np.random.default_rng(0)
    labels = jnp.asarray(rng.integers(0, 10, size=(n, bsz)), jnp.int32)
    loader = None
    if args.loader == "native":
        # C++ worker threads prefetch batches, overlapping with compute
        from bluefog_tpu.native.data_native import NativeDataLoader

        loader = NativeDataLoader((n, bsz, img, img, 3), depth=4, workers=2)
        # zero-copy is only safe where the device copy provably completes
        # before the ring buffer is released: on a TPU the batch is in HBM
        # once block_until_ready returns; the CPU backend may alias host
        # memory — so copy there.
        zero_copy = jax.devices()[0].platform == "tpu"

        def next_batch():
            if zero_copy:
                with loader.next_view() as v:
                    arr = jax.device_put(v)
                    arr.block_until_ready()
                    return arr
            return jnp.asarray(loader.next())
    else:
        fixed = jnp.asarray(
            rng.normal(size=(n, bsz, img, img, 3)).astype(np.float32)
        )
        next_batch = lambda: fixed
    batch = next_batch()

    comm = MODES[args.mode]
    mesh = ctx.hier_mesh if args.mode == "hierarchical" else ctx.mesh
    init_fn, step_fn = make_decentralized_train_step(
        model.apply,
        optax.sgd(0.1, momentum=0.9),
        mesh,
        communication_type=comm,
        plan=ctx.plan if comm == CommunicationType.neighbor_allreduce else None,
        machine_plan=ctx.machine_plan if args.mode == "hierarchical" else None,
        has_batch_stats=True,
        donate=False,
    )
    state = init_fn(params)

    def sync(loss):
        assert np.isfinite(float(np.asarray(jnp.sum(loss))))

    loss = None
    for _ in range(args.warmup):
        params, bstats, state, loss, _ = step_fn(params, bstats, state, batch, labels)
    sync(loss)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        batch = next_batch()
        params, bstats, state, loss, _ = step_fn(params, bstats, state, batch, labels)
    sync(loss)
    dt = (time.perf_counter() - t0) / args.iters
    if loader is not None:
        produced, consumed, stalls = loader.stats()
        print(f"native loader: {produced} produced, {stalls} consumer stalls")
        loader.close()
    total = n * bsz / dt
    print(
        f"model={args.model} topology={args.topology} mode={args.mode} "
        f"ranks={n} batch/rank={bsz}"
    )
    print(
        f"step time {dt * 1e3:.2f} ms | {bsz / dt:.1f} img/s/rank | "
        f"{total:.1f} img/s total"
    )
    bf.shutdown()


if __name__ == "__main__":
    main()

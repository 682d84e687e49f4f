"""bluefog_tpu — TPU-native decentralized (gossip) training framework.

A ground-up JAX/XLA rebuild of the capabilities of Bluefog
(arXiv:2111.04287; upstream layout ``bluefog/`` [U], see SURVEY.md):
virtual-topology gossip collectives (``neighbor_allreduce``,
``hierarchical_neighbor_allreduce``), one-sided window ops emulated with
device-memory mailboxes, and decentralized optimizers — all lowered to XLA
collectives (``lax.ppermute`` / ``psum`` / ``all_to_all``) over a
``jax.sharding.Mesh``, with no MPI/NCCL/GPU anywhere.

The public surface mirrors ``bluefog.torch`` (reference
``bluefog/torch/mpi_ops.py``, ``bluefog/common/basics.py`` [U]) but is
idiomatic JAX: every collective is a pure function, usable both eagerly on
per-rank ("rank-major") arrays and inside user ``jit``/``shard_map`` code.
"""

from bluefog_tpu.version import __version__

from bluefog_tpu.core.basics import (
    init,
    shutdown,
    is_initialized,
    size,
    rank,
    local_size,
    local_rank,
    machine_size,
    machine_rank,
    mesh,
    set_topology,
    load_topology,
    set_machine_topology,
    load_machine_topology,
    in_neighbor_ranks,
    out_neighbor_ranks,
    in_neighbor_machine_ranks,
    out_neighbor_machine_ranks,
    is_topo_weighted,
    is_machine_topo_weighted,
    unified_mpi_window_model_supported,
)

from bluefog_tpu.ops import (
    Handle,
    device_sync,
    allreduce,
    allreduce_nonblocking,
    allgather,
    allgather_nonblocking,
    broadcast,
    broadcast_nonblocking,
    neighbor_allgather,
    neighbor_allgather_nonblocking,
    neighbor_allreduce,
    neighbor_allreduce_nonblocking,
    hierarchical_neighbor_allreduce,
    hierarchical_neighbor_allreduce_nonblocking,
    barrier,
    poll,
    synchronize,
    wait,
)

from bluefog_tpu.windows import (
    win_create,
    win_free,
    win_put,
    win_put_nonblocking,
    win_get,
    win_get_nonblocking,
    win_accumulate,
    win_accumulate_nonblocking,
    win_update,
    win_put_update,
    win_update_then_collect,
    win_wait,
    win_poll,
    win_mutex,
    get_win_version,
    win_associated_p,
    win_set_exposed,
    turn_on_win_ops_with_associated_p,
    turn_off_win_ops_with_associated_p,
)

from bluefog_tpu.optim import (
    CommunicationType,
    DistributedAdaptThenCombineOptimizer,
    DistributedAdaptWithCombineOptimizer,
    DistributedGradientAllreduceOptimizer,
    DistributedWinPutOptimizer,
    one_peer_plan_schedule,
    broadcast_parameters,
    broadcast_optimizer_state,
)

from bluefog_tpu.algorithms import (
    DistributedEXTRAOptimizer,
    DistributedGradientTrackingOptimizer,
    DistributedPushDIGingOptimizer,
)

from bluefog_tpu.timeline import (
    timeline_start_activity,
    timeline_end_activity,
    timeline_context,
)

from bluefog_tpu import topology_util

__all__ = [k for k in dict(vars()) if not k.startswith("_")]

"""Does GSPMD slice the per-layer gather inside nn.scan, or gather the
whole stacked leaf?  (The question the 8B memory table's scan-stacked
caveat hinges on, round 3.)

Method: compile the FSDP+gossip step on a small scan+remat Llama over the
8-device CPU mesh and read the post-partitioner HLO: if all-gather result
shapes carry the full ``[layers, ...]`` axis, stacked leaves gather WHOLE
(the conservative transient in ``benchmarks/zero_8b.py`` is real);
per-layer slicing would show gathers without the layer axis.

Observed (jax 0.9, this config): multiple all-gathers with the full layer
axis in their result shapes → stacks gather whole; 8B ships with UNROLLED
leaves.  Small-scale evidence — rerun at larger configs before relying on
it elsewhere.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python benchmarks/scan_gather_probe.py
"""

import os
import re
import sys
from collections import Counter

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bluefog_tpu as bf
from bluefog_tpu import topology_util
from bluefog_tpu.core import basics
from bluefog_tpu.core.basics import LOCAL_AXIS, MACHINES_AXIS
from bluefog_tpu.models.transformer import LlamaLM
from bluefog_tpu.parallel.zero import (
    fsdp_state_struct,
    make_fsdp_gossip_train_step,
)
from jax.sharding import NamedSharding, PartitionSpec as P


def main():
    bf.init(local_size=4)
    ctx = basics.context()
    bf.set_machine_topology(topology_util.RingGraph(2))

    # mid-size scan+remat model: dff 64 shards over local=4
    lm = LlamaLM(vocab_size=97, hidden_size=32, num_layers=6, num_heads=4,
                 dff=64, remat=True, scan_layers=True, dtype=jnp.float32)
    ids0 = jnp.ones((2, 16), jnp.int32)
    p_shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0), ids0)["params"]

    def apply_fn(p, ids):
        return lm.apply({"params": p}, ids)

    def loss_fn(logits, labels):
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(lp, labels[:, 1:, None], -1))

    _, step_fn, _ = make_fsdp_gossip_train_step(
        apply_fn, loss_fn, ctx.hier_mesh, ctx.machine_plan,
        learning_rate=0.1)
    master = jax.tree_util.tree_map(
        lambda l: fsdp_state_struct(l, ctx.hier_mesh), p_shapes)
    mu = jax.tree_util.tree_map(
        lambda l: fsdp_state_struct(l, ctx.hier_mesh), p_shapes)
    data_sh = NamedSharding(ctx.hier_mesh, P(MACHINES_AXIS, LOCAL_AXIS))
    ids_s = jax.ShapeDtypeStruct((2, 4 * 2, 16), jnp.int32,
                                 sharding=data_sh)
    hlo = step_fn.lower(
        {"master": master, "opt": (mu,)}, ids_s, ids_s).compile().as_text()

    layers = lm.num_layers
    # anchor on the opcode token, and accept tuple results (combined /
    # async all-gather-start forms) — a naive `= (\S+) all-gather` match
    # silently drops those and can flip the verdict to a false
    # "sliced per layer"
    op_re = re.compile(
        r"^\s*(?:ROOT\s+)?%?\S+\s*=\s*(\([^)]*\)|\S+)\s*"
        r"all-gather(?:-start|-done)?\(")
    shapes = Counter()
    for line in hlo.splitlines():
        m = op_re.match(line)
        if m:
            shapes[m.group(1)] += 1

    def has_layer_axis(shape_str):
        # the stacked leaf axis appears as the leading dim or right after
        # the [machines] dim of any tensor in the (possibly tuple) result
        for dims in re.findall(r"\[([\d,]+)\]", shape_str):
            parts = [int(x) for x in dims.split(",") if x]
            if parts[:1] == [layers] or parts[1:2] == [layers]:
                return True
        return False

    full_stack = [s for s in shapes if has_layer_axis(s)]
    print("all-gather result shapes:")
    for s, c in shapes.most_common():
        tag = "  <-- FULL layer stack" if s in full_stack else ""
        print(f"  {c:3d}x {s}{tag}")
    verdict = ("stacked leaves gather WHOLE (per-layer slicing NOT "
               "observed) -> the zero_8b scan-stacked transient is real; "
               "ship 8B with unrolled leaves"
               if full_stack else
               "no full-stack gathers observed -> XLA sliced per layer "
               "at this scale")
    print("verdict:", verdict)


if __name__ == "__main__":
    main()

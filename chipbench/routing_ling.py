"""`python -m chipbench.routing` for the `ling-3.0-flash-vl` configuration,
whose plain reference has no `held_rows` of its own:

    python -m chipbench.routing_ling --workload <cell> --seeds 3 --seconds 20

The same readings (`routing.reading`), one JSON line a seed.  The rows on the
experts held are counted here from the reference's own parts: a layer's mixer
and norms as `layer` applies them, then `route` (the sigmoid scores with the
bias in the choice, the groups that stay), in every layer that has experts.
The eight experts held are one eighth of one routing group of 64, so a layer's
count says how often that group stays, which no other cell's routing shows."""

import argparse
import json
import sys

import jax
import jax.numpy as jnp

import bluefog_tpu as bf

from chipbench import manifest, routing
from chipbench.routing_laguna import NAMES
from chipbench.runner import Session


def held_rows(ref, p, ids, sizes):
    """ids [B, T] -> int[expert layers]: how many of the batch's T x
    num_experts_per_tok assignments go, in each layer that has experts, to the
    experts held here (0 .. num_experts_held - 1), float32."""
    eps, held, kinds = sizes["rms_norm_eps"], sizes["num_experts_held"], ref.kinds(sizes)

    def one(seq):
        h, counts = p[("embed", "embedding")][seq], []
        for i, (mixer_kind, dense) in enumerate(kinds):
            b = f"layer_{i}"
            if not dense:
                mixer = ref.kda_mixer if mixer_kind == "kda" else ref.mla_mixer
                u = ref._rms_norm(h, p[(b, "mixer_norm", "scale")], eps)
                mixed = h + mixer(u, p, (b, "mixer"), sizes, False)
                m = ref._rms_norm(mixed, p[(b, "mlp_norm", "scale")], eps)
                counts.append(jnp.sum(ref.route(m, p, b, sizes)[0] < held))
            if i + 1 < len(kinds):
                h = ref.layer(h, p, b, (mixer_kind, dense), sizes, False)
        return jnp.stack(counts)

    return jnp.sum(jax.lax.map(one, ids), axis=0)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m chipbench.routing_ling")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = manifest.resolve(args.workload)
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("chipbench.routing_ling: JAX found no TPU", file=sys.stderr)
        return 2
    ses = Session(cell, args.rehearse)
    ses.sizes = dict(ses.sizes, **{name: ses.sizes[own] for name, own in NAMES.items()})
    count = jax.jit(lambda flat, ids: held_rows(
        ses.reference, {path: a[0] for path, a in flat.items()}, ids[0], ses.sizes))
    for i in range(args.seeds):
        seed = 300 + i * 178956971  # some beyond 32 signed bits
        print(json.dumps(routing.reading(ses, seed, args.seconds, count)), flush=True)
    bf.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())

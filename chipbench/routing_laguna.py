"""`python -m chipbench.routing` for the `laguna-xs.2` configuration, whose
file keeps its source's names for the three sizes that tool reads:

    python -m chipbench.routing_laguna --workload <cell> --seeds 3 --seconds 20

The same readings (`routing.reading`: the rows on the experts held in each
layer that has experts, `held_rows` of the configuration's plain reference,
before and after a window driven as a run drives it, and the median of the
window's first and last ten step times), one JSON line a seed."""

import argparse
import json
import sys

import jax

import bluefog_tpu as bf

from chipbench import manifest, routing
from chipbench.runner import Session

# the name routing.py reads -> the configuration's own
NAMES = {"moe_num_active_primary_experts": "num_experts_per_tok",
         "moe_num_primary_experts_held": "num_experts_held",
         "moe_num_primary_experts": "num_experts"}


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m chipbench.routing_laguna")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = manifest.resolve(args.workload)
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("chipbench.routing_laguna: JAX found no TPU", file=sys.stderr)
        return 2
    ses = Session(cell, args.rehearse)
    ses.sizes = dict(ses.sizes, **{name: ses.sizes[own] for name, own in NAMES.items()})
    count = jax.jit(lambda flat, ids: ses.reference.held_rows(
        {path: a[0] for path, a in flat.items()}, ids[0], ses.sizes))
    for i in range(args.seeds):
        seed = 300 + i * 178956971  # some beyond 32 signed bits
        print(json.dumps(routing.reading(ses, seed, args.seconds, count)), flush=True)
    bf.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Whether a cell's routing stays what the seed made it through a window.

    python -m chipbench.routing --workload <cell> --seeds 3 --seconds 20

For every seed, in one process: the rows that reach the experts held here in
each layer (`held_rows` of the configuration's plain reference, float32) on
the first batch under the seeded weights, the set-up's five steps and a window
of `--seconds` driven as a run drives them, then the same count on the last
step's batch under the weights the window left, and the median of the
window's first and last ten step times.  One JSON line a seed.  A cell whose
step time depends on where its router sends the tokens is a cell of steady
work only while the two counts agree; a benchmark run never runs this.
"""

import argparse
import json
import sys

import jax
import numpy as np

import bluefog_tpu as bf

from chipbench import check, manifest
from chipbench.runner import WARM_EXTRA, Session, drive


def reading(ses, seed, seconds, count):
    ses.load(seed)
    job = ses.make_job()
    first = np.asarray(count(job.params(), ses.batches[0][0]))
    warm = drive(job, ses.spans, 0, steps=check.STEPS + WARM_EXTRA)
    win = drive(job, ses.spans, warm["next_k"], seconds=seconds)
    last_batch = ses.batches[(win["next_k"] - 1) % len(ses.batches)]
    last = np.asarray(count(job.params(), last_batch[0]))
    job.close()
    ses.spans.clear()
    gaps = np.diff(win["stamps"]) * 1e3
    sizes = ses.sizes
    even = (sizes["per_rank_batch"] * sizes["seq_len"]
            * sizes["moe_num_active_primary_experts"]
            * sizes["moe_num_primary_experts_held"] / sizes["moe_num_primary_experts"])
    return {"seed": seed, "steps_in_window": len(win["stamps"]), "even_rows": even,
            "held_rows_first_step": first.tolist(),
            "held_rows_last_step": last.tolist(),
            "step_ms_median_first_ten": float(np.median(gaps[:10])),
            "step_ms_median_last_ten": float(np.median(gaps[-10:])),
            "step_ms_median": float(np.median(gaps)), "failed": win["failed"]}


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m chipbench.routing")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = manifest.resolve(args.workload)
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("chipbench.routing: JAX found no TPU", file=sys.stderr)
        return 2
    ses = Session(cell, args.rehearse)
    # rank 0's weights and rows: every rank starts from the same weights
    count = jax.jit(lambda flat, ids: ses.reference.held_rows(
        {path: a[0] for path, a in flat.items()}, ids[0], ses.sizes))
    for i in range(args.seeds):
        seed = 300 + i * 178956971  # some beyond 32 signed bits
        print(json.dumps(reading(ses, seed, args.seconds, count)), flush=True)
    bf.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The readings a limit is set from, and the controls that have to fail it.

    python -m chipbench.control --workload <name> --seeds 12 --first-seed 100

For every seed, in one process: the program's first three steps against the
plain reference (the sound reading of every number compared), then the
controls put in the program's place: `step`, the reference computed one
precision below the configuration's (float8_e4m3 operands for bfloat16), and
`payload`, the gossip payload rounded to bfloat16 on its way.  One JSON line
per seed, and a last line with, for every number, the largest sound reading
and the smallest under each control.  No window is measured: training's
readings need none.  A benchmark run never runs this.
"""

import argparse
import json
import sys

import jax

import bluefog_tpu as bf

from chipbench import check, manifest
from chipbench.runner import Session


def readings(ses, seed, controls):
    ses.load(seed)
    job = ses.make_job()
    got = ses.first_steps(job)
    job.close()
    ref = ses.reference_run()
    limits = dict.fromkeys(
        ("loss_gap", "grad_norm_gap", "delta_norm_gap", "params1_rel_l2",
         "change1_rel_l2", "assoc_p_gap"), float("inf"))
    out = {"seed": seed, "sound": {
        k: v["value"] for k, v in check.compare(got, ref, limits)[0].items()}}
    for name in controls:
        ctl = ses.reference_run(**{"lower_" + name: True})
        out["control_" + name] = {
            k: v["value"] for k, v in check.compare(ctl, ref, limits)[0].items()}
    return out


def summary(rows):
    out = {}
    for part in sorted({k for r in rows for k in r if k != "seed"}):
        have = [r[part] for r in rows if part in r]
        pick = max if part == "sound" else min
        out[part + ("_max" if part == "sound" else "_min")] = {
            k: pick(h[k] for h in have) for k in have[0]}
        out[part + "_seeds"] = len(have)
    return out


def main():
    ap = argparse.ArgumentParser(prog="python -m chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=100)
    ap.add_argument("--controls", default="step,payload")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = manifest.resolve(args.workload)
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("chipbench.control: JAX found no TPU", file=sys.stderr)
        return 2
    ses = Session(cell, args.rehearse)
    controls = [c for c in args.controls.split(",") if c]
    rows = []
    for i in range(args.seeds):
        # seeds spread out, some beyond 32 signed bits
        seed = args.first_seed + i * 178956971
        rows.append(readings(ses, seed, controls if i < args.control_seeds else []))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "summary": summary(rows)}),
          flush=True)
    bf.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())

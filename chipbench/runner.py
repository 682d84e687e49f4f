"""One process, one cell, once: set up, time a window, decide `correct`, print
the result line.  See README.md for the contract."""

import argparse
import gc
import glob
import json
import math
import os
import shutil
import sys
import time
import types

import jax
import numpy as np

import bluefog_tpu as bf
from bluefog_tpu import native, topology_util
from bluefog_tpu.core import basics

from chipbench import (check, compile_cache, manifest, seeded,
                       spans as spans_mod, trace_reduce)

WARM_EXTRA = 2     # look-ahead warm-up steps after the three checked ones
TRACED_STEPS = 22  # steps inside the profiler's window (edges are dropped)
TRACE_DIR = ".chipbench_trace"


def _log(*parts):
    print("chipbench:", *parts, flush=True)


def _device(chips):
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def _distinct_devices(tree):
    return min(len({s.device for s in leaf.addressable_shards})
               for leaf in jax.tree_util.tree_leaves(tree))


def _memory_peak(devices):
    """Peak bytes on the fullest chip.  On this runtime a program's
    temporaries are not in `peak_bytes_in_use` but in `peak_bytes_reserved`
    (PERF.md section 6, PR 25), and the two regions are disjoint."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0


def drive(job, spans, k, *, seconds=None, steps=None):
    """The loop a user writes: dispatch step i+1, then block on step i (one
    step of look-ahead) and stamp the host clock.  Returns the stamps, the
    per-rank losses of the completed steps and what went wrong."""
    stamps, losses, failed, attempted = [], [], 0, 0
    until = time.perf_counter() + seconds if seconds is not None else None

    def settle(step):
        with spans.span("block"):
            step[0].block_until_ready()
        stamps.append(time.perf_counter())
        losses.append(step[1])

    prev = None
    while True:
        try:
            with spans.span("dispatch"):
                step = job.step(k)  # (what marks the step done, its losses)
        except Exception as exc:  # the window must report, not die
            _log(f"step {k} raised {type(exc).__name__}: {exc}")
            failed += 1
            attempted += 1
            break
        attempted += 1
        k += 1
        if prev is not None:
            settle(prev)
        prev = step
        if until is not None and time.perf_counter() >= until:
            break
        if steps is not None and attempted >= steps:
            break
    if prev is not None:
        settle(prev)
    losses = np.stack([np.asarray(l, np.float64) for l in losses]) if losses \
        else np.zeros((0, 1))
    failed += int(np.sum(~np.isfinite(losses).all(axis=1)))
    return {"stamps": stamps, "losses": losses, "attempted": attempted,
            "failed": failed, "next_k": k}


def _quantile(values, q):
    """The q-quantile by linear interpolation (numpy's default)."""
    return float(np.quantile(np.asarray(values, np.float64), q))


def _window_numbers(win, per_rank_batch):
    stamps = win["stamps"]
    gaps = np.diff(stamps)
    span = stamps[-1] - stamps[0] if len(stamps) > 1 else float("nan")
    return {
        "steps_completed": len(stamps),
        "gaps_ms": (gaps * 1e3).tolist(),
        "seconds": span,
        # samples completed between the first and last stamp, per chip
        "samples_per_s_chip": (len(stamps) - 1) * per_rank_batch / span,
        "step_ms_p95": _quantile(gaps * 1e3, 0.95) if len(gaps) else float("nan"),
        "step_ms_median": _quantile(gaps * 1e3, 0.5) if len(gaps) else float("nan"),
    }


def trace_path(root):
    found = glob.glob(os.path.join(root, "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


class Session:
    """What one process sets up once for a cell: the mesh and topology, the
    cell's files, and the builder of seeded weights.  `load(seed)` makes that
    seed's batches; `make_job` builds the program's job from fresh weights."""

    def __init__(self, cell, rehearse):
        self.cell, self.rehearse = cell, rehearse
        self.marks = [("imports", time.perf_counter())]  # where set-up's time goes
        self.chips = cell.chips
        self.sizes = cell.sizes(rehearse)
        self.mix = cell.mix
        self.opt_spec = self.mix.get("optimizer", cell.config["optimizer"])
        self.clock = compile_cache.CompileClock()
        self.spans = spans_mod.Spans()
        if not os.path.exists(native._LIB_PATH):
            native.build()
        self.cache_dir = None if rehearse else compile_cache.use_compile_cache()
        bf.init(devices=jax.devices()[:self.chips])
        topo = self.mix["topology"]
        bf.set_topology(
            getattr(topology_util, topo["graph"])(self.chips, **topo["kwargs"]))
        self.ctx = basics.context()
        self.marks.append(("bf.init", time.perf_counter()))
        self.sharding = basics.rank_major_sharding(self.ctx)
        self.reference = cell.module("reference")
        self.program = cell.module("program").build(self.sizes)
        self.job_kind = cell.module("job")
        self.M = cell.module("mixing").matrix(self.chips)
        self.build_weights = seeded.weights_builder(
            self.reference, self.sizes, self.chips, self.sharding)
        self._ref_weights = seeded.weights_builder(self.reference, self.sizes)
        self._local_steps = {}

    def load(self, seed):
        self.seed = seed
        self.key = seeded.key_of(seed)
        self.batches = seeded.make_batches(
            self.reference, self.sizes, seed, self.chips,
            self.mix.get("pool", 4), self.sharding)
        jax.block_until_ready(self.batches)
        self.marks.append(("batches", time.perf_counter()))

    def make_job(self, mix=None, spans=None):
        params, stats = self.build_weights(self.key)
        return self.job_kind.Job(types.SimpleNamespace(
            ctx=self.ctx, mix=mix or self.mix, sizes=self.sizes,
            opt_spec=self.opt_spec, program=self.program, params=params,
            stats=stats, batches=self.batches, spans=spans or self.spans))

    def first_steps(self, job):
        """The first three steps of the object the window will time, captured
        for the comparison with the plain reference."""
        got = {"losses": []}
        for k in range(check.STEPS):
            token, loss = job.step(k)
            token.block_until_ready()
            got["losses"].append(np.asarray(loss, np.float64))
            self.marks.append((f"step {k + 1}", time.perf_counter()))
            if k == 0:
                got["grad_norms"] = check.leaf_norms(job.first_gradient())
                got["params1"] = {p: np.asarray(a) for p, a in job.params().items()}
                p = job.assoc_p()
                got["assoc_p"] = np.ones(self.chips) if p is None else p
        got["losses"] = np.stack(got["losses"])
        # the start is built again, and only now: the step donates its own, and a
        # copy kept beside the steps would be 4 bytes a parameter of their peak
        start_params, _ = self.build_weights(self.key)
        got["delta_norms"] = check.delta_norms(job.params(), start_params)
        self.marks.append(("captures", time.perf_counter()))
        return got

    def reference_run(self, lower_step=False, lower_payload=False):
        if lower_step not in self._local_steps:  # compiled once for all seeds
            self._local_steps[lower_step] = check.local_step_fn(
                self.reference, self.sizes, self.opt_spec, lower_step)
        return check.reference_run(
            self.reference, self.sizes, self.opt_spec, self.M, self.seed,
            self.batches, lower_step=lower_step, lower_payload=lower_payload,
            payload_includes_self=self.mix.get("payload_includes_self", False),
            local_step=self._local_steps[lower_step],
            weights=self._ref_weights(self.key))


def run(args, t0, cell, wrap_job=None):
    """`wrap_job` is for the tests: it stands a broken job in the timed
    path's place, and `correct` has to come out false."""
    rehearse, chips = args.rehearse, cell.chips
    device = _device(chips)

    # ---- set-up ----------------------------------------------------------
    ses = Session(cell, rehearse)
    ses.load(args.seed)
    sizes, mix, spans, clock = ses.sizes, ses.mix, ses.spans, ses.clock
    job_kind, ctx = ses.job_kind, ses.ctx
    job = ses.make_job()
    if wrap_job is not None:
        job = wrap_job(job)
    placed = _distinct_devices((job.placement(), ses.batches))
    ses.marks.append(("job built", time.perf_counter()))
    got = ses.first_steps(job)
    warm = drive(job, spans, check.STEPS, steps=WARM_EXTRA)
    k = warm["next_k"]
    spans.clear()
    gc.collect()
    compiles_setup = clock.take()
    setup_s = time.perf_counter() - t0
    ses.marks.append(("warm-up", time.perf_counter()))
    took = ", ".join(f"{name} {t - prev:.2f}" for (name, t), prev in zip(
        ses.marks, [t0] + [t for _, t in ses.marks]))
    _log(f"set-up {setup_s:.3f} s ({took}); compiles or "
         f"cache fetches {compiles_setup[0]}, {compiles_setup[1]:.3f} s; "
         f"cache {ses.cache_dir}; placed on {placed} device(s) per leaf")

    # ---- the window ------------------------------------------------------
    gc.disable()
    traced = None
    t_window = time.perf_counter()
    if args.trace:
        trace_root = os.path.join(cell.root, TRACE_DIR)
        shutil.rmtree(trace_root, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_root, profiler_options=options)
        spans.annotate = True
        part = drive(job, spans, k, steps=TRACED_STEPS)
        spans.annotate = False
        jax.profiler.stop_trace()
        k = part["next_k"]
        path = trace_path(trace_root)
        if path:
            traced = trace_reduce.reduce(
                trace_reduce.load(path, spans_mod.NAMES),
                job_kind.STEP_ANCHOR, job_kind.WINDOW_PROGRAMS)
        shutil.rmtree(trace_root, ignore_errors=True)
        spans.clear()
    # a traced run keeps at least a quarter of the window untraced, for the
    # numbers that are taken from the window (the profiler's stop is slow)
    left = max(args.seconds - (time.perf_counter() - t_window), args.seconds / 4) \
        if args.trace else args.seconds
    win = drive(job, spans, k, seconds=left)
    gc.enable()
    compiles_window = clock.take()
    numbers = _window_numbers(win, sizes["per_rank_batch"])
    memory_peak = 0 if rehearse else _memory_peak(ctx.devices)
    _log(f"window {numbers['seconds']:.3f} s, {numbers['steps_completed']} steps "
         f"completed, {len(numbers['gaps_ms'])} step-time samples, median "
         f"{numbers['step_ms_median']:.3f} ms, p95 {numbers['step_ms_p95']:.3f} ms, "
         f"max {max(numbers['gaps_ms'], default=float('nan')):.3f} ms at sample "
         f"{int(np.argmax(numbers['gaps_ms'])) if numbers['gaps_ms'] else -1}, "
         f"compiles in window {compiles_window[0]}, peak bytes {memory_peak}")

    # ---- the traced run's second arm (e.g. allreduce beside ATC) ---------
    compare = None
    if args.trace and "traced_compare" in mix:
        other_mix = {**mix, **mix["traced_compare"]}
        other = ses.make_job(other_mix, spans_mod.Spans())
        drive(other, spans_mod.Spans(), 0, steps=check.STEPS + WARM_EXTRA)
        arm = drive(other, spans_mod.Spans(), 0, steps=other_mix["steps"])
        other.close()
        compare = {"median_gap_ms": _quantile(np.diff(arm["stamps"]) * 1e3, 0.5),
                   "own_median_gap_ms": numbers["step_ms_median"]}

    # ---- correct ---------------------------------------------------------
    structure = job.structure()
    job.close()
    gc.collect()
    t_ref = time.perf_counter()
    ref = ses.reference_run()
    compared, agrees = check.compare(got, ref, ses.reference.LIMITS)
    for name, v in compared.items():
        _log(f"check {name}: {v['value']:.6g} (limit {v['limit']:.6g}) "
             f"{'ok' if v['ok'] else 'NOT OK'} {v['where']}")
    _log(f"check structure: {json.dumps(structure)}; leaves on {placed} of "
         f"{chips} device(s); compiles in window {compiles_window[0]}; "
         f"reference took {time.perf_counter() - t_ref:.3f} s")
    # every number that decides `correct`, beside its limit
    structural = {
        "structure_mismatch": int(not structure["ok"]),
        "chips_without_leaves": chips - placed,
        "compiles_in_window": compiles_window[0],
        "failed_steps": win["failed"] + warm["failed"],
        "nonfinite_checked_losses": int(np.sum(~np.isfinite(got["losses"]))),
    }
    correct = bool(agrees and not any(structural.values()))
    checks = {name: {"value": v["value"] if math.isfinite(v["value"]) else None,
                     "limit": v["limit"]} for name, v in compared.items()}
    checks.update({name: {"value": v, "limit": 0} for name, v in structural.items()})

    # ---- the result line -------------------------------------------------
    bench_run = {
        "rehearse": rehearse, "setup_s": setup_s, "window": numbers,
        "spans": spans, "trace": traced, "compare": compare,
        "memory_peak_bytes": memory_peak,
        "peaks": None if rehearse else manifest.peaks(cell.bench_dir, device["kind"]),
        "flops_per_sample": cell.module("flops").train_flops_per_sample(sizes),
    }
    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(bench_run)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = cell.reader(m["name"], "end_to_end").read(bench_run)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": dev}
    if args.trace and traced and not rehearse:
        dev["busy_s"] = traced["busy_s"]
        dev["window_s"] = traced["window_s"]
        result["breakdown"] = traced["breakdown"]
    result["checks"] = checks  # last in the line: the ledger keeps its end
    bf.shutdown()
    return result


def main(t0=None):
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(prog="python -m chipbench", description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny `rehearsal` sizes on the CPU "
                         "backend; a walk through the control flow, never a "
                         "measurement")
    args = ap.parse_args()
    try:
        cell = manifest.resolve(args.workload)
    except manifest.ManifestError as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2

    if args.rehearse:
        # a rehearsal can never be taken for a chip's numbers: it runs on the
        # CPU backend and its line says so
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform != "tpu" and not args.rehearse:
        print(f"chipbench: JAX found no TPU (platform {devs[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chip(s), JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 2
    result = run(args, t0, cell)
    for name, c in result["checks"].items():  # the last lines on stderr
        print(f"chipbench: check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0

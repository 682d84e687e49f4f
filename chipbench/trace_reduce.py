"""The one reduction from a profiler trace (.xplane.pb) to per-layer numbers
and to `breakdown`.

What a v5e trace holds (looked at by hand, PR 25): one plane per chip,
`/device:TPU:<i>`, with the lines `XLA Modules` (one event per program
execution, named `jit_<fn>(<hash>)`), `XLA Ops` (one event per executed HLO
op, back to back, named by the op's HLO text `%fusion.14 = ...`; a wait on an
async op shows as its `-done` op) and `Async XLA Ops` (start-to-done
intervals of async copies and collectives, overlapping the ops); and the
plane `/host:CPU`, whose `python` line holds the TraceAnnotations.  All
planes share one clock.

Events here are (name, start_s, end_s) tuples; the interval arithmetic below
is pure and is what the tests drive with synthetic lists.
"""

import bisect
import collections
import re
import statistics

COLLECTIVE = re.compile(
    r"^%?(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-broadcast|send|recv)")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
TOP = 10
NAME_CUT = 96  # an op's name is the start of its HLO text


# -- interval arithmetic ----------------------------------------------------

def merge(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_length(intervals):
    return sum(e - s for s, e in merge(intervals))


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of `a` that no interval of `b` covers (both any order)."""
    out, b = [], merge(b)
    for s, e in merge(a):
        for bs, be in b:
            if be <= s:
                continue
            if bs >= e:
                break
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def exposed_length(collectives, compute):
    """Seconds of collective time during which no compute op runs."""
    return union_length(subtract(collectives, compute))


def gaps(busy, lo, hi):
    """Idle intervals of [lo, hi] given busy intervals."""
    return subtract([(lo, hi)], busy)


def split_steps(anchor_starts, drop_edges=True):
    """[start_k, start_k+1) for consecutive step anchors.  The first and the
    last whole step are dropped with `drop_edges`: the first follows a
    drained queue, the last is followed by one."""
    starts = sorted(anchor_starts)
    steps = list(zip(starts[:-1], starts[1:]))
    return steps[1:-1] if drop_edges and len(steps) > 2 else steps


def covering_span(t, host_spans):
    """Name of the innermost (shortest) host span that covers instant t."""
    best = None
    for name, s, e in host_spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "(no chipbench span)"


def attribute_gaps(idle, host_spans):
    """Idle seconds by what the host was doing at the middle of each gap:
    [[name, seconds], ...], largest first."""
    total = collections.Counter()
    for s, e in idle:
        total[covering_span((s + e) / 2, host_spans)] += e - s
    return [[n, v] for n, v in total.most_common(TOP)]


def top_ops(events, devices):
    """Device seconds by op, mean per device: [[name, seconds], ...]."""
    total = collections.Counter()
    for name, s, e in events:
        total[name[:NAME_CUT]] += e - s
    return [[n, v / max(devices, 1)] for n, v in total.most_common(TOP)]


# -- reading the file -------------------------------------------------------

def load(path, host_names=()):
    """{"devices": {i: {"modules", "ops", "async"}}, "host": [...]} from an
    .xplane.pb, every list of (name, start_s, end_s)."""
    from jax.profiler import ProfileData

    def events(line):
        return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events]

    out = {"devices": {}, "host": []}
    lines = {"XLA Modules": "modules", "XLA Ops": "ops", "Async XLA Ops": "async"}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": [], "async": []}
            for line in plane.lines:
                if line.name in lines:
                    dev[lines[line.name]] = events(line)
            if dev["ops"]:
                out["devices"][int(m.group(1))] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name == "python":
                    out["host"] += [ev for ev in events(line) if ev[0] in host_names]
    return out


# -- the reduction ----------------------------------------------------------

def _started_in(sorted_events, lo, hi):
    """The events (tuples that begin with their start) that start in [lo, hi)."""
    i = bisect.bisect_left(sorted_events, (lo,))
    j = bisect.bisect_left(sorted_events, (hi,))
    return sorted_events[i:j]


def _median(values):
    return statistics.median(values) if values else None


def _worst(per_device):
    """Over steps the median of each device's value, then the largest over
    the devices: the step waits for the slowest."""
    meds = [m for m in (_median(v) for v in per_device) if m is not None]
    return max(meds) if meds else None


def _ops_ms(sorted_ops, steps):
    """{op name: [its device ms in each step]}: every op of the `XLA Ops`
    line (a collective's own start and done ops too, not its time in
    flight), summed by name as `top_ops` cuts it, counted in the step it
    starts in and clipped to it."""
    by_name = collections.defaultdict(lambda: [0.0] * len(steps))
    for k, (a, b) in enumerate(steps):
        for s, e, name in _started_in(sorted_ops, a, b):
            by_name[name[:NAME_CUT]][k] += 1e3 * (min(e, b) - s)
    return by_name


def reduce(trace, step_anchor, window_programs=()):
    """Per-layer numbers of a traced window.  Times in the result are
    seconds, except the `*_ms` per-step values.  `ops_ms_per_step` is what a
    reader of one kernel looks its name up in: {op name: device ms per step},
    per device the median over steps, then the largest over devices."""
    anchor = re.compile(step_anchor)
    window = [re.compile(p) for p in window_programs]
    devs = trace["devices"]
    if not devs:
        return None
    lo = min(d["ops"][0][1] for d in devs.values())
    hi = max(max(e for _, _, e in d["ops"]) for d in devs.values())
    busy_s, idle, all_ops = [], [], []
    compute_ms, coll_ms, exposed_ms, window_ms, launches = [], [], [], [], []
    ops_ms = collections.defaultdict(list)
    n_steps = 0
    for dev in devs.values():
        ops = dev["ops"]
        busy = merge([(s, e) for _, s, e in ops + dev["async"]])
        busy_s.append(union_length(clip(busy, lo, hi)))
        idle += gaps(busy, lo, hi)
        all_ops += ops
        steps = split_steps([s for n, s, _ in dev["modules"] if anchor.match(n)])
        n_steps = max(n_steps, len(steps))
        compute = sorted((s, e) for n, s, e in ops if not COLLECTIVE.match(n))
        coll = sorted((s, e) for n, s, e in ops + dev["async"] if COLLECTIVE.match(n))
        mods = sorted((s, e, n) for n, s, e in dev["modules"])
        for name, per_step in _ops_ms(sorted((s, e, n) for n, s, e in ops),
                                      steps).items():
            ops_ms[name].append(per_step)
        c_ms, k_ms, x_ms, w_ms, l_n = [], [], [], [], []
        for a, b in steps:
            comp = clip(_started_in(compute, a, b), a, b)
            cl = clip(_started_in(coll, a, b), a, b)
            # the union: a `while` op's interval holds its body's ops'
            c_ms.append(1e3 * union_length(comp))
            k_ms.append(1e3 * union_length(cl))
            x_ms.append(1e3 * exposed_length(cl, comp))
            launched = _started_in(mods, a, b)
            l_n.append(len(launched))
            w_ms.append(1e3 * sum(e - s for s, e, n in launched
                                  if any(p.match(n) for p in window)))
        compute_ms.append(c_ms)
        coll_ms.append(k_ms)
        exposed_ms.append(x_ms)
        window_ms.append(w_ms)
        launches.append(l_n)
    return {
        "devices": len(devs),
        "steps": n_steps,
        "window_s": hi - lo,
        "busy_s": sum(busy_s) / len(busy_s),
        "compute_ms_per_step": _worst(compute_ms),
        "collective_ms_per_step": _worst(coll_ms),
        "collective_exposed_ms_per_step": _worst(exposed_ms),
        "window_device_ms_per_round": _worst(window_ms),
        "launches_per_round": _worst(launches),
        "ops_ms_per_step": {name: _worst(v) for name, v in sorted(ops_ms.items())},
        "breakdown": {
            "device_ops": top_ops(all_ops, len(devs)),
            "idle_gaps": attribute_gaps(idle, trace["host"]),
        },
    }

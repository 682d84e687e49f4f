"""The device time of a traced step, split by where the program says each of
its ops came from.

The join: `bluefog_tpu.timeline.step_scopes()` is the program's own record of
its compiled step, one entry an instruction the device executes with the
`op_name` path it was traced under (`jit(local_step)/forward_backward/.../
layers_1/mlp_dense/mlp/dot_general`); the device trace names each op by its
HLO text, `%fusion.14 = ...`, and `trace_reduce.reduce` hands every reader
`ops_ms_per_step`, device ms a step under the first 96 characters of that
text.  The instruction's name is the key.

The partition: every op of the record that the trace timed goes to exactly
one group, by the first rule of `RULES` that its name or its path meets, and
`unscoped` where none does.  An op that holds others (`while`,
`conditional`, `call`) is charged its own time less what the ops within it
took, so that the groups add up to the step's compute and not past it.
Collectives are left out, as `compute_ms_per_step` leaves them out.  A
fusion that mixes scopes has one path, that of the op the compiler built it
around, and goes where that says: the split is the compiler's view of whose
an op is, not a count of FLOPs.

Several step programs may be registered in one process (the traced run's
allreduce arm beside the ATC step): the one whose instructions cover the
most of the trace's time is the one that was traced.

A reader hands `group_ms` the run and its group's name.  Without a trace (a
run with tracing off) or without `ops_ms_per_step` (a CPU rehearsal has no
device plane) it answers None before it asks the library for anything, and so
compiles nothing; on a program from before the record it finds none: None.
"""

import collections
import re
import time

from bluefog_tpu import timeline

from chipbench.trace_reduce import COLLECTIVE

NAME, PATH = "name", "path"
UNSCOPED = "unscoped"


def _rule(group, kind, *names):
    return group, kind, re.compile("|".join(names))


# (group, what is matched whole, the pattern): the first rule that an op meets
# takes it.  NAME: the instruction's name; PATH: any one component of its path.
RULES = (
    # kernels that metrics of their own read by name (`<kernel>` or
    # `<kernel>.<n>`); groups here too, so that the partition is whole
    _rule("attention_kernels", NAME, r"(flash_fwd_window|flash_bwd_dkv_window|"
          r"flash_bwd_dq_window|attention_global)(\.\d+)?"),
    _rule("scan_kernels", NAME, r"(ssd_chunk_fwd|ssd_chunk_bwd)(\.\d+)?"),
    _rule("expert_products", NAME, r".*ragged-dot.*"),
    # the scopes the library opens and the module names flax writes
    _rule("optimizer", PATH, "optimizer_update"),
    _rule("gossip_combine", PATH, "gossip_combine", "gradient_allreduce"),
    _rule("head_loss", PATH, "lm_head_loss", "final_norm"),
    _rule("expert_dispatch", PATH, "moe_route", "moe_experts"),
    _rule("mlp", PATH, "mlp_dense", "moe_shared", "ffn_norm"),
    _rule("ssm_mixer", PATH, "ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
          "ssm_out_proj"),
    _rule("attention_proj", PATH, "q", "k", "v", "o", "attention_rotary",
          "attention_gate", "attention_window", "attention_global", "attn_norm"),
    _rule("conv", PATH, r"Conv_\d+", "conv_init"),
    _rule("batch_norm", PATH, r"BatchNorm_\d+", "bn_init"),
)
MEMO = "step_scopes"  # the partition, kept in the run's trace beside what it is made from


def instruction_name(trace_name):
    """`fusion.14` of `%fusion.14 = (f32[256]{0:T(256)}, ...`: a traced op's
    name is the start of its HLO text."""
    return trace_name.lstrip("%").split(" = ", 1)[0]


def group_of(op):
    parts = op.path.split("/")
    for group, kind, pattern in RULES:
        if any(pattern.fullmatch(x) for x in ((op.name,) if kind == NAME else parts)):
            return group
    return UNSCOPED


def _is_collective(name):
    return bool(COLLECTIVE.match("%" + name))


def split(program, ms):
    """{"groups": {group: ms}, "recomputed": ms, "found": ms, "ops": [(op,
    group, own ms)]} of one program's record under `ms`, {instruction name:
    device ms a step}.  `found` is what the record's compute ops account for,
    each op's own time; an op the trace did not time is not in `ops`."""
    held = collections.defaultdict(float)
    for op in program.ops:
        if op.within is not None and not _is_collective(op.name):
            held[op.within] += ms.get(op.name, 0.0)
    groups, ops, recomputed = collections.defaultdict(float), [], 0.0
    for op in program.ops:
        if op.name not in ms or _is_collective(op.name):
            continue
        own = max(ms[op.name] - held.get(op.name, 0.0), 0.0)
        group = group_of(op)
        groups[group] += own
        recomputed += own if op.recomputed else 0.0
        ops.append((op, group, own))
    return {"groups": dict(groups), "recomputed": recomputed,
            "found": sum(groups.values()), "ops": ops}


def traced_program(programs, ms):
    """(the program whose record accounts for the most of `ms`, its split);
    (None, None) without a program."""
    best = (None, None)
    for program in programs:
        done = split(program, ms)
        if best[1] is None or done["found"] > best[1]["found"]:
            best = (program, done)
    return best


def partition(run):
    """The traced step's split, made once a run: {"groups", "recomputed",
    "found", "ops", "module", "strays"}, where `strays` are the traced
    compute ops the record does not have, [(name, ms)]: they are counted as
    `unscoped`.  None where there is nothing to split."""
    trace = run.get("trace")
    ops_ms = (trace or {}).get("ops_ms_per_step")
    if not ops_ms:
        return None
    if MEMO in trace:
        return trace[MEMO]
    read = getattr(timeline, "step_scopes", None)  # a program from before the record
    t0 = time.perf_counter()
    try:
        programs = read() if read is not None else []
    except Exception as exc:  # the run's other metrics are worth more than this one
        print(f"chipbench: step scopes: the program's record could not be read: "
              f"{type(exc).__name__}: {exc}", flush=True)
        programs = []
    took = time.perf_counter() - t0
    ms = {instruction_name(k): v for k, v in ops_ms.items() if v is not None}
    program, done = traced_program(programs, ms)
    if program is not None:
        known = {op.name for op in program.ops}
        strays = sorted(((n, v) for n, v in ms.items()
                         if n not in known and not _is_collective(n)),
                        key=lambda s: -s[1])
        done["groups"][UNSCOPED] = done["groups"].get(UNSCOPED, 0.0) + sum(
            v for _, v in strays)
        done.update(module=program.module, strays=strays)
        total, compute = sum(done["groups"].values()), trace["compute_ms_per_step"]
        print(f"chipbench: step scopes: {len(programs)} program(s) read in "
              f"{took:.3f} s; {program.module}: {len(program.ops)} instructions, "
              f"{len(done['ops'])} timed, {done['found']:.3f} ms of "
              f"{compute:.3f} ms compute a step found by name "
              f"({100 * done['found'] / compute:.2f} %), {len(strays)} traced op(s) "
              f"not in the record ({sum(v for _, v in strays):.3f} ms); groups sum "
              f"{total:.3f} ms: " + ", ".join(
                  f"{g} {v:.3f}" for g, v in sorted(
                      done["groups"].items(), key=lambda g: -g[1]))
              + f"; recomputed {done['recomputed']:.3f}", flush=True)
    trace[MEMO] = done
    return done


def group_ms(run, group):
    """Device ms a step of `group`'s ops, or None: no trace, no record, or no
    op of the group in the trace."""
    done = partition(run)
    return None if done is None else done["groups"].get(group)


def recomputed_ms(run):
    done = partition(run)
    return done["recomputed"] if done and done["recomputed"] else None

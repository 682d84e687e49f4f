"""Compiled-program inspection helpers: collective inventory and memory.

The HLO perf contracts (tests/test_hlo_contract*.py) and the memory
contracts (tests/test_memory_contract.py) both pin properties of the
POST-PARTITIONER program — the strongest multi-chip evidence obtainable
without multi-chip hardware, and a tripwire against GSPMD/scheduler
regressions on jax upgrades.  The reference's analogue is asserting which
MPI calls a collective op issues (``mpi_controller.cc`` [U]); here the
"calls" are XLA collective opcodes and the buffer assignment.
"""

from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Iterator, Tuple

COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

# opcode sits after `=` and the (possibly tuple) result type
_OP_RE = re.compile(r"=\s*(?:\([^)]*\)|[^\s(]+)\s+([a-z][a-z0-9\-]*)\(")

# one instruction line: result name `=` result type(s) opcode `(`
_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[^\s(]+)\s+"
    r"([a-z][a-z0-9\-]*)\(")

# a typed shape inside a result type, e.g. ``bf16[6,64,128]{2,1,0}``
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}


@dataclasses.dataclass(frozen=True)
class HloOp:
    """One parsed HLO instruction: opcode (``-start`` forms normalized to
    the base opcode, ``-done`` forms dropped by :func:`iter_ops`'s
    collective filter), its result shapes, and the raw line."""

    opcode: str
    shapes: Tuple[Tuple[str, Tuple[int, ...]], ...]  # (dtype, dims) per result
    line: str

    def result_bytes(self) -> int:
        """Total bytes across result shapes (0 for unknown dtypes)."""
        total = 0
        for dtype, dims in self.shapes:
            n = 1
            for d in dims:
                n *= d
            total += n * _DTYPE_BYTES.get(dtype, 0)
        return total


def iter_ops(compiled_text: str) -> Iterator[HloOp]:
    """Parse every instruction line of ``compiled.as_text()`` into an
    :class:`HloOp`.  Async ``-done`` instructions are skipped and
    ``-start`` opcodes are normalized, mirroring :func:`collective_counts`
    so shape-aware rules and the counter can never disagree on what is
    one logical op."""
    for line in compiled_text.splitlines():
        m = _LINE_RE.match(line)
        if not m:
            continue
        op = m.group(2)
        if op.endswith("-done"):
            continue
        if op.endswith("-start"):
            op = op[: -len("-start")]
        shapes = tuple(
            (dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE_RE.findall(m.group(1))
        )
        yield HloOp(opcode=op, shapes=shapes, line=line)


def collective_ops(compiled_text: str) -> list:
    """The :data:`COLLECTIVES` subset of :func:`iter_ops`."""
    return [op for op in iter_ops(compiled_text) if op.opcode in COLLECTIVES]


def collective_counts(compiled_text: str) -> Counter:
    """Count collective opcodes in ``compiled.as_text()``.

    ``-start`` forms count once; ``-done`` forms are ignored (async
    collectives appear as a start/done pair for one logical op).
    """
    counts = Counter()
    for m in _OP_RE.finditer(compiled_text):
        op = m.group(1)
        if op.endswith("-done"):
            continue
        if op.endswith("-start"):
            op = op[: -len("-start")]
        if op in COLLECTIVES:
            counts[op] += 1
    return counts


_COMPUTATION_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")


def entry_schedule(compiled_text: str) -> str:
    """Where the collective-permutes sit among the convolutions of the
    scheduled ENTRY computation of ``compiled.as_text()``: one letter per
    instruction of interest, in program order -- ``S`` a
    ``collective-permute-start``, ``D`` its ``-done``, ``C`` a convolution
    or a fusion that calls one.  The text of a compiled module is its
    schedule, so ``marks.rfind("C")`` is the last convolution and the
    ``S`` before it are the starts that travel under the backward pass.
    Positions, never times: nothing ran."""
    bodies, entry, cur = {}, None, None
    for line in compiled_text.splitlines():
        m = None if line.startswith(" ") else _COMPUTATION_RE.match(line)
        if m:
            cur = m.group(2)
            bodies[cur] = []
            if m.group(1):
                entry = cur
        elif cur is not None:
            bodies[cur].append(line)
    convs = {name for name, body in bodies.items()
             if any(" convolution(" in l for l in body)}
    marks = []
    for line in bodies.get(entry, ()):
        calls = _CALLS_RE.search(line)
        if " collective-permute-start(" in line:
            marks.append("S")
        elif " collective-permute-done(" in line:
            marks.append("D")
        elif " convolution(" in line or (calls and calls.group(1) in convs):
            marks.append("C")
    return "".join(marks)


def most_outstanding(marks: str) -> int:
    """The most permutes ever started and not yet done along
    :func:`entry_schedule`'s marks."""
    out = most = 0
    for c in marks:
        out += (c == "S") - (c == "D")
        most = max(most, out)
    return most


def memory_bytes(compiled) -> dict:
    """Per-DEVICE byte accounting from XLA's buffer assignment.

    The SPMD module is the per-device program, so these numbers are what
    one chip's HBM must hold: ``arguments`` (live inputs), ``outputs``,
    ``aliased`` (donated in/out pairs, counted once), ``temps`` (peak
    intermediate liveness under the chosen schedule), and
    ``live_peak_upper_bound = arguments + outputs - aliased + temps``.
    """
    ma = compiled.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return {
        "arguments": ma.argument_size_in_bytes,
        "outputs": ma.output_size_in_bytes,
        "aliased": ma.alias_size_in_bytes,
        "temps": ma.temp_size_in_bytes,
        "live_peak_upper_bound": live,
    }

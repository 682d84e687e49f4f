"""Timeline: the library's one span recorder + chrome-trace output.

TPU-native sibling of the reference's ``bluefog/common/timeline.h/.cc`` [U]
(SURVEY.md §5.1): the reference's background loop stamps per-tensor activity
spans into a Chrome-tracing JSON file when ``BLUEFOG_TIMELINE=<path>`` is
set.  Here :func:`timeline_context` wraps every public op's dispatch (and,
beneath a window op, every compiled-program call the library makes).  A span
is recorded while a ``jax.profiler`` session is active or while
``BLUEFOG_TIMELINE`` is set, three ways at once:

- in memory, as a :class:`Span` on ``time.perf_counter`` with the id of the
  span that was open on the same thread when it began; :func:`spans` reads
  them back (a bounded ring, emptied when a profiler session begins, so the
  spans cover exactly the steps the device trace covers);
- ``jax.profiler.TraceAnnotation("bluefog/<name>")`` so the same spans show
  up inside XLA/TPU profiles, on the device trace's clock;
- a Chrome-tracing JSON file (same format the reference emits) when
  ``BLUEFOG_TIMELINE`` is set, written by the native C++ writer
  (``cbluefog`` — sibling of ``timeline.cc``) with a pure-Python fallback.

Otherwise a span costs one ``is_enabled()`` check and reads no clock.

``timeline_start_activity`` / ``timeline_end_activity`` mirror the
reference's custom-span toggles [U].

Beside the host's spans the recorder keeps what the program knows of its own
compiled step: :func:`step_scopes` says, for every instruction the device
executes, which ``jax.named_scope`` and module it was traced in.  A device
trace names its ops by those instructions, so the two join by name.
"""

from __future__ import annotations

import atexit
import collections
import itertools
import json
import os
import re
import signal
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.profiler

from bluefog_tpu.common.logging_util import logger

__all__ = [
    "timeline_start_activity",
    "timeline_end_activity",
    "timeline_context",
    "spans",
    "Span",
    "step_scopes",
    "StepProgram",
    "ScopedOp",
    "TimelineWriter",
]


class TimelineWriter:
    """Chrome-tracing JSON writer (reference ``TimelineWriter`` [U]).

    Prefers the native C++ writer from :mod:`bluefog_tpu.native`; falls back
    to a buffered pure-Python implementation.  Thread-safe.
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._events = []
        self._counter_events = []
        self._t0 = time.perf_counter_ns()
        self._native = None
        try:
            from bluefog_tpu.native import timeline_native

            self._native = timeline_native.NativeTimelineWriter(path)
        except Exception:  # pragma: no cover - native lib optional
            self._native = None
        atexit.register(self.flush)
        self._install_sigterm()

    def _install_sigterm(self) -> None:
        # atexit never runs under SIGTERM's default disposition, and
        # launchers kill islands with SIGTERM — flush the buffer first,
        # then chain to whatever handler was installed before us
        try:
            prev = signal.getsignal(signal.SIGTERM)
        except (ValueError, TypeError):  # pragma: no cover - odd runtimes
            return

        def _on_term(signum, frame):
            try:
                self.flush()
            except Exception:  # noqa: BLE001 - dying anyway
                pass
            if callable(prev):
                prev(signum, frame)
            else:
                try:
                    signal.signal(signal.SIGTERM, signal.SIG_DFL)
                except (ValueError, TypeError):
                    pass
                os.kill(os.getpid(), signal.SIGTERM)

        try:
            signal.signal(signal.SIGTERM, _on_term)
        except (ValueError, TypeError):
            # non-main thread: atexit still covers graceful exits
            pass

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    def now_us(self) -> float:
        """Current timestamp on this writer's clock (µs since creation).
        Public so other layers (telemetry counter sampling) can stamp
        events onto the same timebase as the spans."""
        return self._now_us()

    def record_counter(self, name: str, ts_us: float, value: float) -> None:
        """Emit a chrome-trace counter sample (``"ph": "C"``).  Telemetry
        counters land on the same profile as the activity spans."""
        if self._native is not None and hasattr(self._native, "counter"):
            self._native.counter(name, ts_us, value)
            return
        with self._lock:
            self._counter_events.append(
                {
                    "name": name,
                    "ph": "C",
                    "ts": ts_us,
                    "pid": os.getpid(),
                    "args": {"value": value},
                }
            )

    def record(self, name: str, start_us: float, dur_us: float, tid: int = 0) -> None:
        if self._native is not None:
            self._native.record(name, start_us, dur_us, tid)
            return
        with self._lock:
            self._events.append(
                {
                    "name": name,
                    "ph": "X",
                    "ts": start_us,
                    "dur": dur_us,
                    "pid": os.getpid(),
                    "tid": tid,
                }
            )

    def flush(self) -> None:
        if self._native is not None:
            self._native.flush()
            # Counter events buffered python-side (native lib without
            # bf_timeline_counter) merge into the native-written file.
            with self._lock:
                extra, self._counter_events = self._counter_events, []
            if extra:
                try:
                    with open(self.path, "r") as f:
                        doc = json.load(f)
                    doc.setdefault("traceEvents", []).extend(extra)
                    with open(self.path, "w") as f:
                        json.dump(doc, f)
                except (OSError, ValueError) as e:  # pragma: no cover
                    logger.warning("timeline counter merge failed: %s", e)
            return
        with self._lock:
            if not self._events and not self._counter_events:
                return
            try:
                with open(self.path, "w") as f:
                    json.dump(
                        {"traceEvents": self._events + self._counter_events},
                        f)
            except OSError as e:  # pragma: no cover
                logger.warning("timeline flush failed: %s", e)


# None until the environment has been read, then the writer, or False where
# BLUEFOG_TIMELINE is unset: the environment is read once, not per span
_writer: Union[TimelineWriter, None, bool] = None
_open_spans = {}


def _get_writer() -> Optional[TimelineWriter]:
    global _writer
    if _writer is None:
        path = os.environ.get("BLUEFOG_TIMELINE")
        _writer = TimelineWriter(path) if path else False
    return _writer or None


def timeline_start_activity(name: str, category: str = "custom") -> bool:
    """Open a named span (reference ``bf.timeline_start_activity`` [U])."""
    w = _get_writer()
    _open_spans[(name, category)] = time.perf_counter_ns()
    return w is not None


def timeline_end_activity(name: str, category: str = "custom") -> bool:
    """Close a span opened by :func:`timeline_start_activity`."""
    start = _open_spans.pop((name, category), None)
    w = _get_writer()
    if start is None:
        return False
    if w is not None:
        t0_us = (start - w._t0) / 1e3
        dur_us = (time.perf_counter_ns() - start) / 1e3
        w.record(f"{category}/{name}", t0_us, dur_us)
    return w is not None


class Span(NamedTuple):
    """One recorded span.  ``parent`` is the id of the span that was open on
    the same thread when this one began (None at top level); ``start`` and
    ``end`` are seconds on ``time.perf_counter``; ``nbytes`` is what the op
    was handed to move (0 where it is handed nothing)."""

    id: int
    parent: Optional[int]
    name: str
    start: float
    end: float
    nbytes: int


RING = 1 << 16  # spans kept: a long run under BLUEFOG_TIMELINE must not grow

_profiling = jax.profiler.TraceAnnotation.is_enabled
_ring = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_open = threading.local()  # .stack: ids of the spans open on this thread
_profiled = False  # whether a profiler session was live at the last look


def _session_edge() -> bool:
    """Whether a profiler session is live; one that has just begun empties
    the ring.  ``is_enabled()`` is all JAX tells, so two sessions are told
    apart by a span or a read of :func:`spans` that falls between them."""
    global _profiled
    profiled = _profiling()
    if profiled != _profiled:
        _profiled = profiled
        if profiled:
            _ring.clear()
    return profiled


def spans() -> List[Span]:
    """The spans recorded since the latest profiler session began (under
    ``BLUEFOG_TIMELINE`` alone: since the process started), by start time,
    the newest ``RING`` of them."""
    _session_edge()
    return sorted(_ring.copy())


class timeline_context:
    """Span around an op dispatch: ``with timeline_context("win_put") as
    span``.  While nothing records, ``span`` is None and no clock is read;
    while recording, the op sets ``span.nbytes`` where it is handed a tensor
    to move.

    A span belongs to the CALLING THREAD: its parent is the span open on
    that thread, and its chrome-trace tid is the thread's id, so background
    work (e.g. the overlap optimizer's gossip thread) renders on its own
    track, visually parallel to main-thread spans."""

    __slots__ = ("name", "nbytes", "_id", "_parent", "_start", "_annotation")

    def __init__(self, name: str):
        self.name = name
        self._id = None

    def __enter__(self):
        profiled = _session_edge()
        if not (profiled or (_get_writer() if _writer is None else _writer)):
            return None
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        self._parent = stack[-1] if stack else None
        self._id = next(_ids)
        stack.append(self._id)
        self.nbytes = 0
        self._annotation = None
        if profiled:
            self._annotation = jax.profiler.TraceAnnotation(f"bluefog/{self.name}")
            self._annotation.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._id is None:
            return
        end = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _open.stack.pop()
        _ring.append(Span(self._id, self._parent, self.name, self._start, end,
                          self.nbytes))
        w = _writer
        if w:
            w.record(self.name, self._start * 1e6 - w._t0 / 1e3,
                     (end - self._start) * 1e6,
                     tid=threading.get_ident() & 0x7FFFFFFF)


# -- the compiled step's scopes -------------------------------------------------


class ScopedOp(NamedTuple):
    """One instruction of a compiled step that the device executes.  ``name``
    is the instruction's (``fusion.14``: a device trace names the op
    ``%fusion.14 = ...``); ``path`` the ``op_name`` the compiler kept for it,
    ``jit(local_step)/forward_backward/.../layer_0/mlp_dense/up/dot_general``
    (a fusion has one path, that of the op it was built around; empty where
    the compiler kept none); ``within`` the ``while``, ``conditional`` or
    ``call`` instruction whose computation holds it, None in the entry
    computation.  ``backward``: traced by the gradient's transposition;
    ``recomputed``: in a ``jax.checkpoint`` / ``nn.remat`` block's second
    forward pass."""

    name: str
    path: str
    within: Optional[str]
    backward: bool
    recomputed: bool


class StepProgram(NamedTuple):
    """A step program's module name (``jit_local_step``: a device trace names
    each execution ``jit_local_step(<hash>)``) and its instructions."""

    module: str
    ops: Tuple[ScopedOp, ...]


# how this JAX writes the two passes into an op's path
BACKWARD_MARK = "transpose("
RECOMPUTED_MARK = "rematted_computation"
STEP_PROGRAMS = 8  # programs kept: a process that builds steps forever must not grow

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|true_computation|false_computation)=%?([^\s,)}]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
# `<type> <opcode>(<operands>)`: in a type, tuple or not, no word is followed
# by a bracket after a space (a layout's `T(8,128)` follows a colon or a bracket)
_OPCODE = re.compile(r" ([a-z][a-z-]*)\(")
_HOLDS_OTHERS = ("while", "conditional", "call")

_step_programs = collections.deque(maxlen=STEP_PROGRAMS)  # [jitted, avals, record]


def _register_step_program(jitted, args) -> None:
    """Called by the train step when it builds the program of a new state
    structure, with the arguments of that first call.  Keeps the function and
    the arguments' shapes, types and shardings; compiles and reads nothing.
    A call that is itself being traced (``jax.jit(step_fn)``) runs no program
    of its own and registers none."""
    leaves = jax.tree_util.tree_leaves(args)
    if any(isinstance(a, jax.core.Tracer) for a in leaves):
        return
    # an array that was never placed goes wherever the program wants it: its
    # sharding says where it happens to be, and would pin it there
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=a.sharding if getattr(a, "committed", False) else None), args)
    _step_programs.append([jitted, avals, None])


def _parse_step_program(text: str) -> StepProgram:
    """The record of a compiled module's text: the entry computation's
    instructions and, below an instruction that holds others, its
    computations', to any depth.  A fusion's inside is not walked (the
    device runs a fusion as one op), nor a reduction's or a sort's scalar
    function."""
    module = text.split(None, 2)[1].rstrip(",") if text.startswith("HloModule") else ""
    computations: Dict[str, List[Tuple[str, str]]] = {}
    entry = current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(1), [])
                if line.startswith("ENTRY"):
                    entry = m.group(1)
        elif line.startswith("}"):
            current = None
        else:
            m = _INSTRUCTION.match(line)
            if m:
                current.append(m.groups())
    ops: List[ScopedOp] = []

    def walk(computation, within):
        for name, rest in computations.get(computation, ()):
            found = _OP_NAME.search(rest)
            path = found.group(1) if found else ""
            ops.append(ScopedOp(name, path, within, BACKWARD_MARK in path,
                                RECOMPUTED_MARK in path))
            opcode = _OPCODE.search(" " + rest)
            if opcode and opcode.group(1) in _HOLDS_OTHERS:
                called = _CALLED.findall(rest)
                for group in _BRANCHES.findall(rest):
                    called += [c.strip().lstrip("%") for c in group.split(",")]
                for c in called:
                    walk(c, name)

    if entry is not None:
        walk(entry, None)
    return StepProgram(module, tuple(ops))


def step_scopes() -> List[StepProgram]:
    """For each step program this process has built (the newest
    ``STEP_PROGRAMS``), where every instruction of its compiled module came
    from.  A record is made when it is first read and kept: the registered
    function is lowered and compiled for the registered shapes (the
    executable of the step that ran, from JAX's caches, where the call's
    arguments were laid out as the first call's), and the module's text is
    read.  Nothing is done inside a step."""
    for entry in _step_programs:
        if entry[2] is None:
            jitted, avals, _ = entry
            entry[2] = _parse_step_program(jitted.lower(*avals).compile().as_text())
    return [entry[2] for entry in _step_programs]

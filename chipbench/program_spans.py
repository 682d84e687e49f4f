"""What the program recorded of itself: `bluefog_tpu.timeline.spans()`, the
library's own spans of the traced window (they are kept exactly while the
profiler runs), reduced per step or round as `trace_reduce` reduces the device
trace.  A step or round is the interval between consecutive starts of the
anchor span; the first and the last whole one are dropped and the value is the
median over the rest.

A span is `(id, parent, name, start, end, nbytes)`, seconds on
time.perf_counter (the clock of chipbench's own `Spans`).  A window op's
children are the compiled-program calls the library makes on that path, one
span per call site: `win_update/reset` launches two programs (a `zeros_like`
each) and counts once.  A program from before the recorder keeps no spans, and
every reader then finds nothing: None.
"""

from bluefog_tpu import timeline

from chipbench.trace_reduce import _median, clip, split_steps, union_length

ROUND_ANCHOR = "win_accumulate"  # the push-sum round starts with its deposit
STEP_ANCHOR = "train_step"
DEPOSITS = ("win_put", "win_accumulate", "win_put_update")


def recorded():
    read = getattr(timeline, "spans", None)
    return read() if read is not None else []


def _per_interval(spans, anchor, value):
    """Median over the anchor's intervals of value(spans that start in it);
    None without a whole interval."""
    steps = split_steps([s.start for s in spans if s.name == anchor])
    return _median([value([s for s in spans if a <= s.start < b])
                    for a, b in steps])


def _window_ops(spans):
    """(top-level `win_*` spans, {id: its child spans}) of one round."""
    tops = [s for s in spans if s.name.startswith("win_") and "/" not in s.name]
    children = {t.id: [] for t in tops}
    for s in spans:
        if s.parent in children:
            children[s.parent].append(s)
    return tops, children


def _self_s(span, children):
    covered = union_length(clip([(c.start, c.end) for c in children],
                                span.start, span.end))
    return span.end - span.start - covered


def window_host_ms_per_round(spans):
    def total(members):
        return 1e3 * sum(t.end - t.start for t in _window_ops(members)[0])
    return _per_interval(spans, ROUND_ANCHOR, total)


def window_host_self_ms_per_round(spans):
    def self_time(members):
        tops, children = _window_ops(members)
        return 1e3 * sum(_self_s(t, children[t.id]) for t in tops)
    return _per_interval(spans, ROUND_ANCHOR, self_time)


def window_programs_per_round(spans):
    def calls(members):
        return sum(len(c) for c in _window_ops(members)[1].values())
    return _per_interval(spans, ROUND_ANCHOR, calls)


def window_deposit_mb_per_round(spans):
    def deposited(members):
        return sum(s.nbytes for s in members if s.name in DEPOSITS) / 1e6
    return _per_interval(spans, ROUND_ANCHOR, deposited)


def train_step_host_ms_per_step(spans):
    def duration(members):
        return 1e3 * sum(s.end - s.start for s in members if s.name == STEP_ANCHOR)
    return _per_interval(spans, STEP_ANCHOR, duration)

"""Layer `eager ops, windows`: megabytes handed to `win_put`, `win_accumulate`
and `win_put_update` per round (the `nbytes` of their spans)."""

from chipbench import program_spans


def read(run):
    return program_spans.window_deposit_mb_per_round(program_spans.recorded())

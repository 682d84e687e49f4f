"""Layer `eager ops, windows`: host milliseconds a round spends inside the
library's window ops: the sum of the top-level `win_*` spans that
bluefog_tpu.timeline recorded in the traced window, per round."""

from chipbench import program_spans


def read(run):
    return program_spans.window_host_ms_per_round(program_spans.recorded())

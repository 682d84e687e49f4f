"""Layer `train step`: device milliseconds of non-collective ops per step per
device, from the trace (median over steps, largest over devices)."""


def read(run):
    trace = run["trace"]
    return None if trace is None else trace["compute_ms_per_step"]

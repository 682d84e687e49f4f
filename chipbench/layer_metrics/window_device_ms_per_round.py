"""Layer `eager ops, windows`: device milliseconds of the window programs
(pack, unpack, accumulate, combine, debias, reset) per round, from the trace."""


def read(run):
    trace = run["trace"]
    return None if trace is None else trace["window_device_ms_per_round"]

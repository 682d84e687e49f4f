"""Layer `optimizer + gossip`: the part of the collective time during which
no compute op runs on that device, from the trace."""


def read(run):
    trace = run["trace"]
    return None if trace is None else trace["collective_exposed_ms_per_step"]

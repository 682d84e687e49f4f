"""Layer `optimizer + gossip`: milliseconds per step during which a
collective-permute or all-reduce is in flight on a device, from the trace."""


def read(run):
    trace = run["trace"]
    return None if trace is None else trace["collective_ms_per_step"]

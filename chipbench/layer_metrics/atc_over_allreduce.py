"""Layer `optimizer + gossip`: median step gap of the cell's own arm over that
of the traced run's second arm (CommunicationType.allreduce, same shapes, same
process).  The north star (at least 0.90 of allreduce throughput) is a ratio
of at most 1.11 here."""


def read(run):
    c = run["compare"]
    if c is None or run["rehearse"]:
        return None
    return c["own_median_gap_ms"] / c["median_gap_ms"]

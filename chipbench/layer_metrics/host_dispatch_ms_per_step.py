"""Layer `entry, host loop`: mean host-clock milliseconds of the call(s) that
enqueue one step (chipbench's own `dispatch` span), over the window."""


def read(run):
    d = run["spans"].durations("dispatch")
    return 1e3 * sum(d) / len(d) if d else None

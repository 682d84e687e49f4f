"""Layer `eager ops, windows`: XLA program executions per round, from the
trace's module events."""


def read(run):
    trace = run["trace"]
    return None if trace is None else trace["launches_per_round"]

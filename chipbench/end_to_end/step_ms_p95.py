"""95th percentile of the gaps between consecutive step completions in the
window (all of them), in milliseconds."""


def read(run):
    return run["window"]["step_ms_p95"]

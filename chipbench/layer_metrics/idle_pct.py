"""Layer `device`: 1 - union of op intervals over the traced window, averaged
over the chips used."""


def read(run):
    trace = run["trace"]
    if trace is None or run["rehearse"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

"""Layer `entry, host loop`: 95th percentile of the `dispatch` spans of the
window, in milliseconds."""


import numpy as np


def read(run):
    d = run["spans"].durations("dispatch")
    return 1e3 * float(np.quantile(d, 0.95)) if d else None

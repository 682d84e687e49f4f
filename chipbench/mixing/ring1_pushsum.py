"""One push-sum round on RingGraph(n, connect_style=1), from its definition:
every rank keeps half of its value and of its weight p and deposits the other
half at its successor (i -> i+1).  Column-stochastic: m = M @ x, p = M @ 1,
and the rank goes on from m / p.  A ring of one rank has no edge, so its
deposit has nowhere to go: m = x/2, p = 1/2, and m / p is x again."""

import numpy as np


def matrix(n):
    M = 0.5 * np.eye(n)
    if n > 1:
        for i in range(n):
            M[(i + 1) % n, i] += 0.5
    return M

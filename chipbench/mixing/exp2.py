"""ExponentialTwoGraph(n), written from its definition and not read from the
code under test: rank i hears from (i - 2^j) % n, every in-edge and the
self-loop weigh 1/(in_degree + 1).  Row-stochastic and doubly stochastic:
new = M @ x, and the associated weight M @ 1 stays 1."""

import numpy as np


def matrix(n):
    M = np.zeros((n, n))
    for i in range(n):
        srcs = {(i - (1 << j)) % n for j in range(max(n - 1, 0).bit_length())}
        srcs.discard(i)
        for s in srcs | {i}:
            M[i, s] = 1.0 / (len(srcs) + 1)
    return M

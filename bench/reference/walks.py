"""Plain reference for random walks with restart on a CSR graph.

Independent of the program: numpy and scipy in float64 on the host, from
the graph's (row_ptr, col_idx) alone. A walk moves from v to a uniformly
drawn out-neighbour, and before each move ends with probability eps; a
walk at a vertex with no out-edge ends there.

- `expected_visits`: z[v], the expected visits to v of one walk started
  at every vertex: z = 1 + (1 - eps) Q^T z, with Q the row-stochastic
  out-edge matrix (zero rows where there is no out-edge). z * eps / sum(z)
  is PageRank with damping 1 - eps and the mass of dangling vertices
  spread uniformly, as LDBC Graphalytics defines it.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def walk_matrix(row_ptr, col_idx, n: int):
    """(A^T as CSR, 1/deg with 0 where deg is 0): y = A^T (x * inv_deg)
    pushes x[v] / deg(v) along every edge v -> u."""
    row_ptr = np.asarray(row_ptr, np.int64)
    col_idx = np.asarray(col_idx, np.int64)
    deg = np.diff(row_ptr)
    a = sp.csr_matrix((np.ones(len(col_idx)), col_idx, row_ptr),
                      shape=(n, n))
    inv_deg = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
    return a.T.tocsr(), inv_deg


def expected_visits(row_ptr, col_idx, n: int, eps: float, *,
                    tol: float = 1e-12, max_iters: int = 10_000):
    """z = 1 + (1 - eps) Q^T z, iterated until the largest change is
    under `tol`."""
    at, inv_deg = walk_matrix(row_ptr, col_idx, n)
    z = np.ones(n)
    for _ in range(max_iters):
        z_new = 1.0 + (1.0 - eps) * (at @ (z * inv_deg))
        done = np.abs(z_new - z).max() < tol
        z = z_new
        if done:
            break
    return z


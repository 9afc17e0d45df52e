"""Power-iteration PageRank — the traditional baseline the paper argues
against in the distributed setting. Implemented as a sharded sparse push:

    pi_{t+1} = eps/n + (1-eps) * (Q^T pi_t + dangling_mass/n)

The push over the CSR edge list is a segment-sum.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.graph import CSRGraph


def spmv_push(graph: CSRGraph, x: jnp.ndarray) -> jnp.ndarray:
    """y = Q^T x  where Q is the row-stochastic out-edge matrix.

    Each edge (v -> u) pushes x[v]/deg(v) into y[u].
    """
    src = graph.edge_src()
    contrib = x[src] / graph.out_deg[src].astype(x.dtype)
    return jax.ops.segment_sum(contrib, graph.col_idx, num_segments=graph.n)


@partial(jax.jit, static_argnames=("graph_n", "max_iters"))
def _power_iterate(row_ptr, col_idx, out_deg, edge_src, graph_n: int, eps: float,
                   tol: float, max_iters: int):
    deg_f = jnp.maximum(out_deg, 1).astype(jnp.float32)
    dangling = (out_deg == 0)

    def push(x):
        contrib = x[edge_src] / deg_f[edge_src]
        y = jax.ops.segment_sum(contrib, col_idx, num_segments=graph_n)
        dang_mass = jnp.sum(jnp.where(dangling, x, 0.0))
        return y + dang_mass / graph_n

    def cond(state):
        _, err, it = state
        return jnp.logical_and(err > tol, it < max_iters)

    def body(state):
        x, _, it = state
        x_new = eps / graph_n + (1.0 - eps) * push(x)
        err = jnp.abs(x_new - x).sum()
        return x_new, err, it + 1

    x0 = jnp.full((graph_n,), 1.0 / graph_n, dtype=jnp.float32)
    x, err, iters = jax.lax.while_loop(cond, body, (x0, jnp.inf, jnp.int32(0)))
    return x, err, iters


def power_iteration(graph: CSRGraph, eps: float, *, tol: float = 1e-7,
                    max_iters: int = 10_000
                    ) -> Tuple[jnp.ndarray, float, int]:
    """Returns (pi, final_l1_delta, iterations)."""
    x, err, iters = _power_iterate(
        graph.row_ptr, graph.col_idx, graph.out_deg, graph.edge_src(),
        graph.n, float(eps), float(tol), int(max_iters))
    return x, float(err), int(iters)

"""Engine throughput: counts vs walk-array vs power-iter.

Walks/second (steady-state, jit-compiled) for the faithful count engine and
the TPU-native walk engine; power-iteration L1-convergence wall time as the
classical baseline the paper argues against.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import engine_walks, power_iteration, simple_pagerank
from repro.core.engine_counts import init_state as counts_init, _step as counts_step
from repro.core.graph import padded_adjacency
from repro.graphs import barabasi_albert


def _time(fn, iters=5):
    fn()  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def run(n=512, eps=0.2, K=100):
    g = barabasi_albert(n, 3, seed=4)
    W = n * K
    rows = []

    # walk-array engine, one superstep
    state = engine_walks.init_state(g, K, jax.random.PRNGKey(0))
    step = jax.jit(lambda s: engine_walks._step_core(
        g.row_ptr, g.col_idx, g.out_deg, eps, s)[0])
    dt = _time(lambda: jax.block_until_ready(step(state).zeta))
    rows.append(dict(name="walk_array_step", us=dt * 1e6,
                     walks_per_s=W / dt))

    # count engine, one round
    nbr, _ = padded_adjacency(g)
    cstate = counts_init(g, K, jax.random.PRNGKey(0))
    dt = _time(lambda: jax.block_until_ready(
        counts_step(nbr, g.out_deg, cstate, eps, g.n, int(nbr.shape[1]))[0]
        .counts))
    rows.append(dict(name="count_engine_step", us=dt * 1e6,
                     walks_per_s=W / dt))

    # full solves
    t0 = time.perf_counter()
    simple_pagerank(g, eps, walks_per_node=K, key=jax.random.PRNGKey(1))
    rows.append(dict(name="simple_pagerank_full", us=(time.perf_counter() - t0) * 1e6,
                     walks_per_s=0))
    t0 = time.perf_counter()
    power_iteration(g, eps, tol=1e-7)
    rows.append(dict(name="power_iteration_full", us=(time.perf_counter() - t0) * 1e6,
                     walks_per_s=0))
    return rows


def main():
    rows = run()
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us']:.0f},walks_per_s={r['walks_per_s']:.3e}")
    return rows


if __name__ == "__main__":
    main()

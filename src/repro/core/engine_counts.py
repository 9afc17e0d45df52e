"""Count-based engine — the *faithful* Algorithm 1 implementation.

This engine materializes exactly the paper's CONGEST messages: per round,
every vertex v holding c_v coupons draws terminations ~ Binomial(c_v, eps)
and splits the survivors across its out-edges with a Multinomial (sampled as
a binomial tree over each row's out-edge slots, vectorized over all
vertices). The int matrix T[v, j] of per-edge counts *is* the message set
of the round (Lemma 1: counts, never identities).

Slower than the walk-array engine but byte-for-byte faithful to the
pseudocode — it is the reference for message accounting and for the
engine-equivalence tests. The per-round splits run through the shared
degree-bucketed aggregate sampler (`core/aggregate_sampler`): each
row's split spans its power-of-two bucket width instead of the global
max degree, so per-round sampler FLOPs are
sum_v O(deg(v)) — hubs no longer tax every low-degree vertex.
`use_pallas` routes the draws through the `kernels/multinomial_rows`
Pallas kernel (same counter-RNG math as the jnp ref, so results are
bit-identical either way); `bucketed=False` keeps the single-bucket
max_deg-wide layout for benchmarking the pre-bucketing shape.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accounting import RoundTrace
from repro.core.aggregate_sampler import (build_layout, bucketize_csr,
                                          flatten_moves, sample_buckets)
from repro.core.graph import CSRGraph
from repro.kernels import resolve_use_pallas
from repro.kernels.multinomial_rows._math import key_words


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CountState:
    counts: jnp.ndarray  # [n] int32 coupons currently at each vertex
    zeta: jnp.ndarray    # [n] int32 visit counters
    key: jnp.ndarray
    round: jnp.ndarray


def init_state(graph: CSRGraph, walks_per_node: int, key: jnp.ndarray) -> CountState:
    c0 = jnp.full((graph.n,), walks_per_node, dtype=jnp.int32)
    return CountState(counts=c0, zeta=c0, key=key, round=jnp.int32(0))


def _multinomial_split(key, survivors, deg, max_deg: int):
    """T[v, j] ~ Multinomial(survivors_v, uniform over deg_v slots).

    Conditional-binomial chain: T_j | T_<j ~ Bin(rem, 1/(deg-j)).
    """
    def body(carry, j):
        rem, key = carry
        key, kb = jax.random.split(key)
        slots_left = jnp.maximum(deg - j, 1).astype(jnp.float32)
        p = jnp.where(j < deg, 1.0 / slots_left, 0.0)
        t = jax.random.binomial(kb, rem.astype(jnp.float32), p).astype(jnp.int32)
        t = jnp.minimum(t, rem)
        return (rem - t, key), t

    (rem, _), T = jax.lax.scan(body, (survivors, key), jnp.arange(max_deg))
    # scan stacks on axis 0 -> [max_deg, n]; transpose to [n, max_deg]
    return T.T, rem


@partial(jax.jit, static_argnames=("eps", "n", "layout", "use_pallas"))
def _step(bnbr, perm, deg, state: CountState, eps: float, n: int, layout,
          use_pallas: bool):
    """One super-step through the shared degree-bucketed sampler: each
    bucket draws its fused Binomial(eps) termination + conditional-binomial
    edge split (dangling rows terminate whole), then the per-edge counts
    route through one segment-sum over the flat bucketed adjacency."""
    key, k_sample = jax.random.split(state.key)
    rid = jnp.arange(n, dtype=jnp.int32)
    samples, _, residual = sample_buckets(
        state.counts, deg, rid, key_words(k_sample), perm, layout,
        eps=eps, use_pallas=use_pallas)
    flat_T = flatten_moves(samples)
    # route: new_counts[u] = sum over bucketed edge slots with dst == u
    new_counts = jax.ops.segment_sum(flat_T, bnbr, num_segments=n)
    new_state = CountState(
        counts=new_counts.astype(jnp.int32),
        zeta=state.zeta + new_counts.astype(jnp.int32),
        key=key,
        round=state.round + 1,
    )
    stats = dict(
        active=jnp.sum(state.counts),
        moved=jnp.sum(flat_T),
        messages=jnp.sum(flat_T > 0),
        max_edge_count=jnp.max(flat_T),
        residual=residual,  # must be 0 — multinomial exactness check
    )
    return new_state, stats


def run_traced(graph: CSRGraph, eps: float, walks_per_node: int,
               key: jnp.ndarray, *, max_rounds: int = 100_000,
               use_pallas=None, bucketed: bool = True
               ) -> Tuple[CountState, List[RoundTrace]]:
    use_pallas = resolve_use_pallas(use_pallas)
    deg = np.asarray(graph.out_deg)
    layout, perm_np = build_layout(deg, max(graph.max_out_deg, 1),
                                   bucketed=bucketed)
    bnbr = jnp.asarray(bucketize_csr(graph.row_ptr, graph.col_idx, deg,
                                     perm_np, layout))
    perm = jnp.asarray(perm_np)
    state = init_state(graph, walks_per_node, key)
    traces: List[RoundTrace] = []
    while int(jnp.sum(state.counts)) > 0 and int(state.round) < max_rounds:
        state, stats = _step(bnbr, perm, graph.out_deg, state, float(eps),
                             graph.n, layout, use_pallas)
        assert int(stats["residual"]) == 0, "multinomial split leaked mass"
        traces.append(RoundTrace(
            active_walks=int(stats["active"]),
            messages=int(stats["messages"]),
            max_edge_count=int(stats["max_edge_count"]),
            total_count=int(stats["moved"]),
        ))
    return state, traces

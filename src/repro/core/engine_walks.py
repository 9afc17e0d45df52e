"""Walk-array engine — the TPU-native realization of Algorithm 1.

A dense array of walk positions is advanced with vectorized gathers; visit
counters are segment-sum histograms. Mathematically identical to the paper's
process (walks are iid PageRank random walks terminated at the first
eps-reset); the CONGEST message structure (per-edge *counts*, Lemma 1) is
recovered for accounting by histogramming the per-round edge transitions.

Two drivers:
  * run(...)        — jitted lax.while_loop to exact termination (fast path).
  * run_traced(...) — python-stepped, emits per-round RoundTrace for the
                      CONGEST accounting (benchmarks / theorem validation).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accounting import RoundTrace
from repro.core.graph import CSRGraph


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class WalkState:
    pos: jnp.ndarray    # [W] int32 current vertex
    alive: jnp.ndarray  # [W] bool
    zeta: jnp.ndarray   # [n] int32 visit counters (includes start visits)
    key: jnp.ndarray    # PRNG key
    round: jnp.ndarray  # int32


def init_state(graph: CSRGraph, walks_per_node: int, key: jnp.ndarray,
               sources: Optional[jnp.ndarray] = None) -> WalkState:
    """K walks from every node (or explicit `sources`). Start counts as a visit."""
    if sources is None:
        pos = jnp.tile(jnp.arange(graph.n, dtype=jnp.int32), walks_per_node)
    else:
        pos = sources.astype(jnp.int32)
    zeta = jax.ops.segment_sum(jnp.ones_like(pos), pos, num_segments=graph.n)
    return WalkState(
        pos=pos,
        alive=jnp.ones(pos.shape, dtype=bool),
        zeta=zeta.astype(jnp.int32),
        key=key,
        round=jnp.int32(0),
    )


def _step_core(row_ptr, col_idx, out_deg, eps: float, state: WalkState):
    """One synchronous round. Returns (new_state, moving_mask, edge_ids)."""
    key, k_term, k_edge = jax.random.split(state.key, 3)
    u_term = jax.random.uniform(k_term, state.pos.shape)
    deg = out_deg[state.pos]
    # dangling vertex == immediate reset (Avrachenkov convention)
    survive = state.alive & (u_term >= eps) & (deg > 0)
    u_edge = jax.random.uniform(k_edge, state.pos.shape)
    j = jnp.minimum((u_edge * jnp.maximum(deg, 1)).astype(jnp.int32),
                    jnp.maximum(deg - 1, 0))
    edge_ids = row_ptr[state.pos] + j
    dst = col_idx[jnp.clip(edge_ids, 0, col_idx.shape[0] - 1)]
    new_pos = jnp.where(survive, dst, state.pos)
    arrivals = jax.ops.segment_sum(
        survive.astype(jnp.int32), dst, num_segments=state.zeta.shape[0])
    new_state = WalkState(
        pos=new_pos,
        alive=survive,
        zeta=state.zeta + arrivals,
        key=key,
        round=state.round + 1,
    )
    return new_state, survive, edge_ids


@partial(jax.jit, static_argnames=("eps", "max_rounds"))
def _run_while(row_ptr, col_idx, out_deg, state: WalkState, eps: float,
               max_rounds: int) -> WalkState:
    def cond(s):
        return jnp.logical_and(jnp.any(s.alive), s.round < max_rounds)

    def body(s):
        s2, _, _ = _step_core(row_ptr, col_idx, out_deg, eps, s)
        return s2

    return jax.lax.while_loop(cond, body, state)


def run(graph: CSRGraph, eps: float, walks_per_node: int, key: jnp.ndarray,
        *, max_rounds: int = 100_000) -> WalkState:
    state = init_state(graph, walks_per_node, key)
    return _run_while(graph.row_ptr, graph.col_idx, graph.out_deg, state,
                      float(eps), int(max_rounds))


@partial(jax.jit, static_argnames=("eps", "n_edges"))
def _step_traced(row_ptr, col_idx, out_deg, state: WalkState, eps: float,
                 n_edges: int):
    new_state, survive, edge_ids = _step_core(
        row_ptr, col_idx, out_deg, eps, state)
    # CONGEST payload: count of walks per edge this round (Lemma 1 messages)
    edge_counts = jax.ops.segment_sum(
        survive.astype(jnp.int32), edge_ids, num_segments=n_edges)
    stats = dict(
        active=jnp.sum(state.alive).astype(jnp.int32),
        moved=jnp.sum(survive).astype(jnp.int32),
        messages=jnp.sum(edge_counts > 0).astype(jnp.int32),
        max_edge_count=jnp.max(edge_counts).astype(jnp.int32),
    )
    return new_state, stats


def run_traced(graph: CSRGraph, eps: float, walks_per_node: int,
               key: jnp.ndarray, *, max_rounds: int = 100_000
               ) -> Tuple[WalkState, List[RoundTrace]]:
    state = init_state(graph, walks_per_node, key)
    traces: List[RoundTrace] = []
    while bool(jnp.any(state.alive)) and int(state.round) < max_rounds:
        state, stats = _step_traced(graph.row_ptr, graph.col_idx,
                                    graph.out_deg, state, float(eps),
                                    graph.m)
        traces.append(RoundTrace(
            active_walks=int(stats["active"]),
            messages=int(stats["messages"]),
            max_edge_count=int(stats["max_edge_count"]),
            total_count=int(stats["moved"]),
        ))
    return state, traces

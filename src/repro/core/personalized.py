"""Personalized PageRank via the same Monte-Carlo machinery.

PPR(s) is the stationary distribution of the walk that resets to the
*source distribution* s instead of uniform. In the terminate-at-reset
Monte-Carlo formulation (Avrachenkov et al.; Bahmani et al.), that is
exactly Algorithm 1 with all walks started from s:

    ppr_v = zeta_v * eps / W        (W walks started ~ s)

The walk-array engine already accepts explicit sources, so this is a thin,
fully-supported extension of the paper's framework (used e.g. for
seed-based relevance and local community scoring).

The batched multi-query realization (one shard_map superstep advancing
thousands of queries over the Lemma-1 count wire) lives in
`core/personalized_batch.py`; both derive their walk-to-source assignment
from `source_start_counts` so the single-query and batched engines draw
from the same start distribution for the same key.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine_walks
from repro.core.graph import CSRGraph

# Round cap for the terminate-at-reset walk loop. Walks terminate w.p. eps
# per round, so P(any round beyond r) <= W * (1-eps)^r — at eps >= 0.1 the
# loop exits long before this cap; it exists only to bound a malformed
# (eps ~ 0) call.
DEFAULT_MAX_ROUNDS = 100_000

_START_FOLD = 0x5052_5354  # "PRST": start-assignment substream tag


def _host_key_words(key: jnp.ndarray) -> np.ndarray:
    """uint32 words of `key` on the host (typed or legacy raw keys)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(key, dtype=np.uint32).reshape(-1)


def source_start_counts(key: jnp.ndarray, weights: np.ndarray,
                        walks_total: int) -> np.ndarray:
    """Multinomial(walks_total, weights) walk-to-source assignment.

    Derived from `key` via fold_in onto a dedicated substream, so (a) two
    keys give two independent start assignments (the estimator's variance
    story needs the starts to resample), (b) the same key is bit-exactly
    reproducible, and (c) the draw never collides with the walk-step
    uniforms consumed downstream from the unfolded key.
    """
    weights = np.asarray(weights, dtype=np.float64)
    weights = weights / weights.sum()
    words = _host_key_words(jax.random.fold_in(key, _START_FOLD))
    rng = np.random.default_rng(words)
    return rng.multinomial(int(walks_total), weights)


def normalize_query(sources, weights, n: int):
    """Validate and canonicalize a (sources, weights) PPR query."""
    sources = np.asarray(sources, dtype=np.int32).reshape(-1)
    if sources.size == 0:
        raise ValueError("PPR query needs at least one source vertex")
    if sources.min() < 0 or sources.max() >= n:
        raise ValueError(f"source vertex out of range [0, {n})")
    if weights is None:
        weights = np.full(len(sources), 1.0 / len(sources))
    weights = np.asarray(weights, dtype=np.float64)
    if weights.shape != sources.shape:
        raise ValueError("weights must match sources")
    return sources, weights / weights.sum()


def personalized_pagerank(graph: CSRGraph, eps: float, sources,
                          walks_total: int, key: Optional[jnp.ndarray] = None,
                          weights=None,
                          max_rounds: int = DEFAULT_MAX_ROUNDS) -> jnp.ndarray:
    """Monte-Carlo PPR for a seed set.

    sources: int vertex ids [k]; weights: optional distribution over them.
    `key` drives BOTH the walk-to-source multinomial (via
    `source_start_counts`) and the walk steps — same key, bit-identical
    result; different keys, independent estimates. Returns the
    (unnormalized-estimator) PPR vector [n].
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    sources, weights = normalize_query(sources, weights, graph.n)
    counts = source_start_counts(key, weights, walks_total)
    starts = jnp.asarray(np.repeat(sources, counts), dtype=jnp.int32)

    state = engine_walks.init_state(graph, 0, key, sources=starts)
    state = engine_walks._run_while(graph.row_ptr, graph.col_idx,
                                    graph.out_deg, state, float(eps),
                                    int(max_rounds))
    return state.zeta.astype(jnp.float32) * (eps / walks_total)


def exact_ppr(graph: CSRGraph, eps: float, sources, weights=None) -> np.ndarray:
    """Dense linear-solve oracle: ppr = eps * s (I - (1-eps) Q)^-1."""
    from repro.core.graph import transition_matrix

    n = graph.n
    sources = np.asarray(sources)
    s = np.zeros(n)
    if weights is None:
        s[sources] = 1.0 / len(sources)
    else:
        w = np.asarray(weights, dtype=np.float64)
        s[sources] = w / w.sum()
    Q = (transition_matrix(graph, 0.0) - 0.0)  # pure walk matrix
    A = np.eye(n) - (1 - eps) * Q
    return eps * np.linalg.solve(A.T, s)

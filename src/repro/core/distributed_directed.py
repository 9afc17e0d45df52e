"""Multi-device Section-5 engine — directed graphs in the LOCAL model.

This fills the last cell of the ROADMAP engine matrix: the shard_map
realization of the paper's Section-5 extension of IMPROVED-PAGERANK to
directed graphs. It shares the entire 3-phase machinery with the
Algorithm-2 engine (`distributed_improved._run_three_phase`, built on the
lane/route/exchange primitives in `routing.py`); what Section 5 changes is
the *budget policy* and the *round budget*, not the supersteps:

  Uniform coupon budgets. On a directed graph there is no Lemma-2 bound
    relating walk visits to d(v) (short PageRank walks are not near
    degree-stationary), so Phase 1 cannot size vertex v's pool as
    d(v)*eta. Every node instead precomputes the same
    eta*ceil(log n) short walks (`coupon_pool_sizes(...,
    degree_proportional=False)`), the LOCAL-model analogue of the paper's
    "polynomially many coupons per node".

  Longer short walks. With uniform budgets the optimal split of the
    length-ell long walk is lam = ceil(sqrt(log n / eps)) — the Section-5
    round bound O(sqrt(log n / eps)) — instead of the CONGEST
    lam = ceil(sqrt(log n)).

  Directed out-edges only, dangling resets. Walks move along the CSR
    out-edges exactly as written (nothing is symmetrized), and a walk
    arriving at a dangling node (out-degree 0) takes an immediate reset —
    the owner-side aggregate sampler terminates the whole dangling row,
    the same convention as `graph.transition_matrix` (dangling row =
    uniform teleport), so the estimator stays consistent with power
    iteration.

This engine used to default to worst-case LOCAL buffers (every coupon /
walk co-resident on one shard) because a directed hub can attract
essentially the whole pool in one round and per-walk lanes under the
CONGEST 2*W/P rule overflowed on power-law webs. Count aggregation
(Lemma 1) retired the pool-sized buffers: Phases 1-3 move per-vertex
counts whose lane volume is bounded by distinct vertices, never by walk
multiplicity, so a hub attracting the entire pool still costs ONE lane
entry and no per-coupon slot exists anywhere (the old cap1 was
sum(pool) ~ n*eta*log n slots per shard). The one per-walk surface left
is the naive exhaustion tail, and there a directed hub still has no
degree bound tying its load to 2*W/P — so its buffer holds every tail
walk on one shard (the shared driver's default, sized from the walks the
tail holds), which makes `dropped == 0` structural: lane backpressure
shows up as `waited`, never as a drop.

Phase structure, wire accounting, conservation counters, the exhaustion
fallback to naive distributed walking, and the host-float64 estimator
pi = zeta * eps/(nK) are identical to `distributed_improved.py` — see
that module for the superstep details. Statistical target:
`improved_pagerank.directed_local_pagerank` (the single-device Section-5
engine) and power iteration on directed fixtures.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.distributed import AXIS
from repro.core.distributed_improved import (ImprovedDistResult,
                                             _run_three_phase,
                                             three_phase_audit_spec)
from repro.core.graph import CSRGraph
from repro.core.improved_pagerank import coupon_pool_sizes
from repro.core.simple_pagerank import walks_per_node_for


@dataclasses.dataclass
class DirectedDistResult(ImprovedDistResult):
    """ImprovedDistResult + Section-5 telemetry."""

    uniform_budget: int = 0   # coupons per node (every node gets the same)
    dangling_nodes: int = 0   # out-degree-0 vertices (immediate reset)


def distributed_directed_pagerank(
    graph: CSRGraph,
    eps: float,
    walks_per_node: Optional[int] = None,
    key: Optional[jnp.ndarray] = None,
    *,
    mesh: Optional[Mesh] = None,
    lam: Optional[int] = None,
    eta: Optional[int] = None,
    eta_safety: float = 2.0,
    cap2: Optional[int] = None,
    route_cap2: Optional[int] = None,
    max_rounds: int = 100_000,
    bandwidth_bits: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    fail_at: Optional[Sequence[int]] = None,
    checkpoint_every: int = 10,
    max_restarts: int = 16,
    resume: bool = False,
) -> DirectedDistResult:
    """Run the Section-5 directed/LOCAL algorithm across all devices of
    `mesh` (default: all devices).

    `cap2`/`route_cap2` size only the naive-tail buffers; the aggregated
    phases size themselves. `checkpoint_dir`/`fail_at`/`checkpoint_every`/
    `max_restarts`/`resume` select the checkpoint-restart supervisor over
    the shared phase-machine (see `distributed_improved._run_three_phase`):
    recovery is bit-exact."""
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (AXIS,))
    key = key if key is not None else jax.random.PRNGKey(0)
    n = graph.n
    K = walks_per_node or walks_per_node_for(n, eps)
    log_n = math.log(max(n, 2))
    if lam is None:
        lam = max(1, int(math.ceil(math.sqrt(log_n / eps))))
    ell = max(lam + 1, int(math.ceil(log_n / eps)))
    eta, pool_np = coupon_pool_sizes(graph, eps, K, lam, eta=eta,
                                     eta_safety=eta_safety,
                                     degree_proportional=False, ell=ell)
    return _run_three_phase(
        graph, eps, K, key, mesh, pools=lambda: pool_np, eta=int(eta),
        lam=int(lam), ell=int(ell), cap2=cap2, route_cap2=route_cap2,
        max_rounds=max_rounds, bandwidth_bits=bandwidth_bits,
        checkpoint_dir=checkpoint_dir,
        fail_at=fail_at, checkpoint_every=checkpoint_every,
        max_restarts=max_restarts, resume=resume, name="directed",
        result_cls=DirectedDistResult,
        uniform_budget=int(pool_np[0]),
        dangling_nodes=int((np.asarray(graph.out_deg) == 0).sum()))


def audit_spec(graph: CSRGraph, mesh: Mesh, *, eps: float = 0.2,
               walks_per_node: int = 2, bucketed: bool = True):
    """Section-5 frontend of the 3-phase audit spec: identical supersteps,
    uniform (LOCAL-model) coupon pools and the longer Section-5 lam —
    mirrors `distributed_directed_pagerank`'s sizing exactly."""
    n = graph.n
    K = walks_per_node
    log_n = math.log(max(n, 2))
    lam = max(1, int(math.ceil(math.sqrt(log_n / eps))))
    ell = max(lam + 1, int(math.ceil(log_n / eps)))
    _, pool_np = coupon_pool_sizes(graph, eps, K, lam,
                                   degree_proportional=False, ell=ell)
    return three_phase_audit_spec(graph, mesh, eps=eps, K=K,
                                  pool_np=pool_np, lam=lam,
                                  engine="directed", bucketed=bucketed)

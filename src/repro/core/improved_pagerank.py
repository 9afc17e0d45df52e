"""IMPROVED-PAGERANK-ALGORITHM (Algorithm 2) + the Section-5 directed/LOCAL
variant.

Three phases, exactly as in the paper:

  Phase 1 — every node v pre-computes short PageRank walks of length
    lambda = ceil(sqrt(log n)): d(v)*eta of them in the undirected/CONGEST
    setting (Lemma 2: visits ∝ degree), or a uniform per-node pool in the
    directed/LOCAL setting (Section 5). Trajectories and the edge ids taken
    are recorded; a short walk may terminate early if its eps-reset fires.

  Phase 2 — each of the n*K long walks stitches unused coupons at connector
    nodes via direct communication (O(1) rounds per stitch). Coupons are
    consumed in natural order, which is distributionally identical to
    uniform-without-replacement because coupons are iid and consumption
    order is independent of their realizations. If a node's pool is
    exhausted (eta too small — the paper's whp bound violated), the walk
    falls back to naive walking (tracked in `exhausted_walks`).

  Phase 3 — visits of *used* coupons are counted by re-tracing trajectories
    (the recorded edge ids make the reverse-trace message accounting exact);
    unfinished walks complete naively to their exact eps-reset so the
    estimator stays unbiased (the paper caps at l = log n/eps whp — we walk
    the true tail instead, a strict-superset guarantee).

Estimator: pi_tilde_v = zeta_v * eps / (n*K), identical to Algorithm 1.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import math
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accounting import CongestReport, RoundTrace, default_bandwidth
from repro.core.engine_walks import WalkState, _step_traced
from repro.core.estimator import pagerank_from_visits
from repro.core.graph import CSRGraph
from repro.core.simple_pagerank import PageRankResult, walks_per_node_for


@dataclasses.dataclass
class ImprovedResult(PageRankResult):
    lam: int = 0
    eta: int = 0
    stitch_iterations: int = 0
    phase1_rounds: int = 0
    phase2_rounds: int = 0
    phase3_rounds: int = 0
    tail_rounds: int = 0
    exhausted_walks: int = 0
    coupons_created: int = 0
    coupons_used: int = 0


# ---------------------------------------------------------------------------
# coupon pool sizing (shared with the distributed engine)
# ---------------------------------------------------------------------------

def coupon_pool_sizes(graph: CSRGraph, eps: float, walks_per_node: int,
                      lam: int, *, eta: Optional[int] = None,
                      eta_safety: float = 2.0,
                      degree_proportional: bool = True,
                      ell: Optional[int] = None) -> Tuple[int, np.ndarray]:
    """Phase-1 coupon pool sizes, shared by every Algorithm-2-family engine.

    Degree-proportional (undirected/CONGEST, Lemma 2): d(v)*eta coupons per
    node. eta is sized from the expected stitches-per-node: a long walk has
    expected length 1/eps => ~1/(eps*lam)+1 stitches; connectors land
    ∝ d(v)/Σd (undirected near-stationarity). The paper's Theta(log^3 n/eps)
    overprovisions for whp bounds; we size for the expectation ×safety and
    keep the naive-walk fallback for the (counted) exhaustion tail.
    Isolated vertices get one coupon so every request resolves
    deterministically.

    Uniform (directed/LOCAL, Section 5: `degree_proportional=False`): no
    degree bound relates visits to d(v) on a directed graph, so every node
    gets the same eta*ceil(log n) coupons, with eta = ceil(eta_safety *
    K*ell/lam) — K*ell/lam is the per-node stitch demand if the whole
    walk load concentrated ∝ 1/n, and the extra ceil(log n) factor covers
    connector skew (the paper sends polynomially many coupons; LOCAL
    bandwidth is free, our buffers are not). Requires `ell` (the whp walk
    length cap) unless `eta` is given explicitly.

    Returns (eta, pool_size[n]).
    """
    deg_np = np.asarray(graph.out_deg)
    n = graph.n
    if degree_proportional:
        if eta is None:
            exp_stitches = n * walks_per_node * (1.0 / (eps * lam) + 1.0)
            eta = max(1, int(math.ceil(
                eta_safety * exp_stitches / max(deg_np.sum(), 1))))
        return int(eta), np.maximum(deg_np.astype(np.int64) * eta, 1)
    if eta is None:
        if ell is None:
            raise ValueError("uniform pool sizing needs ell (or explicit eta)")
        eta = max(1, int(math.ceil(eta_safety * walks_per_node * ell / lam)))
    log_n = math.log(max(n, 2))
    per_node = int(eta) * max(1, int(math.ceil(log_n)))
    return int(eta), np.full(n, per_node, dtype=np.int64)


def expected_stitch_arrivals(row_ptr, col_idx, n: int, eps: float,
                             walks_per_node: int, lam: int) -> np.ndarray:
    """a[v]: the expected number of Phase-2 stitches that arrive at v.

    Every vertex starts `walks_per_node` long walks. A stitch hands a
    walk a coupon, which carries it lam steps further or ends it, so the
    walks that still need a coupon after k stitches are x_k = x_0 M^k,
    with x_0 = K at every vertex and M = ((1 - eps) Q)^lam, Q the
    row-stochastic out-edge matrix (a vertex without out-edges ends its
    walks). a = sum_{k>=1} x_k, iterated in float64 on the host until
    fewer than one walk in all is left to arrive."""
    deg = np.diff(np.asarray(row_ptr, np.int64))
    col = np.asarray(col_idx, np.int64)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
    x = np.full(n, float(walks_per_node))
    a = np.zeros(n)
    while x.sum() >= 1.0:
        for _ in range(lam):
            x = (1.0 - eps) * np.bincount(col, weights=np.repeat(x * inv, deg),
                                          minlength=n)
        a += x
    return a


_demand_pools: "collections.OrderedDict" = collections.OrderedDict()


def demand_pool_sizes(graph: CSRGraph, eps: float, walks_per_node: int,
                      lam: int) -> np.ndarray:
    """Coupon pools that cover the walks: K + ceil(a + 4 sqrt(a) + 4) at
    each vertex, a its expected stitch arrivals
    (`expected_stitch_arrivals`). Every walk takes its first coupon where
    it starts, so a pool holds at least the K starts; the arrivals add
    their expectation and a margin of four standard deviations of a
    Poisson count, plus 4 for vertices that expect almost none.

    This departs from Lemma 2's d(v)*eta: a degree rule gives a vertex
    of degree 1 five coupons for its own 32 starts, and on small
    components walks bounce between low-degree vertices many times their
    degree, so most of the work fell to the naive tail. Memoized per
    (graph, K, eps, lam): jobs on one graph pay the host iteration once.
    Read-only result."""
    row_ptr = np.ascontiguousarray(np.asarray(graph.row_ptr, np.int32))
    col = np.ascontiguousarray(np.asarray(graph.col_idx, np.int32))
    digest = hashlib.blake2b(row_ptr.tobytes(), digest_size=16)
    digest.update(col.tobytes())
    key = (digest.hexdigest(), graph.n, float(eps), int(walks_per_node),
           int(lam))
    pool = _demand_pools.get(key)
    if pool is None:
        a = expected_stitch_arrivals(row_ptr, col, graph.n, eps,
                                     walks_per_node, lam)
        pool = walks_per_node + np.ceil(a + 4.0 * np.sqrt(a) + 4.0).astype(
            np.int64)
        pool.setflags(write=False)
        _demand_pools[key] = pool
        while len(_demand_pools) > 8:
            _demand_pools.popitem(last=False)
    _demand_pools.move_to_end(key)
    return pool


# ---------------------------------------------------------------------------
# Phase 1: short walks with trajectory + edge-id recording
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("eps", "lam"))
def _phase1_scan(row_ptr, col_idx, out_deg, src, key, eps: float, lam: int):
    S = src.shape[0]

    def step(carry, k):
        pos, alive = carry
        k_term, k_edge = jax.random.split(k)
        u_term = jax.random.uniform(k_term, (S,))
        deg = out_deg[pos]
        survive = alive & (u_term >= eps) & (deg > 0)
        u_edge = jax.random.uniform(k_edge, (S,))
        j = jnp.minimum((u_edge * jnp.maximum(deg, 1)).astype(jnp.int32),
                        jnp.maximum(deg - 1, 0))
        edge_ids = row_ptr[pos] + j
        dst = col_idx[jnp.clip(edge_ids, 0, col_idx.shape[0] - 1)]
        new_pos = jnp.where(survive, dst, pos)
        rec = dict(pos=new_pos, moved=survive,
                   edge=jnp.where(survive, edge_ids, -1))
        return (new_pos, survive), rec

    keys = jax.random.split(key, lam)
    (final_pos, _), recs = jax.lax.scan(step, (src, jnp.ones((S,), bool)), keys)
    # recs["pos"]: [lam, S] arrival positions; moved: [lam, S]
    valid_arrivals = jnp.sum(recs["moved"], axis=0).astype(jnp.int32)
    terminated = ~recs["moved"][-1]  # reset fired at or before step lam
    return dict(
        traj=recs["pos"],            # [lam, S]
        edges=recs["edge"],          # [lam, S]  (-1 where no move)
        moved=recs["moved"],         # [lam, S]
        dest=final_pos,              # [S]
        valid_arrivals=valid_arrivals,
        terminated=terminated,
    )


def _edge_traces(edges: jnp.ndarray, moved: jnp.ndarray, m: int,
                 mask: Optional[jnp.ndarray] = None) -> List[RoundTrace]:
    """Per-step CONGEST accounting from recorded edge ids ([lam, S])."""
    traces = []
    lam = edges.shape[0]
    for i in range(lam):
        mv = moved[i] if mask is None else (moved[i] & mask)
        eids = jnp.where(mv, edges[i], m)  # dump masked into segment m
        counts = jax.ops.segment_sum(mv.astype(jnp.int32), eids,
                                     num_segments=m + 1)[:m]
        total = int(jnp.sum(counts))
        traces.append(RoundTrace(
            active_walks=int(jnp.sum(mv)),
            messages=int(jnp.sum(counts > 0)),
            max_edge_count=int(jnp.max(counts)) if m else 0,
            total_count=total,
        ))
    return traces


# ---------------------------------------------------------------------------
# Phase 2: stitching
# ---------------------------------------------------------------------------

@jax.jit
def _allocate_coupons(cur, active, next_coupon, pool_start, pool_size):
    """Give each active walk a distinct next-unused coupon of its connector.

    Returns (coupon_id [-1 if exhausted/inactive], new_next_coupon).
    Walks at the same connector receive consecutive offsets via a
    sort-and-rank within the connector group.
    """
    W = cur.shape[0]
    n = next_coupon.shape[0]
    vid = jnp.where(active, cur, n)  # inactive walks sort to the end
    order = jnp.argsort(vid)
    sorted_v = vid[order]
    # rank of each sorted element within its equal-value run
    idx = jnp.arange(W)
    is_start = jnp.concatenate([jnp.ones((1,), bool), sorted_v[1:] != sorted_v[:-1]])
    run_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    rank_sorted = idx - run_start
    rank = jnp.zeros((W,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    offset = next_coupon[jnp.clip(cur, 0, n - 1)] + rank
    ok = active & (offset < pool_size[jnp.clip(cur, 0, n - 1)])
    coupon_id = jnp.where(ok, pool_start[jnp.clip(cur, 0, n - 1)] + offset, -1)
    req = jax.ops.segment_sum(active.astype(jnp.int32), jnp.clip(cur, 0, n - 1),
                              num_segments=n)
    # pool pointer advances by the number of *requests* (paper deletes coupons
    # on sampling); clip to pool size
    new_next = jnp.minimum(next_coupon + req, pool_size)
    return coupon_id, ok, new_next


# ---------------------------------------------------------------------------
# main driver
# ---------------------------------------------------------------------------

def improved_pagerank(
    graph: CSRGraph,
    eps: float,
    *,
    walks_per_node: int | None = None,
    lam: int | None = None,
    eta: int | None = None,
    key: jnp.ndarray | None = None,
    degree_proportional: bool = True,
    local_model: bool = False,
    eta_safety: float = 2.0,
    bandwidth_bits: int | None = None,
) -> ImprovedResult:
    """Algorithm 2 (undirected/CONGEST) or Section 5 (directed/LOCAL when
    `degree_proportional=False, local_model=True`)."""
    n, m = graph.n, graph.m
    key = key if key is not None else jax.random.PRNGKey(0)
    K = walks_per_node or walks_per_node_for(n, eps)
    log_n = math.log(max(n, 2))
    if lam is None:
        lam = max(1, int(math.ceil(math.sqrt(log_n if not local_model
                                             else log_n / eps))))
    ell = max(lam + 1, int(math.ceil(log_n / eps)))

    eta, pool_size_np = coupon_pool_sizes(
        graph, eps, K, lam, eta=eta, eta_safety=eta_safety,
        degree_proportional=degree_proportional, ell=ell)

    pool_start_np = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(pool_size_np, out=pool_start_np[1:])
    S = int(pool_start_np[-1])
    src = np.repeat(np.arange(n, dtype=np.int32), pool_size_np)

    key, k1, k2, k3 = jax.random.split(key, 4)

    # ---------------- Phase 1 ----------------
    p1 = _phase1_scan(graph.row_ptr, graph.col_idx, graph.out_deg,
                      jnp.asarray(src), k1, float(eps), int(lam))
    traces_p1 = _edge_traces(p1["edges"], p1["moved"], m)
    # +1 round: destinations report their ID to sources (direct comm, step 7)
    traces_p1.append(RoundTrace(active_walks=S, messages=S, max_edge_count=1,
                                total_count=S))

    # ---------------- Phase 2 ----------------
    pool_start = jnp.asarray(pool_start_np[:-1], dtype=jnp.int32)
    pool_size = jnp.asarray(pool_size_np, dtype=jnp.int32)
    next_coupon = jnp.zeros((n,), jnp.int32)

    W = n * K
    cur = jnp.tile(jnp.arange(n, dtype=jnp.int32), K)
    len_done = jnp.zeros((W,), jnp.int32)
    long_term = jnp.zeros((W,), bool)
    exhausted = jnp.zeros((W,), bool)
    used = jnp.zeros((S,), bool)

    dest = p1["dest"]
    c_term = p1["terminated"]
    c_len = p1["valid_arrivals"]

    stitch_iters = 0
    max_iters = int(math.ceil(ell / lam)) + 3
    for _ in range(max_iters):
        active = (~long_term) & (~exhausted) & (len_done <= ell - lam)
        if not bool(jnp.any(active)):
            break
        coupon_id, ok, next_coupon = _allocate_coupons(
            cur, active, next_coupon, pool_start, pool_size)
        newly_exhausted = active & (~ok)
        cid = jnp.clip(coupon_id, 0, S - 1)
        used = used.at[cid].max(ok)  # bool-or scatter; False writes are no-ops
        cur = jnp.where(ok, dest[cid], cur)
        len_done = jnp.where(ok, len_done + c_len[cid], len_done)
        long_term = long_term | (ok & c_term[cid])
        exhausted = exhausted | newly_exhausted
        stitch_iters += 1
    traces_p2 = [RoundTrace(active_walks=W, messages=W, max_edge_count=1,
                            total_count=W)] * stitch_iters

    # ---------------- tail: finish un-terminated walks naively ----------
    tail_active = (~long_term)
    tail_rounds = 0
    traces_tail: List[RoundTrace] = []
    zeta_tail = jnp.zeros((n,), jnp.int32)
    if bool(jnp.any(tail_active)):
        state = WalkState(pos=cur, alive=tail_active, zeta=zeta_tail,
                          key=k2, round=jnp.int32(0))
        while bool(jnp.any(state.alive)):
            state, stats = _step_traced(graph.row_ptr, graph.col_idx,
                                        graph.out_deg, state, float(eps),
                                        m)
            traces_tail.append(RoundTrace(
                active_walks=int(stats["active"]),
                messages=int(stats["messages"]),
                max_edge_count=int(stats["max_edge_count"]),
                total_count=int(stats["moved"])))
        zeta_tail = state.zeta
        tail_rounds = int(state.round)

    # ---------------- Phase 3: count visits of used coupons -------------
    # start visits of the W long walks:
    zeta = jnp.full((n,), K, dtype=jnp.int32) + zeta_tail
    # arrivals of used coupons: traj[i, s] counted when moved[i, s] & used[s]
    used_m = p1["moved"] & used[None, :]
    flat_pos = jnp.where(used_m, p1["traj"], n).reshape(-1)
    zeta = zeta + jax.ops.segment_sum(
        used_m.astype(jnp.int32).reshape(-1), flat_pos, num_segments=n + 1)[:n]
    traces_p3 = _edge_traces(p1["edges"], p1["moved"], m, mask=used)

    traces = traces_p1 + traces_p2 + traces_tail + traces_p3
    report = CongestReport(traces=traces, n=n,
                           bandwidth_bits=bandwidth_bits or default_bandwidth(n))
    pi = pagerank_from_visits(zeta, n, K, eps)
    return ImprovedResult(
        pi=pi, zeta=zeta, walks_per_node=K, eps=eps,
        logical_rounds=len(traces), report=report,
        lam=int(lam), eta=int(eta), stitch_iterations=stitch_iters,
        phase1_rounds=len(traces_p1), phase2_rounds=stitch_iters,
        phase3_rounds=len(traces_p3), tail_rounds=tail_rounds,
        exhausted_walks=int(jnp.sum(exhausted)),
        coupons_created=S, coupons_used=int(jnp.sum(used)),
    )


def directed_local_pagerank(graph: CSRGraph, eps: float, **kw) -> ImprovedResult:
    """Section 5: directed graphs in the LOCAL model — uniform per-node
    coupon pools (no degree bound available) and lambda = sqrt(log n / eps)."""
    kw.setdefault("degree_proportional", False)
    kw.setdefault("local_model", True)
    return improved_pagerank(graph, eps, **kw)

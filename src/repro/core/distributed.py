"""Multi-device distributed PageRank engine (shard_map).

This is the TPU-pod realization of the paper's CONGEST network: vertices are
partitioned into contiguous shards, one per mesh device; a logical round is a
bulk-synchronous super-step:

    route  — walks whose current vertex is owned by another shard are
             exchanged with a fixed-capacity `all_to_all` (the payload is the
             paper's Lemma-1 insight: anonymous walk positions/counts, never
             identities);
    step   — each shard advances its owned walks one PageRank step
             (terminate w.p. eps, else uniform out-edge).

Static shapes throughout: per-shard walk buffers of capacity `cap`, per
(shard,shard) routing lanes of capacity `route_cap`. Walks that do not fit a
routing lane in a round *wait* (correctness preserved — a waiting walk is
simply delayed) and are carried over; a `work_cap` bound on steps per shard
per round provides straggler mitigation (uniform round time). Buffer
overflow beyond `cap` is counted in `dropped` and must be 0 for an exact
run — the sizing rule `cap >= 2*W/P + P*route_cap` keeps it 0 in practice.

Visit counting: a walk's arrival is counted by the *owner* shard exactly
once — immediately for intra-shard moves, at receive time for routed walks.
"""
from __future__ import annotations

import dataclasses
import math
from functools import lru_cache, partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.estimator import pagerank_from_visits
from repro.core.graph import CSRGraph
from repro.core.routing import (advance_owned, count_owned_arrivals,
                                merge_walks, rank_within, route_walks,
                                shard_map)

AXIS = "shards"


@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """Vertex-partitioned CSR: shard p owns [p*n_loc, (p+1)*n_loc)."""

    n: int
    n_pad: int
    n_loc: int
    shards: int
    row_ptr: jnp.ndarray   # [P, n_loc+1] rebased per shard
    col_idx: jnp.ndarray   # [P, m_loc_pad] global vertex ids
    out_deg: jnp.ndarray   # [P, n_loc]


def shard_graph(graph: CSRGraph, shards: int) -> ShardedGraph:
    n_loc = math.ceil(graph.n / shards)
    n_pad = n_loc * shards
    row_ptr = np.asarray(graph.row_ptr)
    col = np.asarray(graph.col_idx)
    deg = np.concatenate([np.asarray(graph.out_deg),
                          np.zeros(n_pad - graph.n, dtype=np.int32)])
    m_loc = []
    for p in range(shards):
        lo = min(p * n_loc, graph.n)
        hi = min((p + 1) * n_loc, graph.n)
        m_loc.append(int(row_ptr[hi] - row_ptr[lo]))
    m_pad = max(max(m_loc), 1)
    rp = np.zeros((shards, n_loc + 1), dtype=np.int32)
    ci = np.zeros((shards, m_pad), dtype=np.int32)
    dg = np.zeros((shards, n_loc), dtype=np.int32)
    for p in range(shards):
        lo = min(p * n_loc, graph.n)
        hi = min((p + 1) * n_loc, graph.n)
        local_rp = row_ptr[lo:hi + 1] - row_ptr[lo]
        rp[p, : hi - lo + 1] = local_rp
        rp[p, hi - lo + 1:] = local_rp[-1]
        ci[p, : m_loc[p]] = col[row_ptr[lo]:row_ptr[hi]]
        dg[p, : hi - lo] = deg[lo:hi]
    return ShardedGraph(n=graph.n, n_pad=n_pad, n_loc=n_loc, shards=shards,
                        row_ptr=jnp.asarray(rp), col_idx=jnp.asarray(ci),
                        out_deg=jnp.asarray(dg))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistState:
    pos: jnp.ndarray     # [P, cap] global vertex id, -1 = empty slot
    zeta: jnp.ndarray    # [P, n_loc]
    key: jnp.ndarray     # [P, 2] per-shard PRNG keys (uint32)
    round: jnp.ndarray   # [] int32
    dropped: jnp.ndarray  # [] int32 — must stay 0 for an exact run
    waited: jnp.ndarray   # [] int32 — routing-lane carry-overs (stat)


def _superstep_local(rp, ci, dg, pos, key, zeta, eps: float, n_loc: int,
                     shards: int, route_cap: int, work_cap: int):
    """One super-step on a single shard (runs under shard_map).

    Inputs arrive with a leading size-1 shard dim (shard_map blocks);
    squeeze on entry, re-expand on exit.
    """
    rp, ci, dg, pos, key, zeta = (rp[0], ci[0], dg[0], pos[0], key[0], zeta[0])
    shard_id = jax.lax.axis_index(AXIS)

    # ---- route: send non-owned walks, up to route_cap per target ----
    kept, _, recv, _, waited, sent_entries, sent_bytes = route_walks(
        pos, {}, axis=AXIS, shard_id=shard_id, n_loc=n_loc, shards=shards,
        route_cap=route_cap)
    arrived = recv >= 0
    # count arrivals (they are owned by me by construction)
    zeta = zeta + count_owned_arrivals(arrived, recv, shard_id, n_loc)

    # ---- merge buffer: kept walks + arrivals, compact into cap slots ----
    pos, _, dropped = merge_walks(kept, {}, recv, {}, pos.shape[0])

    # ---- step: advance owned walks (straggler-bounded) ----
    key, k_term, k_edge = jax.random.split(key, 3)
    valid = pos >= 0
    owner = jnp.where(valid, pos // n_loc, shards)
    owned = valid & (owner == shard_id)
    owned_rank, _ = rank_within(jnp.where(owned, 0, 1).astype(jnp.int32))
    stepped = owned & (owned_rank < work_cap) if work_cap else owned
    survive, dst = advance_owned(rp, ci, dg, pos, stepped, k_term, k_edge,
                                 eps, shard_id, n_loc)
    new_pos = jnp.where(survive, dst, jnp.where(stepped, -1, pos))
    # intra-shard arrivals counted immediately
    local_arrival = survive & (dst // n_loc == shard_id)
    zeta = zeta + count_owned_arrivals(local_arrival, dst, shard_id, n_loc)

    # global (replicated) scalar stats
    active = jax.lax.psum(jnp.sum(new_pos >= 0), AXIS)
    dropped = jax.lax.psum(dropped, AXIS)
    waited = jax.lax.psum(waited, AXIS)
    a2a_entries = jax.lax.psum(sent_entries, AXIS)
    a2a_bytes = jax.lax.psum(sent_bytes, AXIS)
    return (new_pos[None], key[None], zeta[None],
            active, dropped, waited, a2a_entries, a2a_bytes)


# memoized: equal (mesh, config) arguments produce byte-identical jitted
# programs, and a fresh closure per engine call would recompile the
# superstep on every invocation (jax interns Mesh, so the cache hits even
# when callers rebuild the mesh over the same devices)
@lru_cache(maxsize=64)
def _make_superstep(mesh: Mesh, eps: float, n_loc: int, shards: int,
                    route_cap: int, work_cap: int):
    fn = partial(_superstep_local, eps=eps, n_loc=n_loc, shards=shards,
                 route_cap=route_cap, work_cap=work_cap)
    sharded = shard_map(
        fn, mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(), P(), P(), P()),
    )

    @jax.jit
    def step(sg_row_ptr, sg_col, sg_deg, state: DistState):
        new_pos, key, zeta, active, dropped, waited, entries, a2a = sharded(
            sg_row_ptr, sg_col, sg_deg, state.pos, state.key, state.zeta)
        return DistState(pos=new_pos, zeta=zeta, key=key,
                         round=state.round + 1,
                         dropped=state.dropped + dropped,
                         waited=state.waited + waited), active, entries, a2a

    return step


@dataclasses.dataclass
class DistributedResult:
    zeta: jnp.ndarray          # [n] global visit counts
    pi: jnp.ndarray
    rounds: int
    dropped: int
    waited: int
    a2a_entries_total: int   # routed lane entries (4 B each, int32 pos)
    a2a_bytes_total: int
    shards: int
    # per-round telemetry: walks alive after each super-step (walks only
    # terminate, so this must be non-increasing for a conserving run)
    round_active: List[int] = dataclasses.field(default_factory=list)


def distributed_pagerank(
    graph: CSRGraph,
    eps: float,
    walks_per_node: int,
    key: jnp.ndarray,
    *,
    mesh: Optional[Mesh] = None,
    cap: Optional[int] = None,
    route_cap: Optional[int] = None,
    work_cap: int = 0,
    max_rounds: int = 100_000,
) -> DistributedResult:
    """Run Algorithm 1 across all devices of `mesh` (default: all devices)."""
    if mesh is None:
        devs = np.array(jax.devices())
        mesh = Mesh(devs, (AXIS,))
    shards = mesh.devices.size
    sg = shard_graph(graph, shards)
    W = graph.n * walks_per_node
    if cap is None:
        cap = max(2 * W // shards + shards * 64, 256)
    if route_cap is None:
        route_cap = max(W // shards, 64)

    # init: walks start at their own vertex; zeta starts at K per real vertex
    pos0 = np.full((shards, cap), -1, dtype=np.int32)
    zeta0 = np.zeros((shards, sg.n_loc), dtype=np.int32)
    for p in range(shards):
        lo = min(p * sg.n_loc, graph.n)
        hi = min((p + 1) * sg.n_loc, graph.n)
        locs = np.repeat(np.arange(lo, hi, dtype=np.int32), walks_per_node)
        assert len(locs) <= cap, "cap too small for initial placement"
        pos0[p, : len(locs)] = locs
        zeta0[p, : hi - lo] = walks_per_node

    keys = jax.random.split(key, shards)
    spec = NamedSharding(mesh, P(AXIS))
    state = DistState(
        pos=jax.device_put(jnp.asarray(pos0), spec),
        zeta=jax.device_put(jnp.asarray(zeta0), spec),
        key=jax.device_put(keys, spec),
        round=jnp.int32(0),
        dropped=jnp.int32(0),
        waited=jnp.int32(0),
    )
    sg_rp = jax.device_put(sg.row_ptr, spec)
    sg_ci = jax.device_put(sg.col_idx, spec)
    sg_dg = jax.device_put(sg.out_deg, spec)

    step = _make_superstep(mesh, float(eps), sg.n_loc, shards,
                           int(route_cap), int(work_cap))
    a2a_total = 0
    entries_total = 0
    rounds = 0
    round_active: List[int] = []
    while rounds < max_rounds:
        state, active, entries, a2a = step(sg_rp, sg_ci, sg_dg, state)
        a2a_total += int(a2a)
        entries_total += int(entries)
        rounds += 1
        round_active.append(int(active))
        if int(active) == 0:
            break
    zeta = state.zeta.reshape(-1)[: graph.n]
    pi = pagerank_from_visits(zeta, graph.n, walks_per_node, eps)
    return DistributedResult(
        zeta=zeta, pi=pi, rounds=rounds, dropped=int(state.dropped),
        waited=int(state.waited), a2a_entries_total=entries_total,
        a2a_bytes_total=a2a_total, shards=shards,
        round_active=round_active)


# --------------------------------------------------------------------------
# checkpoint/restart hooks (used by runtime.fault_tolerance)
# --------------------------------------------------------------------------

def state_to_host(state: DistState) -> dict:
    return dict(pos=np.asarray(state.pos), zeta=np.asarray(state.zeta),
                key=np.asarray(state.key), round=int(state.round),
                dropped=int(state.dropped), waited=int(state.waited))


def state_from_host(d: dict, mesh: Mesh) -> DistState:
    spec = NamedSharding(mesh, P(AXIS))
    return DistState(
        pos=jax.device_put(jnp.asarray(d["pos"]), spec),
        zeta=jax.device_put(jnp.asarray(d["zeta"]), spec),
        key=jax.device_put(jnp.asarray(d["key"]), spec),
        round=jnp.int32(d["round"]),
        dropped=jnp.int32(d["dropped"]),
        waited=jnp.int32(d["waited"]),
    )


# --------------------------------------------------------------------------
# static wire-budget declaration (consumed by `analysis.congest`)
# --------------------------------------------------------------------------

def audit_spec(graph: CSRGraph, mesh: Mesh, *, eps: float = 0.2,
               walks_per_node: int = 2, work_cap: int = 0):
    """The walk engine's `EngineAuditSpec` for the CONGEST auditor.

    The runtime `route_cap` scales with W/P, so this engine's lanes are
    walk-class wire: the auditor traces with `route_cap` PINNED at n_loc
    (legal — overflowing walks wait and retry, any cap is correct), which
    makes the checked capacity a W-free function of the partition. The
    walk-buffer `cap` never touches the wire and is pinned too.
    """
    from repro.checkpoint import pagerank_state_specs
    from repro.core.accounting import (EngineAuditSpec, ExchangeSite,
                                       StageProgram)
    shards = int(mesh.devices.size)
    sg = shard_graph(graph, shards)
    n_loc = sg.n_loc
    route_cap = n_loc
    cap = n_loc
    step = _make_superstep(mesh, float(eps), n_loc, shards, route_cap,
                           int(work_cap))
    sds = jax.ShapeDtypeStruct
    i32, u32 = jnp.int32, jnp.uint32
    state = DistState(pos=sds((shards, cap), i32),
                      zeta=sds((shards, n_loc), i32),
                      key=sds((shards, 2), u32),
                      round=sds((), i32), dropped=sds((), i32),
                      waited=sds((), i32))
    args = (sds((shards, n_loc + 1), i32),
            sds((shards, sg.col_idx.shape[1]), i32),
            sds((shards, n_loc), i32), state)
    site = ExchangeSite(
        site="route", entry_nbytes=4, lane_entries=shards * route_cap,
        budget_entries=shards * n_loc,
        budget_formula="P * n_loc lane slots (auditor-pinned "
                       "route_cap = n_loc)",
        wire_class="walk",
        note="runtime route_cap scales with W/P; overflow waits rather "
             "than widening the lane, so any pinned cap is correct")
    prog = StageProgram(stage="walks", program="step", fn=step,
                        example_args=args, sites=(site,),
                        count_bound=graph.n * walks_per_node)
    return EngineAuditSpec(
        engine="walks", programs=[prog],
        stage_arrays={"walks": ("pos", "zeta", "key", "round", "dropped",
                                "waited")},
        layouts={"walks": pagerank_state_specs(graph.n, cap=cap)},
        meta=dict(shards=shards, n=graph.n, walks_per_node=walks_per_node))

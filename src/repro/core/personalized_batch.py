"""Batched multi-source Personalized PageRank — the query-serving engine.

Walk arrays carry a QUERY-ID LANE: every walk slot is a (position, qid)
pair, so ONE shard_map superstep advances every in-flight query at once.
Cross-shard movement rides the existing Lemma-1 count wire
(`routing.route_counts`) unchanged, over a *virtual* vertex space that
folds the query id into the vertex index:

    u = v * Q + q          owner(u) = u // (n_loc * Q) = v // n_loc

so the all_to_all payload per superstep is bounded by the number of
distinct (vertex, query) pairs with traffic — independent of how many
walks move — and the receiving shard re-materializes walks from the
delivered counts. That re-deal is sound because walks are anonymous
WITHIN a query: Lemma 1 of the paper, extended by one lane.

Hot paths reuse the seed kernels behind `use_pallas`: per-walk
advancement via `walk_step` (`routing.advance_owned`) and the
(vertex, query) aggregation / visit histograms via `histogram`
(`routing.vertex_histogram`).

The engine is RESIDENT: the sharded graph and the walk/visit buffers stay
on device across queries. `admit(slot, sources, ...)` installs a query
into a free slot (start walks + start visits, start counts drawn through
`personalized.source_start_counts` so the single-query engine and this
one share the same key-derived start distribution), `superstep()`
advances everything one round and reports per-query live-walk counts,
`extract(slot)` pulls one query's PPR vector. `serve/ppr_service.py`
layers continuous-batching admission, an LRU/TTL result cache, and
traffic stats on top; `batched_personalized_pagerank` below is the
one-shot batch driver used by the launch CLI and the conformance suite.

Buffer sizing: walks only terminate after admission, so a `cap` of
(num_slots * walks_per_query + slack) per shard can never overflow even
if every live walk lands on one shard — the default. Tighter caps trade
memory for a nonzero `dropped` risk; `dropped` must stay 0 for an exact
run (the serve bench gates on it).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.distributed import AXIS, ShardedGraph, shard_graph
from repro.core.graph import CSRGraph
from repro.core.personalized import (DEFAULT_MAX_ROUNDS, normalize_query,
                                     source_start_counts)
from repro.core.routing import (advance_owned, rank_within, route_counts,
                                count_owned_arrivals, shard_map,
                                vertex_histogram)
from repro.checkpoint import LayoutSpec, relayout_arrays
from repro.kernels import resolve_use_pallas


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BatchPPRState:
    pos: jnp.ndarray    # [P, cap] global padded vertex id, -1 = empty slot
    qid: jnp.ndarray    # [P, cap] query slot of each walk (0 where empty)
    zeta: jnp.ndarray   # [P, n_loc, Q] per-(owned vertex, query) visits
    key: jnp.ndarray    # [P, 2] per-shard PRNG keys


def ppr_state_specs(n: int, cap: int):
    """Elastic layout schema for the resident PPR engine's buffers —
    shared by `relayout_from` and the CONGEST auditor's schema lint."""
    return dict(
        pos=LayoutSpec(kind="walk", n=n, cap=cap, fill=-1, aux=("qid",)),
        qid=LayoutSpec(kind="walk_aux", fill=0),
        zeta=LayoutSpec(kind="vertex", n=n),
        key=LayoutSpec(kind="key"))


def _ppr_superstep(rp, ci, dg, pos, qid, zeta, key, *, eps: float,
                   n_loc: int, shards: int, Q: int, use_pallas: bool,
                   count_bound: Optional[int] = None):
    """One batched PPR round on a single shard (runs under shard_map).

    All buffered walks are owned by this shard by construction (arrivals
    are re-materialized owner-side), so every valid slot is eligible.
    """
    rp, ci, dg, pos, qid, zeta, key = (
        rp[0], ci[0], dg[0], pos[0], qid[0], zeta[0], key[0])
    shard_id = jax.lax.axis_index(AXIS)
    cap = pos.shape[0]
    key, k_term, k_edge = jax.random.split(key, 3)

    valid = pos >= 0
    survive, dst = advance_owned(rp, ci, dg, pos, valid, k_term, k_edge,
                                 eps, shard_id, n_loc,
                                 use_pallas=use_pallas)

    # Lemma-1 aggregation with a query lane: movers collapse to counts per
    # virtual (vertex, query) id and ride ONE route_counts exchange.
    u = dst * Q + qid
    per_virtual = vertex_histogram(u, survive, shards * n_loc * Q,
                                   use_pallas=use_pallas)
    if shards == 1:
        # one shard owns every lane: nothing crosses the wire, and
        # `route_counts` would sort and scatter all n * Q lanes only to
        # hand them back (seconds per superstep at n = 2^22, Q = 8)
        arrivals, sent_entries, sent_bytes = (per_virtual, jnp.int32(0),
                                              jnp.int32(0))
    else:
        arrivals, sent_entries, sent_bytes = route_counts(
            per_virtual, axis=AXIS, shard_id=shard_id, n_loc=n_loc * Q,
            shards=shards, use_pallas=use_pallas, count_bound=count_bound)

    # every arrival is a visit to an owned vertex
    zeta = zeta + arrivals.reshape(n_loc, Q)

    # re-deal the buffer from the arrival counts (anonymity within qid)
    cum = jnp.cumsum(arrivals)
    total = cum[-1]
    slot = jnp.arange(cap, dtype=jnp.int32)
    u_loc = jnp.minimum(
        jnp.searchsorted(cum, slot, side="right").astype(jnp.int32),
        n_loc * Q - 1)
    take = slot < total
    new_pos = jnp.where(take, shard_id * n_loc + u_loc // Q, -1)
    new_qid = jnp.where(take, u_loc % Q, 0)

    active_q = jax.lax.psum(
        jax.ops.segment_sum(take.astype(jnp.int32),
                            jnp.where(take, new_qid, Q),
                            num_segments=Q + 1)[:Q], AXIS)
    dropped = jax.lax.psum(jnp.maximum(total - cap, 0), AXIS)
    sent_entries = jax.lax.psum(sent_entries, AXIS)
    sent_bytes = jax.lax.psum(sent_bytes, AXIS)
    return (new_pos[None], new_qid[None], zeta[None], key[None],
            active_q, sent_entries, sent_bytes, dropped)


def _ppr_admit(pos, qid, zeta, starts, slot, *, n_loc: int, shards: int,
               Q: int, use_pallas: bool):
    """Install a query into slot `slot`: place its start walks into free
    buffer slots of the shards owning the start vertices, and reset the
    slot's visit column to the start visits (a start counts as a visit,
    matching `engine_walks.init_state`). Runs under shard_map; `starts`
    ([walks_per_query] global vertex ids) and `slot` are replicated."""
    pos, qid, zeta = pos[0], qid[0], zeta[0]
    shard_id = jax.lax.axis_index(AXIS)

    # defensive: a freed slot leaves no walks behind, but a re-admitted
    # slot must never inherit strays
    stale = (pos >= 0) & (qid == slot)
    pos = jnp.where(stale, -1, pos)

    mine = (starts >= 0) & (starts // n_loc == shard_id)
    zeta = zeta.at[:, slot].set(
        count_owned_arrivals(mine, starts, shard_id, n_loc,
                             use_pallas=use_pallas))

    # pack my starts into this shard's free buffer slots
    order = jnp.argsort(jnp.where(mine, 0, 1), stable=True)
    vals = starts[order]                       # first n_mine are mine
    n_mine = jnp.sum(mine)
    free_rank, _ = rank_within(jnp.where(pos < 0, 0, 1).astype(jnp.int32))
    take = (pos < 0) & (free_rank < n_mine)
    pick = vals[jnp.minimum(free_rank, starts.shape[0] - 1)]
    pos = jnp.where(take, pick, pos)
    qid = jnp.where(take, slot, qid)
    admit_dropped = jax.lax.psum(n_mine - jnp.sum(take), AXIS)
    return pos[None], qid[None], zeta[None], admit_dropped


class BatchedPPREngine:
    """Resident sharded graph + Q walk-slot batch of PPR queries.

    Telemetry (host counters, cumulative): `rounds`, `a2a_bytes`,
    `dropped` (buffer overflow — must stay 0), `admit_dropped` (admission
    overflow — must stay 0), `active` (the [Q] per-query live-walk counts
    after the last superstep).
    """

    def __init__(self, graph: CSRGraph, eps: float, *, num_slots: int,
                 walks_per_query: int, mesh: Optional[Mesh] = None,
                 cap: Optional[int] = None,
                 use_pallas: Optional[bool] = None):
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), (AXIS,))
        self.graph = graph
        self.eps = float(eps)
        self.Q = int(num_slots)
        self.walks_per_query = int(walks_per_query)
        self.mesh = mesh
        self.shards = mesh.devices.size
        self.use_pallas = resolve_use_pallas(use_pallas)
        self.sg: ShardedGraph = shard_graph(graph, self.shards)
        if cap is None:
            # worst case: every live walk of every slot on one shard
            cap = self.Q * self.walks_per_query + 64
        self.cap = int(cap)

        spec = NamedSharding(mesh, P(AXIS))
        self._spec = spec
        self._rp = jax.device_put(self.sg.row_ptr, spec)
        self._ci = jax.device_put(self.sg.col_idx, spec)
        self._dg = jax.device_put(self.sg.out_deg, spec)

        n_loc = self.sg.n_loc
        step_sh = shard_map(
            partial(_ppr_superstep, eps=self.eps, n_loc=n_loc,
                    shards=self.shards, Q=self.Q,
                    use_pallas=self.use_pallas,
                    count_bound=self.walks_per_query),
            mesh,
            in_specs=(P(AXIS),) * 7,
            out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS),
                       P(), P(), P(), P()))
        admit_sh = shard_map(
            partial(_ppr_admit, n_loc=n_loc, shards=self.shards, Q=self.Q,
                    use_pallas=self.use_pallas),
            mesh,
            in_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P()),
            out_specs=(P(AXIS), P(AXIS), P(AXIS), P()))

        @jax.jit
        def _step(rp, ci, dg, st: BatchPPRState):
            pos, qid, zeta, key, active_q, entries, sent, dropped = step_sh(
                rp, ci, dg, st.pos, st.qid, st.zeta, st.key)
            return (BatchPPRState(pos=pos, qid=qid, zeta=zeta, key=key),
                    active_q, entries, sent, dropped)

        @jax.jit
        def _admit(st: BatchPPRState, starts, slot):
            pos, qid, zeta, admit_dropped = admit_sh(
                st.pos, st.qid, st.zeta, starts, slot)
            return (BatchPPRState(pos=pos, qid=qid, zeta=zeta, key=st.key),
                    admit_dropped)

        self._step = _step
        self._admit = _admit
        self.reset(jax.random.PRNGKey(0))

    # ------------------------------------------------------------ lifecycle
    def reset(self, key: jnp.ndarray) -> None:
        """Clear every slot and re-seed the per-shard PRNG streams."""
        spec = self._spec
        shape = (self.shards, self.cap)
        self.state = BatchPPRState(
            pos=jax.device_put(jnp.full(shape, -1, jnp.int32), spec),
            qid=jax.device_put(jnp.zeros(shape, jnp.int32), spec),
            zeta=jax.device_put(
                jnp.zeros((self.shards, self.sg.n_loc, self.Q), jnp.int32),
                spec),
            key=jax.device_put(jax.random.split(key, self.shards), spec))
        self.active = np.zeros(self.Q, dtype=np.int64)
        self.rounds = 0
        self.a2a_entries = 0
        self.a2a_bytes = 0
        self.dropped = 0
        self.admit_dropped = 0

    # ------------------------------------------------------------ admission
    def admit(self, slot: int, sources, weights=None,
              key: Optional[jnp.ndarray] = None) -> None:
        """Start `walks_per_query` walks from the query's source
        distribution in slot `slot` (which must be idle)."""
        if not 0 <= slot < self.Q:
            raise ValueError(f"slot {slot} out of range [0, {self.Q})")
        if self.active[slot] != 0:
            raise ValueError(f"slot {slot} still has live walks")
        key = key if key is not None else jax.random.PRNGKey(slot)
        sources, weights = normalize_query(sources, weights, self.graph.n)
        counts = source_start_counts(key, weights, self.walks_per_query)
        starts = jnp.asarray(np.repeat(sources, counts), dtype=jnp.int32)
        self.state, admit_dropped = self._admit(
            self.state, starts, jnp.int32(slot))
        self.admit_dropped += int(admit_dropped)
        self.active[slot] = self.walks_per_query - int(admit_dropped)

    # ------------------------------------------------------------- stepping
    def superstep(self) -> np.ndarray:
        """Advance every live walk of every query one round; returns the
        [Q] per-query live-walk counts (0 = query complete)."""
        self.state, active_q, entries, sent, dropped = self._step(
            self._rp, self._ci, self._dg, self.state)
        self.active = np.asarray(active_q, dtype=np.int64)
        self.rounds += 1
        self.a2a_entries += int(entries)
        self.a2a_bytes += int(sent)
        self.dropped += int(dropped)
        return self.active

    # ------------------------------------------------------------- elastic
    def relayout_from(self, other: "BatchedPPREngine") -> None:
        """Adopt `other`'s live serving state onto THIS engine's mesh.

        The walk buffer (with its query-id lane), the per-(vertex, query)
        visit shards, and the telemetry counters carry over through the
        schema-driven `checkpoint.relayout_arrays` — in-flight queries
        keep their walks and visit counts bit-for-bit (per-shard keys are
        re-derived, so the REMAINING steps of live walks are statistical,
        not a replay). Lets `serve.PPRService.resize` swap the resident
        engine onto a grown/shrunk mesh mid-traffic.
        """
        if (other.graph.n != self.graph.n or other.Q != self.Q
                or other.walks_per_query != self.walks_per_query):
            raise ValueError(
                f"engine mismatch: (n, Q, walks_per_query) "
                f"{(other.graph.n, other.Q, other.walks_per_query)} vs "
                f"{(self.graph.n, self.Q, self.walks_per_query)}")
        n = self.graph.n
        specs = ppr_state_specs(n, self.cap)
        arrays = {name: np.asarray(getattr(other.state, name))
                  for name in ("pos", "qid", "zeta", "key")}
        out = relayout_arrays(arrays, specs, other.shards, self.shards)
        self.cap = int(out["pos"].shape[1])    # auto-grown under walk skew
        spec = self._spec
        self.state = BatchPPRState(
            pos=jax.device_put(jnp.asarray(out["pos"]), spec),
            qid=jax.device_put(jnp.asarray(out["qid"]), spec),
            zeta=jax.device_put(jnp.asarray(out["zeta"]), spec),
            key=jax.device_put(jnp.asarray(out["key"]), spec))
        self.active = other.active.copy()
        self.rounds = other.rounds
        self.a2a_entries = other.a2a_entries
        self.a2a_bytes = other.a2a_bytes
        self.dropped = other.dropped
        self.admit_dropped = other.admit_dropped

    # -------------------------------------------------------------- results
    def extract(self, slot: int) -> np.ndarray:
        """The (unnormalized-estimator) PPR vector of slot `slot`:
        zeta * eps / walks_per_query, scaled in float64 on the host."""
        zeta = np.asarray(self.state.zeta[:, :, slot], dtype=np.int64)
        zeta = zeta.reshape(-1)[: self.graph.n]
        return zeta.astype(np.float64) * (self.eps / self.walks_per_query)


@dataclasses.dataclass
class BatchPPRResult:
    ppr: np.ndarray          # [num_queries, n] estimator vectors
    rounds: int
    a2a_bytes: int
    dropped: int             # walk-buffer overflow — 0 for an exact run
    admit_dropped: int       # admission overflow — 0 for an exact run
    shards: int
    active_trace: List[int]  # total live walks after each superstep
    a2a_entries: int = 0     # routed (virtual vertex, count) lane entries


def batched_personalized_pagerank(
        graph: CSRGraph, eps: float,
        queries: Sequence[Tuple[Sequence[int], Optional[Sequence[float]]]],
        walks_per_query: int, key: jnp.ndarray, *,
        mesh: Optional[Mesh] = None, cap: Optional[int] = None,
        use_pallas: Optional[bool] = None,
        max_rounds: int = DEFAULT_MAX_ROUNDS) -> BatchPPRResult:
    """One-shot batch driver: admit every query up front, run every walk
    to termination in shared supersteps, extract all results.

    `queries` is a sequence of (sources, weights-or-None). Query i's walk
    starts are derived from fold_in(key, i), so a batch is reproducible
    per key and each query resamples under a new key.
    """
    engine = BatchedPPREngine(graph, eps, num_slots=len(queries),
                              walks_per_query=walks_per_query, mesh=mesh,
                              cap=cap, use_pallas=use_pallas)
    engine.reset(jax.random.fold_in(key, 0xBA7C))
    for i, (sources, weights) in enumerate(queries):
        engine.admit(i, sources, weights, key=jax.random.fold_in(key, i))
    trace: List[int] = []
    while engine.active.sum() > 0 and engine.rounds < max_rounds:
        active = engine.superstep()
        trace.append(int(active.sum()))
    ppr = np.stack([engine.extract(i) for i in range(len(queries))])
    return BatchPPRResult(ppr=ppr, rounds=engine.rounds,
                          a2a_bytes=engine.a2a_bytes,
                          a2a_entries=engine.a2a_entries,
                          dropped=engine.dropped,
                          admit_dropped=engine.admit_dropped,
                          shards=engine.shards, active_trace=trace)


def audit_spec(graph: CSRGraph, mesh: Mesh, *, eps: float = 0.2,
               num_slots: int = 2, walks_per_query: int = 8,
               use_pallas: bool = False):
    """CONGEST-auditor spec for the batched PPR engine: the resident
    engine's jitted superstep (built with an auditor-pinned walk cap — the
    virtual-lane wire bound is independent of the buffer size), its
    declared (vertex, query)-lane budget, and the elastic schema."""
    from repro.core.accounting import (EngineAuditSpec, ExchangeSite,
                                       StageProgram)
    shards = int(mesh.devices.size)
    engine = BatchedPPREngine(graph, eps, num_slots=num_slots,
                              walks_per_query=walks_per_query, mesh=mesh,
                              cap=64, use_pallas=use_pallas)
    n_loc, Q, cap = engine.sg.n_loc, engine.Q, engine.cap
    sds = jax.ShapeDtypeStruct
    i32, u32 = jnp.int32, jnp.uint32
    sg = engine.sg
    state = BatchPPRState(pos=sds((shards, cap), i32),
                          qid=sds((shards, cap), i32),
                          zeta=sds((shards, n_loc, Q), i32),
                          key=sds((shards, 2), u32))
    args = (sds(sg.row_ptr.shape, sg.row_ptr.dtype),
            sds(sg.col_idx.shape, sg.col_idx.dtype),
            sds(sg.out_deg.shape, sg.out_deg.dtype), state)
    site = ExchangeSite(
        site="ppr", entry_nbytes=8, lane_entries=shards * n_loc * Q,
        budget_entries=shards * n_loc * Q,
        budget_formula=("P * n_loc * Q distinct (vertex, query) virtual "
                        "lanes — Lemma 1 extended by the query-id lane"),
        wire_class="count",
        note="bounded by distinct (vertex, query) pairs, never walk count")
    # a one-shard superstep has no wire (see `_ppr_superstep`)
    prog = StageProgram(stage="serve", program="superstep", fn=engine._step,
                        example_args=args,
                        sites=(site,) if shards > 1 else (),
                        count_bound=walks_per_query)
    return EngineAuditSpec(
        engine="ppr", programs=[prog],
        stage_arrays={"serve": ("pos", "qid", "zeta", "key")},
        layouts={"serve": ppr_state_specs(graph.n, cap)},
        meta=dict(shards=shards, n=graph.n, Q=Q,
                  walks_per_query=walks_per_query))

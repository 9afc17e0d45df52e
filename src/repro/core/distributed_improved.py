"""Multi-device IMPROVED-PAGERANK engine — shard_map realization of
Algorithm 2 on the vertex-partitioned `ShardedGraph`.

The single-device `improved_pagerank.py` holds the whole coupon pool and
every trajectory in one address space; this engine is the CONGEST-faithful
TPU-pod version: vertices are partitioned into contiguous shards (one per
mesh device) and every exchange is a fixed-capacity `all_to_all` built from
the shared lane machinery in `routing.py`. Payloads are count-aggregated
per Lemma 1: walks are anonymous, so everything that moves between shards
travels as (vertex, count) pairs — the wire volume is bounded by the number
of *distinct* (vertex, outcome) pairs, independent of how many walks move.

Phase 1 — short-walk pre-computation. Shard p owns the coupons of its
  vertices, each a PageRank walk given exactly lambda = ceil(sqrt(log n))
  step opportunities. Vertex v gets K + ceil(a + 4 sqrt(a) + 4) coupons,
  a its expected stitch arrivals (`improved_pagerank.demand_pool_sizes`):
  its own K walks each take their first coupon there. This departs from
  Lemma 2's d(v)*eta (still available by an explicit `eta`), which leaves
  a low-degree vertex fewer coupons than it starts walks, so a third of
  the walks of a Graph500 graph fell to the naive tail. Coupons never
  migrate; slot s of shard p's pool table is its identity. Each round is
  one count-aggregated round trip:

    request — every home shard histograms its live coupons' current
      vertices and ships per-vertex counts to the owners
      (`route_counts(by_source=True)`, 8 B/entry);
    sample  — the owner draws, independently for every (home, vertex)
      row, a Binomial(c, eps) termination count (a dangling vertex
      terminates the whole row) and splits the survivors over the
      out-edges with a binomial-tree multinomial — the aggregate
      of c iid walk steps, never c individual steps. The draws run
      through the shared degree-bucketed aggregate sampler
      (`core/aggregate_sampler`): rows grouped by power-of-two degree
      buckets via a static shard-time permutation, each bucket's tree
      spanning the bucket width instead of the global max degree, so
      Phase-1 sampler FLOPs are ~ sum_v deg(v) per round. RNG contract:
      counter-based draws keyed on (round key words, globally-unique row
      id, slot) — see `core/binomial_tree` — so the results are
      independent of bucket layout, and replay stays bit-exact. The
      outcomes stay in the sampler's own layout: per home, one reset
      count per bucket row and one count per bucket slot
      (`aggregate_sampler.bucketize_csr` gives each slot's
      destination), so no Phase-1 array grows with n_loc * max_deg;
    reply   — nonzero (vertex, destination, offset) cells go back to the
      home shard (12 B/entry), the destination -2 for "terminated" and
      the offset where the cell's outcomes start within its row;
    assign  — the home shard sorts its live coupons by (vertex, random
      priority), so vertex v's coupons hold positions [base[v],
      base[v] + c[v]) of that order, base the exclusive cumsum of its
      per-vertex counts; a cell covers [base[v] + offset, + count). The
      rows add up to c (residual 0), so the cells tile the positions and
      every live coupon takes exactly one outcome. A multiset of iid
      outcomes dealt out in uniform-random order IS an iid draw per
      coupon, so every coupon still walks the exact eps-reset chain.

  The per-coupon move is recorded in a home-local trajectory table
  `traj[slot, t]` — this is what Phase 3 counts, so no replay is needed.

Phase 2 — stitching. The n*K long walks are anonymous too ("which coupon
  did walk w use" is never needed — coupons are iid), so the engine keeps
  only per-vertex walk *counts*. Each stitch superstep allocates, at every
  owned vertex, the next `min(walks_here, pool_left)` unused coupons
  (natural-order consumption — distributionally identical to
  uniform-without-replacement because coupons are iid), marks them used,
  retires walks whose coupon recorded an eps-reset, and ships the rest as
  per-destination counts (`route_counts`, 8 B/entry). A coupon's final
  vertex never changes during Phase 2, so the counts are summed by a sort
  of the round's movers on that fixed key, a cumsum and a take at segment
  ends that one program (`jit_p2_index`) builds at Phase 2's entry
  (`routing.sorted_segment_sums`), not by a scatter-add. Walks at an
  exhausted pool (undersized — the paper's whp bound violated)
  accumulate in a per-vertex tail count for the naive fallback.

Phase 3 — counting. One histogram of the used coupons' home-local
  trajectories plus ONE `route_counts` exchange lands every visit at its
  owner shard: the paper's "destinations report their ID" step collapses
  to a single aggregated round (the old implementation re-ran the whole
  Phase-1 schedule as a deterministic replay; the trajectory table makes
  that — and its per-walk wire — unnecessary). Tail walks then finish
  naively through the Algorithm 1 superstep (`distributed._make_superstep`,
  its walk buffer sized from the walks it holds), counting arrivals into
  the same sharded zeta; the estimator
  pi = zeta * eps/(nK) is computed on the host in float64
  (`estimator.pagerank_from_visits`).

Static shapes throughout; count lanes are sized so overflow is
*structurally impossible* (`route_counts` caps lanes at n_loc distinct
vertices; Phase-1 replies at min(n_loc + bucket slots, S_loc_pad)
distinct cells), so `dropped` stays 0 by construction; the naive tail's
buffer holds every tail walk.

Each stage program has its own name in a device trace: `jit_p1_request`,
`jit_p1_sample`, `jit_p1_assign`, `jit_p2_index`, `jit_p2_stitch`,
`jit_p3_count` and `jit_tail_step`. `max_rounds` caps each stage's
rounds: a stage that reaches it stops, and the result counts the walks
still out (`unfinished`).

The phases only ever see a per-node pool-size vector, so the whole driver
lives in the budget-policy-agnostic `_run_three_phase`; this module's
public `distributed_improved_pagerank` feeds it demand-covering pools,
and `distributed_directed.distributed_directed_pagerank` feeds it
the Section-5 uniform/LOCAL pools — count aggregation removed the
worst-case per-walk buffers that engine used to need.

Fault tolerance — the driver is a *checkpointable phase-machine*: each
phase (phase1, phase2, phase3, tail) is a named `runtime.Stage` whose
snapshot is the stage's device buffers (coupon tables, trajectory table,
walk counts, the `used` bitmap) plus the host accumulators (wire/trace
telemetry, round counters) as a pytree of arrays. With `checkpoint_dir`/
`fail_at` set, the `runtime.Supervisor` drives the composed
`StageSchedule`: a killed run resumes mid-phase from the latest
stage-tagged snapshot and — because every stage is deterministic given its
buffers and keys — produces bit-identical `zeta`/`pi` and telemetry vs an
unfailed run.
"""
from __future__ import annotations

import dataclasses
import math
from concurrent.futures import ThreadPoolExecutor
from functools import lru_cache, partial
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.accounting import CongestReport, RoundTrace, default_bandwidth
from repro.core.aggregate_sampler import (build_layout_sharded,
                                          bucketize_csr, sample_buckets)
from repro.core.binomial_tree import key_words
from repro.core.distributed import (AXIS, DistState, _make_superstep,
                                    shard_graph, shard_map)
from repro.core.estimator import pagerank_from_visits
from repro.core.graph import CSRGraph
from repro.core.improved_pagerank import (coupon_pool_sizes,
                                          demand_pool_sizes)
from repro.core.routing import (entry_nbytes, exchange_stacked, lane_slots,
                                pack_lanes, route_counts,
                                sorted_segment_sums, vertex_histogram)
from repro.checkpoint import LayoutSpec
from repro.core.simple_pagerank import walks_per_node_for
from repro.runtime import Stage, StagedState, StageSchedule, run_staged
from repro.runtime import tracing

# ---------------------------------------------------------------------------
# Phase 1: count-aggregated short-walk pre-computation
# ---------------------------------------------------------------------------
#
# Reply layout. Every owner holds, for each home, one reply cell per bucket
# row (its reset count) and one per bucket slot (its count on that
# out-edge): [total_rows + total_edges] cells, the rows in `bperm` order and
# the slots in `bnbr` order (`aggregate_sampler.bucketize_csr`), so a
# cell's vertex and destination are static. Nothing in Phase 1 is as wide
# as n_loc * max_deg.

def _row_cells(x, layout):
    """Per-row values [..., total_rows] (bucket-row order) -> per reply
    cell [..., total_rows + total_edges]: each row's value on its reset
    cell and on every slot of its bucket block (slot-major)."""
    lead = x.shape[:-1]
    parts = [x]
    for s, cap, w in zip(layout.row_starts, layout.caps, layout.widths):
        xb = x[..., None, s:s + cap]
        parts.append(jnp.broadcast_to(xb, lead + (w, cap))
                     .reshape(lead + (w * cap,)))
    return jnp.concatenate(parts, axis=-1)


def _cell_offsets(cells, layout):
    """Where each reply cell's outcomes start within its (home, vertex)
    row: the reset cell at 0, then the out-edge slots in slot order."""
    tr = layout.total_rows
    lead = cells.shape[:-1]
    term = cells[..., :tr]
    parts, e = [jnp.zeros_like(term)], tr
    for s, cap, w in zip(layout.row_starts, layout.caps, layout.widths):
        mv = cells[..., e:e + w * cap].reshape(lead + (w, cap))
        ex = jnp.cumsum(mv, axis=-2) - mv + term[..., None, s:s + cap]
        parts.append(ex.reshape(lead + (w * cap,)))
        e += w * cap
    return jnp.concatenate(parts, axis=-1)


def _group_order(group, key):
    """The order that sorts `group` ascending, ties in a uniformly random
    order: a stable sort by 31 random bits, then a stable sort by group.
    Both passes run one compiled sort (a two-trip loop): the TPU compiler
    takes minutes over a sort of two keys, and about a minute over one."""
    rand = jax.lax.shift_right_logical(
        jax.random.bits(key, group.shape, jnp.uint32), jnp.uint32(1))
    keys = jnp.stack([rand.astype(jnp.int32), group])

    def pass_(i, perm):
        return perm[jnp.argsort(keys[i][perm], stable=True)]

    return jax.lax.fori_loop(0, 2, pass_,
                             jnp.arange(group.shape[0], dtype=jnp.int32))


def _p1_request(pos, alive, *, n_loc: int, shards: int):
    """Phase-1 program 1 (request): per-vertex live-coupon counts to the
    owners. Returns c[home * n_loc + v] = coupons of `home` currently at
    owned vertex v, and this home's own histogram over all n_pad vertices
    (the assign's per-vertex offsets)."""
    pos, alive = pos[0], alive[0]
    shard_id = jax.lax.axis_index(AXIS)
    n_pad = shards * n_loc
    req = vertex_histogram(pos, alive > 0, n_pad)
    c_by_home, req_entries, req_bytes = route_counts(
        req, axis=AXIS, shard_id=shard_id, n_loc=n_loc, shards=shards,
        by_source=True)
    c = c_by_home.reshape(-1)               # [P*n_loc], row = home*n_loc + v
    req_entries = jax.lax.psum(req_entries, AXIS)
    req_bytes = jax.lax.psum(req_bytes, AXIS)
    return c[None], req[None], req_entries, req_bytes


def _p1_sample(bperm, dg, c, key, *, eps: float, n_loc: int, shards: int,
               layout):
    """Phase-1 program 2 (sample): the owner draws, independently for every
    (home, vertex) row, the fused Binomial(eps) termination + conditional-
    binomial edge split through the shared degree-bucketed sampler (a
    dangling row terminates whole). Pure per-shard compute. Returns the
    reply cells [P, total_rows + total_edges] (home-major: per home, the
    reset count of every bucket row, then the count on every bucket
    slot), the advanced key, the assignment key, per-bucket occupancy,
    and the conservation residual.

    RNG contract: every draw is a pure counter-based function of the
    per-round key words, rid = owner*n_pad + home*n_loc + v (globally
    unique per row), and the slot index — independent of bucket order,
    so bucketed/unbucketed layouts are bit-identical.
    """
    bperm, dg, c, key = bperm[0], dg[0], c[0], key[0]
    shard_id = jax.lax.axis_index(AXIS)
    n_pad = shards * n_loc
    key, k_sample, k_perm = jax.random.split(key, 3)

    # tile the local bucket permutation across homes, bucket-major: bucket
    # b's tiled rows are every home's bucket-b rows, offset by home*n_loc
    # (-1 padding slots preserved). Matches layout.tile(shards).
    offs = jnp.arange(shards, dtype=jnp.int32)[:, None] * n_loc
    parts = []
    for start, cap in zip(layout.row_starts, layout.caps):
        pb = bperm[start:start + cap]
        parts.append(jnp.where(pb[None, :] < 0, -1,
                               offs + pb[None, :]).reshape(-1))
    perm_t = jnp.concatenate(parts)
    layout_t = layout.tile(shards)

    deg_row = jnp.tile(dg, shards)
    rid = shard_id * n_pad + jnp.arange(n_pad, dtype=jnp.int32)
    samples, occ, residual = sample_buckets(
        c, deg_row, rid, key_words(k_sample), perm_t, layout_t, eps=eps)
    blocks = [T.reshape(shards, cap, w + 1)
              for (_, T), cap, w in zip(samples, layout.caps, layout.widths)]
    cells = jnp.concatenate(
        [b[:, :, 0] for b in blocks]
        + [b[:, :, 1:].transpose(0, 2, 1).reshape(shards, -1)
           for b in blocks], axis=1)
    occ = jax.lax.psum(occ, AXIS)
    residual = jax.lax.psum(residual, AXIS)
    return cells.reshape(1, -1), key[None], k_perm[None], occ, residual


def _p1_assign(bperm, bnbr, hist, cells, pos, alive, traj, k_perm, t, *,
               n_loc: int, shards: int, layout, rep_cap: int,
               S_loc_pad: int):
    """Phase-1 program 3 (reply + assign). Each home sorts its live coupons
    by (vertex, random priority), so vertex v's coupons hold positions
    [base[v], base[v] + c[v]) of that order, base the exclusive cumsum of
    the home's per-vertex counts. The owner replies with each nonzero
    cell's (vertex, destination, offset within its row); the cell covers
    positions [base[v] + offset, + its count) — a row's cells add up to
    c[v] (residual 0), so the cells tile the positions exactly and every
    live coupon gets one outcome. A multiset of iid outcomes dealt out in
    uniform-random order IS an iid draw per coupon."""
    bperm, bnbr, hist, cells, pos, alive, traj, k_perm = (
        bperm[0], bnbr[0], hist[0], cells[0], pos[0], alive[0], traj[0],
        k_perm[0])
    shard_id = jax.lax.axis_index(AXIS)
    n_pad = shards * n_loc
    i32 = jnp.int32
    cells = cells.reshape(shards, -1)                    # [home, cell]
    off = _cell_offsets(cells, layout)
    dst = jnp.concatenate([jnp.full((layout.total_rows,), -2, i32), bnbr])
    base = jnp.cumsum(hist) - hist                       # [n_pad]

    # the own home's cells: no wire, per-row offsets broadcast per slot
    own_base = jnp.where(bperm >= 0,
                         base[shard_id * n_loc + jnp.maximum(bperm, 0)], 0)
    starts = [off[shard_id] + _row_cells(own_base, layout)]
    dsts, oks = [dst], [cells[shard_id] > 0]
    overflow = rep_entries = rep_bytes = jnp.int32(0)
    if shards > 1:
        # ---- reply: nonzero (vertex, destination, offset) cells home ----
        home = jnp.repeat(jnp.arange(shards, dtype=i32), cells.shape[1])
        remote = (cells.reshape(-1) > 0) & (home != shard_id)
        vid = shard_id * n_loc + _row_cells(bperm, layout)
        sendable, flat_idx = lane_slots(home, remote, shards, rep_cap)
        lanes = [pack_lanes(flat_idx, x, sendable, shards, rep_cap, fill)
                 for x, fill in ((jnp.tile(vid, shards), -1),
                                 (jnp.tile(dst, shards), 0),
                                 (off.reshape(-1), 0))]
        r_vid, r_dst, r_off = exchange_stacked(lanes, AXIS, shards, rep_cap)
        got = r_vid >= 0
        starts.append(base[jnp.where(got, r_vid, 0)] + r_off)
        dsts.append(r_dst)
        oks.append(got)
        # rep_cap = min(n_loc + slots, S_loc_pad) bounds the distinct
        # cells one home can receive, so this stays 0; psum'd into
        # dropped as a tripwire
        overflow = jnp.sum(remote & ~sendable)
        rep_entries = jnp.sum(lanes[0] >= 0)
        rep_bytes = rep_entries * entry_nbytes(*lanes)

    # ---- deal: each position takes the cell whose range holds it ----
    ok = jnp.concatenate(oks)
    at = jnp.full((S_loc_pad,), -1, i32).at[
        jnp.where(ok, jnp.concatenate(starts), S_loc_pad)].set(
            jnp.concatenate(dsts), mode="drop")
    first = jax.lax.cummax(
        jnp.where(at != -1, jnp.arange(S_loc_pad, dtype=i32), 0), axis=0)
    elig = alive > 0
    order = _group_order(jnp.where(elig, pos, n_pad), k_perm)
    out = jnp.zeros((S_loc_pad,), i32).at[order].set(
        at[first], unique_indices=True)   # -2 = reset, >=0 = destination
    survive = elig & (out >= 0)
    new_pos = jnp.where(survive, out, pos)  # dead coupons keep final vertex
    new_alive = survive.astype(i32)
    traj = jax.lax.dynamic_update_slice(
        traj, jnp.where(survive, out, -1).astype(i32)[:, None],
        (jnp.int32(0), t))

    pending = jax.lax.psum(jnp.sum(survive), AXIS)
    overflow = jax.lax.psum(overflow, AXIS)
    rep_entries = jax.lax.psum(rep_entries, AXIS)
    rep_bytes = jax.lax.psum(rep_bytes, AXIS)
    return (new_pos[None], new_alive[None], traj[None],
            pending, overflow, rep_entries, rep_bytes)


# The step makers are memoized: a fresh jitted closure per engine call
# would recompile every stage program on every invocation (seconds per
# program on CPU), while equal (mesh, static-config) arguments produce
# byte-identical programs. jax interns Mesh objects, so repeat calls over
# the same devices hit the cache even when the caller rebuilds the mesh.
@lru_cache(maxsize=64)
def _make_p1_steps(mesh: Mesh, *, eps: float, n_loc: int, shards: int,
                   rep_cap: int, S_loc_pad: int, layout):
    """Returns (request, sample, assign): the three jitted programs of one
    Phase-1 round, named in a device trace `jit_p1_request`,
    `jit_p1_sample` and `jit_p1_assign`."""
    req_sh = shard_map(
        partial(_p1_request, n_loc=n_loc, shards=shards),
        mesh, in_specs=(P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(), P()))
    samp_sh = shard_map(
        partial(_p1_sample, eps=eps, n_loc=n_loc, shards=shards,
                layout=layout),
        mesh, in_specs=(P(AXIS),) * 4,
        out_specs=(P(AXIS),) * 3 + (P(),) * 2)
    asn_sh = shard_map(
        partial(_p1_assign, n_loc=n_loc, shards=shards, layout=layout,
                rep_cap=rep_cap, S_loc_pad=S_loc_pad),
        mesh, in_specs=(P(AXIS),) * 8 + (P(),),
        out_specs=(P(AXIS),) * 3 + (P(),) * 4)

    @jax.jit
    def p1_request(pos, alive):
        return req_sh(pos, alive)

    @jax.jit
    def p1_sample(bperm, dg, c, key):
        return samp_sh(bperm, dg, c, key)

    @jax.jit
    def p1_assign(bperm, bnbr, hist, cells, pos, alive, traj, k_perm, t):
        return asn_sh(bperm, bnbr, hist, cells, pos, alive, traj, k_perm, t)

    return p1_request, p1_sample, p1_assign


# ---------------------------------------------------------------------------
# Phase 2: count-aggregated coupon stitching
# ---------------------------------------------------------------------------

def _p2_index_local(dest, *, n_loc: int, shards: int):
    """The stitch's sort index, fixed for all of Phase 2: each slot's key,
    its coupon's final vertex (a padding slot, dest -1, is keyed n_pad, so
    it sorts past every vertex and counts for none), and the shard's segment
    ends over its own slots, ends[v] = the slots keyed below v."""
    dest = dest[0]
    n_pad = shards * n_loc
    dkey = jnp.where(dest >= 0, dest, n_pad)
    ends = jnp.searchsorted(jnp.sort(dkey),
                            jnp.arange(n_pad + 1, dtype=jnp.int32))
    return dkey[None], ends.astype(jnp.int32)[None]


@lru_cache(maxsize=64)
def _make_p2_index(mesh: Mesh, *, n_loc: int, shards: int):
    fn = partial(_p2_index_local, n_loc=n_loc, shards=shards)
    sharded = shard_map(fn, mesh, in_specs=(P(AXIS),),
                        out_specs=(P(AXIS), P(AXIS)))

    @jax.jit
    def p2_index(dest):
        return sharded(dest)

    return p2_index


def _p2_local(walks, next_c, used, tail_cnt, dkey, ends, cterm, psize,
              pstart, *, n_loc: int, shards: int, S_loc_pad: int):
    """One stitch superstep. Long walks are anonymous, so the state is a
    per-owned-vertex count: allocate the next unused coupons of each
    vertex's pool to the walks waiting there (natural-order consumption —
    distributionally identical to uniform-without-replacement because
    coupons are iid), retire walks whose coupon recorded an eps-reset,
    route the movers as per-destination counts, and bank pool-exhausted
    walks in `tail_cnt` for the naive fallback. The movers are summed per
    destination by a sort on the slots' fixed keys (`_p2_index_local`),
    not a scatter-add."""
    (walks, next_c, used, tail_cnt, dkey, ends, cterm, psize, pstart) = (
        walks[0], next_c[0], used[0], tail_cnt[0], dkey[0], ends[0],
        cterm[0], psize[0], pstart[0])
    shard_id = jax.lax.axis_index(AXIS)

    a = jnp.minimum(walks, psize - next_c)        # coupons allocatable now
    exh = walks - a                               # pool empty: naive tail
    # this round's used slots: vertex v's [pstart + next_c, + a), marked
    # at both ends per vertex and summed along the slots (the ranges are
    # disjoint), not looked up per slot
    lo = pstart + next_c
    edges = (jnp.zeros((S_loc_pad + 1,), jnp.int32)
             .at[lo].add(1).at[lo + a].add(-1))
    alloc = jnp.cumsum(edges[:S_loc_pad]) > 0
    used = jnp.maximum(used, alloc.astype(jnp.int32))
    next_c = next_c + a
    term_now = alloc & (cterm > 0)      # coupon's eps-reset fired: walk done
    go = alloc & (cterm == 0)           # walk continues at coupon's dest
    dcnt = sorted_segment_sums(dkey, ends, go.astype(jnp.int32))
    arrivals, sent_entries, sent_bytes = route_counts(
        dcnt, axis=AXIS, shard_id=shard_id, n_loc=n_loc, shards=shards)
    tail_cnt = tail_cnt + exh

    stitched = jax.lax.psum(jnp.sum(a), AXIS)
    terminated = jax.lax.psum(jnp.sum(term_now), AXIS)
    exhausted = jax.lax.psum(jnp.sum(exh), AXIS)
    active = jax.lax.psum(jnp.sum(arrivals), AXIS)
    entries = jax.lax.psum(sent_entries, AXIS)
    nbytes = jax.lax.psum(sent_bytes, AXIS)
    return (arrivals[None], next_c[None], used[None], tail_cnt[None],
            active, stitched, terminated, exhausted, entries, nbytes)


@lru_cache(maxsize=64)
def _make_p2_step(mesh: Mesh, *, n_loc: int, shards: int, S_loc_pad: int):
    fn = partial(_p2_local, n_loc=n_loc, shards=shards, S_loc_pad=S_loc_pad)
    sharded = shard_map(fn, mesh,
                        in_specs=(P(AXIS),) * 9,
                        out_specs=(P(AXIS),) * 4 + (P(),) * 6)

    @jax.jit
    def p2_stitch(walks, next_c, used, tail_cnt, dkey, ends, cterm, psize,
                  pstart):
        return sharded(walks, next_c, used, tail_cnt, dkey, ends, cterm,
                       psize, pstart)

    return p2_stitch


# ---------------------------------------------------------------------------
# Phase 3: one aggregated counting round over the trajectory table
# ---------------------------------------------------------------------------

def _p3_local(traj, used, zeta, *, n_loc: int, shards: int):
    """Histogram the used coupons' recorded moves and deliver the counts
    to the owner shards in ONE `route_counts` exchange. The histogram
    runs one step column at a time: a histogram of the whole table at
    once needs ~4 GB of scratch on the TPU at 7.8M coupons."""
    traj, used, zeta = traj[0], used[0], zeta[0]
    shard_id = jax.lax.axis_index(AXIS)
    n_pad = shards * n_loc

    def add_step(t, part):
        col = jax.lax.dynamic_index_in_dim(traj, t, axis=1, keepdims=False)
        return part + vertex_histogram(col, (used > 0) & (col >= 0), n_pad)

    part = jax.lax.fori_loop(0, traj.shape[1], add_step,
                             jnp.zeros((n_pad,), jnp.int32))
    arrivals, sent_entries, sent_bytes = route_counts(
        part, axis=AXIS, shard_id=shard_id, n_loc=n_loc, shards=shards)
    zeta = zeta + arrivals
    entries = jax.lax.psum(sent_entries, AXIS)
    nbytes = jax.lax.psum(sent_bytes, AXIS)
    return zeta[None], entries, nbytes


@lru_cache(maxsize=64)
def _make_p3_step(mesh: Mesh, *, n_loc: int, shards: int):
    fn = partial(_p3_local, n_loc=n_loc, shards=shards)
    sharded = shard_map(fn, mesh, in_specs=(P(AXIS),) * 3,
                        out_specs=(P(AXIS), P(), P()))

    @jax.jit
    def p3_count(traj, used, zeta):
        return sharded(traj, used, zeta)

    return p3_count


@lru_cache(maxsize=64)
def _make_tail_step(mesh: Mesh, eps: float, n_loc: int, shards: int,
                    route_cap: int):
    """The naive tail: the Algorithm-1 superstep, under its own name in a
    device trace (`jit_tail_step`)."""
    step = _make_superstep(mesh, eps, n_loc, shards, route_cap, 0)

    @jax.jit
    def tail_step(rp, ci, dg, state):
        return step(rp, ci, dg, state)

    return tail_step


def _tail_sizes(walks: int, shards: int, cap2: Optional[int],
                route_cap2: Optional[int]):
    """(walk buffer per shard, lanes per shard pair) of a naive tail that
    holds `walks` walks: unless given, the buffer is their count rounded
    up to a power of two, at least 1,024 — room for every tail walk on
    one shard, so the merge never drops one — and the lanes follow the
    documented rule route_cap >= ceil(W/P) for that many walks."""
    if cap2 is None:
        cap2 = max(1024, 1 << max(int(walks) - 1, 0).bit_length())
    return int(cap2), _lane_cap(route_cap2, cap2, shards)


# ---------------------------------------------------------------------------
# main driver
# ---------------------------------------------------------------------------

def _lane_cap(requested: Optional[int], load: int, shards: int,
              floor: int = 64) -> int:
    """Single home of the documented lane sizing rule `route_cap >= W/P`.

    With W items resident and P shards, ceil(W/P) slots per (src, dst)
    lane guarantee a full buffer can drain in P rounds even when every
    item targets one shard; floor division under-sizes the lane whenever
    W % P != 0. Defaults are computed with ceil division and the rule is
    asserted for explicit overrides too (an undersized lane only costs
    waiting latency, but it breaks the documented sizing contract)."""
    need = -(-max(int(load), 0) // shards)          # ceil(W / P)
    cap = max(need, floor) if requested is None else int(requested)
    assert cap >= need, (
        f"lane cap {cap} violates route_cap >= ceil(W/P) = {need} "
        f"(W={load}, P={shards})")
    return cap


@dataclasses.dataclass(frozen=True)
class ThreePhasePlan:
    """Every static size the 3-phase driver derives from (graph, shards,
    pool, K) — extracted so the CONGEST auditor can rebuild the EXACT
    step programs (the step makers are lru_cache-memoized on these values,
    so matching statics means the auditor traces the very objects the
    engine runs, not lookalikes)."""
    sg: object                 # distributed.ShardedGraph
    n_loc: int
    md: int
    S_loc_pad: int
    S_total: int
    rep_cap: int               # phase-1 reply lanes per shard pair
    route_cap2: Optional[int]  # naive-tail lanes per shard pair, or None:
    cap2: Optional[int]        # naive-tail walk buffer per shard, or None:
                               # both sized from the tail's walks
    pool_pad: np.ndarray
    psize_sh: np.ndarray
    pstart_sh: np.ndarray
    layout: object             # aggregate_sampler.BucketLayout
    bperm_np: np.ndarray
    bnbr_np: np.ndarray        # [P, layout.total_edges] slot destinations


def plan_three_phase(graph: CSRGraph, shards: int, pool_np: np.ndarray,
                     K: int, *, route_cap2: Optional[int] = None,
                     cap2: Optional[int] = None,
                     bucketed: bool = True) -> ThreePhasePlan:
    """Single home of the 3-phase static sizing rules (see ThreePhasePlan)."""
    n = graph.n
    sg = shard_graph(graph, shards)
    n_loc = sg.n_loc
    md = max(int(np.asarray(sg.out_deg).max()), 1)

    # coupon pool layout: contiguous per shard, padded to S_loc_pad
    pool_pad = np.zeros(sg.n_pad, dtype=np.int64)
    pool_pad[:n] = pool_np
    psize_sh = pool_pad.reshape(shards, n_loc)
    pstart_sh = np.zeros_like(psize_sh)
    pstart_sh[:, 1:] = np.cumsum(psize_sh, axis=1)[:, :-1]
    S_loc = psize_sh.sum(axis=1)
    S_loc_pad = max(int(S_loc.max()), 1)
    S_total = int(pool_np.sum())
    # coupon slot ids, and the assign's keys: a slot's position in its
    # home's (vertex, priority) order, below S_loc_pad
    if shards * S_loc_pad >= 2 ** 31:
        raise ValueError("coupon pool too large for int32 ids")

    deg_np = np.ascontiguousarray(
        np.asarray(sg.out_deg, np.int32).reshape(shards, n_loc))
    layout, bperm_np = build_layout_sharded(deg_np, md, bucketed=bucketed)
    # an owner's reply cells: a reset cell per bucket row and a cell per
    # bucket slot, for every home
    if shards * (layout.total_rows + layout.total_edges) >= 2 ** 31:
        raise ValueError("phase-1 reply cells overflow int32 indices")
    bnbr_np = bucketize_csr(graph.row_ptr, graph.col_idx, deg_np, bperm_np,
                            layout)
    # Phase-1 reply lanes: a home can receive at most one cell per owned
    # vertex's reset and per bucket slot, and at most one per coupon
    rep_cap = min(n_loc + layout.total_edges, S_loc_pad)
    return ThreePhasePlan(sg=sg, n_loc=n_loc, md=md, S_loc_pad=S_loc_pad,
                          S_total=S_total, rep_cap=rep_cap,
                          route_cap2=route_cap2, cap2=cap2,
                          pool_pad=pool_pad, psize_sh=psize_sh,
                          pstart_sh=pstart_sh, layout=layout,
                          bperm_np=bperm_np, bnbr_np=bnbr_np)


def _three_phase_layouts(n: int, pool_np: np.ndarray, cap2: Optional[int]):
    """Elastic layout schema per stage — shared by the phase-machine and
    the CONGEST auditor's schema lint. Declared per stage so snapshots are
    mesh-size-agnostic: a resume onto a different device count re-homes
    every buffer through `checkpoint.relayout_staged_flat` (coupon slots
    re-placed via the pool bijection, vertex shards re-split, walk lanes
    re-bucketed, per-shard keys re-derived). Slot/vertex/walk/replicated
    buffers re-layout bit-exactly; per-shard `key` streams are re-derived,
    so a mid-phase-1 (or mid-tail, with tail walks live) elastic resume is
    statistically — not bit — identical."""
    _slot = partial(LayoutSpec, kind="slot", n=n, pool=pool_np)
    _vert = LayoutSpec(kind="vertex", n=n)
    _rep = LayoutSpec(kind="replicated")
    return dict(
        phase1=dict(pos=_slot(fill=-1), alive=_slot(fill=0),
                    traj=_slot(fill=-1), key=LayoutSpec(kind="key")),
        phase2=dict(walks=_vert, next_c=_vert, used=_slot(fill=0),
                    tail_cnt=_vert, dest=_slot(fill=-1),
                    cterm=_slot(fill=1), traj=_slot(fill=-1), zeta=_vert),
        phase3=dict(traj=_slot(fill=-1), used=_slot(fill=0), zeta=_vert,
                    tail_cnt=_vert),
        tail=dict(pos=LayoutSpec(kind="walk", n=n, cap=cap2, fill=-1),
                  zeta=_vert, key=LayoutSpec(kind="key"),
                  round=_rep, dropped=_rep, waited=_rep),
    )


def short_walk_length(n: int) -> int:
    """Algorithm 2's lambda = ceil(sqrt(log n)): the steps of a coupon."""
    return max(1, int(math.ceil(math.sqrt(math.log(max(n, 2))))))


@dataclasses.dataclass
class ImprovedDistResult:
    zeta: jnp.ndarray            # [n] global visit counts
    pi: jnp.ndarray
    shards: int
    walks_per_node: int
    eps: float
    lam: int
    eta: int                     # Lemma 2's d(v)*eta, or 0: pools from
                                 # the expected demand
    ell: int
    rounds: int                  # total supersteps across all phases
    phase1_rounds: int
    report_rounds: int           # 0: the report phase is gone — coupons
                                 # stay home, so (dest, term) is local
    phase2_rounds: int           # stitch supersteps
    phase3_rounds: int           # aggregated counting exchanges (== 1)
    tail_rounds: int             # naive-fallback supersteps
    stitch_iterations: int
    exhausted_walks: int
    terminated_by_coupon: int
    tail_walks: int
    coupons_created: int
    coupons_used: int
    dropped: int
    waited: int
    a2a_bytes_total: int
    a2a_bytes_by_phase: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    a2a_entries_by_site: Dict[str, int] = dataclasses.field(
        default_factory=dict)   # routed lane entries per exchange site
                                # (phase1_req/phase1_rep/phase2/phase3/tail)
    phase2_records: List[dict] = dataclasses.field(default_factory=list)
    report: Optional[CongestReport] = None
    total_visits: int = 0
    restarts: int = 0            # supervisor recoveries (fault injection)
    checkpoints_written: int = 0
    p1_occupancy: tuple = ()     # per-bucket rows-with-coupons, summed over
                                 # rounds and shards (len = #buckets)
    residual: int = 0            # sampler conservation leak — must stay 0
    unfinished: int = 0          # walks still out when a stage reached
                                 # max_rounds (0 for a complete run)


def distributed_improved_pagerank(
    graph: CSRGraph,
    eps: float,
    walks_per_node: Optional[int] = None,
    key: Optional[jnp.ndarray] = None,
    *,
    mesh: Optional[Mesh] = None,
    lam: Optional[int] = None,
    eta: Optional[int] = None,
    cap2: Optional[int] = None,
    route_cap2: Optional[int] = None,
    max_rounds: int = 100_000,
    bandwidth_bits: Optional[int] = None,
    use_pallas: bool = False,
    checkpoint_dir: Optional[str] = None,
    fail_at: Optional[Sequence[int]] = None,
    checkpoint_every: int = 10,
    max_restarts: int = 16,
    resume: bool = False,
    bucketed: bool = True,
) -> ImprovedDistResult:
    """Run Algorithm 2 across all devices of `mesh` (default: all devices).

    Coupon pools cover the expected demand
    (`improved_pagerank.demand_pool_sizes`); an explicit `eta` gives
    Lemma 2's d(v)*eta instead. `cap2`/`route_cap2` size only the
    naive-tail buffers (by default from the walks it holds; Phases 1-3
    are count-aggregated and size themselves). `max_rounds` caps the
    rounds of each stage: one that reaches it stops, and the result
    counts the walks still out (`unfinished`). With `checkpoint_dir`
    and/or `fail_at` set, the phase-machine runs under the
    checkpoint-restart supervisor (see `_run_three_phase`).
    `bucketed=False` keeps the single-bucket max_deg-wide Phase-1 sampler
    layout; the draws are layout-independent.

    `use_pallas` does nothing and a true value is refused: the engine has
    one implementation per stage. The keyword stays only until the
    benchmark's configurations and drivers stop passing `use_pallas`."""
    if use_pallas:
        raise ValueError("use_pallas=True: the Pallas kernels were removed; "
                         "every stage runs its XLA implementation")
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (AXIS,))
    key = key if key is not None else jax.random.PRNGKey(0)
    n = graph.n
    K = walks_per_node or walks_per_node_for(n, eps)
    if lam is None:
        lam = short_walk_length(n)
    ell = max(lam + 1, int(math.ceil(math.log(max(n, 2)) / eps)))
    if eta is None:
        pools = partial(demand_pool_sizes, graph, eps, K, lam)
    else:
        pools = lambda: coupon_pool_sizes(graph, eps, K, lam, eta=eta)[1]
    return _run_three_phase(
        graph, eps, K, key, mesh, pools=pools, eta=int(eta or 0),
        lam=int(lam), ell=int(ell), cap2=cap2, route_cap2=route_cap2,
        max_rounds=max_rounds, bandwidth_bits=bandwidth_bits,
        checkpoint_dir=checkpoint_dir,
        fail_at=fail_at, checkpoint_every=checkpoint_every,
        max_restarts=max_restarts, resume=resume, bucketed=bucketed)


def _run_three_phase(
    graph: CSRGraph,
    eps: float,
    K: int,
    key: jnp.ndarray,
    mesh: Mesh,
    *,
    pools: Callable[[], np.ndarray],
    eta: int,
    lam: int,
    ell: int,
    cap2: Optional[int] = None,
    route_cap2: Optional[int] = None,
    max_rounds: int = 100_000,
    bandwidth_bits: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    fail_at: Optional[Sequence[int]] = None,
    checkpoint_every: int = 10,
    max_restarts: int = 16,
    resume: bool = False,
    bucketed: bool = True,
    name: str = "improved",
    result_cls: type = ImprovedDistResult,
    **extra_fields,
):
    """Budget-policy-agnostic 3-phase stitching driver, structured as a
    checkpointable phase-machine.

    The whole engine — Phase-1 count-aggregated short walks, Phase-2
    count-aggregated stitching, the Phase-3 one-shot counting exchange,
    the naive tail, and the host-float64 estimator — only ever sees the
    per-node pool-size vector `pools()` returns, never the policy that
    produced it. `distributed_improved_pagerank` (expected demand, or
    Lemma 2's d(v)*eta) and
    `distributed_directed.distributed_directed_pagerank` (Section 5,
    uniform budgets in the LOCAL model) are thin frontends over this core.
    `result_cls`/`extra_fields` let a frontend return a telemetry subclass
    of ImprovedDistResult.

    A job is one `<name>.job` span of `runtime.tracing` (counts `rounds`,
    `phase1_rounds`, `phase2_rounds`, `tail_rounds`, `tail_walks` and
    `coupons`): `<name>.build` (pools, plan, placement, device puts;
    count `stitch_sort_entries`, the slots a stitch round sorts),
    one `round.<stage>` per round — a Phase-1 round holds `p1.request`,
    `p1.sample`, `p1.assign` (the three dispatches) and `p1.sync` — then
    `<name>.finish` (the estimator).

    Each phase is a `runtime.Stage` over a `StagedState` whose `arrays`
    hold the phase's device buffers and whose `host` dict holds the
    accumulators (round counters, wire volumes, traces, Phase-2 records).
    Without `checkpoint_dir`/`fail_at` the composed `StageSchedule` is
    stepped in a plain loop (no snapshot overhead); with either set, the
    `runtime.Supervisor` drives it with periodic stage-tagged checkpoints
    and (optionally) injected failures at the listed *global* rounds —
    round indices span all phases, so failures can land at phase
    boundaries or mid-phase. Recovery restores the latest snapshot and
    replays the identical trajectory: `zeta`/`pi` and all telemetry are
    bit-identical to an unfailed run. `resume=True` cold-starts from the
    latest snapshot in `checkpoint_dir` (a previously killed run).

    Elastic resume: every stage declares a `checkpoint.LayoutSpec` schema
    for its buffers, so the snapshot is mesh-size-agnostic — `resume=True`
    with a `mesh` of a DIFFERENT device count re-homes coupon slots,
    vertex shards, and walk lanes onto the new mesh and continues.
    Phases 2/3 are RNG-free, so a mid-Phase-2 resume re-layouts
    bit-exactly; only live per-shard key streams (mid-Phase-1, or a tail
    with surviving walks) are re-derived and therefore statistical,
    gated by the conformance tolerance.
    """
    shards = int(mesh.devices.size)
    n = graph.n
    with tracing.span(f"{name}.job"):
        with tracing.span(f"{name}.build"):
            pool_np = pools()
            ms, schedule, _put, plan = _build_three_phase(
                graph, eps, K, key, mesh, pool_np=pool_np, lam=lam,
                cap2=cap2, route_cap2=route_cap2, max_rounds=max_rounds,
                bucketed=bucketed)
            # the slots the stitch sorts each round
            tracing.count("stitch_sort_entries", plan.S_loc_pad)

        # global rounds sum over the four stages, each bounded by
        # max_rounds
        ms, restarts, checkpoints_written = run_staged(
            schedule, ms, _put, checkpoint_dir=checkpoint_dir,
            fail_at=fail_at, checkpoint_every=checkpoint_every,
            max_restarts=max_restarts, resume=resume,
            max_rounds=(len(schedule.stages) * max_rounds
                        + len(schedule.stages)),
            tmp_prefix="pr3p_ckpt_")
        h = ms.host
        rounds = (h["phase1_rounds"] + h["report_rounds"]
                  + h["phase2_rounds"] + h["phase3_rounds"]
                  + h["tail_rounds"])
        for k, v in dict(rounds=rounds, phase1_rounds=h["phase1_rounds"],
                         phase2_rounds=h["phase2_rounds"],
                         tail_rounds=h["tail_rounds"],
                         tail_walks=h["tail_walks"],
                         coupons=plan.S_total).items():
            tracing.count(k, v)

        with tracing.span(f"{name}.finish"):
            # ------------- estimator: host float64 scaling -------------
            zeta = ms.arrays["zeta"].reshape(-1)[:n]
            pi = pagerank_from_visits(zeta, n, K, eps)
            total_visits = int(np.asarray(zeta, dtype=np.int64).sum())
            wire = h["wire"]
            traces = [RoundTrace(active_walks=a, messages=m,
                                 max_edge_count=1, total_count=m)
                      for a, m in h["traces"]]
            report = CongestReport(traces=traces, n=n,
                                   bandwidth_bits=bandwidth_bits
                                   or default_bandwidth(n))
            return result_cls(
                zeta=zeta, pi=pi, shards=shards, walks_per_node=K, eps=eps,
                lam=int(lam), eta=int(eta), ell=int(ell), rounds=rounds,
                phase1_rounds=h["phase1_rounds"],
                report_rounds=h["report_rounds"],
                phase2_rounds=h["phase2_rounds"],
                phase3_rounds=h["phase3_rounds"],
                tail_rounds=h["tail_rounds"],
                stitch_iterations=h["phase2_rounds"],
                exhausted_walks=h["exhausted"],
                terminated_by_coupon=h["terminated"],
                tail_walks=h["tail_walks"], coupons_created=plan.S_total,
                coupons_used=h["coupons_used"], dropped=h["dropped"],
                waited=h["waited"], a2a_bytes_total=sum(wire.values()),
                a2a_bytes_by_phase=wire,
                a2a_entries_by_site=dict(h["wire_entries"]),
                phase2_records=h["phase2_records"], report=report,
                total_visits=total_visits, restarts=restarts,
                checkpoints_written=checkpoints_written,
                p1_occupancy=tuple(h["p1_occupancy"]),
                residual=int(h["residual"]), unfinished=h["unfinished"],
                **extra_fields)


_compiled: set = set()


def _compile_concurrently(programs) -> None:
    """Compile jitted programs for their arguments' shapes on threads of
    their own: the compiler then works on them side by side (at 7.8M
    coupons the stage programs took about three minutes to compile for
    a TPU v5e one after another, 75 s side by side), and each later
    call with those shapes finds its program compiled. A program
    compiled before is left alone."""
    def sig(args):
        return tuple((a.shape, str(a.dtype)) for a in args)

    todo = [(f, args) for f, args in programs
            if (f, sig(args)) not in _compiled]
    if not todo:
        return
    with ThreadPoolExecutor(len(todo)) as pool:
        list(pool.map(lambda fa: fa[0].lower(*fa[1]).compile(), todo))
    _compiled.update((f, sig(args)) for f, args in todo)


def _build_three_phase(graph, eps, K, key, mesh, *, pool_np, lam, cap2,
                       route_cap2, max_rounds, bucketed):
    """The phase-machine of one job: (initial state, schedule, the
    snapshot `put`, plan)."""
    shards = int(mesh.devices.size)
    n = graph.n
    # all static sizing comes from the shared plan (also what the CONGEST
    # auditor rebuilds — see ThreePhasePlan)
    plan = plan_three_phase(graph, shards, pool_np, K,
                            route_cap2=route_cap2, cap2=cap2,
                            bucketed=bucketed)
    sg, n_loc = plan.sg, plan.n_loc
    S_loc_pad, S_total = plan.S_loc_pad, plan.S_total
    pool_pad, psize_sh, pstart_sh = (plan.pool_pad, plan.psize_sh,
                                     plan.pstart_sh)
    spec, rep = NamedSharding(mesh, P(AXIS)), NamedSharding(mesh, P())
    sg_rp = jax.device_put(sg.row_ptr, spec)
    sg_ci = jax.device_put(sg.col_idx, spec)
    sg_dg = jax.device_put(sg.out_deg, spec)

    # ---- Phase-1 placement: slot s of shard p = p's s-th coupon, at its
    # source vertex; slots beyond S_loc[p] are padding (never allocated) --
    pos0 = np.full((shards, S_loc_pad), -1, dtype=np.int32)
    for p in range(shards):
        owned = pool_pad[p * n_loc:(p + 1) * n_loc]
        src = np.repeat(np.arange(p * n_loc, (p + 1) * n_loc,
                                  dtype=np.int32), owned)
        pos0[p, : len(src)] = src
    # ---- Phase-2 placement: K long walks per real vertex (counts) ----
    walks0_np = np.zeros((shards, n_loc), dtype=np.int32)
    zeta3_np = np.zeros((shards, n_loc), np.int32)
    for p in range(shards):
        lo = min(p * n_loc, n)
        hi = min((p + 1) * n_loc, n)
        walks0_np[p, : hi - lo] = K
        zeta3_np[p, : hi - lo] = K           # start visits of long walks

    key, k1, k_tail = jax.random.split(key, 3)
    k1_shards = jax.random.split(k1, shards)

    # ---- Phase-1 degree-bucketed sampler layout (static, memoized) ----
    layout = plan.layout
    bperm_j = jax.device_put(jnp.asarray(plan.bperm_np), spec)
    bnbr_j = jax.device_put(jnp.asarray(plan.bnbr_np), spec)

    # ---- jitted per-phase step functions (shared by fresh + resumed) ----
    p1_req, p1_samp, p1_asn = _make_p1_steps(
        mesh, eps=float(eps), n_loc=n_loc, shards=shards,
        rep_cap=plan.rep_cap, S_loc_pad=S_loc_pad, layout=layout)
    p2_idx = _make_p2_index(mesh, n_loc=n_loc, shards=shards)
    p2_step = _make_p2_step(mesh, n_loc=n_loc, shards=shards,
                            S_loc_pad=S_loc_pad)
    p3_step = _make_p3_step(mesh, n_loc=n_loc, shards=shards)
    psize_j = jax.device_put(jnp.asarray(psize_sh, dtype=jnp.int32), spec)
    pstart_j = jax.device_put(jnp.asarray(pstart_sh, dtype=jnp.int32), spec)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=spec)
    vert, slot = sds((shards, n_loc)), sds((shards, S_loc_pad))
    traj = sds((shards, S_loc_pad, lam))
    ends = sds((shards, shards * n_loc + 1))
    _compile_concurrently([
        (p1_req, (slot, slot)),
        (p1_samp, (bperm_j, sg_dg, sds((shards, shards * n_loc)),
                   sds((shards, 2), jnp.uint32))),
        (p1_asn, (bperm_j, bnbr_j, sds((shards, shards * n_loc)),
                  sds((shards, shards * (layout.total_rows
                                         + layout.total_edges))),
                  slot, slot, traj, sds((shards, 2), jnp.uint32),
                  jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))),
        (p2_idx, (slot,)),
        (p2_step, (vert, vert, slot, vert, slot, ends, slot, vert, vert)),
        (p3_step, (traj, slot, vert)),
    ])

    # The stitch's sort index is derived from `dest` once at Phase 2's
    # entry and never snapshotted: a per-shard index over n_pad does not
    # survive an elastic re-layout, so a resume into Phase 2 rebuilds it
    # from the restored `dest` at its first stitch.
    stitch_index = {}

    def _stitch_index(dest):
        if stitch_index.get("dest") is not dest:
            stitch_index.update(dest=dest, index=p2_idx(dest))
        return stitch_index["index"]

    # ---------------- stage step functions + host transitions ----------
    # Telemetry lives in the JSON-able `host` dict so a restored snapshot
    # rolls the accumulators back in lockstep with the device buffers.

    def _phase1(ms: StagedState):
        a = ms.arrays
        t = jax.device_put(np.int32(ms.host["phase1_rounds"]), rep)
        with tracing.span("p1.request"):
            c, hist, req_entries, req_bytes = p1_req(a["pos"], a["alive"])
        with tracing.span("p1.sample"):
            cells, key1, k_perm, occ, residual = p1_samp(
                bperm_j, sg_dg, c, a["key"])
        with tracing.span("p1.assign"):
            pos, alive, traj, pending, overflow, rep_entries, rep_bytes = \
                p1_asn(bperm_j, bnbr_j, hist, cells, a["pos"], a["alive"],
                       a["traj"], k_perm, t)
        a.update(pos=pos, alive=alive, traj=traj, key=key1)
        # one device sync for all the round's telemetry, not one per value
        with tracing.span("p1.sync"):
            (pending, overflow, req_e, req_b, rep_e, rep_b, occ_v,
             res) = jax.device_get((pending, overflow, req_entries,
                                    req_bytes, rep_entries, rep_bytes, occ,
                                    residual))
        h = ms.host
        h["phase1_rounds"] += 1
        h["dropped"] += int(overflow)
        h["wire"]["phase1"] += int(req_b) + int(rep_b)
        h["wire_entries"]["phase1_req"] += int(req_e)
        h["wire_entries"]["phase1_rep"] += int(rep_e)
        h["p1_occupancy"] = [int(x) + int(y)
                             for x, y in zip(h["p1_occupancy"], occ_v)]
        h["residual"] += int(res)
        h["traces"].append([int(pending), int(req_e) + int(rep_e)])
        # each coupon gets exactly lam step opportunities, one per round
        return ms, int(pending) == 0 or h["phase1_rounds"] >= lam

    def _after_phase1(ms: StagedState) -> StagedState:
        # Coupons never moved buffers, so their summaries are already
        # home-local: dest = final vertex, cterm = the reset fired.
        # The trajectory table rides along untouched for Phase 3.
        a = ms.arrays
        ms.arrays = dict(
            walks=jax.device_put(jnp.asarray(walks0_np), spec),
            next_c=jax.device_put(jnp.zeros((shards, n_loc), jnp.int32),
                                  spec),
            used=jax.device_put(jnp.zeros((shards, S_loc_pad), jnp.int32),
                                spec),
            tail_cnt=jax.device_put(jnp.zeros((shards, n_loc), jnp.int32),
                                    spec),
            dest=a["pos"], cterm=1 - a["alive"], traj=a["traj"],
            zeta=jax.device_put(jnp.asarray(zeta3_np), spec))
        _stitch_index(ms.arrays["dest"])
        return ms

    def _phase2(ms: StagedState):
        a = ms.arrays
        dkey, ends = _stitch_index(a["dest"])
        (walks, next_c, used, tail_cnt, active, stitched, terminated,
         exhausted, entries, nbytes) = p2_step(
            a["walks"], a["next_c"], a["used"], a["tail_cnt"], dkey, ends,
            a["cterm"], psize_j, pstart_j)
        a.update(walks=walks, next_c=next_c, used=used, tail_cnt=tail_cnt)
        # one device sync for all six telemetry scalars, not six
        active, stitched, terminated, exhausted, entries, nbytes = (
            int(x) for x in jax.device_get(
                (active, stitched, terminated, exhausted, entries, nbytes)))
        h = ms.host
        h["phase2_rounds"] += 1
        h["stitches"] += stitched
        h["terminated"] += terminated
        h["exhausted"] += exhausted
        h["wire"]["phase2"] += nbytes
        h["wire_entries"]["phase2"] += entries
        h["phase2_records"].append(dict(
            active=active, stitched=stitched,
            terminated=terminated, exhausted=exhausted))
        h["traces"].append([active, entries])
        if active and h["phase2_rounds"] >= max_rounds:
            h["unfinished"] += active    # cut: these walks end here
            return ms, True
        return ms, active == 0

    def _after_phase2(ms: StagedState) -> StagedState:
        a = ms.arrays
        ms.host["coupons_used"] = int(jnp.sum(a["used"]))
        stitch_index.clear()
        ms.arrays = dict(traj=a["traj"], used=a["used"], zeta=a["zeta"],
                         tail_cnt=a["tail_cnt"])
        return ms

    def _phase3(ms: StagedState):
        a = ms.arrays
        zeta, entries, nbytes = p3_step(a["traj"], a["used"], a["zeta"])
        a["zeta"] = zeta
        entries, nbytes = (int(x) for x in
                           jax.device_get((entries, nbytes)))
        h = ms.host
        h["phase3_rounds"] += 1
        h["wire"]["phase3"] += nbytes
        h["wire_entries"]["phase3"] += entries
        h["traces"].append([0, entries])
        return ms, True          # the whole count lands in ONE exchange

    def _after_phase3(ms: StagedState) -> StagedState:
        a = ms.arrays
        h = ms.host
        tail_np = np.asarray(a["tail_cnt"])
        h["tail_walks"] = int(tail_np.sum())
        h["tail_active"] = h["tail_walks"]
        buf, _ = _tail_sizes(h["tail_walks"], shards, cap2, route_cap2)
        pos_tail = np.full((shards, buf), -1, dtype=np.int32)
        for p in range(shards):
            vids = np.repeat(
                np.arange(p * n_loc, (p + 1) * n_loc, dtype=np.int32),
                tail_np[p])
            assert len(vids) <= buf, "cap2 too small for tail placement"
            pos_tail[p, : len(vids)] = vids
        ms.arrays = dict(
            pos=jax.device_put(jnp.asarray(pos_tail), spec),
            zeta=a["zeta"],
            key=jax.device_put(jax.random.split(k_tail, shards), spec),
            round=jnp.int32(0), dropped=jnp.int32(0), waited=jnp.int32(0))
        return ms

    def _tail(ms: StagedState):
        a = ms.arrays
        h = ms.host
        if h["tail_active"] and h["tail_rounds"] < max_rounds:
            _, lanes = _tail_sizes(h["tail_walks"], shards, cap2,
                                   route_cap2)
            tail_step = _make_tail_step(mesh, float(eps), n_loc, shards,
                                        lanes)
            tstate = DistState(pos=a["pos"], zeta=a["zeta"], key=a["key"],
                               round=a["round"], dropped=a["dropped"],
                               waited=a["waited"])
            tstate, active, entries, a2a = tail_step(sg_rp, sg_ci, sg_dg,
                                                     tstate)
            a.update(pos=tstate.pos, zeta=tstate.zeta, key=tstate.key,
                     round=tstate.round, dropped=tstate.dropped,
                     waited=tstate.waited)
            active, entries, a2a = (int(x) for x in
                                    jax.device_get((active, entries, a2a)))
            h["tail_rounds"] += 1
            h["wire"]["tail"] += a2a
            h["wire_entries"]["tail"] += entries
            h["traces"].append([active, entries])
            h["tail_active"] = active
            if active and h["tail_rounds"] < max_rounds:
                return ms, False
        h["unfinished"] += h["tail_active"]   # cut at max_rounds
        h["dropped"] += int(a["dropped"])
        h["waited"] += int(a["waited"])
        return ms, True

    schedule = StageSchedule([
        Stage("phase1", _phase1, on_done=_after_phase1),
        Stage("phase2", _phase2, on_done=_after_phase2),
        Stage("phase3", _phase3, on_done=_after_phase3),
        Stage("tail", _tail),
    ])

    traj0 = np.full((shards, S_loc_pad, lam), -1, dtype=np.int32)
    # ---- layout schema: how each stage's buffers sit on the mesh ------
    # (shared with the CONGEST auditor — see _three_phase_layouts)
    layouts = _three_phase_layouts(n, pool_np, cap2)
    ms = StagedState(
        stage=schedule.first_stage,
        arrays=dict(
            pos=jax.device_put(jnp.asarray(pos0), spec),
            alive=jax.device_put(jnp.asarray((pos0 >= 0).astype(np.int32)),
                                 spec),
            traj=jax.device_put(jnp.asarray(traj0), spec),
            key=jax.device_put(k1_shards, spec)),
        host=dict(phase1_rounds=0, report_rounds=0, phase2_rounds=0,
                  phase3_rounds=0, tail_rounds=0, dropped=0, waited=0,
                  stitches=0, terminated=0, exhausted=0, coupons_used=0,
                  tail_walks=0, tail_active=0, unfinished=0,
                  wire=dict(phase1=0, report=0, phase2=0, phase3=0, tail=0),
                  wire_entries=dict(phase1_req=0, phase1_rep=0, phase2=0,
                                    phase3=0, tail=0),
                  p1_occupancy=[0] * len(layout.caps), residual=0,
                  traces=[], phase2_records=[]),
        layouts=layouts, shards=shards)

    _scalar_keys = ("round", "dropped", "waited")

    def _put(name: str, arr: np.ndarray):
        if name in _scalar_keys:
            return jnp.asarray(arr)              # replicated scalars
        return jax.device_put(jnp.asarray(arr), spec)

    return ms, schedule, _put, plan


# ---------------------------------------------------------------------------
# CONGEST auditor spec
# ---------------------------------------------------------------------------

def three_phase_audit_spec(graph: CSRGraph, mesh: Mesh, *, eps: float,
                           K: int, pool_np: np.ndarray, lam: int,
                           engine: str = "improved",
                           bucketed: bool = True):
    """CONGEST-auditor spec for the 3-phase engines (improved + directed
    frontends): all seven stage programs rebuilt through the SAME memoized
    step makers with the SAME statics the engine would use (via
    `plan_three_phase`), each exchange's declared per-round wire budget,
    and the elastic layout schema.

    The tail stage is a walk-class exchange whose runtime lane cap scales
    with W/P; overflow there waits rather than widening the lane, so the
    auditor pins route_cap = cap = n_loc at trace time — any pinned cap
    yields a correct (and W-free) program to verify."""
    from repro.core.accounting import (EngineAuditSpec, ExchangeSite,
                                       StageProgram)
    shards = int(mesh.devices.size)
    n = graph.n
    plan = plan_three_phase(graph, shards, pool_np, K, bucketed=bucketed)
    n_loc, layout = plan.n_loc, plan.layout
    S_loc_pad, S_total = plan.S_loc_pad, plan.S_total
    rep_cap = plan.rep_cap

    p1_req, p1_samp, p1_asn = _make_p1_steps(
        mesh, eps=float(eps), n_loc=n_loc, shards=shards, rep_cap=rep_cap,
        S_loc_pad=S_loc_pad, layout=layout)
    p2_idx = _make_p2_index(mesh, n_loc=n_loc, shards=shards)
    p2_step = _make_p2_step(mesh, n_loc=n_loc, shards=shards,
                            S_loc_pad=S_loc_pad)
    p3_step = _make_p3_step(mesh, n_loc=n_loc, shards=shards)
    tail_cap = n_loc                       # auditor-pinned (walk-class)
    tail_step = _make_tail_step(mesh, float(eps), n_loc, shards, tail_cap)

    sds = jax.ShapeDtypeStruct
    i32, u32 = jnp.int32, jnp.uint32
    sg = plan.sg
    rp = sds(sg.row_ptr.shape, sg.row_ptr.dtype)
    ci = sds(sg.col_idx.shape, sg.col_idx.dtype)
    dg = sds(sg.out_deg.shape, sg.out_deg.dtype)
    pos = sds((shards, S_loc_pad), i32)
    alive = sds((shards, S_loc_pad), i32)
    traj = sds((shards, S_loc_pad, int(lam)), i32)
    key = sds((shards, 2), u32)
    bperm = sds(plan.bperm_np.shape, plan.bperm_np.dtype)
    bnbr = sds(plan.bnbr_np.shape, plan.bnbr_np.dtype)
    c = sds((shards, shards * n_loc), i32)
    hist = sds((shards, shards * n_loc), i32)
    cells = sds((shards, shards * (layout.total_rows + layout.total_edges)),
                i32)
    t = sds((), i32)
    vert = sds((shards, n_loc), i32)
    slot = sds((shards, S_loc_pad), i32)
    ends = sds((shards, shards * n_loc + 1), i32)
    tail_state = DistState(pos=sds((shards, tail_cap), i32), zeta=vert,
                           key=key, round=t, dropped=t, waited=t)

    count_budget = shards * n_loc          # Lemma-1 lanes: distinct vertices
    _count = dict(entry_nbytes=8, lane_entries=count_budget,
                  budget_entries=count_budget, wire_class="count",
                  budget_formula="P * n_loc distinct (vertex, count) pairs")
    rep_site = ExchangeSite(
        site="phase1_rep", entry_nbytes=12,
        lane_entries=shards * rep_cap,
        budget_entries=shards * (n_loc + layout.total_edges),
        budget_formula=("P * min(n_loc + slots, S_loc_pad) distinct "
                        "(vertex, destination, offset) cells <= "
                        "P * (n_loc + slots), slots = bucketed out-edges"),
        wire_class="count",
        note="stacked F=3 lanes (vertex, destination, offset in its row)")
    tail_site = ExchangeSite(
        site="tail", entry_nbytes=4, lane_entries=shards * tail_cap,
        budget_entries=shards * n_loc,
        budget_formula="P * n_loc lane slots (auditor-pinned cap = n_loc)",
        wire_class="walk",
        note="naive-fallback walk routing; overflow waits, never widens")

    progs = [
        StageProgram(stage="phase1", program="request", fn=p1_req,
                     example_args=(pos, alive),
                     sites=(ExchangeSite(site="phase1_req", **_count),),
                     count_bound=S_total),
        StageProgram(stage="phase1", program="sample", fn=p1_samp,
                     example_args=(bperm, dg, c, key), sites=(),
                     count_bound=S_total),
        # one shard replies to itself: no wire (see `_p1_assign`)
        StageProgram(stage="phase1", program="assign", fn=p1_asn,
                     example_args=(bperm, bnbr, hist, cells, pos, alive,
                                   traj, key, t),
                     sites=(rep_site,) if shards > 1 else (),
                     count_bound=S_total),
        StageProgram(stage="phase2", program="index", fn=p2_idx,
                     example_args=(slot,), sites=(), count_bound=S_total),
        StageProgram(stage="phase2", program="stitch", fn=p2_step,
                     example_args=(vert, vert, slot, vert, slot, ends, slot,
                                   vert, vert),
                     sites=(ExchangeSite(site="phase2", **_count),),
                     count_bound=n * K),
        StageProgram(stage="phase3", program="count", fn=p3_step,
                     example_args=(traj, slot, vert),
                     sites=(ExchangeSite(site="phase3", **_count),),
                     count_bound=S_total),
        StageProgram(stage="tail", program="step", fn=tail_step,
                     example_args=(rp, ci, dg, tail_state),
                     sites=(tail_site,), count_bound=n * K),
    ]
    return EngineAuditSpec(
        engine=engine, programs=progs,
        stage_arrays={
            "phase1": ("pos", "alive", "traj", "key"),
            "phase2": ("walks", "next_c", "used", "tail_cnt", "dest",
                       "cterm", "traj", "zeta"),
            "phase3": ("traj", "used", "zeta", "tail_cnt"),
            "tail": ("pos", "zeta", "key", "round", "dropped", "waited"),
        },
        layouts=_three_phase_layouts(n, pool_np, plan.cap2),
        meta=dict(shards=shards, n=graph.n, K=K, lam=int(lam),
                  md=plan.md, rep_cap=rep_cap, S_loc_pad=S_loc_pad,
                  S_total=S_total))


def audit_spec(graph: CSRGraph, mesh: Mesh, *, eps: float = 0.2,
               walks_per_node: int = 2, bucketed: bool = True):
    """Frontend of the 3-phase audit spec with the pools
    `distributed_improved_pagerank` uses by default (expected demand) —
    mirrors its sizing exactly."""
    K = walks_per_node
    lam = short_walk_length(graph.n)
    return three_phase_audit_spec(
        graph, mesh, eps=eps, K=K,
        pool_np=demand_pool_sizes(graph, eps, K, lam), lam=lam,
        engine="improved", bucketed=bucketed)

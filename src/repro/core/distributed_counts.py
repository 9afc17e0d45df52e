"""Count-aggregated distributed engine — Lemma 1 applied to our own wire.

The walk-array engine (distributed.py) routes every cross-shard walk as its
own int32 position: payload ∝ moving walks. The paper's core insight
(Lemma 1) says walks are anonymous — only *counts* per edge matter. This
engine keeps per-vertex coupon counts as shard state and exchanges
(dst_vertex, count) pairs, so the all_to_all payload is bounded by the
number of CUT EDGES with traffic this round — **independent of how many
walks run in parallel**.

Payload bound is static: lane capacity per (src,dst) shard pair =
|edges crossing that pair| (precomputed from the partition), so there is no
overflow path at all (the walk engine needs waiting/carry-over logic).

Per super-step, per shard:
  1. terminations  ~ Binomial(counts, eps)                (paper line 4-5)
  2. survivors split over out-edges via the binomial tree
     (exact Multinomial — same sampler as engine_counts)
  3. per-edge counts aggregated per destination *vertex* and exchanged with
     one all_to_all of (vertex, count) lanes               (Lemma 1 wire)
     — the aggregation is a sort of the counts by their static
     destination, a cumsum, and a take at each vertex's segment end
     (fixed at shard time), not a scatter-add
  4. arrivals summed into counts + visit counters zeta

Steps 1-2 run through the shared degree-bucketed aggregate sampler
(`core/aggregate_sampler`): rows are grouped by power-of-two degree
buckets via a static permutation computed at shard time (memoized like
the step makers), and each bucket's tree spans the bucket width instead
of the global max degree — per-round sampler FLOPs ~ sum_v deg(v), not
n_loc * max_deg. Sampler RNG contract: draws are a pure counter-based
function of (per-round key words, global row id = padded vertex id, slot
index) — see `kernels/multinomial_rows/_math` — so rows sample
independently of bucket order and blocking, `use_pallas` (kernel vs jnp
ref) never changes the draws, and checkpoint replay stays bit-exact.
The super-step is two jitted programs, sample then exchange, dispatched
back to back with one host sync per round (the `device_get` of the
round's counters); a profiler trace times each on the device
(`jit_sample`, `jit_exchange`). Per-bucket occupancy lands in the host
telemetry dict next to the wire counters (`occupancy`).

A job is one `counts.job` span of `runtime.tracing` (count `rounds`):
`counts.build` (host build and placement; counts `sampler_depth`, the
sampler's sequential split levels a round, and `exchange_sort_entries`,
the slots the exchange sorts a round), one `round.counts` per round
holding `counts.sample`, `counts.exchange` (the two dispatches) and
`counts.sync` (count `active`, walks alive after the round), then
`counts.finish`.
"""
from __future__ import annotations

import dataclasses
import math
from functools import lru_cache, partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.aggregate_sampler import (BucketLayout, build_layout_sharded,
                                          bucketize_csr, flatten_moves,
                                          sample_buckets)
from repro.core.distributed import AXIS, shard_map
from repro.core.estimator import pagerank_from_visits
from repro.core.graph import CSRGraph
from repro.core.routing import entry_nbytes, lane_slots
from repro.checkpoint import LayoutSpec
from repro.kernels import resolve_use_pallas
from repro.kernels.multinomial_rows._math import key_words
from repro.runtime import Stage, StagedState, StageSchedule, run_staged
from repro.runtime import tracing


@dataclasses.dataclass(frozen=True)
class ShardedPaddedGraph:
    """Per-shard degree-bucketed adjacency (see `core/aggregate_sampler`)
    with static cross-shard lane bounds. Host numpy arrays: the engine
    places them on its mesh itself."""

    n: int
    n_pad: int
    n_loc: int
    shards: int
    max_deg: int
    deg: np.ndarray         # [P, n_loc]
    lane_cap: int           # max edges crossing any (src,dst) shard pair
    layout: BucketLayout    # shard-uniform bucket caps/widths (static)
    bperm: np.ndarray       # [P, layout.total_rows] bucket-grouped local
                            # row ids (-1 = padding slot)
    bnbr: np.ndarray        # [P, layout.total_edges] flat bucketed global
                            # dst (n_pad = padding slot)
    bends: np.ndarray       # [P, n_pad + 1] int32: bends[p, v] = shard p's
                            # real slots with dst < v, so vertex v's slots
                            # are [bends[v], bends[v + 1]) in dst order


def shard_graph_padded(graph: CSRGraph, shards: int, *,
                       bucketed: bool = True) -> ShardedPaddedGraph:
    n_loc = math.ceil(graph.n / shards)
    n_pad = n_loc * shards
    md = max(graph.max_out_deg, 1)
    col = np.asarray(graph.col_idx)
    degs = np.asarray(graph.out_deg)
    deg_sh = np.zeros(n_pad, np.int32)
    deg_sh[:graph.n] = degs
    deg_sh = deg_sh.reshape(shards, n_loc)
    # static lane bound: edges from shard p to shard q
    src_owner = np.repeat(np.arange(graph.n) // n_loc, degs)
    cut = np.bincount(src_owner * shards + col // n_loc,
                      minlength=shards * shards)
    # lanes hold (vertex,count) pairs: at most min(cut, n_loc) distinct
    lane_cap = int(min(cut.max(initial=0), n_loc)) or 1
    layout, bperm = build_layout_sharded(deg_sh, md, bucketed=bucketed)
    # padding slots key past the last vertex: they sort last and fall in
    # no vertex's segment
    bnbr = bucketize_csr(graph.row_ptr, col, deg_sh, bperm, layout,
                         pad_dst=n_pad)
    bends = np.zeros((shards, n_pad + 1), np.int32)
    for p in range(shards):
        bends[p, 1:] = np.cumsum(np.bincount(bnbr[p], minlength=n_pad + 1)
                                 [:n_pad])
    return ShardedPaddedGraph(
        n=graph.n, n_pad=n_pad, n_loc=n_loc, shards=shards, max_deg=md,
        deg=deg_sh, lane_cap=lane_cap, layout=layout, bperm=bperm,
        bnbr=bnbr, bends=bends)


def check_sum_exact(total_walks: int) -> None:
    """The exchange's int32 cumsum over a round's per-slot counts is exact
    while the walks in flight, at most n * walks, stay below 2^31; a job
    that could wrap is refused rather than run."""
    if total_walks >= 1 << 31:
        raise ValueError(
            f"the counts exchange sums in int32 and needs n * walks < 2^31; "
            f"got n * walks = {total_walks}")


# Packed lanes carry (local vid:16b | count:15b) in one int32, plus one
# spill entry per vertex, so they are exact only while every local vertex
# id fits 16 bits and no vertex can receive more than 2 * _PACK_CMAX walks
# in a round (bounded by the total walk count).
_PACK_CMAX = 32767


def resolve_packed(packed: Optional[bool], n_loc: int,
                   total_walks: int) -> bool:
    """`None` packs whenever packing is exact; an explicit True that
    cannot be exact is refused rather than silently losing counts."""
    fits = n_loc <= 1 << 16 and total_walks <= 2 * _PACK_CMAX
    if packed is None:
        return fits
    if packed and not fits:
        raise ValueError(
            f"packed count lanes need n_loc <= 65536 and n * walks <= "
            f"{2 * _PACK_CMAX}; got n_loc={n_loc}, walks={total_walks}")
    return bool(packed)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ExchangeIndex:
    """The exchange's static first argument, fixed for a job: each slot's
    global destination (`ShardedPaddedGraph.bnbr`) and each vertex's
    segment ends in destination order (`ShardedPaddedGraph.bends`)."""
    bnbr: jnp.ndarray     # [P, layout.total_edges]
    ends: jnp.ndarray     # [P, n_pad + 1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CountDistState:
    counts: jnp.ndarray   # [P, n_loc]
    zeta: jnp.ndarray     # [P, n_loc]
    key: jnp.ndarray      # [P, 2]
    round: jnp.ndarray


def _sample_step(bperm, deg, counts, key, *, eps: float, n_loc: int,
                 shards: int, layout: BucketLayout, use_pallas: bool):
    """Program 1 of the super-step: the degree-bucketed aggregate draw.

    Pure per-shard compute (no collectives beyond the telemetry psums).
    Returns the flat per-edge counts aligned with
    `ShardedPaddedGraph.bnbr`, the advanced key, global per-bucket
    occupancy, and the (must-be-zero) conservation residual.
    """
    bperm, deg, counts, key = bperm[0], deg[0], counts[0], key[0]
    shard_id = jax.lax.axis_index(AXIS)
    key, k_sample = jax.random.split(key)
    # rid: globally-unique padded vertex id -> draws independent per vertex
    rid = shard_id * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
    samples, occ, residual = sample_buckets(
        counts, deg, rid, key_words(k_sample), bperm, layout,
        eps=eps, use_pallas=use_pallas)
    flat_T = flatten_moves(samples)
    occ = jax.lax.psum(occ, AXIS)
    residual = jax.lax.psum(residual, AXIS)
    return flat_T[None], key[None], occ, residual


def sorted_segment_sums(dst, ends, values):
    """Per-vertex sums of per-slot `values`: sort them by their static
    destination `dst`, cumsum, and difference the prefix sums at each
    vertex's segment `ends` ([n + 1], vertex v's slots are
    [ends[v], ends[v + 1]) in dst order). Slots keyed past the last
    vertex sort after every segment and count for none. Integer sums do
    not depend on order, so this equals a scatter-add exactly; on the chip
    a sort moves data several times faster than a scatter or a gather."""
    _, v = jax.lax.sort((dst, values), num_keys=1)
    csum = jnp.cumsum(v)
    at = jnp.where(ends > 0, csum[jnp.maximum(ends - 1, 0)], 0)
    return at[1:] - at[:-1]


def _exchange_step(index: ExchangeIndex, flat_T, zeta, *, n_loc: int,
                   shards: int, lane_cap: int, packed: bool = True):
    """Program 2 of the super-step: aggregate per destination vertex and
    run the Lemma-1 (vertex, count) lane exchange."""
    bnbr, ends = index.bnbr[0], index.ends[0]
    flat_T, zeta = flat_T[0], zeta[0]
    shard_id = jax.lax.axis_index(AXIS)

    # this shard's counts summed per global destination vertex
    per_vertex = sorted_segment_sums(bnbr, ends, flat_T)
    if shards == 1:
        # one shard owns every vertex: no lane can fill, so skip the lane
        # packing below (a stable sort and scatters over every vertex)
        zero = jnp.int32(0)
        return (per_vertex[None], (zeta + per_vertex)[None],
                jax.lax.psum(jnp.sum(per_vertex), AXIS), zero, zero, zero)

    # local arrivals are the own slice; the rest is lane-packed as
    # (vertex, count) per target shard. Aggregating first bounds the lanes
    # by #distinct vertices, not #edges.
    arrive = jax.lax.dynamic_slice(per_vertex, (shard_id * n_loc,), (n_loc,))
    vid = jnp.arange(n_loc * shards, dtype=jnp.int32)
    per_vertex = jnp.where(vid // n_loc == shard_id, 0, per_vertex)
    if packed:
        # 4B lanes: (local vid:16b | count:15b) — 15-bit count keeps the
        # packed int32 non-negative (-1 stays the empty sentinel); larger
        # counts spill into a second entry for the same vertex (see
        # `resolve_packed` for when that is exact).
        spill = jnp.maximum(per_vertex - _PACK_CMAX, 0)
        c_main = jnp.minimum(per_vertex, _PACK_CMAX)
        vid2 = jnp.concatenate([vid, vid])
        cnt2 = jnp.concatenate([c_main, jnp.minimum(spill, _PACK_CMAX)])
    else:
        vid2 = vid
        cnt2 = per_vertex
    has = cnt2 > 0
    v_owner = vid2 // n_loc
    ok, lane_idx = lane_slots(v_owner, has, shards, lane_cap)
    if packed:
        local_vid = (vid2 % n_loc).astype(jnp.int32)
        payload = local_vid | (cnt2.astype(jnp.int32) << 16)
        lanes = (jnp.full((shards * lane_cap,), -1, jnp.int32)
                 .at[lane_idx].set(jnp.where(ok, payload, -1), mode="drop"))
        overflow = jax.lax.psum(jnp.sum(jnp.where(has & ~ok, cnt2, 0)), AXIS)
        recv = jax.lax.all_to_all(lanes.reshape(shards, lane_cap), AXIS,
                                  split_axis=0, concat_axis=0,
                                  tiled=True).reshape(-1)
        got = recv >= 0
        rv = recv & 0xFFFF
        rc = jnp.where(got, recv >> 16, 0)
        arrive = arrive + jax.ops.segment_sum(
            rc, jnp.where(got, rv, 0), num_segments=n_loc)
        wire_entries = jnp.sum(lanes >= 0)
        # dtype-derived, not a magic constant: one packed int32 lane column
        bytes_per = entry_nbytes(lanes)
    else:
        lanes_v = (jnp.full((shards * lane_cap,), -1, jnp.int32)
                   .at[lane_idx].set(jnp.where(ok, vid2, -1), mode="drop"))
        lanes_c = (jnp.zeros((shards * lane_cap,), jnp.int32)
                   .at[lane_idx].set(jnp.where(ok, cnt2, 0), mode="drop"))
        overflow = jax.lax.psum(jnp.sum(jnp.where(has & ~ok, cnt2, 0)), AXIS)
        recv_v = jax.lax.all_to_all(lanes_v.reshape(shards, lane_cap), AXIS,
                                    split_axis=0, concat_axis=0,
                                    tiled=True).reshape(-1)
        recv_c = jax.lax.all_to_all(lanes_c.reshape(shards, lane_cap), AXIS,
                                    split_axis=0, concat_axis=0,
                                    tiled=True).reshape(-1)
        got = recv_v >= 0
        arrive = arrive + jax.ops.segment_sum(
            jnp.where(got, recv_c, 0),
            jnp.clip(recv_v - shard_id * n_loc, 0, n_loc - 1),
            num_segments=n_loc)
        wire_entries = jnp.sum(lanes_v >= 0)
        bytes_per = entry_nbytes(lanes_v, lanes_c)

    new_counts = arrive
    new_zeta = zeta + arrive
    active = jax.lax.psum(jnp.sum(new_counts), AXIS)
    a2a_entries = jax.lax.psum(wire_entries, AXIS)
    a2a_bytes = a2a_entries * bytes_per
    return (new_counts[None], new_zeta[None], active, a2a_entries,
            a2a_bytes, overflow)


# memoized like the other engines' step makers: the graph's static layout
# (n_loc/shards/bucket layout/lane_cap) is the cache key, not the array
# payload, so repeat runs over same-shaped graphs skip recompilation
@lru_cache(maxsize=64)
def make_count_superstep(mesh: Mesh, eps: float, *, n_loc: int, shards: int,
                         layout: BucketLayout, lane_cap: int,
                         packed: bool = True, use_pallas: bool = False):
    """Returns (sample, exchange): the two jitted halves of the super-step.
    Their names are what a profiler trace calls them (`jit_sample`,
    `jit_exchange`)."""
    sample_sh = shard_map(
        partial(_sample_step, eps=eps, n_loc=n_loc, shards=shards,
                layout=layout, use_pallas=use_pallas),
        mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(), P()),
    )
    exch_sh = shard_map(
        partial(_exchange_step, n_loc=n_loc, shards=shards,
                lane_cap=lane_cap, packed=packed),
        mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(), P(), P(), P()),
    )

    @jax.jit
    def sample(bperm, deg, state: CountDistState):
        return sample_sh(bperm, deg, state.counts, state.key)

    @jax.jit
    def exchange(index: ExchangeIndex, flat_T, key, state: CountDistState):
        counts, zeta, active, entries, a2a, overflow = exch_sh(
            index, flat_T, state.zeta)
        return (CountDistState(counts=counts, zeta=zeta, key=key,
                               round=state.round + 1),
                active, entries, a2a, overflow)

    return sample, exchange


def _count_layouts(n: int):
    """Elastic layout schema for the counts engine's single stage — shared
    by the engine and the CONGEST auditor's schema lint."""
    return dict(counts=LayoutSpec(kind="vertex", n=n),
                zeta=LayoutSpec(kind="vertex", n=n),
                key=LayoutSpec(kind="replicated_key"),
                round=LayoutSpec(kind="replicated"))


@dataclasses.dataclass
class CountDistResult:
    zeta: jnp.ndarray
    pi: jnp.ndarray
    rounds: int
    a2a_bytes_total: int
    overflow: int
    shards: int
    lane_cap: int
    a2a_entries_total: int = 0   # routed (vertex, count) lane entries
    restarts: int = 0            # supervisor recoveries (fault injection)
    checkpoints_written: int = 0
    occupancy: tuple = ()        # per-bucket rows-with-coupons, summed over
                                 # rounds and shards (len = #buckets)
    residual: int = 0            # conservation leak — must stay 0


def distributed_pagerank_counts(graph: CSRGraph, eps: float,
                                walks_per_node: int, key: jnp.ndarray, *,
                                mesh: Optional[Mesh] = None,
                                packed: Optional[bool] = None,
                                max_rounds: int = 100_000,
                                checkpoint_dir: Optional[str] = None,
                                fail_at: Optional[Sequence[int]] = None,
                                checkpoint_every: int = 10,
                                max_restarts: int = 16,
                                resume: bool = False,
                                use_pallas=None,
                                bucketed: bool = True) -> CountDistResult:
    """Count-aggregated Algorithm 1 across all devices of `mesh`.

    With `checkpoint_dir`/`fail_at` set, the super-step loop runs under the
    checkpoint-restart supervisor (single-stage schedule): recovery from an
    injected failure replays the identical trajectory (state includes the
    PRNG keys), so the recovered run is bit-exact. `bucketed=False` keeps
    the single-bucket max_deg-wide sampler layout (pre-bucketing shape,
    for benchmarking); the draws themselves are layout-independent.
    `packed=None` ships 4-byte packed lanes where they are exact and
    8-byte (vertex, count) lanes otherwise (`resolve_packed`).

    Snapshots are mesh-size-agnostic: the round key is REPLICATED across
    shards (every shard advances the same stream; draws are distinguished
    purely by the counter-based global vertex id, which is mesh-size
    independent), and the state declares its layout schema, so
    `resume=True` onto a mesh with a different device count re-layouts
    the snapshot and continues BIT-EXACTLY — same zeta/pi as the
    uninterrupted run at the original shard count."""
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (AXIS,))
    use_pallas = resolve_use_pallas(use_pallas)
    shards = mesh.devices.size
    with tracing.span("counts.job"):
        with tracing.span("counts.build"):
            check_sum_exact(graph.n * walks_per_node)
            sg = shard_graph_padded(graph, shards, bucketed=bucketed)
            tracing.count("sampler_depth", sg.layout.depth)
            tracing.count("exchange_sort_entries", sg.layout.total_edges)
            packed = resolve_packed(packed, sg.n_loc,
                                    graph.n * walks_per_node)
            spec = NamedSharding(mesh, P(AXIS))
            counts0 = np.zeros((shards, sg.n_loc), np.int32)
            counts0.reshape(-1)[: graph.n] = walks_per_node
            # REPLICATED round key: every shard splits the same stream,
            # draws are distinguished only by the counter-based global
            # vertex id — so the trajectory is a pure function of
            # (seed, graph), not the mesh size
            keys = np.tile(np.asarray(key)[None], (shards, 1))
            deg = jax.device_put(sg.deg, spec)
            bperm = jax.device_put(sg.bperm, spec)
            index = ExchangeIndex(bnbr=jax.device_put(sg.bnbr, spec),
                                  ends=jax.device_put(sg.bends, spec))
            arrays = dict(counts=jax.device_put(counts0, spec),
                          zeta=jax.device_put(counts0, spec),
                          key=jax.device_put(keys, spec),
                          round=jnp.int32(0))
            sample, exchange = make_count_superstep(
                mesh, float(eps), n_loc=sg.n_loc, shards=sg.shards,
                layout=sg.layout, lane_cap=sg.lane_cap, packed=packed,
                use_pallas=use_pallas)

        def _step(ms: StagedState):
            a = ms.arrays
            st = CountDistState(counts=a["counts"], zeta=a["zeta"],
                                key=a["key"], round=a["round"])
            with tracing.span("counts.sample"):
                flat_T, key2, occ, residual = sample(bperm, deg, st)
            with tracing.span("counts.exchange"):
                st, active, entries, a2a, ovf = exchange(index, flat_T,
                                                         key2, st)
            a.update(counts=st.counts, zeta=st.zeta, key=st.key,
                     round=st.round)
            with tracing.span("counts.sync"):
                active_i, entries_i, a2a_i, ovf_i, occ_v, res_i = \
                    jax.device_get((active, entries, a2a, ovf, occ,
                                    residual))
                tracing.count("active", int(active_i))
            h = ms.host
            h["rounds"] += 1
            h["a2a"] += int(a2a_i)
            h["a2a_entries"] += int(entries_i)
            h["overflow"] += int(ovf_i)
            h["occupancy"] = [int(x) + int(y)
                              for x, y in zip(h["occupancy"], occ_v)]
            h["residual"] += int(res_i)
            return ms, int(active_i) == 0 or h["rounds"] >= max_rounds

        schedule = StageSchedule([Stage("counts", _step)])
        ms = StagedState(
            stage=schedule.first_stage, arrays=arrays,
            host=dict(rounds=0, a2a=0, a2a_entries=0, overflow=0,
                      occupancy=[0] * len(sg.layout.caps), residual=0),
            layouts={"counts": _count_layouts(graph.n)},
            shards=shards)

        def _put(name, arr):
            return (jnp.asarray(arr) if name == "round"
                    else jax.device_put(np.asarray(arr), spec))

        ms, restarts, checkpoints_written = run_staged(
            schedule, ms, _put, checkpoint_dir=checkpoint_dir,
            fail_at=fail_at, checkpoint_every=checkpoint_every,
            max_restarts=max_restarts, resume=resume,
            max_rounds=max_rounds + 1, tmp_prefix="prcnt_ckpt_")
        h = ms.host
        tracing.count("rounds", h["rounds"])

        with tracing.span("counts.finish"):
            zeta = ms.arrays["zeta"].reshape(-1)[: graph.n]
            pi = pagerank_from_visits(zeta, graph.n, walks_per_node, eps)
            return CountDistResult(
                zeta=zeta, pi=pi, rounds=h["rounds"],
                a2a_bytes_total=h["a2a"], overflow=h["overflow"],
                shards=shards, lane_cap=sg.lane_cap,
                a2a_entries_total=h["a2a_entries"], restarts=restarts,
                checkpoints_written=checkpoints_written,
                occupancy=tuple(h["occupancy"]),
                residual=int(h["residual"]))


def audit_spec(graph: CSRGraph, mesh: Mesh, *, eps: float = 0.2,
               walks_per_node: int = 2, packed: Optional[bool] = None,
               use_pallas: bool = False, bucketed: bool = True):
    """CONGEST-auditor spec: the exact memoized step programs the engine
    runs (same cache keys => same traced jaxprs), the declared wire budget
    for the single (vertex, count) all_to_all, and the elastic schema."""
    from repro.core.accounting import (EngineAuditSpec, ExchangeSite,
                                       StageProgram)
    shards = int(mesh.devices.size)
    sg = shard_graph_padded(graph, shards, bucketed=bucketed)
    packed = resolve_packed(packed, sg.n_loc, graph.n * walks_per_node)
    n_loc = sg.n_loc
    sample, exchange = make_count_superstep(
        mesh, float(eps), n_loc=n_loc, shards=shards, layout=sg.layout,
        lane_cap=sg.lane_cap, packed=packed, use_pallas=use_pallas)
    sds = jax.ShapeDtypeStruct
    i32, u32 = jnp.int32, jnp.uint32
    state = CountDistState(counts=sds((shards, n_loc), i32),
                           zeta=sds((shards, n_loc), i32),
                           key=sds((shards, 2), u32),
                           round=sds((), i32))
    bperm = sds((shards, sg.bperm.shape[1]), sg.bperm.dtype)
    deg = sds((shards, n_loc), sg.deg.dtype)
    index = ExchangeIndex(bnbr=sds(sg.bnbr.shape, sg.bnbr.dtype),
                          ends=sds(sg.bends.shape, sg.bends.dtype))
    flat_T = sds((shards, sg.layout.total_edges), i32)
    key = sds((shards, 2), u32)
    width = 4 if packed else 8
    site = ExchangeSite(
        site="counts", entry_nbytes=width,
        lane_entries=shards * sg.lane_cap,
        budget_entries=shards * n_loc,
        budget_formula=("P * min(cut_max, n_loc) distinct (vertex, count) "
                        "cells <= P * n_loc"),
        wire_class="count",
        note="Lemma 1: lane bound counts distinct destination vertices, "
             "never walk multiplicity W")
    progs = [
        StageProgram(stage="counts", program="sample", fn=sample,
                     example_args=(bperm, deg, state), sites=(),
                     count_bound=graph.n * walks_per_node),
        # a one-shard exchange has no wire (see `_exchange_step`)
        StageProgram(stage="counts", program="exchange", fn=exchange,
                     example_args=(index, flat_T, key, state),
                     sites=(site,) if shards > 1 else (),
                     count_bound=graph.n * walks_per_node),
    ]
    return EngineAuditSpec(
        engine="counts", programs=progs,
        stage_arrays={"counts": ("counts", "zeta", "key", "round")},
        layouts={"counts": _count_layouts(graph.n)},
        meta=dict(shards=shards, n=graph.n, lane_cap=sg.lane_cap,
                  packed=packed, walks_per_node=walks_per_node))

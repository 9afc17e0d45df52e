"""Shared CONGEST routing machinery for the shard_map engines.

Every multi-device engine in this repo (Algorithm 1 walk-routing in
`distributed.py`, count-aggregation in `distributed_counts.py`, Algorithm 2
in `distributed_improved.py`) moves data between vertex shards with the same
static-shape discipline:

  * per (src_shard, dst_shard) routing lanes of fixed capacity — one
    `all_to_all` per exchange, payload slots that did not fill carry the
    sentinel value;
  * a stable sort-and-rank to assign each outgoing item a distinct lane
    slot for its target shard; items beyond the lane capacity *wait* and
    are retried next round (correctness preserved, only latency paid);
  * walk buffers of fixed capacity `cap`, compacted after each merge, with
    overflow counted in `dropped` (must stay 0 under the sizing rule
    `cap >= 2*W/P + P*route_cap`).

This module owns that machinery so the engines share one implementation:
`rank_within` (stable in-group ranks), `pack_lanes`/`exchange` (lane
scatter + all_to_all), `route_walks`/`merge_walks` (full route superstep for
walk buffers with arbitrary payload fields riding along), `route_counts`
(the Lemma-1 count-aggregated exchange: per-destination-vertex counts as
(vertex, count) lanes, payload independent of how many walks move),
`vertex_histogram` and `sorted_segment_sums` (the per-vertex counts it
ships: a scatter-add, or a sort on keys fixed for a job), `advance_owned`
(one eps-reset/uniform-out-edge PageRank step for owned walks) and
`count_owned_arrivals` (owner-side visit accounting).

Wire accounting: `entry_nbytes` is the single source of truth for
bytes-per-lane-entry — it is derived from the dtypes of the arrays actually
exchanged, and the routing helpers return `sent_bytes` computed with it, so
an engine's wire telemetry cannot drift from its payload when a column is
added or dropped.

All helpers run *inside* shard_map: `jax.lax.axis_index`/`all_to_all` refer
to the mesh axis passed as `axis`.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def shard_map(f, mesh, in_specs, out_specs):
    # check_vma=False: jax.random.binomial's internal while_loop mixes
    # varying/invariant carries under the VMA checker; collectives in our
    # supersteps are explicit (psum/all_to_all), so the check adds nothing.
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def rank_within(sort_key: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """For each element, its rank within its equal-key group (stable).

    Returns (rank, order): `rank[i]` is the 0-based position of element i
    among elements with the same `sort_key`, `order` is the stable argsort.

    Stability is load-bearing, not cosmetic: `lane_slots`' zero-drop
    property, natural-order coupon consumption in Phase 2, and the
    Phase-3 deterministic replay all require equal keys to keep buffer
    order — so it is requested explicitly rather than relying on the
    jnp.argsort default.
    """
    W = sort_key.shape[0]
    order = jnp.argsort(sort_key, stable=True)
    sorted_k = sort_key[order]
    idx = jnp.arange(W)
    is_start = jnp.concatenate([jnp.ones((1,), bool),
                                sorted_k[1:] != sorted_k[:-1]])
    # each run's first index, found by run number (a cumsum) rather than
    # a running max: on TPU `associative_scan` takes minutes to compile
    # at millions of elements, a cumsum and a scatter take seconds
    run = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    first = jnp.zeros((W,), idx.dtype).at[
        jnp.where(is_start, run, W)].set(idx, mode="drop")
    rank_sorted = idx - first[run]
    rank = jnp.zeros((W,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    return rank, order


def lane_slots(target: jnp.ndarray, valid: jnp.ndarray, num_targets: int,
               lane_cap: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Assign each valid item a distinct (target, rank) lane slot.

    Returns (sendable, flat_idx): `sendable` marks items that fit their
    target's lane this round; `flat_idx` is the scatter index into a
    [num_targets * lane_cap] lane array (non-sendable items point at the
    sentinel slot one past the end — scatter with mode="drop").
    """
    sort_key = jnp.where(valid, target, num_targets)  # invalid sort last
    rank, _ = rank_within(sort_key)
    sendable = valid & (rank < lane_cap)
    flat_idx = jnp.where(sendable, target * lane_cap + rank,
                         num_targets * lane_cap)
    return sendable, flat_idx


def pack_lanes(flat_idx: jnp.ndarray, values: jnp.ndarray,
               sendable: jnp.ndarray, num_targets: int, lane_cap: int,
               fill: int = -1) -> jnp.ndarray:
    """Scatter `values[sendable]` into a [num_targets * lane_cap] lane array."""
    return (jnp.full((num_targets * lane_cap,), fill, dtype=jnp.int32)
            .at[flat_idx].set(jnp.where(sendable, values, fill), mode="drop"))


def exchange(lanes: jnp.ndarray, axis: str, num_targets: int,
             lane_cap: int) -> jnp.ndarray:
    """all_to_all a flat [num_targets * lane_cap] lane array; returns the
    received lanes flattened back to [num_targets * lane_cap]."""
    return jax.lax.all_to_all(lanes.reshape(num_targets, lane_cap), axis,
                              split_axis=0, concat_axis=0,
                              tiled=True).reshape(-1)


def exchange_stacked(lanes: list, axis: str, num_targets: int,
                     lane_cap: int) -> list:
    """all_to_all several same-shape lane arrays as ONE collective: slots
    are interleaved so each (target, slot) carries its F payload columns
    contiguously. Values are identical to F separate `exchange` calls —
    this only collapses F collective launches into one."""
    stacked = jnp.stack(lanes, axis=-1)        # [num_targets*lane_cap, F]
    F = stacked.shape[-1]
    recv = jax.lax.all_to_all(
        stacked.reshape(num_targets, lane_cap * F), axis,
        split_axis=0, concat_axis=0, tiled=True)
    recv = recv.reshape(num_targets * lane_cap, F)
    return [recv[:, i] for i in range(F)]


def entry_nbytes(*columns) -> int:
    """Bytes per lane entry: the sum of the dtype sizes of the payload
    columns actually exchanged (dicts of columns count every value).

    The single home of wire accounting — engines charge
    `sent_entries * entry_nbytes(<the exchanged arrays>)`, so the telemetry
    bytes track the payload by construction instead of via hand-maintained
    magic constants.
    """
    total = 0
    for col in columns:
        if isinstance(col, dict):
            total += sum(jnp.asarray(v).dtype.itemsize for v in col.values())
        else:
            total += jnp.asarray(col).dtype.itemsize
    return int(total)


def _seg_reduce(values: jnp.ndarray, seg: jnp.ndarray,
                num_segments: int) -> jnp.ndarray:
    """Sum `values` into `num_segments` buckets in their own dtype (exact
    for integer counts); out-of-range seg ids drop."""
    return jax.ops.segment_sum(values, jnp.where(
        (seg >= 0) & (seg < num_segments), seg, num_segments),
        num_segments=num_segments + 1)[:num_segments]


def vertex_histogram(v: jnp.ndarray, mask: jnp.ndarray,
                     num_vertices: int) -> jnp.ndarray:
    """[num_vertices] histogram of `v[mask]` (any shape, flattened);
    ids outside [0, num_vertices) drop. The per-vertex count builder
    feeding `route_counts`."""
    v = v.reshape(-1)
    mask = mask.reshape(-1)
    return jax.ops.segment_sum(
        mask.astype(jnp.int32),
        jnp.where(mask & (v >= 0) & (v < num_vertices), v, num_vertices),
        num_segments=num_vertices + 1)[:num_vertices]


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


def route_counts(per_vertex: jnp.ndarray, *, axis: str,
                 shard_id: jnp.ndarray, n_loc: int, shards: int,
                 by_source: bool = False):
    """One Lemma-1 aggregated exchange: per-destination-vertex counts
    travel as (vertex, count) pairs — payload bounded by the number of
    distinct destination vertices, independent of how many walks move.

    `per_vertex` is a [shards * n_loc] int32 count vector indexed by global
    (padded) vertex id. Counts destined for vertices this shard owns are
    applied locally and never hit the wire. At most `n_loc` distinct
    vertices can target one owner, so the built-in lane capacity of `n_loc`
    makes lane overflow structurally impossible (no waiting, no dropping).

    Returns (arrivals, sent_entries, sent_bytes): `arrivals` is the
    [n_loc] count of items delivered to each owned vertex, or
    [shards, n_loc] broken down by source shard when `by_source` (the own
    shard's contribution sits in row `shard_id`).
    """
    n_pad = shards * n_loc
    vid = jnp.arange(n_pad, dtype=jnp.int32)
    owner = vid // n_loc
    own = per_vertex.reshape(shards, n_loc)[shard_id]
    remote = (owner != shard_id) & (per_vertex > 0)
    sendable, flat_idx = lane_slots(owner, remote, shards, n_loc)
    lanes_v = pack_lanes(flat_idx, vid, sendable, shards, n_loc, fill=-1)
    lanes_c = pack_lanes(flat_idx, per_vertex, sendable, shards, n_loc,
                         fill=0)
    recv_v, recv_c = exchange_stacked([lanes_v, lanes_c], axis, shards,
                                      n_loc)
    got = recv_v >= 0
    sent_entries = jnp.sum(lanes_v >= 0)
    sent_bytes = sent_entries * entry_nbytes(lanes_v, lanes_c)
    local_v = recv_v - shard_id * n_loc          # in [0, n_loc) where got
    cnt = jnp.where(got, recv_c, 0)
    if by_source:
        src = jnp.arange(shards * n_loc, dtype=jnp.int32) // n_loc
        seg = jnp.where(got, src * n_loc + local_v, n_pad)
        arrivals = _seg_reduce(cnt, seg, n_pad).reshape(shards, n_loc)
        arrivals = arrivals.at[shard_id].add(own)
    else:
        seg = jnp.where(got, local_v, n_loc)
        arrivals = _seg_reduce(cnt, seg, n_loc) + own
    return arrivals, sent_entries, sent_bytes


def route_walks(pos: jnp.ndarray, fields: Dict[str, jnp.ndarray], *,
                axis: str, shard_id: jnp.ndarray, n_loc: int, shards: int,
                route_cap: int):
    """One routing exchange: send walks whose current vertex is owned by
    another shard (up to `route_cap` per target; the rest wait).

    `fields` are extra int32 payload columns riding along with `pos`
    (coupon ids, lengths, flags, ...). Returns (kept_pos, kept_fields,
    recv_pos, recv_fields, waited, sent_entries, sent_bytes); `recv_*` are
    [shards * route_cap] with -1 in empty `recv_pos` slots, and
    `sent_bytes` charges `entry_nbytes` over the columns actually shipped.
    """
    valid = pos >= 0
    owner = jnp.where(valid, pos // n_loc, shards)
    needs = valid & (owner != shard_id)
    sendable, flat_idx = lane_slots(owner, needs, shards, route_cap)
    send_pos = pack_lanes(flat_idx, pos, sendable, shards, route_cap)
    if fields:
        send_f = [pack_lanes(flat_idx, vals, sendable, shards, route_cap,
                             fill=0) for vals in fields.values()]
        recvs = exchange_stacked([send_pos] + send_f, axis, shards,
                                 route_cap)
        recv_pos = recvs[0]
        recv_fields = dict(zip(fields.keys(), recvs[1:]))
    else:
        recv_pos = exchange(send_pos, axis, shards, route_cap)
        recv_fields = {}
    kept_pos = jnp.where(sendable, -1, pos)  # sent slots freed
    kept_fields = {name: jnp.where(sendable, 0, vals)
                   for name, vals in fields.items()}
    waited = jnp.sum(needs & ~sendable)
    sent_entries = jnp.sum(send_pos >= 0)
    sent_bytes = sent_entries * entry_nbytes(pos, fields)
    return (kept_pos, kept_fields, recv_pos, recv_fields, waited,
            sent_entries, sent_bytes)


def merge_walks(kept_pos: jnp.ndarray, kept_fields: Dict[str, jnp.ndarray],
                recv_pos: jnp.ndarray, recv_fields: Dict[str, jnp.ndarray],
                cap: int):
    """Compact kept walks + arrivals into the fixed-capacity buffer.

    Valid walks sort first (stable), so arrivals beyond `cap` are the ones
    dropped; returns (pos, fields, dropped)."""
    arrived = recv_pos >= 0
    merged_pos = jnp.concatenate([kept_pos, jnp.where(arrived, recv_pos, -1)])
    order = jnp.argsort(jnp.where(merged_pos >= 0, 0, 1), stable=True)
    merged_pos = merged_pos[order]
    total_valid = jnp.sum(merged_pos >= 0)
    dropped = jnp.maximum(total_valid - cap, 0)
    fields = {}
    for name in kept_fields:
        merged = jnp.concatenate([kept_fields[name], recv_fields[name]])
        fields[name] = merged[order][:cap]
    return merged_pos[:cap], fields, dropped


def count_owned_arrivals(mask: jnp.ndarray, v_global: jnp.ndarray,
                         shard_id: jnp.ndarray, n_loc: int) -> jnp.ndarray:
    """[n_loc] histogram of `v_global[mask]` rebased to this shard's range
    (masked entries dump into a discarded overflow segment)."""
    local = jnp.where(mask, v_global - shard_id * n_loc, -1)
    return jax.ops.segment_sum(
        mask.astype(jnp.int32), jnp.where(mask, local, n_loc),
        num_segments=n_loc + 1)[:n_loc]


def advance_owned(rp: jnp.ndarray, ci: jnp.ndarray, dg: jnp.ndarray,
                  pos: jnp.ndarray, eligible: jnp.ndarray,
                  k_term: jnp.ndarray, k_edge: jnp.ndarray, eps: float,
                  shard_id: jnp.ndarray, n_loc: int):
    """One PageRank step for the `eligible` walks of this shard: terminate
    w.p. eps (or on a dangling vertex), else move along a uniform out-edge.

    Returns (survive, dst): `survive` marks walks that moved, `dst` their
    new global vertex (meaningful only where `survive`)."""
    cap = pos.shape[0]
    local = jnp.where(eligible, pos - shard_id * n_loc, 0)
    u_term = jax.random.uniform(k_term, (cap,))
    u_edge = jax.random.uniform(k_edge, (cap,))
    deg = dg[local]
    survive = eligible & (u_term >= eps) & (deg > 0)
    j = jnp.minimum((u_edge * jnp.maximum(deg, 1)).astype(jnp.int32),
                    jnp.maximum(deg - 1, 0))
    eid = jnp.clip(rp[local] + j, 0, ci.shape[0] - 1)
    dst = ci[eid]
    return survive, dst

"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-test.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import engine_counts, routing
from repro.core.graph import from_edges, padded_adjacency
from repro.kernels.histogram import histogram
from repro.kernels.histogram.ref import histogram_ref
from repro.models.moe import _rank_within
from repro.train.optimizer import dequantize_blockwise, quantize_blockwise

settings.register_profile("ci", deadline=None, max_examples=25)
settings.load_profile("ci")


@given(st.lists(st.integers(min_value=-1, max_value=49), min_size=1,
                max_size=400),
       st.integers(min_value=1, max_value=50))
def test_histogram_matches_ref(ids, n):
    ids = jnp.asarray(ids, jnp.int32)
    np.testing.assert_array_equal(np.asarray(histogram(ids, n)),
                                  np.asarray(histogram_ref(ids, n)))


@given(st.lists(st.integers(min_value=0, max_value=9), min_size=1,
                max_size=300))
def test_rank_within_is_a_ranking(ids):
    ids_j = jnp.asarray(ids, jnp.int32)
    rank = np.asarray(_rank_within(ids_j))
    for v in set(ids):
        ranks_v = sorted(rank[np.asarray(ids) == v].tolist())
        assert ranks_v == list(range(len(ranks_v)))  # 0..k-1, no dup/gap


@given(st.integers(min_value=1, max_value=2**20))
def test_quantize_roundtrip_bound(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (256,)) * 10
    q, s = quantize_blockwise(x)
    back = dequantize_blockwise(q, s)
    bound = np.asarray(jnp.abs(x.reshape(-1, 128)).max(axis=1)) / 127.0
    err = np.asarray(jnp.abs((x - back).reshape(-1, 128)).max(axis=1))
    assert (err <= bound * 0.51 + 1e-6).all()


@given(st.integers(min_value=2, max_value=40),
       st.integers(min_value=0, max_value=100),
       st.integers(min_value=0, max_value=2**16))
def test_multinomial_split_conserves(deg, count, seed):
    """Binomial-chain multinomial: total out == total in, any degree."""
    degs = jnp.asarray([deg, 1, 3], jnp.int32)
    counts = jnp.asarray([count, 5, 0], jnp.int32)
    T, rem = engine_counts._multinomial_split(
        jax.random.PRNGKey(seed), counts, degs, int(degs.max()))
    assert int(rem.sum()) == 0
    np.testing.assert_array_equal(np.asarray(T.sum(axis=1)),
                                  np.asarray(counts))


@given(st.lists(st.tuples(st.integers(0, 19), st.integers(0, 19)),
                min_size=1, max_size=100))
def test_csr_total_degree(edges):
    src = np.array([e[0] for e in edges])
    dst = np.array([e[1] for e in edges])
    g = from_edges(src, dst, 20, undirected=False, dedup=True)
    assert int(np.asarray(g.out_deg).sum()) == g.m
    nbr, valid = padded_adjacency(g)
    assert int(np.asarray(valid).sum()) == g.m


# ---------------------------------------------------------------------------
# CONGEST routing-lane primitives (core/routing.py): every shard_map engine
# moves data through rank_within -> lane_slots -> pack_lanes -> all_to_all,
# so these invariants gate all four distributed engines at once.
# ---------------------------------------------------------------------------

def _check_rank_within(keys):
    rank, _ = routing.rank_within(jnp.asarray(keys, jnp.int32))
    rank, keys = np.asarray(rank), np.asarray(keys)
    for v in set(keys.tolist()):
        ranks_v = rank[keys == v]
        # a permutation of 0..k-1 per equal-key group (no dup, no gap) ...
        assert sorted(ranks_v.tolist()) == list(range(len(ranks_v)))
        # ... assigned stably: rank order == original index order
        assert (np.diff(ranks_v) > 0).all() if len(ranks_v) > 1 else True


@given(st.lists(st.integers(min_value=0, max_value=11), min_size=1,
                max_size=300))
def test_rank_within_stable_ranking(keys):
    _check_rank_within(keys)


def _check_lane_slots(targets, valids, shards, lane_cap):
    t = np.asarray(targets)
    v = np.asarray(valids)
    sendable, flat = routing.lane_slots(
        jnp.asarray(t, jnp.int32), jnp.asarray(v), shards, lane_cap)
    sendable, flat = np.asarray(sendable), np.asarray(flat)
    assert not (sendable & ~v).any()          # only valid items get slots
    for q in range(shards):
        grp = v & (t == q)
        sent = sendable & grp
        # exactly min(|group|, cap) go this round — the rest *wait*,
        # nothing is silently dropped
        assert sent.sum() == min(grp.sum(), lane_cap), q
        slots = flat[sent]
        assert ((slots >= q * lane_cap) & (slots < (q + 1) * lane_cap)).all()
    assert len(set(flat[sendable].tolist())) == int(sendable.sum())
    assert (flat[~sendable] == shards * lane_cap).all()  # sentinel slot


@given(st.integers(min_value=1, max_value=6).flatmap(lambda s: st.tuples(
           st.just(s),
           st.lists(st.tuples(st.integers(0, s - 1), st.booleans()),
                    min_size=1, max_size=120),
           st.integers(min_value=1, max_value=8))))
def test_lane_slots_no_silent_drops(case):
    shards, items, lane_cap = case
    _check_lane_slots([t for t, _ in items], [v for _, v in items],
                      shards, lane_cap)


def _check_pack_exchange_roundtrip(per_shard_targets, lane_cap):
    """Pack every shard's outbox and emulate the tiled all_to_all (shard
    q's block p arrives at shard p as block q): the delivered + waiting
    multisets must equal the sent multiset, each item must land at its
    target shard, and each (src, dst) lane must preserve source order."""
    shards = len(per_shard_targets)
    lanes, waiting = [], []
    sent_to = {q: [] for q in range(shards)}
    for p, targets in enumerate(per_shard_targets):
        t = np.asarray(targets, np.int32)
        values = (p * 1000 + np.arange(len(t))).astype(np.int32)  # traceable
        sendable, flat = routing.lane_slots(
            jnp.asarray(t), jnp.ones(len(t), bool), shards, lane_cap)
        lane = routing.pack_lanes(flat, jnp.asarray(values),
                                  sendable, shards, lane_cap)
        lanes.append(np.asarray(lane).reshape(shards, lane_cap))
        sendable = np.asarray(sendable)
        waiting.extend(values[~sendable].tolist())
        for q in range(shards):
            sent_to[q].extend(values[sendable & (t == q)].tolist())
    delivered = []
    for p in range(shards):
        recv = np.stack([lanes[q][p] for q in range(shards)])  # [src, cap]
        for q in range(shards):
            lane = recv[q][recv[q] >= 0]
            # occupied slots form a prefix in source order (stable ranks)
            assert (recv[q][:len(lane)] >= 0).all()
            assert (np.diff(lane) > 0).all() if len(lane) > 1 else True
        got = recv[recv >= 0].tolist()
        assert sorted(got) == sorted(sent_to[p]), p   # right shard, exactly
        delivered.extend(got)
    total = sum(len(t) for t in per_shard_targets)
    assert len(delivered) + len(waiting) == total     # conservation
    all_values = [p * 1000 + i for p, t in enumerate(per_shard_targets)
                  for i in range(len(t))]
    assert sorted(delivered + waiting) == sorted(all_values)


@given(st.integers(min_value=1, max_value=5).flatmap(lambda s: st.tuples(
           st.lists(st.lists(st.integers(0, s - 1), min_size=1, max_size=40),
                    min_size=s, max_size=s),
           st.integers(min_value=1, max_value=6))))
def test_pack_exchange_roundtrip_conserves(case):
    per_shard_targets, lane_cap = case
    _check_pack_exchange_roundtrip(per_shard_targets, lane_cap)


def _check_merge_walks(kept, recv):
    cap = len(kept)  # engine contract: the buffer IS the kept array
    kept_j = jnp.asarray(kept, jnp.int32)
    recv_j = jnp.asarray(recv, jnp.int32)
    tag = lambda pos: jnp.where(pos >= 0, pos * 7 + 1, 0)  # paired payload
    pos, fields, dropped = routing.merge_walks(
        kept_j, {"x": tag(kept_j)}, recv_j, {"x": tag(recv_j)}, cap)
    pos, x = np.asarray(pos), np.asarray(fields["x"])
    n_kept = int((np.asarray(kept) >= 0).sum())
    n_recv = int((np.asarray(recv) >= 0).sum())
    assert pos.shape == (cap,)
    assert int((pos >= 0).sum()) == min(n_kept + n_recv, cap)
    assert int(dropped) == max(0, n_kept + n_recv - cap)
    # payload columns travel with their walk through the compaction
    assert (x[pos >= 0] == pos[pos >= 0] * 7 + 1).all()
    surviving = pos[pos >= 0].tolist()
    kept_valid = [p for p in kept if p >= 0]
    pool = kept_valid + [p for p in recv if p >= 0]
    if int(dropped) == 0:
        assert sorted(surviving) == sorted(pool)
    else:
        # resident walks are never the ones dropped (they sort first)
        assert sorted(surviving[:n_kept]) == sorted(kept_valid)
        remainder = list(surviving)
        for p in pool:  # surviving ⊆ pool as multisets
            if p in remainder:
                remainder.remove(p)
        assert not remainder


@given(st.lists(st.integers(min_value=-1, max_value=99), min_size=1,
                max_size=60),
       st.lists(st.integers(min_value=-1, max_value=99), min_size=1,
                max_size=60))
def test_merge_walks_conserves_and_drops_exactly(kept, recv):
    _check_merge_walks(kept, recv)


# ---------------------------------------------------------------------------
# degree-bucketed aggregate sampler (core/aggregate_sampler): the static
# layout machinery every count-moving engine now routes through.
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(min_value=0, max_value=200), min_size=1,
                max_size=200))
def test_bucket_permutation_is_a_bijection(degs):
    """The bucket-grouping permutation hits every row exactly once; the
    -1 entries are pure padding; every row lands in the bucket whose
    width covers its degree."""
    from repro.core.aggregate_sampler import bucket_of, build_layout
    deg = np.asarray(degs, np.int32)
    md = max(int(deg.max()), 1)
    layout, perm = build_layout(deg, md)
    real = perm[perm >= 0]
    assert sorted(real.tolist()) == list(range(len(deg)))
    assert (perm >= -1).all() and (perm < len(deg)).all()
    starts = np.asarray(layout.row_starts)
    b_of = bucket_of(deg)
    for b, (start, cap, w) in enumerate(
            zip(layout.row_starts, layout.caps, layout.widths)):
        rows = perm[start:start + cap]
        rows = rows[rows >= 0]
        assert (b_of[rows] == b).all()
        assert (deg[rows] <= w).all()        # chain covers the whole row


@given(st.integers(min_value=1, max_value=5).flatmap(lambda s: st.tuples(
           st.just(s),
           st.lists(st.integers(min_value=0, max_value=60),
                    min_size=s * 2, max_size=s * 8))))
def test_bucketed_adjacency_roundtrips_flat_csr(case):
    """The flat bucketed neighbor table is a pure re-layout of the CSR:
    reading back through the permutation reproduces each row's out-
    neighbours in CSR order bit-exactly, and every other slot is 0.
    Each bucket's block is slot-major: slot j of bucket row i sits at
    j * cap + i."""
    from repro.core.aggregate_sampler import (build_layout_sharded,
                                              bucketize_csr)
    shards, degs = case
    n_loc = len(degs) // shards
    deg = np.asarray(degs[:n_loc * shards], np.int32).reshape(shards, n_loc)
    md = max(int(deg.max()), 1)
    rng = np.random.default_rng(0)
    nbr = rng.integers(1, 1000, size=(shards, n_loc, md)).astype(np.int32)
    row_ptr = np.concatenate([[0], np.cumsum(deg.reshape(-1))])
    col = np.concatenate([nbr[p, r, :deg[p, r]] for p in range(shards)
                          for r in range(n_loc)] + [np.zeros(0, np.int32)])
    layout, perm = build_layout_sharded(deg, md)
    flat = bucketize_csr(row_ptr, col, deg, perm, layout)
    assert flat.shape == (shards, layout.total_edges)
    s_rows, s_edges = 0, 0
    for cap, w in zip(layout.caps, layout.widths):
        for p in range(shards):
            for i in range(cap):
                r = perm[p, s_rows + i]
                blk = flat[p, s_edges + i: s_edges + cap * w: cap]
                if r < 0:
                    np.testing.assert_array_equal(blk, 0)
                else:
                    d = deg[p, r]
                    np.testing.assert_array_equal(blk[:d], nbr[p, r, :d])
                    np.testing.assert_array_equal(blk[d:], 0)
        s_rows += cap
        s_edges += cap * w


@given(st.integers(min_value=1, max_value=7),
       st.integers(min_value=0, max_value=2**20),
       st.integers(min_value=0, max_value=2**16))
def test_residual_zero_at_bucket_boundary_degrees(k, count, seed):
    """Conservation (residual == 0) exactly at the bucket-boundary
    degrees d = 2^k (last row of bucket k) and d = 2^k + 1 (first row of
    bucket k+1), where an off-by-one in widths would leak mass."""
    from repro.core.aggregate_sampler import (build_layout, sample_buckets)
    degs = np.asarray([2 ** k, 2 ** k + 1, 1, 0], np.int32)
    md = int(degs.max())
    layout, perm = build_layout(degs, md)
    counts = jnp.asarray([count, count, seed % 97, 3], jnp.int32)
    rid = jnp.arange(4, dtype=jnp.int32)
    kw = jnp.asarray(np.array([seed, seed ^ 0xABCDEF], np.uint32))
    samples, occ, residual = sample_buckets(
        counts, jnp.asarray(degs), rid, kw, jnp.asarray(perm), layout,
        eps=0.2, use_pallas=False)
    assert int(residual) == 0
    total = sum(int(T.sum()) for _, T in samples)
    assert total == int(counts.sum())


@given(st.integers(min_value=1, max_value=2**16))
def test_pagerank_estimate_near_normalized(seed):
    """pi_tilde sums to ~1 (unbiased estimator of a distribution)."""
    from repro.core import simple_pagerank
    from repro.graphs import erdos_renyi
    g = erdos_renyi(48, 4.0, seed=seed % 7)
    res = simple_pagerank(g, 0.3, walks_per_node=60,
                          key=jax.random.PRNGKey(seed))
    assert 0.9 < float(res.pi.sum()) < 1.1

"""The routing helpers' per-stage reductions and walk step against plain
numpy: the visit histograms (`vertex_histogram`, `count_owned_arrivals`)
against `np.bincount`, the count reduction (`_seg_reduce`) against an
int64 sum, and `advance_owned` against a numpy step on the same
uniforms. The inputs have the engines' shapes: -1 padding, ids past the
last vertex, masks, dead walks, dangling vertices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.distributed import AXIS, shard_graph
from repro.core.routing import (_seg_reduce, advance_owned,
                                count_owned_arrivals, route_counts,
                                shard_map, vertex_histogram)
from repro.graphs import barabasi_albert, erdos_renyi


def _bincount(ids, n, mask=None):
    ids = np.asarray(ids)
    keep = (ids >= 0) & (ids < n)
    if mask is not None:
        keep &= np.asarray(mask)
    return np.bincount(ids[keep], minlength=n)


def _check_histograms(ids, n, mask):
    """Both histograms against `np.bincount`: `vertex_histogram` on the
    ids, `count_owned_arrivals` on the same ids as the global ids of
    shard 1's vertices (owned entries only, as the engines call it)."""
    ids, mask = jnp.asarray(ids, jnp.int32), jnp.asarray(mask, bool)
    np.testing.assert_array_equal(
        np.asarray(vertex_histogram(ids, mask, n)),
        _bincount(ids, n, mask))
    owned = mask & (ids >= 0) & (ids < n)
    np.testing.assert_array_equal(
        np.asarray(count_owned_arrivals(owned, ids + n, jnp.int32(1), n)),
        _bincount(ids, n, owned))


@pytest.mark.parametrize("W,n", [(64, 8), (1000, 100), (4096, 512),
                                 (5000, 700), (257, 1), (1, 31)])
def test_histogram_shapes(W, n, key):
    k1, k2 = jax.random.split(key)
    ids = jax.random.randint(k1, (W,), -1, n + 2)
    mask = jax.random.bernoulli(k2, 0.8, (W,))
    _check_histograms(ids, n, mask)


def test_histogram_out_of_range():
    ids = jnp.array([-5, 0, 3, 99, 3, -1], jnp.int32)
    got = vertex_histogram(ids, jnp.ones((6,), bool), 4)
    np.testing.assert_array_equal(np.asarray(got), [1, 0, 0, 2])


def test_histogram_padded_lane_shape(key):
    """Routing-lane shape: mostly -1 padding, valid ids clustered (a lane
    carries one destination shard's vertices)."""
    W, n = 4096, 64
    ids = np.full(W, -1, dtype=np.int32)
    k1, k2 = jax.random.split(key)
    npos = int(jax.random.randint(k1, (), 1, 200))
    ids[:npos] = np.asarray(jax.random.randint(k2, (npos,), 0, n))
    _check_histograms(ids, n, ids >= 0)


def test_histogram_all_padding():
    ids = jnp.full((512,), -1, jnp.int32)
    _check_histograms(ids, 16, jnp.ones((512,), bool))
    np.testing.assert_array_equal(
        np.asarray(vertex_histogram(ids, ids >= 0, 16)), np.zeros(16))


# --------------------------------------------------------------------------
# the count reduction behind `route_counts`
# --------------------------------------------------------------------------

def _exact_sum(values, seg, n):
    out = np.zeros(n, np.int64)
    seg = np.asarray(seg)
    keep = (seg >= 0) & (seg < n)
    np.add.at(out, seg[keep], np.asarray(values, np.int64)[keep])
    return out


@pytest.mark.parametrize("E,n", [(100, 10), (4000, 300), (999, 50),
                                 (8192, 1024)])
def test_seg_reduce_matches_exact_sum(E, n, key):
    k1, k2 = jax.random.split(key)
    values = jax.random.randint(k1, (E,), 0, 1 << 20)
    seg = jax.random.randint(k2, (E,), -1, n + 3)
    got = _seg_reduce(values, seg, n)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), _exact_sum(values, seg, n))


def test_seg_reduce_exact_past_f32_range():
    """Counts add in int32: 2**24 + 1, which float32 cannot hold, comes
    out exact."""
    assert np.float32(2 ** 24) + np.float32(1) == np.float32(2 ** 24)
    values = jnp.asarray([2 ** 24, 1, 7], jnp.int32)
    seg = jnp.asarray([0, 0, 1], jnp.int32)
    np.testing.assert_array_equal(np.asarray(_seg_reduce(values, seg, 2)),
                                  [2 ** 24 + 1, 7])


def test_seg_reduce_exact_up_to_int32_max():
    values = jnp.asarray([2 ** 30, 2 ** 30 - 1, 2 ** 29, 2 ** 29], jnp.int32)
    seg = jnp.asarray([0, 0, 1, 1], jnp.int32)
    np.testing.assert_array_equal(np.asarray(_seg_reduce(values, seg, 2)),
                                  [2 ** 31 - 1, 2 ** 30])


def test_seg_reduce_drops_out_of_range_ids():
    values = jnp.asarray([2 ** 24, 5, 9, 11, 13], jnp.int32)
    seg = jnp.asarray([0, -1, 2, 99, 1], jnp.int32)
    np.testing.assert_array_equal(np.asarray(_seg_reduce(values, seg, 2)),
                                  [2 ** 24, 13])


@pytest.mark.parametrize("by_source", [False, True])
def test_route_counts_one_shard_keeps_counts(by_source, key):
    """On one shard every count is owned: `route_counts` hands the
    per-vertex counts back unchanged, sends nothing, and keeps counts
    past 2**24 exact."""
    n = 96
    counts = np.array(jax.random.randint(key, (n,), 0, 1000), np.int32)
    counts[5] = 2 ** 24 + 1
    mesh = Mesh(np.array(jax.devices()[:1]), (AXIS,))

    def body(c):
        arrivals, entries, nbytes = route_counts(
            c[0], axis=AXIS, shard_id=jax.lax.axis_index(AXIS), n_loc=n,
            shards=1, by_source=by_source)
        return arrivals[None], entries, nbytes

    f = jax.jit(shard_map(body, mesh, in_specs=(P(AXIS),),
                          out_specs=(P(AXIS), P(), P())))
    arrivals, entries, nbytes = f(jnp.asarray(counts)[None])
    np.testing.assert_array_equal(np.asarray(arrivals).reshape(-1), counts)
    assert int(entries) == 0 and int(nbytes) == 0


# --------------------------------------------------------------------------
# one PageRank step of the owned walks
# --------------------------------------------------------------------------

def _numpy_step(rp, ci, dg, pos, eligible, u_term, u_edge, eps, shard_id,
                n_loc):
    """The step in numpy on the uniforms `advance_owned` draws: stop w.p.
    eps or at a dangling vertex, else take out-edge floor(u * deg)."""
    rp, ci, dg = np.asarray(rp), np.asarray(ci), np.asarray(dg)
    pos, eligible = np.asarray(pos), np.asarray(eligible)
    u_term = np.asarray(u_term, np.float32)
    u_edge = np.asarray(u_edge, np.float32)
    local = np.where(eligible, pos - shard_id * n_loc, 0)
    deg = dg[local]
    survive = eligible & (u_term >= np.float32(eps)) & (deg > 0)
    j = np.minimum((u_edge * np.maximum(deg, 1).astype(np.float32))
                   .astype(np.int32), np.maximum(deg - 1, 0))
    dst = ci[np.clip(rp[local] + j, 0, len(ci) - 1)]
    return survive, dst


def _advance(rp, ci, dg, pos, eligible, eps, shard_id, n_loc, key):
    kt, ke = jax.random.split(key)
    survive, dst = advance_owned(rp, ci, dg, pos, eligible, kt, ke, eps,
                                 jnp.int32(shard_id), n_loc)
    cap = pos.shape[0]
    want = _numpy_step(rp, ci, dg, pos, eligible,
                       jax.random.uniform(kt, (cap,)),
                       jax.random.uniform(ke, (cap,)), eps, shard_id, n_loc)
    return np.asarray(survive), np.asarray(dst), want


@pytest.mark.parametrize("graph_maker,W", [
    (lambda: erdos_renyi(128, 5.0, seed=1), 1000),
    (lambda: barabasi_albert(200, 3, seed=2), 4096),
])
@pytest.mark.parametrize("eps", [0.1, 0.5])
def test_walk_step_sweep(graph_maker, W, eps, key):
    """Shard 1 of 2 advances its eligible walks (-1 padding and parked
    walks stay): each survivor moves to one of its vertex's out-
    neighbours, exactly the numpy step's."""
    g = graph_maker()
    sg = shard_graph(g, 2)
    n_loc = sg.n_loc
    k1, k2, k3 = jax.random.split(key, 3)
    lo, hi = n_loc, min(2 * n_loc, g.n)
    pos = jax.random.randint(k1, (W,), lo - 1, hi)
    pos = jnp.where(pos < lo, -1, pos)
    eligible = (pos >= 0) & jax.random.bernoulli(k2, 0.8, (W,))
    survive, dst, (w_surv, w_dst) = _advance(
        sg.row_ptr[1], sg.col_idx[1], sg.out_deg[1], pos, eligible, eps, 1,
        n_loc, k3)
    np.testing.assert_array_equal(survive, w_surv)
    np.testing.assert_array_equal(dst[survive], w_dst[w_surv])
    assert not survive[~np.asarray(eligible)].any()
    rp, ci = np.asarray(g.row_ptr), np.asarray(g.col_idx)
    for v, u in zip(np.asarray(pos)[survive], dst[survive]):
        assert u in ci[rp[v]:rp[v + 1]]
    # about a fraction eps of the eligible walks stop
    stop = 1 - survive[np.asarray(eligible)].mean()
    assert abs(stop - eps) < 0.1


def test_walk_step_dead_walks_stay(key):
    g = erdos_renyi(32, 4.0, seed=3)
    sg = shard_graph(g, 1)
    pos = jnp.arange(10, dtype=jnp.int32)
    survive, _, (w_surv, _) = _advance(
        sg.row_ptr[0], sg.col_idx[0], sg.out_deg[0], pos,
        jnp.zeros((10,), bool), 0.3, 0, sg.n_loc, key)
    assert not survive.any() and not w_surv.any()


def test_walk_step_dangling_reset(key):
    """Dangling vertices (out-degree 0) end the walk on the spot — the
    directed engines' reset convention. eps = 0: no random reset."""
    rp = jnp.asarray([0, 1, 1], jnp.int32)       # 0 -> 1, 1 dangling
    ci = jnp.asarray([1], jnp.int32)
    dg = jnp.asarray([1, 0], jnp.int32)
    pos = jnp.asarray([0, 1, 1], jnp.int32)
    survive, dst, (w_surv, w_dst) = _advance(
        rp, ci, dg, pos, jnp.ones((3,), bool), 0.0, 0, 2, key)
    np.testing.assert_array_equal(survive, [True, False, False])
    np.testing.assert_array_equal(w_surv, survive)
    assert int(dst[0]) == 1 == int(w_dst[0])

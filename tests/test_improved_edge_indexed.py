"""Algorithm 2's edge-indexed Phase 1, its demand-covering coupon pools,
its round cap and its program spans.

* each Phase-1 round deals every (home, vertex) row exactly the outcomes
  the sampler drew for it, one to every live coupon, at 1 and 4 shards;
* no Phase-1 program or plan array grows with n_loc * max_degree, and the
  plan of the Graph500 scale-17 benchmark graph passes its int32 guards;
* the rank vector matches the plain reference (`bench/reference/walks.py`)
  on a Graph500 graph, with no walk left to the naive tail;
* `max_rounds` stops a stage and counts the walks still out;
* one job records its spans and counts in the ring.
"""
import os
import sys
import textwrap

import jax
import numpy as np
import pytest

from conftest import run_forced_devices
from repro.analysis.lint import iter_subjaxprs
from repro.core import distributed_improved as di
from repro.core.improved_pagerank import (demand_pool_sizes,
                                          expected_stitch_arrivals)
from repro.graphs import barabasi_albert_hub
from repro.runtime import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.compare import grouped_l1  # noqa: E402
from bench.data.graph500 import graph500  # noqa: E402
from bench.reference.walks import expected_visits  # noqa: E402


def _graph500(scale):
    from repro.core.graph import CSRGraph
    row_ptr, col_idx, out_deg = graph500(scale, 22)
    return CSRGraph(row_ptr=row_ptr, col_idx=col_idx, out_deg=out_deg,
                    n=len(out_deg), m=len(col_idx), undirected=True)


# One Phase-1 round at a time, through the engine's own programs; every
# (home, vertex) row's dealt outcomes are compared with the sampler's
# reply cells, re-derived here from the plan's bucket tables.
DEAL_CODE = textwrap.dedent("""
    import collections, json
    import jax, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import distributed_improved as di
    from repro.core.improved_pagerank import demand_pool_sizes
    from repro.graphs import barabasi_albert_hub

    g = barabasi_albert_hub(90, 3, seed=4)
    mesh = Mesh(np.array(jax.devices()), (di.AXIS,))
    shards = mesh.devices.size
    K, eps, lam = 3, 0.3, 3
    pool = demand_pool_sizes(g, eps, K, lam)
    ms, _, _, plan = di._build_three_phase(
        g, eps, K, jax.random.PRNGKey(5), mesh, pool_np=pool, lam=lam,
        cap2=None, route_cap2=None, max_rounds=100, use_pallas=False,
        bucketed=True)
    L, n_loc = plan.layout, plan.n_loc
    req, samp, asn = di._make_p1_steps(
        mesh, eps=eps, n_loc=n_loc, shards=shards, rep_cap=plan.rep_cap,
        S_loc_pad=plan.S_loc_pad, layout=L, use_pallas=False,
        count_bound=plan.S_total)
    spec = NamedSharding(mesh, P(di.AXIS))
    bperm = jax.device_put(plan.bperm_np, spec)
    bnbr = jax.device_put(plan.bnbr_np, spec)
    dg = jax.device_put(plan.sg.out_deg, spec)

    # per owner: each reply cell's local row (-1: padding) and destination
    rows, dsts = [], []
    for q in range(shards):
        r = [plan.bperm_np[q]]
        for s, cap, w in zip(L.row_starts, L.caps, L.widths):
            r.append(np.broadcast_to(plan.bperm_np[q][s:s + cap][None],
                                     (w, cap)).reshape(-1))
        rows.append(np.concatenate(r))
        dsts.append(np.concatenate([np.full(L.total_rows, -2),
                                    plan.bnbr_np[q]]))

    a = ms.arrays
    pos, alive, traj, key = a["pos"], a["alive"], a["traj"], a["key"]
    report = []
    for t in range(lam):
        c, hist, _, _ = req(pos, alive)
        cells, key, k_perm, _, residual = samp(bperm, dg, c, key)
        tt = jax.device_put(np.int32(t), NamedSharding(mesh, P()))
        pos2, alive2, traj, pending, overflow, _, _ = asn(
            bperm, bnbr, hist, cells, pos, alive, traj, k_perm, tt)
        p0, a0, p1, a1 = (np.asarray(x) for x in (pos, alive, pos2, alive2))
        cells = np.asarray(cells).reshape(shards, shards, -1)
        drawn = collections.defaultdict(collections.Counter)
        for q in range(shards):
            for h in range(shards):
                for i in np.flatnonzero(cells[q, h]):
                    v = q * n_loc + int(rows[q][i])
                    drawn[h, v][int(dsts[q][i])] += int(cells[q, h, i])
        dealt = collections.defaultdict(collections.Counter)
        for h in range(shards):
            for s in np.flatnonzero(a0[h] > 0):
                out = int(p1[h, s]) if a1[h, s] else -2
                dealt[h, int(p0[h, s])][out] += 1
        live = int(sum(a0.sum(axis=1)))
        report.append(dict(
            same=drawn == dealt, live=live,
            dealt=sum(sum(c.values()) for c in dealt.values()),
            # a coupon that did not walk this round stays where it ended
            idle_kept=bool(np.all(np.where(a0 > 0, True, p1 == p0))),
            residual=int(residual), overflow=int(overflow),
            pending=int(pending), alive_after=int(a1.sum())))
        pos, alive = pos2, alive2
    print(json.dumps(dict(shards=shards, rounds=report)))
""")


@pytest.mark.parametrize("devices", [1, 4])
def test_each_round_deals_the_sampled_outcomes(devices):
    out = run_forced_devices(DEAL_CODE, devices=devices)
    assert out["shards"] == devices
    for r in out["rounds"]:
        assert r["same"], r
        assert r["dealt"] == r["live"] > 0, r
        assert r["idle_kept"], r
        assert r["residual"] == 0 and r["overflow"] == 0, r
        assert r["pending"] == r["alive_after"], r


def _sizes(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield int(np.prod(getattr(v.aval, "shape", ())))
        for sub, _, _ in iter_subjaxprs(eqn):
            yield from _sizes(sub)


def test_phase1_programs_do_not_grow_with_max_degree():
    """On a graph whose hub makes n_loc * max_degree far larger than its
    vertices, edges and coupons, no value any Phase-1 program computes
    comes near it."""
    g = barabasi_albert_hub(8192, 2, seed=4)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), (di.AXIS,))
    spec = di.audit_spec(g, mesh)
    lam = spec.meta["lam"]
    plan = di.plan_three_phase(
        g, 1, demand_pool_sizes(g, 0.2, 2, lam), 2)
    cells = plan.layout.total_rows + plan.layout.total_edges
    bound = 2 * max(plan.S_loc_pad * lam, cells, g.n)
    assert 20 * bound < plan.n_loc * plan.md   # 17.5M here
    progs = [p for p in spec.programs if p.stage == "phase1"]
    assert len(progs) == 3
    for prog in progs:
        jaxpr = jax.make_jaxpr(prog.fn)(*prog.example_args).jaxpr
        assert max(_sizes(jaxpr)) <= bound, prog.program


def test_benchmark_graph_plan_passes_its_int32_guards():
    """The plan of `batch.g500`'s Graph500 scale-17 graph, on one shard, on
    the host alone: the guards pass, and the reply's lanes and cells are
    sized by vertices and bucket slots, not by n_loc * (max_degree + 1) =
    1,422,558,912."""
    g = _graph500(17)
    md = int(np.asarray(g.out_deg).max())
    assert (g.n, g.m, md) == (90_304, 3_728_982, 15_752)
    K, eps = 32, 0.15
    lam = di.short_walk_length(g.n)
    assert lam == 4
    pool = demand_pool_sizes(g, eps, K, lam)
    plan = di.plan_three_phase(g, 1, pool, K)
    L = plan.layout
    assert L.total_edges == 5_450_529
    assert plan.S_loc_pad == plan.S_total == int(pool.sum())
    assert plan.rep_cap == min(plan.n_loc + L.total_edges, plan.S_loc_pad)
    dense = plan.n_loc * (md + 1)
    assert dense == 1_422_558_912
    for arr in (plan.bnbr_np, plan.bperm_np, plan.psize_sh, plan.pstart_sh,
                plan.pool_pad):
        assert arr.size * 100 < dense
    assert (L.total_rows + L.total_edges) * 100 < dense
    assert plan.S_loc_pad < 2 ** 31


def test_expected_arrivals_match_the_linear_solve():
    """The iteration's a = x0 M (I - M)^-1, M = ((1 - eps) Q)^lam, solved
    densely on a small graph with a dangling-free hub."""
    g = barabasi_albert_hub(40, 2, seed=1)
    eps, K, lam = 0.2, 5, 3
    rp, ci = np.asarray(g.row_ptr), np.asarray(g.col_idx)
    a = expected_stitch_arrivals(rp, ci, g.n, eps, K, lam)
    Q = np.zeros((g.n, g.n))
    for v in range(g.n):
        for u in ci[rp[v]:rp[v + 1]]:
            Q[v, u] += 1.0 / (rp[v + 1] - rp[v])
    M = np.linalg.matrix_power((1 - eps) * Q, lam)
    x0 = np.full(g.n, float(K))
    want = x0 @ M @ np.linalg.inv(np.eye(g.n) - M)
    assert np.abs(a - want).max() < 1.0 / (1 - (1 - eps) ** lam)
    pool = demand_pool_sizes(g, eps, K, lam)
    assert np.all(pool >= K + np.ceil(want + 4 * np.sqrt(want)))


@pytest.fixture(scope="module")
def g500_job():
    """One whole job on a Graph500 scale-10 graph, as `batch.g500.improved`
    runs it at its CPU-test size, with the spans it recorded."""
    g = _graph500(10)
    tracing.clear()
    res = di.distributed_improved_pagerank(g, 0.15, 32,
                                           jax.random.PRNGKey(7))
    return g, res, tracing.spans()


def test_pools_cover_the_walks(g500_job):
    """Every vertex's pool holds its own K starts, and no walk finds its
    pool empty, so nothing is left to the naive tail."""
    g, res, _ = g500_job
    pool = demand_pool_sizes(g, 0.15, 32, res.lam)
    assert pool.min() >= 32
    assert res.coupons_created == int(pool.sum())
    assert res.tail_walks == res.exhausted_walks == 0
    assert res.tail_rounds == 0
    assert res.dropped == res.residual == res.unfinished == 0


def test_rank_vector_matches_the_plain_reference(g500_job):
    """grouped_l1 against the reference's expectation, the number and the
    limit that decide `correct` in `batch.g500.improved` at its CPU-test
    size: sound jobs read at most 0.0101 there over 10 job keys, and the
    control, which cuts the walks short, 0.0227 or more
    (`bench/configs/g500_batch_improved.tiny.json`). The total of the
    visits is an unbiased count of n * K / eps."""
    g, res, _ = g500_job
    z = expected_visits(g.row_ptr, g.col_idx, g.n, 0.15)
    assert grouped_l1(np.asarray(res.pi), z * 0.15 / g.n) < 0.015
    want = g.n * 32 / 0.15
    assert abs(res.total_visits - want) / want < 0.02


def test_max_rounds_stops_a_stage_and_counts_the_walks_out():
    g = _graph500(8)
    res = di.distributed_improved_pagerank(g, 0.15, 8, jax.random.PRNGKey(1),
                                           max_rounds=2)
    assert res.phase2_rounds == 2
    assert res.unfinished > 0
    whole = di.distributed_improved_pagerank(g, 0.15, 8,
                                             jax.random.PRNGKey(1))
    assert whole.unfinished == 0 and whole.phase2_rounds > 2
    assert res.total_visits < whole.total_visits


def test_a_job_records_its_spans_and_counts(g500_job):
    _, res, recs = g500_job
    (job,) = [r for r in recs if r.name == "improved.job"]
    assert job.counts == dict(rounds=res.rounds,
                              phase1_rounds=res.phase1_rounds,
                              phase2_rounds=res.phase2_rounds,
                              tail_rounds=res.tail_rounds,
                              tail_walks=res.tail_walks,
                              coupons=res.coupons_created)
    kids = [r for r in recs if r.parent == job.id]
    names = [r.name for r in kids]
    assert names[0] == "improved.build" and names[-1] == "improved.finish"
    assert names.count("round.phase1") == res.phase1_rounds
    assert names.count("round.phase2") == res.phase2_rounds
    assert names.count("round.phase3") == 1
    for r in kids:
        if r.name == "round.phase1":
            assert [c.name for c in recs if c.parent == r.id] == [
                "p1.request", "p1.sample", "p1.assign", "p1.sync"]
    assert all(a.end_ns <= b.start_ns for a, b in zip(kids, kids[1:]))


@pytest.mark.parametrize("walks,shards,want", [
    (0, 1, (1024, 1024)), (289, 1, (1024, 1024)), (1500, 8, (2048, 256)),
])
def test_tail_buffer_is_sized_from_its_walks(walks, shards, want):
    assert di._tail_sizes(walks, shards, None, None) == want

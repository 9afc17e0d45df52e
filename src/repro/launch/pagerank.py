"""Distributed PageRank driver: sharded engines + fault tolerance.

    PYTHONPATH=src python -m repro.launch.pagerank --n 512 --eps 0.2 \
        --walks 64 --graph erdos_renyi --algo improved

Engine selection (`--algo`):
  walks     Algorithm 1, walk-routing shard_map engine (default).
  counts    Algorithm 1, count-aggregated engine (Lemma-1 wire: per-vertex
            coupon counts, payload independent of the walk count).
  improved  Algorithm 2 (IMPROVED-PAGERANK), three-phase sharded engine:
            sqrt(log n)-length short-walk pre-computation, count-
            aggregated coupon stitching, one-exchange owner-shard visit
            counting (see `repro.core.distributed_improved`). All three
            phases move Lemma-1 aggregated (vertex, count) payloads.
  directed  Section 5 (directed/LOCAL), the same three-phase engine with
            uniform per-node coupon budgets, lam = sqrt(log n / eps)
            short walks, and dangling-node resets (see
            `repro.core.distributed_directed`). Count aggregation retired
            the worst-case LOCAL buffers this engine used to need: lane
            volume is bounded by distinct vertices, not walk multiplicity.
            Pair it with `--graph directed_web` to exercise a power-law
            directed fixture.

`main` keeps JAX's persistent compilation cache in
`JAX_COMPILATION_CACHE_DIR`, or in `.jax_cache/` at the checkout's root
(`repro.launch.compile_cache`).

Fault tolerance applies to EVERY engine: `--checkpoint-dir` enables
periodic snapshots, `--fail-at R [R ...]` injects simulated failures at
the listed global rounds (for the 3-phase engines, round indices span all
phases, so a failure can land at a phase boundary or mid-phase), and
recovery from the latest snapshot is bit-exact — the recovered run prints
the same pi, telemetry, and accuracy as an unfailed one, plus restarts>0.
`--resume` cold-starts from the latest snapshot in --checkpoint-dir (a
previously killed run) instead of from round 0.

Elastic resume: snapshots record the mesh size they were written under
and every engine declares a per-buffer layout schema
(`checkpoint.LayoutSpec`: walk lanes, vertex shards, coupon slots,
per-shard keys, replicated scalars — see `checkpoint/elastic.py`), so
`--resume` does NOT need the original device count. Pass `--shards N` to
run on the first N local devices; when N differs from the snapshot's
recorded shard count, restore routes through the schema-driven relayout
and the run continues on the resized mesh. The count-state engine
(`--algo counts`) resumes BIT-exactly at any N (its RNG is counter-based
per vertex and its round key replicated); the 3-phase engines resume
bit-exactly from RNG-free stages (mid-Phase-2/3) and statistically —
gated by the same `--check` tolerances — when live per-shard key streams
had to be re-derived. `--shards` also works without `--resume`, simply
running any engine on a submesh.

Every run validates against power iteration (L1 and top-10 overlap);
`--check` turns that report into a hard gate (non-zero exit on miss) for
CI smoke legs.

Telemetry printed for `--algo improved` and `--algo directed` (also
available on the returned `ImprovedDistResult`/`DirectedDistResult`):
  phase rounds   per-phase superstep counts: phase1 (short walks, <= lam),
                 report (always 0 — coupons never migrate, so the old
                 coupon-summary report phase no longer exists; the column
                 stays as a regression tripwire), phase2 (stitching),
                 phase3 (always 1 — counting is ONE aggregated exchange
                 over the home-local trajectory tables, not a replay),
                 tail (naive fallback) — their sum is the engine's total
                 round count, the quantity the paper bounds by
                 O(sqrt(log n)/eps) undirected resp. O(sqrt(log n / eps))
                 directed.
  coupons        created vs used pool sizes and exhausted walks (pool
                 ran dry -> naive fallback).
  wire           all_to_all payload bytes by phase. Every phase ships
                 Lemma-1 aggregated (vertex, count) entries — 8 B/entry
                 for stitch/count traffic, 8+12 B/entry for the Phase-1
                 request/reply — and each column is charged as
                 entries * entry_nbytes(<the routed columns>), derived
                 from the actual lane dtypes (never a hand-kept
                 constant). `dropped` (lane overflows) must be 0;
                 `waited` counts tail-lane carry-overs.
  budget         (`directed` only) the uniform per-node coupon budget and
                 the dangling-node count (out-degree 0, immediate reset).
  spans          (`counts`, `improved`, `directed`) each program span
                 inside the job (`runtime.tracing`), with its count and
                 mean milliseconds: for the 3-phase engines the build,
                 `round.<stage>` per round, a Phase-1 round's
                 `p1.request` / `p1.sample` / `p1.assign` / `p1.sync`,
                 and the finish; then the sampler's per-bucket occupancy
                 (rows holding coupons, summed over rounds and shards;
                 bucket b covers degrees in (2^(b-1), 2^b]), the
                 conservation residual (must be 0), and the walks a stage
                 cut at max_rounds (`unfinished`, 0 for a complete run).

`--algo ppr` runs the batched Personalized-PageRank engine
(`repro.core.personalized_batch`): `--queries` seed-derived multi-source
queries advance together, every superstep moving ALL queries' walks over
one `route_counts` exchange (query ids folded into a virtual vertex
space, so the wire stays Lemma-1 counts). Telemetry printed:
  rounds         supersteps to drain every query's walks.
  a2a_bytes      total all_to_all payload (8 B per routed (vertex-lane,
                 count) entry, summed over rounds).
  dropped / admit_dropped
                 walk-buffer resp. admission overflow — both must be 0
                 (the default cap is sized so overflow is impossible).
  peak_active    peak concurrently-live walks across the run (from the
                 per-round active trace).
Accuracy is reported per query against the `exact_ppr` dense linear
solve (NOT power iteration — PPR's stationary vector depends on the
query's source distribution); `--check` gates on the same L1/top-10
thresholds as the global-PageRank algos.

`--audit` runs the CONGEST auditor instead of an engine: every engine's
jitted stage programs are traced to jaxprs (the engines' own memoized
programs — identical cache keys, so the trace IS the runtime program),
each all_to_all is checked against its declared per-round lane budget,
the RNG / dtype / elastic-schema lints run over the same traces, the
engines execute on fixture graphs to cross-check the static widths
against runtime telemetry, and AUDIT.json is written next to the table.
Non-zero exit on any violation. Per-engine wire budgets (P = shards,
n_loc = ceil(n/P), slots = the degree-bucketed adjacency's out-edge
slots per shard, Q = PPR query slots; every entry is
a Lemma-1 (vertex, count) cell except the walk-class lanes, whose caps
the auditor pins at n_loc so the checked capacity stays W-free):

  engine    site         B/entry  per-shard-per-round lane budget
  walks     route          4      P * n_loc walk slots       [walk-class]
  counts    counts         4      P * min(cut_max, n_loc) cells
  improved  phase1_req     8      P * n_loc cells
            phase1_rep    12      P * (n_loc + slots) (vertex,dest,offset)
            phase2         8      P * n_loc cells
            phase3         8      P * n_loc cells
            tail           4      P * n_loc walk slots       [walk-class]
  directed  same five sites as improved (uniform-budget coupon pools)
  ppr       ppr            8      P * n_loc * Q (vertex, query) lanes

No budget depends on the walk multiplicity W: the auditor rebuilds every
spec at 2x walks and fails if any budget moves. The RNG lint also
certifies which stages resume bit-exactly after an elastic restore:
`counts` (replicated round key, counter-based RNG) and the 3-phase
engines' phase2/phase3 programs (RNG-free) are bit-exact; walks, phase1,
tail, and ppr consume per-shard key streams that are re-derived on a
resized mesh, so their resume is statistical (tolerance-gated).
"""
from __future__ import annotations

import argparse
import dataclasses
import tempfile
import time
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import Checkpointer, relayout_pagerank_state
from repro.core import l1_error, normalized, power_iteration, topk_overlap
from repro.core.distributed import (AXIS, DistState, _make_superstep,
                                    shard_graph, state_from_host,
                                    state_to_host)
from repro.core.distributed_counts import distributed_pagerank_counts
from repro.core.distributed_directed import distributed_directed_pagerank
from repro.core.distributed_improved import distributed_improved_pagerank
from repro.core.graph import CSRGraph
from repro.graphs import GENERATORS
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import FailureSchedule, Supervisor, tracing

import jax.numpy as jnp

# the --check accuracy gate against the plain reference
L1_TOL = 0.15
TOPK_MIN = 0.6


@dataclasses.dataclass
class RunResult:
    pi: np.ndarray             # rank vector; [queries, n] estimates for ppr
    engine: object = None      # the engine's own result and telemetry
                               # (None for walks and ppr)
    accuracy: Optional[dict] = None   # vs power iteration (not for ppr)


def _report_accuracy(pi, g, eps: float, check: bool = False,
                     l1_tol: float = L1_TOL,
                     topk_min: float = TOPK_MIN) -> dict:
    pi = np.asarray(pi, dtype=np.float64)
    t0 = time.perf_counter()
    pi_ref, _, iters = power_iteration(g, eps)
    seconds = time.perf_counter() - t0
    l1 = l1_error(pi / pi.sum(), pi_ref)
    topk = topk_overlap(pi, np.asarray(pi_ref))
    print(f"[pagerank] L1 vs power-iter: {l1:.4f}  "
          f"top-10 overlap: {topk:.2f}  (power iteration: {iters} iters)")
    if check and (l1 >= l1_tol or topk < topk_min):
        raise SystemExit(
            f"[pagerank] accuracy check FAILED: L1 {l1:.4f} "
            f"(tol {l1_tol}) top-10 {topk:.2f} (min {topk_min})")
    return dict(l1=l1, topk=topk, iters=iters, seconds=seconds)


def run_walks(g, eps: float, walks_per_node: int, checkpoint_dir,
              fail_at, seed: int, resume: bool = False, mesh=None,
              max_restarts: int = 16):
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (AXIS,))
    shards = mesh.devices.size
    sg = shard_graph(g, shards)
    W = g.n * walks_per_node
    cap = 2 * W // shards + shards * 64
    route_cap = W // shards + 64

    pos0 = np.full((shards, cap), -1, np.int32)
    zeta0 = np.zeros((shards, sg.n_loc), np.int32)
    for p in range(shards):
        lo = min(p * sg.n_loc, g.n)
        hi = min((p + 1) * sg.n_loc, g.n)
        locs = np.repeat(np.arange(lo, hi, dtype=np.int32), walks_per_node)
        pos0[p, : len(locs)] = locs
        zeta0[p, : hi - lo] = walks_per_node
    spec = NamedSharding(mesh, P(AXIS))
    keys = jax.random.split(jax.random.PRNGKey(seed), shards)
    state = DistState(pos=jax.device_put(jnp.asarray(pos0), spec),
                      zeta=jax.device_put(jnp.asarray(zeta0), spec),
                      key=jax.device_put(keys, spec),
                      round=jnp.int32(0), dropped=jnp.int32(0),
                      waited=jnp.int32(0))
    rp, ci, dg = (jax.device_put(x, spec)
                  for x in (sg.row_ptr, sg.col_idx, sg.out_deg))
    step = _make_superstep(mesh, eps, sg.n_loc, shards, route_cap, 0)

    def step_fn(s):
        s2, active, _, _ = step(rp, ci, dg, s)
        return s2, int(active) == 0

    ckpt_dir = checkpoint_dir or tempfile.mkdtemp(prefix="pr_ckpt_")
    sup = Supervisor(step_fn, state_to_host,
                     lambda f: state_from_host(f, mesh),
                     Checkpointer(ckpt_dir), checkpoint_every=10,
                     max_restarts=max_restarts,
                     failure_schedule=FailureSchedule(fail_at) if fail_at
                     else None,
                     meta_fn=lambda: dict(shards=shards),
                     relayout=lambda f, old: relayout_pagerank_state(
                         f, g.n, shards, cap=cap))
    res = sup.run(state, resume=resume)
    zeta = np.asarray(res.state.zeta).reshape(-1)[: g.n]
    pi = zeta.astype(np.float64) * eps / (g.n * walks_per_node)
    print(f"[pagerank] algo=walks n={g.n} shards={shards} "
          f"rounds={res.rounds} restarts={res.restarts} "
          f"dropped={int(res.state.dropped)}")
    return pi


def run_ppr(g, eps: float, walks_per_query: int, num_queries: int,
            seed: int, check: bool = False, l1_tol: float = L1_TOL,
            topk_min: float = TOPK_MIN, mesh=None):
    """Batched PPR: seed-derived multi-source queries, one shared engine.

    Validates each query against its OWN `exact_ppr` oracle — PPR has no
    single power-iteration reference, so this path never reaches
    `_report_accuracy`. Returns the [num_queries, n] estimator matrix.
    """
    from repro.core.personalized import exact_ppr
    from repro.core.personalized_batch import batched_personalized_pagerank

    rng = np.random.default_rng(seed)
    queries = []
    for _ in range(num_queries):
        k = int(rng.integers(1, 4))
        sources = rng.choice(g.n, size=k, replace=False)
        queries.append((sources, None))
    res = batched_personalized_pagerank(
        g, eps, queries, walks_per_query, jax.random.PRNGKey(seed),
        mesh=mesh)
    peak = max(res.active_trace) if res.active_trace else 0
    print(f"[pagerank] algo=ppr n={g.n} shards={res.shards} "
          f"queries={num_queries} walks/query={walks_per_query} "
          f"rounds={res.rounds} a2a_bytes={res.a2a_bytes} "
          f"dropped={res.dropped} admit_dropped={res.admit_dropped} "
          f"peak_active={peak}")
    worst_l1, worst_topk = 0.0, 1.0
    for i, (sources, weights) in enumerate(queries):
        ref = exact_ppr(g, eps, sources, weights=weights)
        est = res.ppr[i]
        l1 = l1_error(normalized(est), normalized(ref))
        topk = topk_overlap(est, ref)
        print(f"[pagerank]   query {i} sources={list(map(int, sources))} "
              f"L1 vs exact_ppr: {l1:.4f}  top-10 overlap: {topk:.2f}")
        worst_l1, worst_topk = max(worst_l1, l1), min(worst_topk, topk)
    if check and (worst_l1 >= l1_tol or worst_topk < topk_min
                  or res.dropped or res.admit_dropped):
        raise SystemExit(
            f"[pagerank] ppr check FAILED: worst L1 {worst_l1:.4f} "
            f"(tol {l1_tol}) worst top-10 {worst_topk:.2f} "
            f"(min {topk_min}) dropped={res.dropped} "
            f"admit_dropped={res.admit_dropped}")
    return res.ppr


def _span_totals(records, job: str = "counts.job") -> str:
    """Count and mean milliseconds of each span name inside the newest
    `job` span, in the order the names first open."""
    job = [r for r in records if r.name == job][-1]
    inside, totals = {job.id}, {}
    for r in records:          # recorded as they open: parents first
        if r.parent in inside:
            inside.add(r.id)
            k, s = totals.get(r.name, (0, 0.0))
            totals[r.name] = (k + 1, s + r.seconds)
    return ", ".join(f"{name} {k} x {1e3 * s / k:.3f} ms"
                     for name, (k, s) in totals.items())


def run(n: int, eps: float, walks_per_node: int, graph_kind: str,
        checkpoint_dir: str | None, fail_at: list[int], seed: int = 0,
        algo: str = "walks", avg_deg: float = 6.0, resume: bool = False,
        check: bool = False, num_queries: int = 4, shards: int | None = None,
        max_restarts: int = 16,
        graph: Optional[CSRGraph] = None) -> RunResult:
    """One run of `algo` on a `graph_kind` graph made from `seed` (or on
    `graph`, when given, in place of generating one)."""
    if resume and not checkpoint_dir:
        raise SystemExit("[pagerank] --resume needs --checkpoint-dir "
                         "(there is no snapshot to cold-start from)")
    mesh = None
    if shards is not None:
        devs = jax.devices()
        if not 1 <= shards <= len(devs):
            raise SystemExit(f"[pagerank] --shards {shards} out of range: "
                             f"{len(devs)} devices available")
        mesh = Mesh(np.array(devs[:shards]), (AXIS,))
    if graph is not None:
        g = graph
    elif graph_kind == "ring":
        g = GENERATORS[graph_kind](n)
    else:
        g = GENERATORS[graph_kind](n, avg_deg, seed)
    res = None
    if algo == "ppr":
        # PPR validates per-query vs exact_ppr inside run_ppr; the
        # power-iteration report below does not apply to it
        return RunResult(pi=run_ppr(g, eps, walks_per_node * g.n,
                                    num_queries, seed, check=check,
                                    mesh=mesh))
    if algo == "walks":
        pi = run_walks(g, eps, walks_per_node, checkpoint_dir, fail_at,
                       seed, resume=resume, mesh=mesh,
                       max_restarts=max_restarts)
    elif algo == "counts":
        res = distributed_pagerank_counts(
            g, eps, walks_per_node, jax.random.PRNGKey(seed), mesh=mesh,
            checkpoint_dir=checkpoint_dir, fail_at=fail_at, resume=resume,
            max_restarts=max_restarts)
        print(f"[pagerank] algo=counts n={g.n} shards={res.shards} "
              f"rounds={res.rounds} restarts={res.restarts} "
              f"lane_cap={res.lane_cap} "
              f"a2a_bytes={res.a2a_bytes_total} overflow={res.overflow}")
        print(f"[pagerank] spans: {_span_totals(tracing.spans())}; "
              f"bucket_occupancy={list(res.occupancy)} "
              f"residual={res.residual}")
        pi = res.pi
    elif algo in ("improved", "directed"):
        engine = (distributed_improved_pagerank if algo == "improved"
                  else distributed_directed_pagerank)
        res = engine(g, eps, walks_per_node, jax.random.PRNGKey(seed),
                     mesh=mesh, checkpoint_dir=checkpoint_dir,
                     fail_at=fail_at, resume=resume,
                     max_restarts=max_restarts)
        print(f"[pagerank] algo={algo} n={g.n} shards={res.shards} "
              f"lam={res.lam} eta={res.eta} ell={res.ell} "
              f"rounds={res.rounds} restarts={res.restarts} "
              f"(p1={res.phase1_rounds} "
              f"report={res.report_rounds} p2={res.phase2_rounds} "
              f"p3={res.phase3_rounds} tail={res.tail_rounds})")
        print(f"[pagerank] coupons created={res.coupons_created} "
              f"used={res.coupons_used} exhausted_walks="
              f"{res.exhausted_walks} tail_walks={res.tail_walks}")
        print(f"[pagerank] wire by phase: {res.a2a_bytes_by_phase} "
              f"dropped={res.dropped} waited={res.waited}")
        print(f"[pagerank] spans: "
              f"{_span_totals(tracing.spans(), algo + '.job')}; "
              f"bucket_occupancy={list(res.p1_occupancy)} "
              f"residual={res.residual} unfinished={res.unfinished}")
        if algo == "directed":
            print(f"[pagerank] uniform budget={res.uniform_budget} "
                  f"coupons/node dangling_nodes={res.dangling_nodes}")
        pi = res.pi
    else:
        raise ValueError(f"unknown algo {algo!r}")
    accuracy = _report_accuracy(pi, g, eps, check=check)
    return RunResult(pi=pi, engine=res, accuracy=accuracy)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--eps", type=float, default=0.2)
    ap.add_argument("--walks", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0,
                    help="graph-generator and PRNG seed")
    ap.add_argument("--avg-deg", type=float, default=6.0,
                    help="generator degree parameter (ignored by ring)")
    ap.add_argument("--graph", default="erdos_renyi",
                    choices=sorted(GENERATORS))
    ap.add_argument("--algo", default="walks",
                    choices=["walks", "counts", "improved", "directed",
                             "ppr"])
    ap.add_argument("--queries", type=int, default=4,
                    help="(--algo ppr) number of seed-derived multi-"
                         "source queries batched into one engine; each "
                         "query gets --walks * n walks")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--resume", action="store_true",
                    help="cold-start from the latest snapshot in "
                         "--checkpoint-dir instead of round 0. The "
                         "snapshot's mesh size does NOT have to match: "
                         "combine with --shards N to resume a run killed "
                         "at a different device count (elastic relayout; "
                         "bit-exact for --algo counts, tolerance-gated "
                         "when live per-shard key streams are re-derived)")
    ap.add_argument("--shards", type=int, default=None,
                    help="run on the first N local devices instead of all "
                         "of them; with --resume, the mesh size to resume "
                         "ONTO (may differ from the snapshot's)")
    ap.add_argument("--max-restarts", type=int, default=16,
                    help="supervisor restart budget before an injected "
                         "failure is re-raised (0 = die on first failure, "
                         "leaving the snapshot dir for an elastic resume)")
    ap.add_argument("--check", action="store_true",
                    help="non-zero exit if the accuracy report misses "
                         "L1 < 0.15 / top-10 >= 0.6 (CI smoke gate)")
    ap.add_argument("--audit", action="store_true",
                    help="run the CONGEST wire-budget + lint auditor over "
                         "every engine instead of a PageRank run: prints "
                         "the per-engine wire table, writes AUDIT.json, "
                         "exits non-zero on any violation (see the module "
                         "docstring for the budget table)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.audit:
        import json

        from repro.analysis.congest import (audit_all_engines,
                                            format_wire_table)
        report = audit_all_engines(eps=args.eps)
        print(format_wire_table(report))
        with open("AUDIT.json", "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print("[pagerank] wrote AUDIT.json")
        if not report["ok"]:
            raise SystemExit("[pagerank] CONGEST audit FAILED")
        return
    run(args.n, args.eps, args.walks, args.graph, args.checkpoint_dir,
        args.fail_at, seed=args.seed, algo=args.algo, avg_deg=args.avg_deg,
        resume=args.resume, check=args.check, num_queries=args.queries, shards=args.shards,
        max_restarts=args.max_restarts)


if __name__ == "__main__":
    main()

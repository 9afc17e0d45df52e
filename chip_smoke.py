"""Smoke run of the batch PageRank job and the PPR service on one TPU chip.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the counts engine on 4 chips vs 1

Phases, in one process (a chip belongs to one process at a time):

  1. device check: fail unless JAX's first device is a TPU;
  2. batch job: `repro.launch.pagerank.run(algo="counts", check=True)` on
     a seeded `directed_web` graph (n = 2^22, average out-degree 8) with
     32 walks per node on a one-chip mesh, run twice (cold, then warm)
     with one seed; the runs must agree bit for bit;
  3. serving: a `PPRService` with 8 slots and 2^18 walks per query
     answers 16 seeded queries of 1 to 3 sources through `submit` and
     `drain` on the same kind of graph cut to n = 2^20 (see
     `SERVE_LOG2_N`); each answer is checked against a plain personalized
     power iteration run on the device.

`--four-chips` runs only the counts engine on `Mesh(devices[:4])` and on
`Mesh(devices[:1])` with one seed and graph (n = 2^20), and requires
equal `zeta`.

Every phase checks its own result and any failure exits nonzero. The last
line of stdout is one JSON object naming the device. Times printed here
are smoke-run times, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import l1_error, normalized, topk_overlap  # noqa: E402
from repro.core.distributed import AXIS  # noqa: E402
from repro.graphs import directed_web  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.pagerank import L1_TOL, TOPK_MIN, run  # noqa: E402
from repro.serve import PPRService  # noqa: E402

EPS = 0.2
AVG_DEG = 8.0
SEED = 0
LOG2_N = 22
WALKS_PER_NODE = 32
SLOTS = 8
WALKS_PER_QUERY = 1 << 18
NUM_QUERIES = 16
# At n = 2^22 the serving phase took 582 s of a 1386 s run on one v5e
# chip, over the script's 1200 s limit: every superstep routes and sorts
# n * SLOTS entries, so the phase scales with n. At 2^20 it is a quarter.
SERVE_LOG2_N = 20
# 4 chips cost four times as much per second; 2^20 keeps the comparison
# of the two meshes short
FOUR_CHIP_LOG2_N = 20
# the reference's truncation error is (1 - EPS)^PPR_ITERS ~ 2e-6
PPR_ITERS = 60


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {what}")


def peak_bytes(device):
    """The device's `peak_bytes_in_use`, or None where the backend keeps
    no memory statistics."""
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _phase_done(what: str, t0: float) -> None:
    print(f"[smoke] {what} done at {time.perf_counter() - t0:.1f} s "
          f"(smoke-run time)", flush=True)


def device_check() -> dict:
    devs = jax.devices()
    d = devs[0]
    print(f"[smoke] device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    _require(d.platform == "tpu", f"no TPU: JAX's first device is "
             f"{d.platform!r}")
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


def build_graph(log2_n: int):
    t0 = time.perf_counter()
    g = directed_web(1 << log2_n, AVG_DEG, SEED)
    print(f"[smoke] graph directed_web n={g.n} m={g.m} avg_out_deg={AVG_DEG}"
          f" host build {time.perf_counter() - t0:.2f} s", flush=True)
    return g


def _counts(g, walks_per_node: int, *, shards: int):
    """One gated counts run through the launch entry point; returns the
    run and its time net of the power-iteration reference."""
    t0 = time.perf_counter()
    r = run(g.n, EPS, walks_per_node, "directed_web", None, [], seed=SEED,
            algo="counts", avg_deg=AVG_DEG, check=True, shards=shards,
            graph=g)
    seconds = time.perf_counter() - t0 - r.accuracy["seconds"]
    _require(r.engine.overflow == 0, f"overflow={r.engine.overflow}")
    _require(r.engine.residual == 0, f"residual={r.engine.residual}")
    return r, seconds


def batch_phase(g, walks_per_node: int = WALKS_PER_NODE) -> dict:
    cold, t_cold = _counts(g, walks_per_node, shards=1)
    warm, t_warm = _counts(g, walks_per_node, shards=1)
    _require(np.array_equal(np.asarray(cold.engine.zeta),
                            np.asarray(warm.engine.zeta)),
             "two counts runs with one seed disagree")
    out = dict(n=g.n, m=g.m, rounds=warm.engine.rounds,
               overflow=warm.engine.overflow, residual=warm.engine.residual,
               l1=warm.accuracy["l1"], topk=warm.accuracy["topk"],
               power_iters=warm.accuracy["iters"],
               setup_s=t_cold - t_warm, run_s=t_warm,
               peak_bytes=peak_bytes(jax.devices()[0]))
    print(f"[smoke] batch: {out}  (smoke-run times; setup = cold - warm "
          f"run, i.e. compilation)", flush=True)
    return out


@partial(jax.jit, static_argnames=("iters",))
def _ppr_power(s, src, dst, w_edge, *, iters: int):
    """x <- EPS * s + (1 - EPS) * Q^T x over the edge list. A vertex with
    no out-edge passes nothing on: a walk that reaches it ends there, as
    in the engine."""
    def body(_, x):
        push = jax.ops.segment_sum(x[src] * w_edge, dst,
                                   num_segments=s.shape[0],
                                   indices_are_sorted=True)
        return EPS * s + (1.0 - EPS) * push
    return jax.lax.fori_loop(0, iters, body, EPS * s)


def ppr_reference(g):
    """Plain personalized power iteration on the device: a function from
    a query's sources (uniform weights) to its PPR vector."""
    deg = np.asarray(g.out_deg)
    src = np.repeat(np.arange(g.n, dtype=np.int32), deg)
    dst = np.asarray(g.col_idx)
    by_dst = np.argsort(dst, kind="stable")
    src, dst = jnp.asarray(src[by_dst]), jnp.asarray(dst[by_dst])
    w_edge = (1.0 / jnp.maximum(jnp.asarray(deg), 1).astype(jnp.float32))[src]

    def solve(sources) -> np.ndarray:
        s = np.zeros(g.n, np.float32)
        s[np.asarray(sources)] = 1.0 / len(sources)
        return np.asarray(_ppr_power(jnp.asarray(s), src, dst, w_edge,
                                     iters=PPR_ITERS), np.float64)
    return solve


def serve_phase(g, *, slots: int = SLOTS,
                walks_per_query: int = WALKS_PER_QUERY,
                num_queries: int = NUM_QUERIES) -> dict:
    rng = np.random.default_rng(SEED)
    queries = [rng.choice(g.n, size=int(rng.integers(1, 4)), replace=False)
               for _ in range(num_queries)]
    mesh = Mesh(np.array(jax.devices()[:1]), (AXIS,))
    t0 = time.perf_counter()
    svc = PPRService(g, EPS, slots=slots, walks_per_query=walks_per_query,
                     mesh=mesh, key=jax.random.PRNGKey(SEED))
    reqs = [svc.submit(q) for q in queries]
    svc.drain()
    seconds = time.perf_counter() - t0
    st = svc.stats
    _require(all(r.done and r.result is not None for r in reqs),
             "a query was not answered")
    _require(st.dropped_walks == 0 and st.admit_dropped == 0
             and st.rejected == 0,
             f"dropped={st.dropped_walks} admit_dropped={st.admit_dropped}"
             f" rejected={st.rejected}")
    t0 = time.perf_counter()
    reference = ppr_reference(g)
    worst_l1, worst_topk = 0.0, 1.0
    for i, (q, r) in enumerate(zip(queries, reqs)):
        ref = reference(q)
        l1 = l1_error(normalized(r.result), normalized(ref))
        topk = topk_overlap(r.result, ref)
        print(f"[smoke]   query {i} sources={q.tolist()} L1 vs power "
              f"iteration {l1:.4f} top-10 {topk:.2f}", flush=True)
        worst_l1, worst_topk = max(worst_l1, l1), min(worst_topk, topk)
    reference_s = time.perf_counter() - t0
    _require(worst_l1 < L1_TOL and worst_topk >= TOPK_MIN,
             f"PPR gate: worst L1 {worst_l1:.4f} (tol {L1_TOL}) worst "
             f"top-10 {worst_topk:.2f} (min {TOPK_MIN})")
    out = dict(n=g.n, m=g.m, queries=num_queries, slots=slots,
               walks_per_query=walks_per_query, supersteps=st.supersteps,
               dropped=st.dropped_walks, admit_dropped=st.admit_dropped,
               rejected=st.rejected, worst_l1=worst_l1,
               worst_topk=worst_topk, serve_s=seconds,
               reference_s=reference_s,
               peak_bytes=peak_bytes(jax.devices()[0]))
    print(f"[smoke] serve: {out}  (smoke-run times; serve_s includes "
          f"compilation)", flush=True)
    return out


def four_chip_phase(g, walks_per_node: int = WALKS_PER_NODE) -> dict:
    devs = jax.devices()
    _require(len(devs) >= 4, f"--four-chips needs 4 devices, have "
             f"{len(devs)}")
    r4, t4 = _counts(g, walks_per_node, shards=4)
    peaks4 = [peak_bytes(d) for d in devs[:4]]
    r1, t1 = _counts(g, walks_per_node, shards=1)
    z4 = np.asarray(r4.engine.zeta, np.int64)
    z1 = np.asarray(r1.engine.zeta, np.int64)
    out = dict(n=g.n, rounds_4=r4.engine.rounds, rounds_1=r1.engine.rounds,
               a2a_bytes_4=r4.engine.a2a_bytes_total,
               zeta_equal=bool(np.array_equal(z4, z1)),
               zeta_l1=float(np.abs(z4 - z1).sum() / z1.sum()),
               run_s_4=t4, run_s_1=t1, peak_bytes_after_4=peaks4,
               peak_bytes_after_1=[peak_bytes(d) for d in devs[:4]])
    print(f"[smoke] four chips: {out}  (smoke-run times, compilation "
          f"included)", flush=True)
    _require(out["zeta_equal"], "4-chip zeta differs from 1-chip zeta")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the counts engine on 4 chips against 1")
    args = ap.parse_args(argv)
    enable_compile_cache()
    t0 = time.perf_counter()
    device = device_check()
    if args.four_chips:
        four_chip_phase(build_graph(FOUR_CHIP_LOG2_N))
    else:
        batch_phase(build_graph(LOG2_N))
        _phase_done("batch", t0)
        serve_phase(build_graph(SERVE_LOG2_N))
        _phase_done("serve", t0)
    _phase_done("all phases", t0)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()

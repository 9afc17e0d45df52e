"""Traffic driver `batch_improved`: whole batch-ranking jobs by Algorithm 2,
back to back.

Each job is one call of the IMPROVED-PAGERANK engine
(`repro.core.distributed_improved.distributed_improved_pagerank`) on the
configuration's graph, held in host memory as a user's loaded dataset
is. The job keys, the reference and the checks are `batch_jobs`': both
engines estimate the same expectation, so they are held to one
comparison. The warm-up runs one whole job, so that every stage program
a window job runs is compiled before the window; the coupon pools of the
graph are computed there too, once (`demand_pool_sizes` memoizes them).

`max_rounds` counts walk steps, as `bench/control.py` gives them: a
stitch carries a walk lambda steps, so the engine gets
ceil(max_rounds / lambda) rounds a stage. A job whose walks a stage cut
there is `unconverged`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np
from jax.sharding import Mesh

from bench.data import csr_graph
from bench.drivers.batch_jobs import checks as _checks
from bench.drivers.batch_jobs import job_keys, reference_pi
from bench.harness import Check, Window
from repro.core.distributed import AXIS
from repro.core.distributed_improved import (distributed_improved_pagerank,
                                             short_walk_length)
from repro.core.graph import CSRGraph

@dataclasses.dataclass
class Job:
    pi: np.ndarray
    rounds: int
    overflow: int
    residual: int
    unfinished: int
    tail_walks: int
    coupons: int
    seconds: float


@dataclasses.dataclass
class State:
    graph: CSRGraph
    mesh: Mesh
    keys: list
    order: np.ndarray
    jobs: List[Job] = dataclasses.field(default_factory=list)


def run_job(config, graph, mesh, key, max_rounds=None) -> Job:
    eng = config["engine"]
    steps = max_rounds or eng["max_rounds"]
    t0 = time.perf_counter()
    r = distributed_improved_pagerank(
        graph, config["eps"], eng["walks_per_node"], key, mesh=mesh,
        max_rounds=-(-steps // short_walk_length(graph.n)),
        use_pallas=eng["use_pallas"])
    return Job(pi=np.asarray(r.pi, np.float64), rounds=int(r.rounds),
               overflow=int(r.dropped), residual=int(r.residual),
               unfinished=int(r.unfinished), tail_walks=int(r.tail_walks),
               coupons=int(r.coupons_created),
               seconds=time.perf_counter() - t0)


def setup(run) -> State:
    config, traffic = run.config, run.traffic
    graph = csr_graph(config["graph"])
    mesh = Mesh(np.array(run.devices[:config["engine"]["shards"]]), (AXIS,))
    keys = job_keys(traffic)
    order = np.random.default_rng(run.seed).permutation(len(keys))
    with run.span("warmup"):
        run_job(config, graph, mesh, keys[order[0]])
    return State(graph=graph, mesh=mesh, keys=keys, order=order)


def failed(job: Job) -> bool:
    return job.overflow != 0 or job.residual != 0 or job.unfinished != 0


def measure(run, st: State, seconds: float) -> Window:
    st.jobs = []
    t0 = time.perf_counter()
    while True:
        key = st.keys[st.order[len(st.jobs) % len(st.keys)]]
        with run.span("job"):
            st.jobs.append(run_job(run.config, st.graph, st.mesh, key))
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    return Window(
        e2e=dict(batch_job_s=elapsed / len(st.jobs)),
        counters=dict(n=st.graph.n, m=st.graph.m,
                      rounds=[j.rounds for j in st.jobs],
                      coupons=[j.coupons for j in st.jobs]),
        attempted=len(st.jobs),
        failed=sum(failed(j) for j in st.jobs))


def checks(config, jobs: List[Job], ref: np.ndarray) -> List[Check]:
    """`batch_jobs`' checks, with `unconverged` the jobs whose walks a
    stage cut at max_rounds."""
    return [Check("unconverged", sum(j.unfinished > 0 for j in jobs), 0)
            if c.name == "unconverged" else c
            for c in _checks(config, jobs, ref)]


def check(run, st: State, window: Window) -> List[Check]:
    return checks(run.config, st.jobs, reference_pi(run.config, st.graph))

"""Traffic driver `batch_jobs`: whole batch-ranking jobs, back to back.

Each job is one call of the counts engine
(`repro.core.distributed_counts.distributed_pagerank_counts`) on the
configuration's graph, held in host memory as a user's loaded dataset
is, so each job pays the engine's own host build (`shard_graph_padded`).
Jobs start while the window is open, so the last one may end after it;
`batch_job_s` is the time from the first job's start to the last job's
end over the number of jobs.

A job's length is set by its longest walk: the round count is the
maximum of millions of geometric walk lengths, and moves by tens of
rounds from key to key. So every seed runs the same pool of job keys
(`job_keys`, from `key_seed`), in an order drawn from the seed: the same
work in another order, and runs of different seeds measure the same
thing. The traffic's window is set so that the pool runs once.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import jax
import numpy as np
from jax.sharding import Mesh

from bench import compare
from bench.data import csr_graph
from bench.harness import Check, Window
from bench.reference import walks
from repro.core.distributed import AXIS
from repro.core.distributed_counts import distributed_pagerank_counts
from repro.core.graph import CSRGraph


@dataclasses.dataclass
class Job:
    pi: np.ndarray
    rounds: int
    overflow: int
    residual: int
    seconds: float


@dataclasses.dataclass
class State:
    graph: CSRGraph
    mesh: Mesh
    keys: list
    order: np.ndarray
    jobs: List[Job] = dataclasses.field(default_factory=list)


def job_keys(traffic) -> list:
    base = jax.random.PRNGKey(int(traffic["key_seed"]))
    return [jax.random.fold_in(base, j) for j in range(traffic["job_keys"])]


def run_job(config, graph, mesh, key, max_rounds=None) -> Job:
    eng = config["engine"]
    t0 = time.perf_counter()
    r = distributed_pagerank_counts(
        graph, config["eps"], eng["walks_per_node"], key, mesh=mesh,
        max_rounds=max_rounds or eng["max_rounds"],
        use_pallas=eng["use_pallas"])
    return Job(pi=np.asarray(r.pi, np.float64), rounds=int(r.rounds),
               overflow=int(r.overflow), residual=int(r.residual),
               seconds=time.perf_counter() - t0)


def setup(run) -> State:
    config, traffic = run.config, run.traffic
    graph = csr_graph(config["graph"])
    mesh = Mesh(np.array(run.devices[:config["engine"]["shards"]]), (AXIS,))
    keys = job_keys(traffic)
    order = np.random.default_rng(run.seed).permutation(len(keys))
    with run.span("warmup"):
        run_job(config, graph, mesh, keys[order[0]],
                max_rounds=traffic["warmup_max_rounds"])
    return State(graph=graph, mesh=mesh, keys=keys, order=order)


def failed(config, job: Job) -> bool:
    return (job.overflow != 0 or job.residual != 0
            or job.rounds >= config["engine"]["max_rounds"])


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
    rounds = [j.rounds for j in st.jobs]
    return Window(
        e2e=dict(batch_job_s=elapsed / len(st.jobs)),
        counters=dict(n=st.graph.n, m=st.graph.m, rounds=rounds),
        attempted=len(st.jobs),
        failed=sum(failed(run.config, j) for j in st.jobs))


def reference_pi(config, graph) -> np.ndarray:
    """E[pi] of the engine's estimator pi = zeta * eps / (n * K): the
    expected visits of one walk from every vertex, times eps / n."""
    eps = config["eps"]
    z = walks.expected_visits(graph.row_ptr, graph.col_idx, graph.n, eps)
    return z * eps / graph.n


def checks(config, jobs: List[Job], ref: np.ndarray) -> List[Check]:
    limits = config["limits"]
    return [
        Check("grouped_l1", max(compare.grouped_l1(j.pi, ref)
                                for j in jobs), limits["grouped_l1"]),
        Check("overflow", sum(j.overflow for j in jobs), 0),
        Check("residual", sum(abs(j.residual) for j in jobs), 0),
        Check("unconverged", sum(j.rounds >= config["engine"]["max_rounds"]
                                 for j in jobs), 0),
    ]


def check(run, st: State, window: Window) -> List[Check]:
    return checks(run.config, st.jobs, reference_pi(run.config, st.graph))

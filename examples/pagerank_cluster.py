"""Distributed PageRank on a device mesh with injected failures.

Simulates a pod: 8 forced host devices, vertex-sharded graph, all_to_all
walk routing, checkpoint-restart supervision with two injected node
failures, and exact-recovery validation.

    python examples/pagerank_cluster.py     (sets its own XLA_FLAGS)
"""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import tempfile

import jax
import numpy as np

from repro.launch.pagerank import run


def main():
    print(f"devices: {len(jax.devices())}")
    with tempfile.TemporaryDirectory() as ckpt_dir:
        print("--- clean run ---")
        pi_clean = run(n=256, eps=0.2, walks_per_node=64,
                       graph_kind="erdos_renyi", checkpoint_dir=None,
                       fail_at=[]).pi
        print("--- run with failures at rounds 6 and 17 ---")
        pi_ft = run(n=256, eps=0.2, walks_per_node=64,
                    graph_kind="erdos_renyi", checkpoint_dir=ckpt_dir,
                    fail_at=[6, 17]).pi
    exact = np.array_equal(np.asarray(pi_clean), np.asarray(pi_ft))
    print(f"recovered run bit-exact with clean run: {exact}")
    assert exact


if __name__ == "__main__":
    main()

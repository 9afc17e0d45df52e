"""Benchmark harness entry: one module per paper claim/table.

    PYTHONPATH=src python -m benchmarks.run

Runs each module in a process of its own, one after another; this parent
never imports JAX, so a module that needs the accelerator can take it.
Exits nonzero if any module failed. Each prints ``name,us_per_call,derived``
CSV:
  bench_rounds      — Theorem 1 & 2 round complexity scaling
  bench_accuracy    — Monte-Carlo accuracy vs K (Avrachenkov claim)
  bench_congestion  — Lemma 1/3 per-edge message bits
  bench_directed    — Theorem 3 directed/LOCAL variant
  bench_engines     — engine throughput (counts vs walk-array vs baseline)
  bench_distributed — multi-shard wire volume: walk-routing vs count lanes
  bench_serve       — PPR query serving: Poisson traffic qps + latency
  roofline_report   — dry-run roofline aggregation (all cells)
"""
import os
import subprocess
import sys

MODULES = [
    "benchmarks.bench_rounds",
    "benchmarks.bench_accuracy",
    "benchmarks.bench_congestion",
    "benchmarks.bench_directed",
    "benchmarks.bench_engines",
    "benchmarks.bench_distributed",
    "benchmarks.bench_serve",
    "benchmarks.roofline_report",
]
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def main() -> int:
    failed = []
    for name in MODULES:
        print(f"\n=== {name} ===", flush=True)
        rc = subprocess.run([sys.executable, "-m", name], cwd=ROOT).returncode
        if rc != 0:
            print(f"{name},0,ERROR=exit code {rc}", flush=True)
            failed.append(name)
    if failed:
        print(f"\nfailed: {', '.join(failed)}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

"""On-chip benchmark of the PageRank engines: one cell, one seed.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, on the machine that holds the chips the
cell asks for. Exits nonzero, printing no result, when JAX's first device
is not a TPU in `bench/peaks.json` or there are fewer chips than the cell
needs. Otherwise the last line of stdout is one JSON object with
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown`
with `--trace 1`), and last `checks`: each number compared with the
reference beside its limit, which also end stderr.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.DeviceError as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

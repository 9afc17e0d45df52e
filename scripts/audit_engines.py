#!/usr/bin/env python
"""CONGEST wire-budget + lint audit over every distributed engine.

Traces each engine's own jitted stage programs to jaxprs, checks every
collective against its declared W-free lane budget, runs the RNG / dtype /
elastic-schema lints, executes the engines on fixture graphs to cross-check
the static widths against runtime telemetry, prints the wire-budget table,
and writes machine-readable AUDIT.json. `--strict` exits non-zero on any
violation — that is the CI gate.

Usage:
    python scripts/audit_engines.py --strict --out AUDIT.json
    python scripts/audit_engines.py --devices 8 --engines walks counts
"""
import argparse
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on any violation (CI gate)")
    ap.add_argument("--out", default="AUDIT.json",
                    help="path for the machine-readable report")
    ap.add_argument("--devices", type=int, default=8,
                    help="forced host device count (shards)")
    ap.add_argument("--engines", nargs="*", default=None,
                    help="subset of engines (default: all five)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="static checks only (skip the fixture runs)")
    ap.add_argument("--eps", type=float, default=0.2)
    ap.add_argument("--walks-per-node", type=int, default=2)
    args = ap.parse_args()

    # must happen before jax import
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.devices}")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

    from repro.analysis.congest import audit_all_engines, format_wire_table

    report = audit_all_engines(
        run_telemetry=not args.no_telemetry,
        eps=args.eps, walks_per_node=args.walks_per_node,
        engines=tuple(args.engines) if args.engines else None)
    print(format_wire_table(report))
    for e in report["engines"].values():
        for v in e["violations"]:
            print(f"VIOLATION [{v['engine']}] {v['kind']} at {v['where']}: "
                  f"{v['message']}")
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")
    if args.strict and not report["ok"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

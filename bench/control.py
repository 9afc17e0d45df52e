"""Readings that set the limits of `correct`: sound runs and the control.

    python3 bench/control.py --workload <cell> [--seeds 12] [--control-seeds 3]

Runs on the chip, in one process, at the cell's own size; the benchmark's
own runs never run it. It prints one JSON line per reading and a summary
line last: for every number the cell compares, the largest that sound
runs give (the lower reading) and the smallest that the control gives
(the upper reading).

The control breaks the guarantee that every walk runs until it ends: the
answer is taken once 95% of the walks would have ended, after
T = ceil(ln 0.05 / ln(1 - eps)) steps, the step that would tempt a
change that wants shorter jobs: the program's own round cap,
`max_rounds=T`, on `--control-seeds` job keys. Sound runs are whole jobs
on `--seeds` job keys, the cell's pool among them.
"""
import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

CONTROL_ENDED = 0.95


def control_steps(eps: float) -> int:
    return math.ceil(math.log(1.0 - CONTROL_ENDED) / math.log(1.0 - eps))


def emit(**kw):
    print(json.dumps(kw), flush=True)


def _summary(sound, control):
    names = sorted({k for r in sound + control for k in r})
    out = {}
    for k in names:
        lo = max((r[k] for r in sound if k in r), default=None)
        hi = min((r[k] for r in control if k in r), default=None)
        out[k] = dict(lower=lo, upper=hi,
                      ratio=(hi / lo if lo and hi is not None else None))
    return out


def batch(run, seeds, control_seeds):
    drv = run.cell.driver
    st = drv.setup(run)
    ref = drv.reference_pi(run.config, st.graph)
    keys = drv.job_keys(dict(run.traffic, job_keys=max(seeds,
                                                       control_seeds)))
    steps = control_steps(run.config["eps"])
    sound, control = [], []
    for k in range(seeds):
        job = drv.run_job(run.config, st.graph, st.mesh, keys[k])
        r = {c.name: c.value for c in drv.checks(run.config, [job], ref)}
        emit(kind="sound", key=k, rounds=job.rounds, seconds=job.seconds,
             **r)
        sound.append(r)
    for k in range(control_seeds):
        job = drv.run_job(run.config, st.graph, st.mesh, keys[k],
                          max_rounds=steps)
        r = {c.name: c.value for c in drv.checks(run.config, [job], ref)}
        emit(kind="control", key=k, rounds=job.rounds, max_rounds=steps,
             **r)
        control.append(r)
    return sound, control


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    try:
        harness.check_devices(devices, cell.chips, harness.load_peaks())
    except harness.DeviceError as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run = harness.Run(cell, 0, 0.0, devices[:cell.chips])
    sound, control = batch(run, args.seeds, args.control_seeds)
    emit(kind="summary", workload=args.workload,
         limits=cell.config["limits"], readings=_summary(sound, control))
    return 0


if __name__ == "__main__":
    sys.exit(main())

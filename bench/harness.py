"""The benchmark's harness: one cell, one seed, one process.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name in `BENCHMARK.json`:

    bench/configs/<config>.json    the deployment as it is run
    bench/traffic/<traffic>.json   the mix; its "driver" names
    bench/drivers/<driver>.py      the code that builds, warms, drives
                                   and checks one kind of traffic
    bench/metrics/<metric>.py      one reader per per-layer metric
    bench/reference/<name>.py      the plain references

A run: check the device, build and warm up (set-up), measure for
`--seconds` (traced when `--trace 1`), read the peak memory, check the
window's answers against the reference, print one JSON line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class DeviceError(RuntimeError):
    """No usable accelerator: the run prints no result and exits nonzero."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct only while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a driver's measured window gives the harness."""
    e2e: Dict[str, float]          # end-to-end metrics by name
    counters: Dict[str, Any]       # host counters for the metric readers
    attempted: int
    failed: int


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Any                    # module with setup / measure / check
    end_to_end: List[dict]
    per_layer: List[dict]


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json`, with its configuration,
    traffic, driver and metrics, each found by its name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    (cfg_entry,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    driver = load_module(
        os.path.join(root, "bench", "drivers", traffic["driver"] + ".py"),
        "bench_driver_" + traffic["driver"])
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, driver=driver,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def load_reader(root: str, metric: str) -> Callable:
    return load_module(os.path.join(root, "bench", "metrics",
                                    metric + ".py"),
                       "bench_metric_" + metric.replace(".", "_")).read


def load_peaks(root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        return json.load(f)


def check_devices(devices, chips: int, peaks: dict) -> Tuple[dict, dict]:
    """(the result's `device` entry, the chip's peaks) or DeviceError:
    the first device must be a TPU of a kind in the peak table, and there
    must be as many as the cell asks for."""
    if not devices or devices[0].platform != "tpu":
        kind = devices[0].platform if devices else "none"
        raise DeviceError(f"no TPU: JAX's first device is {kind!r}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise DeviceError(f"device kind {kind!r} is not in bench/peaks.json")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devices)}")
    return (dict(platform=devices[0].platform, kind=kind, count=chips),
            peaks[kind])


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader sees."""
    trace: Any                     # trace.TraceSummary, or None
    counters: Dict[str, Any]
    peaks: dict


class Run:
    """The context a driver gets: cell, seed, devices, and spans."""

    def __init__(self, cell: Cell, seed: int, seconds: float, devices):
        self.cell = cell
        self.seconds = seconds
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.devices = devices

    def span(self, name: str):
        """A host span in the profiler's trace (free when not tracing)."""
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, require_tpu: bool = True,
        t_start: Optional[float] = None) -> dict:
    """One run of one cell; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(workload, root)
    import jax
    devices = jax.devices()
    if require_tpu:
        device, peaks = check_devices(devices, cell.chips, load_peaks(root))
    else:
        device, peaks = dict(platform=devices[0].platform,
                             kind=devices[0].device_kind,
                             count=cell.chips), {}
    devices = devices[:cell.chips]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    ctx = Run(cell, seed, seconds, devices)
    drv = cell.driver
    state = drv.setup(ctx)
    setup_s = time.perf_counter() - t_start

    summary = None
    trace_dir = os.path.join(root, "bench", "_out", "trace")
    if trace:
        from bench import trace as tr
        shutil.rmtree(trace_dir, ignore_errors=True)
        part = cell.traffic.get("trace_window") or {}
        recorder = tr.Recorder(trace_dir, part.get("offset_s", 0.0),
                               part.get("seconds"))
        recorder.start()
        try:
            window = drv.measure(ctx, state, seconds)
        finally:
            recorder.stop()
    else:
        window = drv.measure(ctx, state, seconds)
    mem = memory_peak_bytes(devices)
    checks = drv.check(ctx, state, window)
    del state
    if trace and os.path.isdir(trace_dir):
        summary = tr.summarize_dir(trace_dir)

    out = dict(correct=all(c.ok for c in checks),
               attempted=int(window.attempted), failed=int(window.failed))
    metrics = {}
    if trace:
        reading = Reading(trace=summary, counters=window.counters,
                          peaks=peaks)
        for m in cell.per_layer:
            value = load_reader(root, m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = dict(value=float(value),
                                          unit=m["unit"])
    else:
        values = dict(window.e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = dict(value=float(values[m["name"]]),
                                      unit=m["unit"])
    out["metrics"] = metrics
    device = dict(device, memory_peak_bytes=mem)
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["breakdown"] = dict(device_ops=summary.device_ops,
                                idle_gaps=summary.idle_gaps)
    out["device"] = device
    out["checks"] = {c.name: dict(value=c.value, limit=c.limit)
                     for c in checks}
    return out


def print_result(out: dict) -> None:
    """The checks as the last lines of stderr, then the result line as
    the last line of stdout."""
    for name, c in out["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"[bench] check {name} = {c['value']!r} limit "
              f"{c['limit']!r} {verdict}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


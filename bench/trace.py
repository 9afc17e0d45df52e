"""From a profiler trace to the benchmark's device numbers.

A traced run records one `jax.profiler` session, marked by the host span
`bench.window`: around the whole measured window, or around a part of it
where the traffic file asks for one (`trace_window`), because the TPU
traces every op execution and the counts sampler runs about a million
of them a round. The reduction reads the session's `.xplane.pb` with
`jax.profiler.ProfileData` and keeps, for each device plane
(`/device:TPU:<i>`), the events of its `XLA Modules` line: one event per
execution of a compiled program, named `<module>(<fingerprint>)`, e.g.
`jit_sample(1348...)`. The per-op lines are skipped.

- busy: the union of the module intervals inside the window, per device,
  averaged over the devices;
- idle share: 1 - busy / window;
- program time: seconds and count of the executions that lie wholly in
  the window, per module name (fingerprint dropped), summed over the
  devices;
- breakdown: the programs that took most device time, and the idle time
  by the innermost host event open at each gap's middle (a `bench.*`
  span of the harness, or one of JAX's own, e.g. `PjitFunction(_step)`),
  averaged over the devices.

All times are seconds. Every function here takes plain tuples, so a test
can check it on a trace recorded on the CPU.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
TPU_PLANE = r"^/device:TPU:\d+$"
MODULE_LINE = r"^XLA Modules$"
HOST_PLANE = r"^/host:CPU$"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float    # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # averaged over devices
    devices: int
    programs: Dict[str, Tuple[float, int]]   # name -> (seconds, executions)
                                       # of the executions wholly inside
    device_ops: List[List]             # [[name, seconds in window], ...]
    idle_gaps: List[List]              # [[label, seconds], ...] top 10

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program(self, name: str) -> Tuple[float, int]:
        return self.programs.get(name, (0.0, 0))


def profile_options():
    """No Python function tracing: host events are the TraceMe spans."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load_planes(path: str, skip_line: str = r"^XLA Ops$|^Async XLA Ops$"
                ) -> Dict[str, Dict[str, List[Event]]]:
    """plane name -> line name -> events, times in seconds; lines named
    like `skip_line` are left out."""
    from jax.profiler import ProfileData
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            if re.search(skip_line, line.name):
                continue
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                start = e.start_ns * 1e-9
                evs.append(Event(e.name, start, start + e.duration_ns * 1e-9))
    return out


def select(planes, plane_re: str, line_re: str) -> Dict[str, List[Event]]:
    """Events of the matching lines, grouped by plane."""
    out: Dict[str, List[Event]] = {}
    for pname, lines in planes.items():
        if not re.search(plane_re, pname):
            continue
        for lname, evs in lines.items():
            if re.search(line_re, lname):
                out.setdefault(pname, []).extend(evs)
    return out


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_seconds(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def clip(ev: Event, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(ev.start, lo), min(ev.end, hi)
    return (s, e) if e > s else None


def program_name(event_name: str) -> str:
    """`jit_sample(1348...)` -> `jit_sample`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The intervals of [lo, hi] that `busy` leaves uncovered."""
    out, t = [], lo
    for s, e in merge(busy):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def label_of(spans: Sequence[Event], t: float) -> str:
    """Name of the innermost (latest-starting) span open at time t."""
    best = None
    for sp in spans:
        if sp.start <= t < sp.end and (best is None or sp.start > best.start):
            best = sp
    return best.name if best is not None else "no host event"


def summarize(planes, *, device_plane: str = TPU_PLANE,
              module_line: str = MODULE_LINE, module_re: str = r".",
              host_plane: str = HOST_PLANE) -> TraceSummary:
    """Reduce one traced window (see the module docstring). The device
    events are those of `module_line` on each `device_plane` whose names
    match `module_re`."""
    host = [e for evs in select(planes, host_plane, r".").values()
            for e in evs]
    windows = [e for e in host if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    inner = [e for e in host if e.name != WINDOW_SPAN
             and e.end > lo and e.start < hi]
    per_device = select(planes, device_plane, module_line)
    whole: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0.0, 0])
    clipped = collections.Counter()
    busy_total = 0.0
    idle = collections.Counter()
    for evs in per_device.values():
        busy = []
        for ev in evs:
            if not re.search(module_re, ev.name):
                continue
            iv = clip(ev, lo, hi)
            if iv is None:
                continue
            busy.append(iv)
            name = program_name(ev.name)
            clipped[name] += iv[1] - iv[0]
            if lo <= ev.start and ev.end <= hi:
                whole[name][0] += ev.end - ev.start
                whole[name][1] += 1
        busy_total += union_seconds(busy)
        for s, e in gaps(busy, lo, hi):
            idle[label_of(inner, 0.5 * (s + e))] += (e - s) / len(per_device)
    n_dev = len(per_device)
    return TraceSummary(
        window_s=hi - lo,
        busy_s=busy_total / n_dev if n_dev else 0.0,
        devices=n_dev,
        programs={k: (v[0], int(v[1])) for k, v in whole.items()},
        device_ops=[[k, v] for k, v in clipped.most_common(10)],
        idle_gaps=[[k, v] for k, v in idle.most_common(10)])


def summarize_dir(log_dir: str, **kw) -> TraceSummary:
    return summarize(load_planes(find_xplane(log_dir)), **kw)


class Recorder(threading.Thread):
    """Records one profiler session, marked by the `bench.window` span:
    from `offset_s` after `start()` for `seconds` (None: until `stop()`).
    Runs on its own thread so that the traced part of a window can begin
    and end while the window's loop is inside the program."""

    def __init__(self, log_dir: str, offset_s: float = 0.0,
                 seconds: Optional[float] = None):
        super().__init__(daemon=True)
        self.log_dir, self.offset_s, self.seconds = log_dir, offset_s, seconds
        self.done = threading.Event()

    def run(self):
        import jax
        if self.done.wait(self.offset_s):
            return
        jax.profiler.start_trace(self.log_dir,
                                 profiler_options=profile_options())
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                self.done.wait(self.seconds)
        finally:
            jax.profiler.stop_trace()

    def stop(self):
        self.done.set()
        self.join()

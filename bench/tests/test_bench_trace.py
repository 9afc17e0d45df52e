"""The trace-to-metrics reduction (bench/trace.py), on hand-made
intervals and on a small trace recorded on the CPU backend
(`fixtures/cpu_window.xplane.pb`, see `record_trace_fixture.py`). On the
CPU the programs' `PjitFunction(...)` host events stand in for the TPU's
`XLA Modules` events."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import trace  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "cpu_window.xplane.pb")
CPU = dict(device_plane=r"^/host:CPU$", module_line=r"^python3$",
           module_re=r"^PjitFunction\(")


def test_union_merges_overlaps_and_touching_intervals():
    ivs = [(0.0, 1.0), (0.5, 2.0), (2.0, 2.5), (3.0, 4.0), (3.2, 3.4)]
    assert trace.merge(ivs) == [(0.0, 2.5), (3.0, 4.0)]
    assert trace.union_seconds(ivs) == pytest.approx(3.5)
    assert trace.union_seconds([]) == 0.0


def test_gaps_are_the_window_minus_the_union():
    busy = [(1.0, 2.0), (1.5, 3.0), (5.0, 7.0)]
    assert trace.gaps(busy, 0.0, 6.0) == [(0.0, 1.0), (3.0, 5.0)]
    assert trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_gap_label_is_the_innermost_open_span():
    spans = [trace.Event("bench.job", 0.0, 10.0),
             trace.Event("bench.step", 2.0, 4.0)]
    assert trace.label_of(spans, 3.0) == "bench.step"
    assert trace.label_of(spans, 5.0) == "bench.job"
    assert trace.label_of(spans, 11.0) == "no host event"


def test_program_name_drops_the_fingerprint():
    assert trace.program_name("jit_sample(13489748691510342799)") == \
        "jit_sample"
    assert trace.program_name("jit__step(1)") == "jit__step"


@pytest.fixture(scope="module")
def planes():
    return trace.load_planes(FIXTURE)


def _window_and_programs(planes):
    host = planes["/host:CPU"]
    (win,) = [e for evs in host.values() for e in evs
              if e.name == trace.WINDOW_SPAN]
    progs = [e for e in host["python3"] if e.name.startswith("PjitFunction(")
             and win.start <= e.start and e.end <= win.end]
    return win, progs


def test_recorded_window_busy_idle_and_program_time(planes):
    s = trace.summarize(planes, **CPU)
    win, progs = _window_and_programs(planes)
    assert s.devices == 1
    assert s.window_s == pytest.approx(win.end - win.start)
    # busy: a plain sweep over the program events
    events = sorted((e.start, e.end) for e in progs)
    busy, t = 0.0, win.start
    for a, b in events:
        busy += max(0.0, b - max(a, t))
        t = max(t, b)
    assert s.busy_s == pytest.approx(busy)
    assert s.idle_share == pytest.approx(1.0 - busy / s.window_s)
    for name in ("PjitFunction(sample)", "PjitFunction(exchange)"):
        mine = [e for e in progs if e.name == name]
        seconds, runs = s.program(name)
        assert runs == len(mine) > 0
        assert seconds == pytest.approx(sum(e.end - e.start for e in mine))


def test_recorded_idle_gaps_carry_the_host_span(planes):
    s = trace.summarize(planes, **CPU)
    gaps = dict(s.idle_gaps)
    # three 30 ms sleeps, each inside its own `bench.sleep` span
    assert gaps["bench.sleep"] >= 3 * 0.03
    assert max(gaps, key=gaps.get) == "bench.sleep"
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    assert s.device_ops[0][1] >= s.device_ops[-1][1]


def test_a_trace_without_device_planes_reads_no_device():
    s = trace.summarize(trace.load_planes(FIXTURE))
    assert s.devices == 0 and s.busy_s == 0.0 and s.programs == {}

"""The per-layer metric readers, on hand-made readings."""
import os
import types

import pytest

from bench import harness
from bench.data.graph500 import graph500
from bench.tests.tiny import ROOT


def _reader(name):
    return harness.load_reader(ROOT, name)


def _reading(programs=None, busy=0.0, window=1.0, **counters):
    tr = None
    if programs is not None:
        tr = types.SimpleNamespace(
            programs=programs, busy_s=busy, window_s=window, devices=1,
            idle_share=1.0 - busy / window,
            program=lambda name: programs.get(name, (0.0, 0)))
    return harness.Reading(trace=tr, counters=counters,
                           peaks=harness.load_peaks()["TPU v5 lite"])


def test_sample_roofline_counts_bytes_from_n_and_m():
    """A triangle and one more vertex: n = 4, m = 6 directed entries; a
    round reads 2 int32 per vertex and writes 1 per entry."""
    mod = harness.load_module(
        os.path.join(ROOT, "bench", "metrics", "counts_sample_roofline.py"),
        "roofline_probe")
    assert mod.least_bytes(4, 6) == 4 * (2 * 4 + 6) == 56
    row_ptr, col_idx, out_deg = graph500(8, 1)
    n, m = len(out_deg), len(col_idx)
    assert mod.least_bytes(n, m) == 4 * (2 * n + int(out_deg.sum()))
    r = _reading({"jit_sample": (0.2, 2)}, n=4, m=6)
    assert mod.read(r) == pytest.approx(100 * 56 / 819e9 / 0.1)


def test_program_times_are_per_execution():
    r = _reading({"jit_sample": (0.3, 3), "jit_exchange": (0.06, 3)})
    assert _reader("counts_sample_ms")(r) == pytest.approx(100.0)
    assert _reader("counts_exchange_ms")(r) == pytest.approx(20.0)


def test_readers_return_nothing_without_something_to_read():
    empty = _reading({})
    for name in ("counts_sample_ms", "counts_exchange_ms",
                 "counts_sample_roofline"):
        assert _reader(name)(empty) is None
        assert _reader(name)(_reading(None)) is None
    for name in ("batch_rounds", "batch_idle_share"):
        assert _reader(name)(_reading(None)) is None


def test_idle_share_and_counters():
    r = _reading({}, busy=0.75, window=1.0, rounds=[90, 100])
    assert _reader("batch_idle_share")(r) == pytest.approx(25.0)
    assert _reader("batch_rounds")(r) == 95

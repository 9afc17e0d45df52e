"""`batch.g500.improved` at its CPU-test size: `correct` comes out false
for the control and for the faults the cell can have, and its per-layer
readers read the program's own spans and counts, or nothing where the
program keeps none.

The control breaks the guarantee that every walk runs until it ends
(`bench/control.py`): T = 19 walk steps, which the driver gives the
engine as ceil(T / lambda) stitch rounds. The faults: Phase 3, which
counts the coupons' visits, skipped; and, under pools too small for the
walks (Lemma 2's d(v) * eta with eta = 1), the naive tail's walks
dropped instead of walked.
"""
import math
import os

import jax
import numpy as np
import pytest

import repro.core.distributed_improved as di
from bench import control, harness
from bench.tests.tiny import ROOT, tiny_root

CELL = "batch.g500.improved"
SEED = 2**33 + 13


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("bench")))


def _correct(root):
    out = harness.run(CELL, SEED, 2.0, False, root=root, require_tpu=False)
    return out["correct"], out["checks"]


def _cap_rounds(real, graph, eps, k, key, **kw):
    lam = di.short_walk_length(graph.n)
    kw["max_rounds"] = min(kw["max_rounds"],
                           math.ceil(control.control_steps(eps) / lam))
    return real(graph, eps, k, key, **kw)


def _phase3_skipped(real, graph, eps, k, key, **kw):
    make = di._make_p3_step

    def skipped(*a, **kw2):
        count = make(*a, **kw2)

        @jax.jit
        def nothing_counted(traj, used, zeta):
            _, entries, nbytes = count(traj, used, zeta)
            return zeta, entries, nbytes
        return nothing_counted
    di._make_p3_step = skipped
    try:
        return real(graph, eps, k, key, **kw)
    finally:
        di._make_p3_step = make


def _tail_dropped(real, graph, eps, k, key, **kw):
    make = di._make_tail_step

    def dropping(*a, **kw2):
        step = make(*a, **kw2)

        def drop(rp, ci, dg, state):
            state.pos = np.full(state.pos.shape, -1, np.int32)
            return step(rp, ci, dg, state)
        return drop
    di._make_tail_step = dropping
    try:
        r = real(graph, eps, k, key, eta=1, **kw)
    finally:
        di._make_tail_step = make
    assert r.tail_walks > 0
    return r


@pytest.mark.parametrize("fault", [_cap_rounds, _phase3_skipped,
                                   _tail_dropped])
def test_improved_control_and_faults_are_not_correct(root, monkeypatch,
                                                     fault):
    real = di.distributed_improved_pagerank
    monkeypatch.setattr(di, "distributed_improved_pagerank",
                        lambda *a, **kw: fault(real, *a, **kw))
    ok, checks = _correct(root)
    assert not ok, checks


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "bench", "metrics", name + ".py"),
        "improved_probe_" + name).read


SPAN_READERS = ["improved_rounds", "improved_tail_walks",
                "improved_p1_round_ms", "improved_p2_round_ms"]


def _ring(jobs, ms=1_000_000):
    """Hand-made span records of whole improved jobs, back to back on the
    host clock: `jobs` is a list of (build, [p1 round], [p2 round], tail
    walks) in ms."""
    from repro.runtime.tracing import Record
    recs, t = [], 0

    def rec(name, start, end, parent, **counts):
        recs.append(Record(id=len(recs), name=name, start_ns=start,
                           end_ns=end, parent=parent, counts=counts))
        return recs[-1]

    for build, p1, p2, tail in jobs:
        job = rec("improved.job", t, None, None,
                  rounds=len(p1) + len(p2) + 1, tail_walks=tail)
        rec("improved.build", t, t + build * ms, job.id)
        t += build * ms
        for name, rounds in (("round.phase1", p1), ("round.phase2", p2),
                             ("round.phase3", [1])):
            for whole in rounds:
                rec(name, t, t + whole * ms, job.id)
                t += whole * ms
        rec("improved.finish", t, t + ms, job.id)
        t += ms
        job.end_ns = t
    return recs


# the warm-up job, then the window's two jobs: 100 + 4x50 + 3x10 + 1 + 1
# = 332 ms each, the first window job opening at 332 ms
RING = [(100, [50] * 4, [10] * 3, 9), (100, [50] * 4, [10] * 3, 0),
        (100, [60] * 4, [20] * 3, 2)]


@pytest.mark.parametrize("name,offset_s,want", [
    ("improved_rounds", 10.0, 8.0),
    ("improved_tail_walks", 10.0, 1.0),
    ("improved_p1_round_ms", 10.0, 55.0),
    ("improved_p2_round_ms", 10.0, 15.0),
    # a session that can begin 0.3 s into the window: the first job's
    # Phase-1 rounds end by 300 ms of it, its stitch rounds after
    ("improved_p1_round_ms", 0.35, 50.0),
    ("improved_p2_round_ms", 0.35, None),
    ("improved_rounds", 0.35, 8.0),
])
def test_improved_span_readers_take_the_windows_jobs(monkeypatch, name,
                                                     offset_s, want):
    from bench import improved_spans
    monkeypatch.setattr(improved_spans, "ring", lambda: _ring(RING))
    monkeypatch.setattr(improved_spans, "session_offset_s", lambda: offset_s)
    reading = harness.Reading(trace=None, counters=dict(rounds=[8, 8]),
                              peaks={})
    got = _reader(name)(reading)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_improved_readers_read_nothing_without_the_spans(monkeypatch, name):
    """A program that records no `improved.job` span (the counts job's
    alone, or no ring at all) gives no reading, and no error."""
    from bench import improved_spans
    from repro.runtime.tracing import Record
    counts_only = [Record(id=0, name="counts.job", start_ns=0, end_ns=9,
                          parent=None, counts=dict(rounds=8))]
    reading = harness.Reading(trace=None, counters=dict(rounds=[8]),
                              peaks={})
    for recs in (counts_only, None):
        monkeypatch.setattr(improved_spans, "ring", lambda: recs)
        assert _reader(name)(reading) is None


class _Trace:
    def __init__(self, programs):
        self.programs = programs

    def program(self, name):
        return self.programs.get(name, (0.0, 0))


def test_assign_readers_from_the_device_trace():
    peaks = harness.load_peaks()["TPU v5 lite"]
    counters = dict(n=100, m=900, coupons=[1000, 1000])
    trace = _Trace({"jit_p1_assign": (0.004, 2)})
    r = harness.Reading(trace=trace, counters=counters, peaks=peaks)
    assert _reader("improved_p1_assign_ms")(r) == pytest.approx(2.0)
    least = 4 * (5 * 1000 + 2 * (100 + 900)) / peaks["hbm_bytes_per_s"]
    assert _reader("improved_p1_assign_roofline")(r) == pytest.approx(
        100 * least / 0.002)
    none = harness.Reading(trace=_Trace({}), counters=counters, peaks=peaks)
    assert _reader("improved_p1_assign_ms")(none) is None
    assert _reader("improved_p1_assign_roofline")(none) is None


def test_traced_tiny_run_reads_the_program_metrics(root):
    """A traced run at CPU size: the program counters and the round spans
    before the session are read from the ring; the CPU records no TPU
    device plane, so the device readers read nothing there."""
    out = harness.run(CELL, SEED, 4.0, True, root=root, require_tpu=False)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert got["improved_tail_walks"]["value"] == 0.0
    assert got["improved_rounds"]["value"] > 4
    assert got["improved_p1_round_ms"]["value"] > 0
    assert got["improved_p2_round_ms"]["value"] > 0
    assert "improved_p1_assign_ms" not in got

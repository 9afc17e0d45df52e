"""The program-span readers (`bench/program_spans.py` and the batch
job's span metrics): on hand-made span lists, and in a traced run of the
tiny cell on the CPU."""
import json
import os

import pytest

from bench import harness
from bench.tests.tiny import ROOT, tiny_root


def _reader(name):
    return harness.load_reader(ROOT, name)


def _reading(**counters):
    """A reading of an untraced window: its counters alone."""
    return harness.Reading(trace=None, counters=counters,
                           peaks=harness.load_peaks()["TPU v5 lite"])


def _ring(jobs, ms=1_000_000):
    """Hand-made span records of whole counts jobs, back to back on the
    host clock: `jobs` is a list of (build, [(round, sync), ...]) in ms."""
    from repro.runtime.tracing import Record
    recs, t = [], 0

    def rec(name, start, end, parent, **counts):
        recs.append(Record(id=len(recs), name=name, start_ns=start,
                           end_ns=end, parent=parent, counts=counts))
        return recs[-1]

    for build, rounds in jobs:
        job = rec("counts.job", t, None, None, rounds=len(rounds))
        rec("counts.build", t, t + build * ms, job.id)
        t += build * ms
        for i, (whole, sync) in enumerate(rounds):
            rnd = rec("round.counts", t, t + whole * ms, job.id)
            rec("counts.sample", t, t + ms // 10, rnd.id)
            rec("counts.sync", t + (whole - sync) * ms, t + whole * ms,
                rnd.id, active=len(rounds) - 1 - i)
            t += whole * ms
        rec("counts.finish", t, t + ms, job.id)
        t += ms
        job.end_ns = t
    return recs


# a warm-up job cut to 1 round (0-510 ms), then the window's jobs of 2
# rounds (510-641 ms: build to 610, rounds to 620 and 640) and 1 round
# (641-972 ms: build to 941, round to 971)
RING = [(500, [(9, 8)]), (100, [(10, 9), (20, 18)]), (300, [(30, 27)])]
SPAN_READERS = ["batch_build_ms", "batch_round_ms", "batch_round_host_ms"]


def _span_reader(name):
    return harness.load_module(
        os.path.join(ROOT, "bench", "metrics", name + ".py"),
        "span_probe_" + name)


@pytest.mark.parametrize("name,want", [
    ("batch_build_ms", (100 + 300) / 2),
    ("batch_round_ms", (10 + 20 + 30) / 3),
    ("batch_round_host_ms", (1 + 2 + 3) / 3),
])
def test_span_readers_take_the_windows_jobs(name, want):
    """With no session in the window, every span of its jobs counts."""
    mod = _span_reader(name)
    assert mod.from_spans(_ring(RING), [2, 1], 10.0) == pytest.approx(want)
    # the last job alone, when the window ran one
    last = dict(batch_build_ms=300, batch_round_ms=30,
                batch_round_host_ms=3)[name]
    assert mod.from_spans(_ring(RING), [1], 10.0) == pytest.approx(last)


@pytest.mark.parametrize("name,offset_s,want", [
    # the session can begin 50 ms before offset_s after the window's
    # first job opens (510 ms): spans that end later are left out
    ("batch_build_ms", 0.2, 100),
    ("batch_round_ms", 0.2, (10 + 20) / 2),
    ("batch_round_host_ms", 0.2, (1 + 2) / 2),
    ("batch_build_ms", 0.165, 100),
    ("batch_round_ms", 0.165, 10),
    ("batch_round_host_ms", 0.165, 1),
    ("batch_build_ms", 0.14, None),
    ("batch_round_ms", 0.14, None),
    ("batch_round_host_ms", 0.14, None),
])
def test_span_readers_stop_where_the_session_can_begin(name, offset_s,
                                                       want):
    got = _span_reader(name).from_spans(_ring(RING), [2, 1], offset_s)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_need_the_windows_jobs(name):
    mod = _span_reader(name)
    assert mod.from_spans(None, [2, 1], 10.0) is None          # no ring
    assert mod.from_spans(_ring(RING), None, 10.0) is None     # no jobs
    assert mod.from_spans(_ring(RING), [], 10.0) is None
    assert mod.from_spans(_ring(RING[1:]), [1, 2, 1], 10.0) is None
    with pytest.raises(ValueError, match="counts.job spans ran"):
        mod.from_spans(_ring(RING), [1, 2], 10.0)
    # in the benchmark's process the reader takes the program's own ring
    from repro.runtime import tracing
    tracing.clear()
    assert _reader(name)(_reading(rounds=[2, 1])) is None


def test_span_readers_take_the_traffics_session_offset():
    from bench import program_spans
    with open(program_spans.TRAFFIC) as f:
        want = json.load(f)["trace_window"]["offset_s"]
    assert program_spans.session_offset_s() == want > program_spans.MARGIN_S


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("bench")))


def test_tiny_cell_traced_reports_program_spans(root):
    """A traced run of `batch.g500` at its CPU-test size reads the round
    driver's and the host build's spans (a 1 s window ends before the
    traced session's offset, so all of its jobs; the CPU has no device
    trace to read)."""
    out = harness.run("batch.g500", 7, 1.0, True, root=root,
                      require_tpu=False)
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"batch_rounds", "batch_build_ms", "batch_round_ms",
                      "batch_round_host_ms"}
    assert m["batch_build_ms"] > 0
    assert 0 < m["batch_round_host_ms"] <= m["batch_round_ms"]
    assert out["metrics"]["batch_round_ms"]["unit"] == "ms/round"

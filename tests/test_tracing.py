"""Program spans and counts (`runtime.tracing`): the ring itself, the
spans the counts engine records per job and per round, and their host
events in a profiler trace on the same clock."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.distributed_counts import (distributed_pagerank_counts,
                                           shard_graph_padded)
from repro.graphs import barabasi_albert_hub, erdos_renyi
from repro.runtime import tracing


@pytest.fixture(autouse=True)
def _empty_ring():
    tracing.clear()
    yield
    tracing.clear()


def _by_name(records, name):
    return [r for r in records if r.name == name]


def test_nested_spans_record_their_parents():
    with tracing.span("a.outer") as outer:
        with tracing.span("a.inner") as inner:
            pass
        with tracing.span("a.inner"):
            pass
    with tracing.span("a.next"):
        pass
    recs = tracing.spans()
    assert [r.name for r in recs] == ["a.outer", "a.inner", "a.inner",
                                      "a.next"]
    assert [r.parent for r in recs] == [None, outer.id, outer.id, None]
    assert inner.parent == outer.id
    for r in recs:
        assert r.end_ns >= r.start_ns
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert len({r.id for r in recs}) == 4


def test_counts_go_to_the_innermost_open_span():
    with tracing.span("a.outer", n=3) as outer:
        with tracing.span("a.inner") as inner:
            tracing.count("active", 5)
            tracing.count("active", 2)
        tracing.count("rounds", 1)
    assert inner.counts == {"active": 7}
    assert outer.counts == {"n": 3, "rounds": 1}
    with pytest.raises(RuntimeError, match="outside any span"):
        tracing.count("active", 1)


def test_a_span_closes_when_its_block_raises():
    with pytest.raises(ValueError):
        with tracing.span("a.outer"):
            with tracing.span("a.inner"):
                raise ValueError("boom")
    with tracing.span("a.after") as after:
        pass
    assert all(r.end_ns is not None for r in tracing.spans())
    assert after.parent is None


def test_the_ring_keeps_the_newest_records():
    for i in range(tracing.RING_SIZE + 10):
        with tracing.span("a.step", i=i):
            pass
    recs = tracing.spans()
    assert len(recs) == tracing.RING_SIZE
    assert recs[0].counts["i"] == 10
    assert recs[-1].counts["i"] == tracing.RING_SIZE + 9


def test_clear_empties_the_ring():
    with tracing.span("a.step"):
        pass
    assert tracing.spans()
    tracing.clear()
    assert tracing.spans() == []


GRAPH = dict(n=64, avg_deg=4.0, seed=1)


def _job(**kw):
    g = erdos_renyi(GRAPH["n"], GRAPH["avg_deg"], seed=GRAPH["seed"])
    return g, distributed_pagerank_counts(g, 0.3, 8, jax.random.PRNGKey(4),
                                          **kw)


def _job_spans(recs):
    (job,) = _by_name(recs, "counts.job")
    kids = [r for r in recs if r.parent == job.id]
    return job, kids


def test_counts_job_records_build_rounds_and_finish():
    _, res = _job()
    recs = tracing.spans()
    job, kids = _job_spans(recs)
    assert job.counts == dict(rounds=res.rounds)
    assert [r.name for r in kids if not r.name.startswith("round.")] == \
        ["counts.build", "counts.finish"]
    rounds = _by_name(kids, "round.counts")
    assert len(rounds) == res.rounds == len(kids) - 2
    assert all(a.end_ns <= b.start_ns for a, b in zip(rounds, rounds[1:]))
    active = []
    for r in rounds:
        inner = [c for c in recs if c.parent == r.id]
        assert [c.name for c in inner] == ["counts.sample",
                                           "counts.exchange", "counts.sync"]
        active.append(inner[-1].counts["active"])
    assert active[-1] == 0 and all(a > 0 for a in active[:-1])
    # everything but a few host statements of the job lies in its children
    covered = sum(r.end_ns - r.start_ns for r in kids)
    assert covered <= job.end_ns - job.start_ns


def test_build_counts_the_sampler_depth():
    """`counts.build` carries `sampler_depth`: the split levels a round
    of the layout's deepest bucket, ceil(log2) of its widest bucket (and
    the exchange's sorted slots, `exchange_sort_entries`)."""
    g = barabasi_albert_hub(96, 3, seed=4)
    distributed_pagerank_counts(g, 0.3, 4, jax.random.PRNGKey(4))
    (build,) = _by_name(tracing.spans(), "counts.build")
    layout = shard_graph_padded(g, jax.device_count()).layout
    want = int(np.ceil(np.log2(max(layout.widths))))
    assert build.counts == dict(sampler_depth=want,
                                exchange_sort_entries=layout.total_edges)
    assert want == int(np.ceil(np.log2(g.max_out_deg))) >= 4


def test_supervised_rounds_are_spans_too(tmp_path):
    """Under the checkpoint-restart supervisor a round is still one
    `round.counts` span, and a replayed round is another."""
    _, plain = _job()
    tracing.clear()
    _, res = _job(checkpoint_dir=str(tmp_path / "a"), checkpoint_every=2)
    assert np.array_equal(np.asarray(res.zeta), np.asarray(plain.zeta))
    job, kids = _job_spans(tracing.spans())
    assert len(_by_name(kids, "round.counts")) == res.rounds
    tracing.clear()
    _, res = _job(checkpoint_dir=str(tmp_path / "b"), checkpoint_every=2,
                  fail_at=[3])
    assert res.restarts == 1
    assert np.array_equal(np.asarray(res.zeta), np.asarray(plain.zeta))
    job, kids = _job_spans(tracing.spans())
    assert job.counts["rounds"] == res.rounds
    assert len(_by_name(kids, "round.counts")) > res.rounds


def test_launch_line_totals_the_newest_jobs_spans():
    from repro.launch.pagerank import _span_totals
    _job()
    _, res = _job()
    line = _span_totals(tracing.spans())
    names = [part.split(" ")[0] for part in line.split(", ")]
    assert names == ["counts.build", "round.counts", "counts.sample",
                     "counts.exchange", "counts.sync", "counts.finish"]
    assert f"round.counts {res.rounds} x " in line
    assert "counts.build 1 x " in line


def _host_events(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    return {k: sorted(v) for k, v in out.items()}


def test_program_spans_are_host_events_on_the_profilers_clock(tmp_path):
    """Every span in the ring appears in the profiler's trace as a host
    event of its name, with its duration and its offset from the job's
    start each within 100 us."""
    _job()                                   # compile outside the session
    tracing.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _job()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    recs = tracing.spans()
    assert len(recs) > 10
    (job,) = _by_name(recs, "counts.job")
    (job_ev,) = events["counts.job"]
    tol = 100_000
    for name in {r.name for r in recs}:
        mine = sorted((r.start_ns, r.end_ns) for r in _by_name(recs, name))
        theirs = events.get(name, [])
        assert len(theirs) == len(mine), name
        for (s, e), (ts, te) in zip(mine, theirs):
            assert abs((e - s) - (te - ts)) <= tol, name
            assert abs((s - job.start_ns) - (ts - job_ev[0])) <= tol, name

"""The Algorithm-2 batch job's program spans and counts, read from the
program's own ring (`repro.runtime.tracing`) in the benchmark's process.

The window's jobs are the last k `improved.job` spans in the ring, k the
number of jobs the window ran (the length of `counters["rounds"]`): the
warm-up job runs before them and the check runs no engine. Their
`rounds` counts must equal the window's, in order. The job counts
(`rounds`, `tail_walks`, ...) are read from every window job.

Round spans are read as `bench/program_spans.py` reads the counts job's:
only those that end before the traced session can begin,
`trace_window.offset_s` of this traffic (`bench/traffic/
batch_jobs_improved.json`) after the first window job opens, less
`MARGIN_S`, because a profiler session slows the host from then to the
end of the process. A program without the ring, or a window with no such
span, has nothing to read: the readers give None.
"""
from __future__ import annotations

import collections
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

JOB = "improved.job"
TRAFFIC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "traffic", "batch_jobs_improved.json")
MARGIN_S = 0.05


def ring() -> Optional[list]:
    """The program's span records, or None where it keeps none."""
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    return tracing.spans()


def session_offset_s() -> float:
    """Seconds from the window's start to the traced session's."""
    with open(TRAFFIC) as f:
        return float(json.load(f).get("trace_window", {}).get("offset_s",
                                                               0.0))


def window_jobs(records: Optional[Sequence],
                rounds: Optional[Sequence[int]]) -> Optional[list]:
    """The window's `improved.job` spans, or None when the records hold
    fewer than the window ran."""
    if records is None or not rounds:
        return None
    jobs = [r for r in records if r.name == JOB]
    if len(jobs) < len(rounds):
        return None
    jobs = jobs[-len(rounds):]
    got = [j.counts.get("rounds") for j in jobs]
    if got != list(rounds):
        raise ValueError(f"the last {len(rounds)} {JOB} spans ran {got} "
                         f"rounds, the window's jobs {list(rounds)}")
    return jobs


def job_mean(records, rounds, count: str) -> Optional[float]:
    """The mean of a job count over the window's jobs."""
    jobs = window_jobs(records, rounds)
    if jobs is None or any(count not in j.counts for j in jobs):
        return None
    return sum(j.counts[count] for j in jobs) / len(jobs)


def before_session(records, rounds, offset_s: float, name: str
                   ) -> Optional[Tuple[list, Dict[int, list]]]:
    """(the window jobs' `name` children that end before the traced
    session can begin, span id -> its child spans), or None."""
    jobs = window_jobs(records, rounds)
    if jobs is None:
        return None
    children: Dict[int, List] = collections.defaultdict(list)
    for r in records:
        children[r.parent].append(r)
    cutoff = jobs[0].start_ns + int(1e9 * (offset_s - MARGIN_S))
    spans = [s for j in jobs for s in children[j.id]
             if s.name == name and s.end_ns <= cutoff]
    return (spans, children) if spans else None


def mean_ms(records, rounds, offset_s: float, name: str) -> Optional[float]:
    """Mean milliseconds of the window jobs' `name` spans that end before
    the traced session can begin."""
    found = before_session(records, rounds, offset_s, name)
    if found is None:
        return None
    spans, _ = found
    return 1e3 * sum(s.seconds for s in spans) / len(spans)

"""The batch job's program spans, read from the program's own ring
(`repro.runtime.tracing`) in the benchmark's process.

The window's jobs are the last k `counts.job` spans in the ring, k the
number of jobs the window ran (the length of `counters["rounds"]`): the
warm-up job runs before them and the check runs no engine. Their
`rounds` counts must equal the window's, in order.

The readers run only in a traced run, and a profiler session slows the
host's part of every round from the moment it starts to the end of the
process (PERF.md, Findings). So they read only the spans that end
before the session can begin: `trace_window.offset_s` of the traffic
(`bench/traffic/batch_jobs.json`) after the first window job opens, less
`MARGIN_S` for the thread that starts the session. A program without the
ring, or a window with no such span, has nothing to read: the readers
give None.
"""
from __future__ import annotations

import collections
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

TRAFFIC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "traffic", "batch_jobs.json")
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


def before_session(records: Optional[Sequence],
                   rounds: Optional[Sequence[int]], offset_s: float,
                   name: str) -> Optional[Tuple[list, Dict[int, list]]]:
    """(the window jobs' `name` children that end before the traced
    session can begin, span id -> its child spans), or None when the
    records hold fewer jobs than the window ran or no such child."""
    if records is None or not rounds:
        return None
    jobs = [r for r in records if r.name == "counts.job"]
    if len(jobs) < len(rounds):
        return None
    jobs = jobs[-len(rounds):]
    got = [j.counts.get("rounds") for j in jobs]
    if got != list(rounds):
        raise ValueError(f"the last {len(rounds)} counts.job spans ran "
                         f"{got} rounds, the window's jobs {list(rounds)}")
    children: Dict[int, List] = collections.defaultdict(list)
    for r in records:
        children[r.parent].append(r)
    cutoff = jobs[0].start_ns + int(1e9 * (offset_s - MARGIN_S))
    spans = [s for j in jobs for s in children[j.id]
             if s.name == name and s.end_ns <= cutoff]
    return (spans, children) if spans else None

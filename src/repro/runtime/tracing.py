"""Program spans and counts, kept in a bounded in-process ring.

    with tracing.span("counts.sync"):
        active = jax.device_get(active)
        tracing.count("active", int(active))

`span(name, **counts)` times a block of host code and appends one
`Record` to the ring: its name, start and end from
`time.perf_counter_ns()`, the id of the span that was open around it on
the same thread (its parent), and its counts. `count(name, n)` adds to a
count of the innermost open span. Each span also opens a
`jax.profiler.TraceAnnotation` of the same name, so whenever a profiler
session is on it appears in the trace as a host event, on the same clock
as the device's events.

The ring is always on and keeps the newest `RING_SIZE` records; a span
costs a few microseconds of host time. Readers take `spans()` in-process;
there is no exporter. Names are `<layer>.<what>`.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Dict, Iterator, List, Optional

import jax

RING_SIZE = 65536

_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_ids = itertools.count()
_local = threading.local()


@dataclasses.dataclass
class Record:
    """One span. `end_ns` is None while the span is open; `parent` is the
    `id` of the enclosing span on the same thread, or None."""
    id: int
    name: str
    start_ns: int
    end_ns: Optional[int]
    parent: Optional[int]
    counts: Dict[str, int]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def _open() -> List[Record]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str, **counts: int) -> Iterator[Record]:
    """Time the enclosed block as one span named `name`, with `counts`."""
    stack = _open()
    rec = Record(id=next(_ids), name=name, start_ns=0, end_ns=None,
                 parent=stack[-1].id if stack else None, counts=counts)
    _ring.append(rec)
    stack.append(rec)
    try:
        with jax.profiler.TraceAnnotation(name):
            rec.start_ns = time.perf_counter_ns()
            try:
                yield rec
            finally:
                rec.end_ns = time.perf_counter_ns()
    finally:
        stack.pop()


def count(name: str, n: int) -> None:
    """Add `n` to the count `name` of this thread's innermost open span."""
    stack = _open()
    if not stack:
        raise RuntimeError(f"count({name!r}) outside any span")
    c = stack[-1].counts
    c[name] = c.get(name, 0) + n


def spans() -> List[Record]:
    """The ring's records, oldest first (a span is recorded as it opens)."""
    return list(_ring)


def clear() -> None:
    _ring.clear()

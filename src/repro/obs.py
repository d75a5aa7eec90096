"""The program's span recorder: where its host time goes, on the clock the
device trace and the benchmark share.

    with obs.span("serve.dispatch") as s:       # s.id names it to children
        ...
    obs.record("serve.queue", t_submit, t_pop, req_id, s.id)
    obs.record_many("serve.queue", t_submits, t_pop, req_ids, s.id)
    obs.spans("serve.dispatch", start, end)      # records inside [start, end]

A span is one record ``(start, end, id, parent)``: ``start`` and ``end`` in
seconds of :func:`clock` (``time.perf_counter``), ``id`` drawn from one
process-wide counter (:func:`next_id`, from 1), and ``parent`` the id of
the span that caused it, :data:`NO_PARENT` (0) for none. :func:`span` takes
its parent from the innermost span open on the same thread;
:func:`record` and :func:`record_many` write intervals that cross threads,
such as a request's wait from ``submit`` to the batcher's pop, with the
ids the caller passes.

Each name keeps its records in a fixed ring of :data:`CAPACITY` records
(4 MiB), allocated on first use: no Python object is kept per span, and
once a ring is full the oldest records are overwritten and counted by
:func:`dropped`. :func:`span` also opens a
``jax.profiler.TraceAnnotation`` of its name while a profiler is
recording, so that the span lands on the trace's host plane beside the
device's operations.
Recording is always on, as an operator's flight record.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

RECORD = np.dtype([("start", "f8"), ("end", "f8"), ("id", "i8"),
                   ("parent", "i8")])
CAPACITY = 1 << 17
NO_PARENT = 0

clock = time.perf_counter
_ids = itertools.count(1)


def next_id() -> int:
    """A fresh span id, unique in the process."""
    return next(_ids)


class _Ring:
    __slots__ = ("buf", "n")

    def __init__(self, capacity: int):
        self.buf = np.zeros(capacity, RECORD)
        self.n = 0                       # records ever written


class Span:
    """One open span; ``id`` and ``parent`` are set on entry."""

    __slots__ = ("name", "id", "parent", "start", "_rec", "_ann", "_stack")

    def __init__(self, recorder: "Recorder", name: str):
        self._rec, self.name = recorder, name

    def __enter__(self) -> "Span":
        self._stack = stack = self._rec._stack()
        self.parent = stack[-1] if stack else NO_PARENT
        self.id = next_id()
        stack.append(self.id)
        # annotate only while a profiler records: unrecorded, it is pure cost
        self._ann = TraceAnnotation(self.name) \
            if TraceAnnotation.is_enabled() else None
        if self._ann is not None:
            self._ann.__enter__()
        self.start = clock()
        return self

    def __exit__(self, *exc) -> None:
        end = clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._stack.pop()
        self._rec.record(self.name, self.start, end, self.id, self.parent)


class Recorder:
    """Rings of span records by name, shared by every thread."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = int(capacity)
        self._rings: Dict[str, _Ring] = {}
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def span(self, name: str) -> Span:
        return Span(self, name)

    def _ring(self, name: str) -> _Ring:
        ring = self._rings.get(name)            # the caller holds the lock
        if ring is None:
            ring = self._rings[name] = _Ring(self.capacity)
        return ring

    def record(self, name: str, start: float, end: float, id: int,
               parent: int = NO_PARENT) -> None:
        with self._lock:
            ring = self._ring(name)
            ring.buf[ring.n % self.capacity] = (start, end, id, parent)
            ring.n += 1

    def record_many(self, name: str, starts: Sequence[float], end: float,
                    ids: Sequence[int], parent: int = NO_PARENT) -> None:
        """One record per ``(start, id)`` pair, all ending at ``end`` with
        the same ``parent``: a slice of the ring written field by field."""
        k, cap = len(ids), self.capacity
        with self._lock:
            ring = self._ring(name)
            lo = max(0, k - cap)        # more than the ring holds: the newest
            ring.n += lo
            while lo < k:               # to the ring's end, then on from 0
                i = ring.n % cap
                hi = min(k, lo + cap - i)
                rows = ring.buf[i:i + hi - lo]
                rows["start"] = starts[lo:hi]
                rows["end"] = end
                rows["id"] = ids[lo:hi]
                rows["parent"] = parent
                ring.n += hi - lo
                lo = hi

    def spans(self, name: str, start: Optional[float] = None,
              end: Optional[float] = None) -> np.ndarray:
        """The records of ``name`` that lie wholly inside ``[start, end]``
        (either bound may be left open), oldest written first."""
        with self._lock:
            ring = self._rings.get(name)
            if ring is None:
                return np.zeros(0, RECORD)
            i = ring.n % self.capacity
            out = ring.buf[:ring.n].copy() if ring.n <= self.capacity \
                else np.concatenate([ring.buf[i:], ring.buf[:i]])
        if start is not None:
            out = out[out["start"] >= start]
        if end is not None:
            out = out[out["end"] <= end]
        return out

    def dropped(self, name: str) -> int:
        """Records of ``name`` overwritten since the ring filled."""
        with self._lock:
            ring = self._rings.get(name)
            return 0 if ring is None else max(0, ring.n - self.capacity)


_RECORDER = Recorder()
span = _RECORDER.span
record = _RECORDER.record
record_many = _RECORDER.record_many
spans = _RECORDER.spans
dropped = _RECORDER.dropped

"""Admission queue and futures for the continuous-batching engine.

The queue is the concurrency boundary of the serving subsystem: client
threads ``push`` requests under a single lock, the batcher thread calls
``next_batch`` to pop a *coalescible* run — FIFO requests for ONE model
whose total rows fit one ``max_rows`` dispatch — and everything else
(padding, jit, scatter) happens outside the lock. Admission control lives
here too: a bounded waiting queue (``QueueFull`` at push), and per-request
deadlines checked at pop time, so an expired request is rejected cleanly
instead of wasting a dispatch slot. Because the batcher wakes whenever the
queue is non-empty, an expired request is failed within one dispatch
interval — timeouts cannot wedge behind live traffic.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from repro import obs


class Rejected(RuntimeError):
    """Base of every clean admission-control rejection."""


class QueueFull(Rejected):
    """The bounded waiting queue (or the in-flight cap) is at capacity."""


class RequestTimeout(Rejected):
    """The request's deadline expired before its rows were dispatched."""


class CircuitOpen(Rejected):
    """This model's circuit breaker is open after repeated dispatch
    failures — the request is fast-rejected without queueing. Retry after
    the breaker's cooldown (the next caller through probes the model)."""


class EngineStopped(RuntimeError):
    """The engine shut down while this request was pending."""


class ServeFuture:
    """One caller's pending margins. ``result()`` blocks until the batcher
    scatters this request's row slice back (or fails it)."""

    __slots__ = ("_event", "_value", "_exc")

    def __init__(self):
        self._event = threading.Event()
        self._value: Optional[np.ndarray] = None
        self._exc: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def set_result(self, value: np.ndarray) -> None:
        self._value = value
        self._event.set()

    def set_exception(self, exc: BaseException) -> None:
        self._exc = exc
        self._event.set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """This request's (rows[, K]) margins. Raises the request's failure
        (:class:`RequestTimeout`, :class:`EngineStopped`, or the dispatch
        error) — or :class:`TimeoutError` if ``timeout`` seconds pass with
        the request still pending."""
        if not self._event.wait(timeout):
            raise TimeoutError("request still pending")
        if self._exc is not None:
            raise self._exc
        return self._value


@dataclasses.dataclass
class Request:
    """One admitted request: rows for one model plus its completion slot.

    Times are on :func:`repro.obs.clock`; ``id`` names the request's
    ``serve.queue`` span."""
    model: str
    X: np.ndarray
    future: ServeFuture
    deadline: Optional[float]      # obs.clock() cutoff, None = never
    submitted_at: float
    id: int = obs.NO_PARENT

    @property
    def n(self) -> int:
        return self.X.shape[0]


class RequestQueue:
    """Bounded multi-model FIFO with coalescing pops.

    Requests are kept FIFO *per model* (coalescing never reorders one
    client's stream) and models with pending work are served round-robin,
    so a chatty model cannot starve a quiet one. ``next_batch`` returns
    ``(model, live, expired)``: the longest FIFO prefix of one model's
    queue whose rows sum to at most ``max_rows`` (always at least one
    request — oversize requests dispatch alone and split downstream),
    plus any requests whose deadline lapsed while queued.
    """

    def __init__(self, max_queue: int):
        self.max_queue = int(max_queue)
        self._lock = threading.Lock()
        self._nonempty = threading.Condition(self._lock)
        self._pending: Dict[str, Deque[Request]] = {}
        self._order: Deque[str] = collections.deque()   # round-robin cursor
        self._total = 0
        self._closed = False

    def __len__(self) -> int:
        with self._lock:
            return self._total

    def close(self) -> None:
        """Refuse pushes from now on (:class:`EngineStopped`).

        Called FIRST in engine shutdown, so a ``submit`` racing ``stop()``
        either lands before the close (and is failed by the drain) or is
        rejected here — it can never strand a request in a queue nobody
        will ever pop again."""
        with self._lock:
            self._closed = True
            self._nonempty.notify_all()

    def open(self) -> None:
        """Accept pushes again (engine restart after ``stop()``)."""
        with self._lock:
            self._closed = False

    def push(self, req: Request) -> None:
        with self._lock:
            if self._closed:
                raise EngineStopped("serve engine stopped")
            if self._total >= self.max_queue:
                raise QueueFull(
                    f"serving queue at capacity ({self.max_queue} waiting "
                    f"requests); retry or raise EngineConfig.max_queue")
            dq = self._pending.get(req.model)
            if dq is None:
                dq = self._pending[req.model] = collections.deque()
            if not dq:
                self._order.append(req.model)
            dq.append(req)
            self._total += 1
            self._nonempty.notify()

    def next_batch(self, max_rows: int, wait_s: float
                   ) -> Optional[Tuple[str, List[Request], List[Request]]]:
        """Pop one coalescible run, waiting up to ``wait_s`` for work.

        Returns ``None`` on timeout with an empty queue. ``live`` may be
        empty if every popped request had already expired."""
        now = obs.clock()
        with self._lock:
            if not self._total:
                self._nonempty.wait(wait_s)
                if not self._total:
                    return None
                now = obs.clock()
            model = self._order[0]
            dq = self._pending[model]
            live: List[Request] = []
            expired: List[Request] = []
            rows = 0
            while dq:
                head = dq[0]
                if head.deadline is not None and now > head.deadline:
                    expired.append(dq.popleft())
                    self._total -= 1
                    continue
                if live and rows + head.n > max_rows:
                    break                 # next dispatch picks it up
                live.append(dq.popleft())
                self._total -= 1
                rows += head.n
                if rows >= max_rows:
                    break
            self._order.popleft()
            if dq:
                self._order.append(model)   # rotate: other models next
            else:
                del self._pending[model]
            return model, live, expired

    def drain(self) -> List[Request]:
        """Remove and return every pending request (engine shutdown)."""
        with self._lock:
            out: List[Request] = []
            for dq in self._pending.values():
                out.extend(dq)
            self._pending.clear()
            self._order.clear()
            self._total = 0
            return out

    def notify(self) -> None:
        """Wake a blocked ``next_batch`` (used by engine stop)."""
        with self._lock:
            self._nonempty.notify_all()

"""Asynchronous continuous-batching serve engine over the decide arms.

The paper's deployment punchline — prediction is row-partitioned, needs no
AllReduce, and is one kmvp — means serving is pure batched matrix work,
and the only thing standing between a single-caller endpoint and
production throughput is *batch formation*. This engine does exactly
that: many client threads ``submit`` rows concurrently, a single batcher
thread continuously drains the admission queue, coalesces queued requests
for the same model into one block, runs ONE bucketed jit dispatch
(:class:`~repro.api.infer.BucketedDecider` pads to the power-of-two
bucket), and scatters the margin rows back to each caller's future
(:func:`~repro.api.infer.scatter_rows`). Continuous means no waiting for
full batches: whatever is queued when the dispatcher frees up forms the
next batch, so latency stays request-bounded at low load and occupancy
climbs with pressure.

Correctness contract: per-row margins are batch-composition independent
(each row reduces over m alone), so a request's rows served inside any
coalesced block are bitwise-identical to the same rows served alone
through the same jitted decide family — asserted, not assumed, by
``tests/test_serve_engine.py``. No cross-request leakage is possible by
construction: scatter slices are disjoint row ranges of one output block.

Admission control: a bounded waiting queue and an in-flight cap reject at
``submit`` with :class:`~repro.serve.batching.QueueFull`; per-request
deadlines reject queued-too-long work with
:class:`~repro.serve.batching.RequestTimeout` before it wastes a dispatch.
Rejections are clean — the batcher never wedges, and ``stop()`` fails
stragglers with :class:`~repro.serve.batching.EngineStopped`.

Self-healing: a dispatch exception fails only its batch (the guard in
:meth:`ServeEngine._dispatch`), a per-model
:class:`~repro.serve.health.CircuitBreaker` turns a persistently failing
model into fast :class:`~repro.serve.batching.CircuitOpen` rejections at
submit (then probes its way closed again after a cooldown), and the
engine-level health gauge (STARTING/READY/DEGRADED/DRAINING) is exposed
through ``ServeMetrics``.

Spans (:mod:`repro.obs`): every queued request records ``serve.queue``
(submit to the batcher's pop, timed out, failed and cancelled requests
included) under its own id, naming as parent the ``serve.dispatch`` span
that took it; ``serve.dispatch`` covers one dispatch from block assembly
to the last future set, with the decide arm's ``infer.decide`` inside it.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Sequence

import numpy as np

from repro import faults, obs
from repro.api.infer import scatter_rows
from repro.serve.batching import (CircuitOpen, EngineStopped, QueueFull,
                                  Request, RequestQueue, RequestTimeout,
                                  ServeFuture)
from repro.serve.health import (DEGRADED, DRAINING, READY, STARTING,
                                CircuitBreaker)
from repro.serve.metrics import ServeMetrics
from repro.serve.registry import ModelRegistry

_UNSET = object()


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """SLO knobs for :class:`ServeEngine`.

    ``max_batch`` caps rows per dispatch (the top bucket). ``max_queue``
    bounds *waiting* requests; ``max_inflight`` bounds admitted-but-
    uncompleted requests (waiting + being dispatched) — both reject at
    submit. ``timeout_s`` is the default per-request deadline (None =
    wait forever); ``poll_s`` is the batcher's idle wait between queue
    checks (latency floor when the queue is empty is one notify, not one
    poll — the queue wakes the batcher on push).

    ``breaker_threshold`` consecutive dispatch failures open a model's
    circuit (submits fast-reject with ``CircuitOpen`` until a probe
    succeeds after ``breaker_cooldown_s``); 0 disables the breaker. The
    default is deliberately above one so an isolated failure — a model
    swapped out for a single batch — never trips it."""
    max_batch: int = 256
    max_queue: int = 1024
    max_inflight: int = 4096
    timeout_s: Optional[float] = None
    poll_s: float = 0.05
    breaker_threshold: int = 5
    breaker_cooldown_s: float = 5.0


class ServeEngine:
    """Continuous batcher over a :class:`~repro.serve.registry.ModelRegistry`.

    Use as a context manager (``with ServeEngine(reg) as eng:``) or call
    :meth:`start`/:meth:`stop`. ``submit`` returns a
    :class:`~repro.serve.batching.ServeFuture`; ``__call__`` is the
    blocking convenience. Construct with ``autostart=False`` to submit
    before any dispatching happens (tests use this to force saturation
    and timeouts deterministically).
    """

    def __init__(self, registry: ModelRegistry,
                 config: EngineConfig = EngineConfig(), *,
                 autostart: bool = True):
        self.registry = registry
        self.config = config
        self.metrics = ServeMetrics()
        self._queue = RequestQueue(config.max_queue)
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._breakers: Dict[str, CircuitBreaker] = {}
        self._breaker_lock = threading.Lock()
        self.metrics.set_health(STARTING)
        if autostart:
            self.start()

    # ---------------------------------------------------------- lifecycle
    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "ServeEngine":
        if self.running:
            return self
        self._queue.open()           # accept submits again after a stop()
        self._stop.clear()
        self._thread = threading.Thread(target=self._batch_loop,
                                        name="serve-batcher", daemon=True)
        self._thread.start()
        self._update_health()        # READY, or DEGRADED if circuits stayed
        return self                  # open across a stop/start cycle

    def stop(self, timeout: float = 10.0) -> None:
        """Stop the batcher and fail every still-pending request with
        :class:`EngineStopped` (clean shutdown, never a hang).

        The queue is closed *before* the drain, so a ``submit`` racing this
        call either lands in the queue (and is failed here) or raises
        :class:`EngineStopped` at push — it cannot be stranded after the
        drain with its in-flight slot leaked. ``start()`` afterwards
        restores a fully serviceable engine."""
        self.metrics.set_health(DRAINING)
        self._queue.close()
        self._stop.set()
        self._queue.notify()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        drained = self._queue.drain()
        self._settle(drained, "cancelled", obs.clock())
        for req in drained:
            req.future.set_exception(EngineStopped("serve engine stopped"))
        self.metrics.set_health(STARTING)   # stopped = not serving yet

    def __enter__(self) -> "ServeEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    @property
    def health(self) -> str:
        """STARTING / READY / DEGRADED / DRAINING (see repro.serve.health)."""
        return self.metrics.health

    def _breaker(self, model: str) -> CircuitBreaker:
        with self._breaker_lock:
            br = self._breakers.get(model)
            if br is None:
                br = CircuitBreaker(self.config.breaker_threshold,
                                    self.config.breaker_cooldown_s)
                self._breakers[model] = br
            return br

    def _update_health(self) -> None:
        if not self.running:
            return                    # stop() owns the gauge while draining
        with self._breaker_lock:
            degraded = any(b.state != CircuitBreaker.CLOSED
                           for b in self._breakers.values())
        self.metrics.set_health(DEGRADED if degraded else READY)

    # ---------------------------------------------------------- admission
    def submit(self, X, *, model: Optional[str] = None,
               timeout: object = _UNSET) -> ServeFuture:
        """Admit one request (rows for one model); returns its future.

        Raises :class:`QueueFull` when the waiting queue or in-flight cap
        is at capacity — the caller's clean backpressure signal. ``timeout``
        overrides ``EngineConfig.timeout_s`` for this request (None = no
        deadline)."""
        entry = self.registry.get(model)
        X = np.asarray(X, dtype=np.float32)
        if X.ndim != 2 or X.shape[1] != entry.d:
            raise ValueError(f"model {entry.name!r} serves (rows, {entry.d}) "
                             f"requests, got {X.shape}")
        self.metrics.add(submitted=1)
        if not self._breaker(entry.name).allow():
            self.metrics.add(rejected_open=1)
            raise CircuitOpen(
                f"model {entry.name!r}: circuit open after repeated "
                f"dispatch failures; retry after "
                f"{self.config.breaker_cooldown_s:g}s cooldown")
        future = ServeFuture()
        if X.shape[0] == 0:              # nothing to dispatch: empty margins
            shape = (0, entry.n_classes) if entry.n_classes else (0,)
            future.set_result(np.zeros(shape, np.float32))
            self.metrics.add(completed=1)
            return future
        timeout_s = self.config.timeout_s if timeout is _UNSET else timeout
        now = obs.clock()
        req = Request(model=entry.name, X=X, future=future,
                      deadline=None if timeout_s is None else now + timeout_s,
                      submitted_at=now, id=obs.next_id())
        with self._inflight_lock:
            if self._inflight >= self.config.max_inflight:
                self.metrics.add(rejected_full=1)
                raise QueueFull(
                    f"engine at max_inflight={self.config.max_inflight}")
            self._inflight += 1
        try:
            self._queue.push(req)
        except BaseException as exc:
            # EVERY push failure (QueueFull, EngineStopped from a racing
            # stop(), anything else) must release the in-flight slot, or
            # restarts inherit phantom occupancy and eventually reject
            # all traffic with a spurious QueueFull
            with self._inflight_lock:
                self._inflight -= 1
            if isinstance(exc, QueueFull):
                self.metrics.add(rejected_full=1)
            raise
        return future

    def __call__(self, X, *, model: Optional[str] = None,
                 timeout: object = _UNSET) -> np.ndarray:
        """Blocking convenience: submit and wait for this caller's margins."""
        return self.submit(X, model=model, timeout=timeout).result()

    @property
    def inflight(self) -> int:
        with self._inflight_lock:
            return self._inflight

    # ----------------------------------------------------------- batching
    def _settle(self, reqs: Sequence[Request], counter: str, popped: float,
                parent: int = obs.NO_PARENT) -> None:
        """Free a batch's in-flight slots, count it under ``counter`` and
        record each request's ``serve.queue`` span (submit to ``popped``),
        once per batch: the process's Python work per request shows in the
        latency of every request queued behind it. The caller then
        resolves the futures."""
        if not reqs:
            return
        with self._inflight_lock:
            self._inflight -= len(reqs)
        self.metrics.add(**{counter: len(reqs)})
        obs.record_many("serve.queue", [r.submitted_at for r in reqs],
                        popped, [r.id for r in reqs], parent)

    def _batch_loop(self) -> None:
        cfg = self.config
        while not self._stop.is_set():
            batch = self._queue.next_batch(cfg.max_batch, cfg.poll_s)
            if batch is None:
                continue
            popped = obs.clock()
            model, live, expired = batch
            self._settle(expired, "rejected_timeout", popped)
            for req in expired:
                req.future.set_exception(_timeout_error(req, popped))
            if live:
                self._dispatch(model, live, popped)

    def _dispatch(self, model: str, reqs: Sequence[Request],
                  popped: float) -> None:
        with obs.span("serve.dispatch") as span:
            sizes = [r.n for r in reqs]
            rows = sum(sizes)
            try:
                # registry lookup and block assembly are inside the guard
                # too: a model unregistered mid-flight (or a bad request
                # that slipped admission) must fail ITS batch, not kill the
                # batcher thread with every in-flight slot still held
                faults.fire("serve.dispatch", detail=model)
                entry = self.registry.get(model)
                block = reqs[0].X if len(reqs) == 1 \
                    else np.concatenate([r.X for r in reqs], axis=0)
                margins = np.asarray(entry.decider(block))
            except Exception as exc:         # fail the batch, keep serving
                if self._breaker(model).record_failure():
                    self.metrics.add(breaker_opened=1)
                    self._update_health()
                self._settle(reqs, "failed", popped, span.id)
                for req in reqs:
                    req.future.set_exception(exc)
                return
            if self._breaker(model).record_success():
                self.metrics.add(breaker_closed=1)
                self._update_health()
            self.metrics.add(dispatches=1, dispatched_rows=rows,
                             padded_rows=entry.decider.padded_rows(rows),
                             coalesced_requests=len(reqs))
            self._settle(reqs, "completed", popped, span.id)
            for req, part in zip(reqs, scatter_rows(margins, sizes)):
                # copy: the caller's slice must not pin the block alive
                req.future.set_result(np.array(part, copy=True))


def _timeout_error(req: Request, popped: float) -> RequestTimeout:
    waited = popped - req.submitted_at
    return RequestTimeout(
        f"request for model {req.model!r} ({req.n} rows) expired after "
        f"{waited * 1e3:.0f} ms in queue")

"""The one traffic generator: it reads a mix's data file and nothing else.

A mix is a JSON file under ``bench/traffic/``. Its ``kind`` says how the
cell drives the program:

``fit_repeat``
    ``KernelMachine.fit`` called back to back on the cell's data for the
    whole window. No further parameters.

``open_loop``
    Requests sent on a schedule, whether or not earlier ones have finished
    (independent users). Parameters:

    - ``rate_profile``: ``[[seconds, requests_per_s], ...]``, the arrival
      intensity, repeated for the length of the window. One segment is a
      steady Poisson stream; several make bursts.
    - ``rows``: ``{"median", "log_sigma", "min", "max"}``, a lognormal
      number of rows per request, rounded and clipped.

Every seed gets the same set of arrival gaps and request sizes, in its own
order: the gaps are the quantiles of the exponential distribution and the
sizes those of the lognormal, shuffled by the seed. So the work of a run is
fixed, and the seed changes only its order and the rows sent.
"""
from __future__ import annotations

import math
import statistics
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional

import numpy as np

KINDS = ("fit_repeat", "open_loop")


def kind(traffic: dict) -> str:
    k = traffic.get("kind")
    if k not in KINDS:
        raise ValueError(f"traffic kind {k!r} is not one of {KINDS}")
    return k


class Schedule(NamedTuple):
    due_s: np.ndarray      # offset of each request from the window's start
    rows: np.ndarray       # rows in each request


def _expected_requests(profile, seconds: float) -> float:
    """Integral of the rate profile over [0, seconds)."""
    period = sum(d for d, _ in profile)
    full, rest = divmod(seconds, period)
    total = full * sum(d * r for d, r in profile)
    for d, r in profile:
        take = min(d, rest)
        total += take * r
        rest -= take
        if rest <= 0:
            break
    return total


def _warp(u: np.ndarray, profile) -> np.ndarray:
    """Map arrival times of a unit-rate process onto the profile's clock:
    t with integral_0^t rate = u (time-warped Poisson process)."""
    per_period = sum(d * r for d, r in profile)
    period = sum(d for d, _ in profile)
    out = np.empty_like(u)
    for i, ui in enumerate(u):
        k, rest = divmod(ui, per_period)
        t = k * period
        for d, r in profile:
            if r > 0 and rest <= d * r:
                t += rest / r
                break
            rest -= d * r
            t += d
        out[i] = t
    return out


def schedule(traffic: dict, seconds: float, rng: np.random.Generator
             ) -> Schedule:
    """Due times and sizes of every request of an ``open_loop`` mix."""
    profile = [(float(d), float(r)) for d, r in traffic["rate_profile"]]
    n = int(round(_expected_requests(profile, seconds)))
    if n < 1:
        raise ValueError("the rate profile sends no request in the window")
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)                         # unit-rate exponential
    gaps *= n / gaps.sum()                       # n arrivals in n units
    due = _warp(np.cumsum(rng.permutation(gaps)), profile)
    spec = traffic["rows"]
    z = np.array([statistics.NormalDist().inv_cdf(v) for v in q])
    sizes = np.clip(np.rint(spec["median"] * np.exp(spec["log_sigma"] * z)),
                    spec["min"], spec["max"]).astype(np.int64)
    return Schedule(due_s=due, rows=rng.permutation(sizes))


class OpenLoop:
    """Sends a schedule through ``submit(i) -> future`` on its due times and
    collects every completion on a second thread, handing each answer to
    ``on_done(i, answer)``.

    Per request it keeps floats in preallocated arrays (``due``, ``sent``,
    ``done``, on the ``perf_counter`` clock; ``done`` is NaN for a request
    that failed or never came) and drops each future once it is answered,
    so the generator leaves no garbage that would set off the interpreter's
    full collections in the window. ``failed`` maps a request to its error.
    ``span(name)`` wraps the sender's waits and submits in named host spans
    (``bench.generator_wait``, ``bench.submit``)."""

    def __init__(self, submit: Callable[[int], object], due_s: np.ndarray,
                 span: Callable[[str], object],
                 on_done: Callable[[int, object], None]):
        n = len(due_s)
        self._submit, self._due_s = submit, due_s
        self._span, self._on_done = span, on_done
        self.due = np.zeros(n)
        self.sent = np.zeros(n)
        self.done = np.full(n, np.nan)
        self.failed: Dict[int, BaseException] = {}
        self._futures: List[Optional[object]] = [None] * n
        self._count = 0
        self._ready = threading.Condition()
        self._collector: Optional[threading.Thread] = None

    def run(self, start: float) -> None:
        """Send every request; returns after the last has been sent."""
        self._collector = threading.Thread(target=self._collect,
                                           name="bench-collector",
                                           daemon=True)
        self._collector.start()
        for i, offset in enumerate(self._due_s):
            due = start + float(offset)
            wait = due - time.perf_counter()
            if wait > 0:
                with self._span("bench.generator_wait"):
                    time.sleep(wait)
            with self._span("bench.submit"):
                try:
                    fut = self._submit(i)
                except Exception as exc:          # refused at admission
                    fut = None
                    self.failed[i] = exc
            with self._ready:
                self.due[i], self.sent[i] = due, time.perf_counter()
                self._futures[i] = fut
                self._count = i + 1
                self._ready.notify()

    def _collect(self) -> None:
        for i in range(len(self._due_s)):
            with self._ready:
                while self._count <= i:
                    self._ready.wait()
                fut, self._futures[i] = self._futures[i], None
            if fut is None:                       # refused at admission
                continue
            while not fut.done() and time.perf_counter() < self.deadline:
                try:
                    fut.result(timeout=0.05)
                except Exception:                 # pending, or failed
                    pass
            if not fut.done():
                self.failed[i] = TimeoutError("no answer by the deadline")
                continue
            at = time.perf_counter()
            try:
                answer = fut.result()
            except Exception as exc:              # the request failed
                self.failed[i] = exc
                continue
            self.done[i] = at
            self._on_done(i, answer)

    def join(self, close: float, grace_s: float = 60.0) -> None:
        """Wait for every answer until ``grace_s`` past ``close``."""
        self.deadline = close + grace_s
        self._collector.join(max(0.0, self.deadline - time.perf_counter())
                             + 1.0)
        if self._collector.is_alive():
            raise RuntimeError("the collector outlived its deadline")

    deadline = math.inf

"""SLO load generator: N concurrent clients against a serving target.

One harness drives both serving shapes with the SAME offered load so
their numbers are comparable:

* the request-at-a-time baseline — a lock-serialized
  :class:`~repro.api.infer.BucketedDecider` per model, exactly what the
  pre-engine ``ServingEndpoint`` gave one caller at a time, and
* the continuous-batching :class:`~repro.serve.engine.ServeEngine`.

Each client thread fires its own deterministic mixed-size (and
mixed-model, hence mixed-K) request stream, keeping up to ``window``
requests outstanding (window=1 is a fully synchronous caller).  Every
request is timed submit-to-result; verification against the precomputed
synchronous references happens AFTER the timed region, so correctness
checking never masks the throughput difference under test.  Latency
percentiles come from the one shared helper
(:func:`repro.serve.metrics.percentiles`).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.serve.batching import Rejected
from repro.serve.metrics import percentiles
from repro.serve.registry import ModelRegistry


@dataclasses.dataclass(frozen=True)
class LoadRequest:
    """One scripted request: rows for a model plus its precomputed
    reference margins (None skips verification)."""
    model: str
    X: np.ndarray
    reference: Optional[np.ndarray]


@dataclasses.dataclass
class LoadReport:
    """What one load phase measured. ``mismatches`` counts responses whose
    margins did not match the precomputed synchronous reference (bitwise
    at atol=0, else within atol), ``failed`` requests whose dispatch
    raised — the acceptance criterion for both is zero. ``rejected``
    counts clean admission-control rejections."""
    label: str
    clients: int
    requests: int
    completed: int = 0
    rejected: int = 0
    failed: int = 0
    mismatches: int = 0
    rows: int = 0
    wall_s: float = 0.0
    rows_per_s: float = 0.0
    latency_ms: Dict[str, float] = dataclasses.field(default_factory=dict)

    def row(self) -> Dict:
        """Flat dict for BENCH_serve.json / CSV emission."""
        out = dataclasses.asdict(self)
        out.update(out.pop("latency_ms"))
        return out


def make_workload(registry: ModelRegistry, *, clients: int,
                  requests_per_client: int, max_rows: int,
                  models: Optional[Sequence[str]] = None,
                  seed: int = 0, d_fallback: int = 0,
                  verify: bool = True) -> List[List[LoadRequest]]:
    """Script one mixed request stream per client.

    Sizes are drawn uniformly from [1, max_rows] and models uniformly from
    ``models`` (default: every registered model), so a stream interleaves
    small/large and binary/multiclass traffic — the shape continuous
    batching has to get right. References are computed synchronously
    through each model's own bucketed decider BEFORE any load runs, so
    verification compares the concurrent path against the identical jit
    family."""
    names = list(models) if models else registry.names()
    streams: List[List[LoadRequest]] = []
    for c in range(clients):
        rng = np.random.default_rng(seed * 1000 + c)
        stream = []
        for _ in range(requests_per_client):
            name = names[int(rng.integers(len(names)))]
            entry = registry.get(name)
            n = int(rng.integers(1, max_rows + 1))
            X = rng.standard_normal((n, entry.d or d_fallback)) \
                   .astype(entry.dtype)
            ref = np.asarray(entry.decider(X)) if verify else None
            stream.append(LoadRequest(model=name, X=X, reference=ref))
        streams.append(stream)
    return streams


def run_load(target: Callable[[str, np.ndarray], object],
             streams: List[List[LoadRequest]], *,
             label: str, window: int = 1,
             atol: float = 0.0) -> LoadReport:
    """Fire every client stream concurrently at ``target``.

    ``target(model, X)`` submits one request and returns a future-like
    object whose ``.result()`` blocks until the margins are available (a
    plain ndarray is also accepted as an already-complete result). Each
    client keeps up to ``window`` submissions outstanding before awaiting
    the oldest — window=1 is a synchronous caller. Rejections
    (:class:`~repro.serve.batching.Rejected`, at submit or resolve time)
    are counted, not fatal; so are failed dispatches (any other exception
    a result raises), which a client survives to send the rest of its
    stream. Responses are verified against each request's
    reference AFTER all clients finish, bitwise when ``atol`` is 0 and
    within ``atol`` otherwise, so verification cost never lands inside
    the timed region. Returns the aggregated :class:`LoadReport`."""
    window = max(int(window), 1)
    report = LoadReport(label=label, clients=len(streams),
                        requests=sum(len(s) for s in streams))
    lock = threading.Lock()
    latencies: List[float] = []
    responses: List[Tuple[LoadRequest, np.ndarray]] = []
    start_gate = threading.Barrier(len(streams) + 1)

    def client(stream: List[LoadRequest]) -> None:
        done = rejected = failed = rows = 0
        lats: List[float] = []
        outs: List[Tuple[LoadRequest, np.ndarray]] = []
        pending: List[Tuple[float, LoadRequest, object]] = []

        def harvest(entry) -> None:
            nonlocal done, rejected, failed, rows
            t0, req, fut = entry
            try:
                out = fut.result() if hasattr(fut, "result") else fut
            except Rejected:
                rejected += 1
                return
            except Exception:            # a failed dispatch: counted
                failed += 1
                return
            lats.append(time.perf_counter() - t0)
            done += 1
            rows += req.X.shape[0]
            outs.append((req, np.asarray(out)))

        start_gate.wait()
        for req in stream:
            t0 = time.perf_counter()
            try:
                fut = target(req.model, req.X)
            except Rejected:
                rejected += 1
                continue
            pending.append((t0, req, fut))
            if len(pending) >= window:
                harvest(pending.pop(0))
        while pending:
            harvest(pending.pop(0))
        with lock:
            report.completed += done
            report.rejected += rejected
            report.failed += failed
            report.rows += rows
            latencies.extend(lats)
            responses.extend(outs)

    threads = [threading.Thread(target=client, args=(s,), daemon=True)
               for s in streams]
    for t in threads:
        t.start()
    start_gate.wait()                    # all clients released together
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    report.wall_s = time.perf_counter() - t0
    report.rows_per_s = report.rows / max(report.wall_s, 1e-9)
    report.latency_ms = percentiles(latencies)

    # verification happens outside the timed region on purpose
    for req, out in responses:
        if req.reference is None:
            continue
        if out.shape != req.reference.shape:
            ok = False
        elif atol:
            ok = bool(np.allclose(out, req.reference, rtol=0.0, atol=atol))
        else:
            ok = bool(np.array_equal(out, req.reference))
        if not ok:
            report.mismatches += 1
    return report


def baseline_target(registry: ModelRegistry, *, workers: int = 64
                    ) -> Callable[[str, np.ndarray], object]:
    """The request-at-a-time strawman: one request holds the (single)
    dispatch slot start to finish — the old synchronous ``ServingEndpoint``
    semantics under concurrency. A worker pool accepts windowed
    submissions, but the global lock still serializes every dispatch;
    that serialization is the architecture under test, not the client
    pattern."""
    lock = threading.Lock()
    pool = ThreadPoolExecutor(max_workers=workers,
                              thread_name_prefix="serve-baseline")

    def work(model: str, X: np.ndarray) -> np.ndarray:
        with lock:
            return np.asarray(registry.get(model).decider(X))

    def call(model: str, X: np.ndarray):
        return pool.submit(work, model, X)

    call.close = lambda: pool.shutdown(wait=False)
    return call


def engine_target(engine) -> Callable[[str, np.ndarray], object]:
    """Adapter from the load harness calling convention to ServeEngine."""
    def call(model: str, X: np.ndarray):
        return engine.submit(X, model=model)

    return call

"""Concurrent serving correctness: the repro.serve engine under real
thread interleaving.

The engine's whole claim is that coalescing many callers' rows into one
bucketed dispatch changes *when* margins are computed but never *what*
they are. These tests prove it the hard way: client threads fire
interleaved mixed-size, mixed-K requests and every response must be
BITWISE the synchronous bucketed-decider result for that caller's rows
(per-row margins are batch-composition independent — the bucket floor in
``repro.api.infer.MIN_BUCKET`` exists exactly to keep that true), and
within 1e-6 of the eager ``decision_function`` path. Liveness is proven
too: queue saturation and expired deadlines reject cleanly and the
batcher keeps serving afterwards — no deadlock, no wedged queue.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import KernelMachine, MachineConfig
from repro.api.infer import BucketedDecider, bucket_rows, scatter_rows
from repro.core import KernelSpec, TronConfig, random_basis
from repro.data import make_classification, make_multiclass
from repro.serve import (EngineConfig, EngineStopped, ModelRegistry,
                         QueueFull, RequestTimeout, ServeEngine,
                         ServeMetrics, baseline_target, engine_target,
                         make_workload, percentiles, run_load)

N, D, M = 256, 8, 16
CFG = MachineConfig(kernel=KernelSpec("gaussian", sigma=2.0), lam=1.0,
                    tron=TronConfig(max_iter=40))


@pytest.fixture(scope="module")
def km():
    X, y = make_classification(jax.random.PRNGKey(0), N, D,
                               clusters_per_class=4)
    return KernelMachine(CFG).fit(X, y, random_basis(jax.random.PRNGKey(1),
                                                     X, M))


@pytest.fixture(scope="module")
def km_mc():
    X, y = make_multiclass(jax.random.PRNGKey(0), N, D, 3,
                           clusters_per_class=2)
    return KernelMachine(CFG).fit(X, y, random_basis(jax.random.PRNGKey(1),
                                                     X, M))


@pytest.fixture(scope="module")
def registry(km, km_mc):
    reg = ModelRegistry(max_batch=32)
    reg.add("bin", km)
    reg.add("mc3", km_mc)
    reg.warmup()
    return reg


# ----------------------------------------------------------------- pieces
def test_scatter_rows_inverts_concat():
    rng = np.random.default_rng(0)
    parts = [rng.standard_normal((n, 3)) for n in (1, 4, 2, 7)]
    out = scatter_rows(np.concatenate(parts), [p.shape[0] for p in parts])
    assert len(out) == len(parts)
    for got, want in zip(out, parts):
        np.testing.assert_array_equal(got, want)
    assert scatter_rows(np.zeros((0, 2)), []) == []


def test_bucket_floor_is_multirow():
    # the determinism contract: no (1, d) dispatch shape ever exists
    assert bucket_rows(1, 256) == 2
    assert BucketedDecider(lambda x: x, max_batch=8).padded_rows(1) == 2


def test_warmup_precompiles_every_bucket(km):
    dec = BucketedDecider(km.decider(), max_batch=32)
    assert dec.n_executables == 0
    n = dec.warmup(D)
    assert n == dec.n_executables == 5          # {2, 4, 8, 16, 32}
    # traffic of every size adds no executables after warmup
    for s in range(1, 33):
        dec(np.zeros((s, D), np.float32))
    assert dec.n_executables == 5


def test_registry_warmup_and_routing(registry):
    counts = registry.warmup()
    assert set(counts) == {"bin", "mc3"}
    assert registry.get("bin").n_classes == 0
    assert registry.get("mc3").n_classes == 3
    assert registry.get().name == "bin"          # first added is default
    with pytest.raises(KeyError, match="unknown model"):
        registry.get("nope")


# ---------------------------------------------- concurrent correctness
def test_concurrent_mixed_requests_bitwise(registry, km, km_mc):
    """4 client threads, interleaved mixed-size and mixed-K requests:
    every response bitwise-matches the synchronous bucketed result for
    that caller's rows and is within 1e-6 of eager decision_function —
    zero cross-request row leakage."""
    machines = {"bin": km, "mc3": km_mc}
    clients, per_client = 4, 40
    streams = make_workload(registry, clients=clients,
                            requests_per_client=per_client, max_rows=32,
                            seed=7)
    errors = []
    with ServeEngine(registry, EngineConfig(max_batch=32,
                                            timeout_s=60.0)) as engine:
        def client(stream, ci):
            try:
                for ri, req in enumerate(stream):
                    got = engine(req.X, model=req.model)
                    assert got.shape == req.reference.shape
                    np.testing.assert_array_equal(
                        got, req.reference,
                        err_msg=f"client {ci} request {ri} "
                                f"({req.model}, {req.X.shape})")
                    eager = np.asarray(
                        machines[req.model].decision_function(req.X))
                    np.testing.assert_allclose(got, eager, atol=1e-6)
            except Exception as exc:            # surface in the main thread
                errors.append(exc)

        threads = [threading.Thread(target=client, args=(s, i))
                   for i, s in enumerate(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = engine.metrics.snapshot()
    if errors:
        raise errors[0]
    assert snap["completed"] == clients * per_client
    assert snap["rejected_full"] == snap["rejected_timeout"] == 0
    assert 0.0 < snap["occupancy"] <= 1.0


def test_engine_vs_baseline_same_margins(registry):
    """The load harness's two targets agree exactly on every response."""
    streams = make_workload(registry, clients=2, requests_per_client=15,
                            max_rows=32, seed=3)
    base = run_load(baseline_target(registry), streams, label="baseline")
    with ServeEngine(registry, EngineConfig(max_batch=32,
                                            timeout_s=60.0)) as engine:
        eng = run_load(engine_target(engine), streams, label="engine")
    assert base.mismatches == 0 and eng.mismatches == 0
    assert base.completed == eng.completed == 30
    assert set(eng.latency_ms) == {"p50_ms", "p95_ms", "p99_ms"}


def test_multiclass_never_coalesces_with_binary(registry):
    """Per-model grouping: a (n,) and an (n, 3) machine served from one
    engine return correct shapes even when submitted back to back."""
    with ServeEngine(registry, EngineConfig(max_batch=32)) as engine:
        futs = []
        for i in range(10):
            X = np.random.default_rng(i).standard_normal((3, D)) \
                  .astype(np.float32)
            futs.append((engine.submit(X, model="bin"),
                         engine.submit(X, model="mc3")))
        for fb, fm in futs:
            assert fb.result(30).shape == (3,)
            assert fm.result(30).shape == (3, 3)


# ------------------------------------------------- admission / liveness
def test_queue_saturation_rejects_cleanly(registry):
    """Submissions beyond the bounded queue raise QueueFull without
    wedging the batcher: once started, the admitted backlog completes and
    the engine keeps serving fresh traffic."""
    engine = ServeEngine(registry,
                         EngineConfig(max_batch=32, max_queue=4),
                         autostart=False)
    X = np.zeros((2, D), np.float32)
    admitted = [engine.submit(X) for _ in range(4)]
    with pytest.raises(QueueFull):
        engine.submit(X)
    assert engine.metrics.snapshot()["rejected_full"] == 1
    engine.start()
    for fut in admitted:
        assert fut.result(30).shape == (2,)
    # the engine is not wedged: a post-saturation request still serves
    assert engine(X).shape == (2,)
    engine.stop()


def test_inflight_cap_rejects(registry):
    engine = ServeEngine(registry,
                         EngineConfig(max_batch=32, max_queue=100,
                                      max_inflight=2),
                         autostart=False)
    X = np.zeros((1, D), np.float32)
    engine.submit(X), engine.submit(X)
    with pytest.raises(QueueFull, match="max_inflight"):
        engine.submit(X)
    engine.start()
    time.sleep(0.1)
    assert engine.inflight == 0                  # drained after start
    engine.stop()


def test_timeout_rejects_cleanly_without_wedging(registry):
    """Requests whose deadline lapses while queued fail with
    RequestTimeout; the batcher survives and serves what follows."""
    engine = ServeEngine(registry, EngineConfig(max_batch=32),
                         autostart=False)
    X = np.zeros((2, D), np.float32)
    doomed = [engine.submit(X, timeout=0.02) for _ in range(3)]
    alive = engine.submit(X, timeout=60.0)
    time.sleep(0.1)                              # deadlines lapse unqueued
    engine.start()
    for fut in doomed:
        with pytest.raises(RequestTimeout):
            fut.result(30)
    assert alive.result(30).shape == (2,)
    snap = engine.metrics.snapshot()
    assert snap["rejected_timeout"] == 3
    assert snap["completed"] == 1
    # liveness after the rejections
    assert engine(X).shape == (2,)
    engine.stop()


def test_stop_fails_pending_requests(registry):
    engine = ServeEngine(registry, EngineConfig(max_batch=32),
                         autostart=False)
    fut = engine.submit(np.zeros((2, D), np.float32))
    engine.stop()
    with pytest.raises(EngineStopped):
        fut.result(5)
    assert engine.metrics.snapshot()["cancelled"] == 1


def test_stop_releases_inflight_and_rejects_new_submits(registry):
    """Every terminal path — completed, timeout, cancelled-at-stop,
    rejected-at-push — must release its in-flight slot, and a stopped
    engine must reject submits instead of stranding them in a queue
    nobody pops."""
    engine = ServeEngine(registry,
                         EngineConfig(max_batch=32, max_queue=100,
                                      max_inflight=100),
                         autostart=False)
    X = np.zeros((2, D), np.float32)
    served = engine.submit(X)                    # completes after start
    doomed = engine.submit(X, timeout=0.01)      # expires in queue
    time.sleep(0.05)
    engine.start()
    assert served.result(30).shape == (2,)
    with pytest.raises(RequestTimeout):
        doomed.result(30)
    stranded = engine.submit(X)          # races stop: served OR cancelled,
    engine.stop()                        # but NEVER left hanging
    try:
        assert stranded.result(5).shape == (2,)
    except EngineStopped:
        pass
    with pytest.raises(EngineStopped):           # post-stop submit: rejected
        engine.submit(X)
    assert engine.inflight == 0, \
        "a terminal path leaked its in-flight slot"


def test_stop_start_cycle_serves_again_without_spurious_queuefull(registry):
    """Saturate to the in-flight cap, stop (cancelling everything), then
    restart: the engine must serve a full load again. Before the lifecycle
    fixes, slots leaked by stop()/failed dispatches survived the restart
    as phantom occupancy and fresh traffic died with QueueFull."""
    cap = 8
    engine = ServeEngine(registry,
                         EngineConfig(max_batch=32, max_queue=100,
                                      max_inflight=cap),
                         autostart=False)
    X = np.zeros((2, D), np.float32)
    # Saturate while the batcher is NOT running, so admission is
    # deterministic: exactly cap slots fill, the next submit must be
    # rejected, and stop() cancels every queued request.
    futs = [engine.submit(X) for _ in range(cap)]
    with pytest.raises(QueueFull):
        engine.submit(X)
    engine.stop()
    for fut in futs:
        with pytest.raises(EngineStopped):
            fut.result(5)
    assert engine.inflight == 0, "stop() leaked in-flight slots"
    for cycle in range(3):
        engine.start()
        # a full complement of NEW requests must be admitted and served:
        # phantom occupancy surviving the restart would reject these
        # with QueueFull at admission.
        again = [engine.submit(X) for _ in range(cap)]
        for fut in again:
            assert fut.result(30).shape == (2,)
        engine.stop()
        assert engine.inflight == 0, f"slots leaked in cycle {cycle}"
    with pytest.raises(EngineStopped):
        engine.submit(X)


def test_dispatch_failure_releases_slots_and_keeps_batcher_alive(registry):
    """A model unregistered between admission and dispatch fails ITS
    requests (never the batcher thread) and releases their slots."""
    reg = ModelRegistry(max_batch=32)
    reg.add("bin", registry.get("bin").km)
    engine = ServeEngine(reg, EngineConfig(max_batch=32, max_inflight=8),
                         autostart=False)
    X = np.zeros((2, D), np.float32)
    doomed = engine.submit(X, model="bin")
    reg.remove("bin")                    # lookup now fails inside _dispatch
    engine.start()
    with pytest.raises(KeyError):
        doomed.result(30)
    assert engine.metrics.snapshot()["failed"] == 1
    reg.add("bin", registry.get("bin").km)
    assert engine(X, model="bin").shape == (2,)   # batcher still alive
    assert engine.inflight == 0
    engine.stop()


# ---------------------------------------------------------------- spans
def _spans(t0, *names):
    t1 = obs.clock()
    return [obs.spans(n, t0, t1) for n in names]


def test_spans_match_the_metrics_on_a_clean_run(registry):
    """One serve.dispatch per dispatch and one serve.queue per completed
    request; every request's queue record names an existing dispatch, and
    every infer.decide lies inside its dispatch."""
    t0 = obs.clock()
    with ServeEngine(registry, EngineConfig(max_batch=32)) as engine:
        before = engine.metrics.snapshot()
        rng = np.random.default_rng(0)

        def client(k):
            for _ in range(25):
                engine(rng.standard_normal((1 + k, D)).astype(np.float32))

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
        after = engine.metrics.snapshot()
    disp, queue, decide = _spans(t0, "serve.dispatch", "serve.queue",
                                 "infer.decide")
    assert len(disp) == after["dispatches"] - before["dispatches"] > 0
    assert len(queue) == after["completed"] - before["completed"] == 100
    assert len(set(queue["id"])) == 100
    assert set(queue["parent"]) <= set(disp["id"])
    assert list(decide["parent"]) == list(disp["id"])
    by_id = {r["id"]: r for r in disp}
    for d in decide:
        p = by_id[d["parent"]]
        assert p["start"] <= d["start"] <= d["end"] <= p["end"]
    for q in queue:                  # popped before its dispatch began
        assert q["start"] <= q["end"] <= by_id[q["parent"]]["start"]


def test_refused_requests_close_their_spans(registry):
    """A request that timed out, failed in its dispatch or was cancelled
    by EngineStopped still records its queue wait; one refused at submit
    (QueueFull) never queued and records none."""
    X = np.zeros((2, D), np.float32)
    t0 = obs.clock()
    engine = ServeEngine(registry, EngineConfig(max_batch=32, max_queue=2),
                         autostart=False)
    doomed = engine.submit(X, timeout=0.01)
    stranded = engine.submit(X)
    with pytest.raises(QueueFull):
        engine.submit(X)
    time.sleep(0.05)
    engine.stop()                                # cancels both
    for fut in (doomed, stranded):
        with pytest.raises(EngineStopped):
            fut.result(5)
    (queue,) = _spans(t0, "serve.queue")
    assert len(queue) == 2
    assert set(queue["parent"]) == {obs.NO_PARENT}

    t0 = obs.clock()
    engine = ServeEngine(registry, EngineConfig(max_batch=32),
                         autostart=False)
    doomed = engine.submit(X, timeout=0.01)
    time.sleep(0.05)
    engine.start()
    with pytest.raises(RequestTimeout):
        doomed.result(30)
    engine.stop()
    (queue,) = _spans(t0, "serve.queue")
    assert len(queue) == 1
    assert queue["end"][0] - queue["start"][0] >= 0.01
    assert queue["parent"][0] == obs.NO_PARENT

    reg = ModelRegistry(max_batch=32)
    reg.add("bin", registry.get("bin").km)
    t0 = obs.clock()
    engine = ServeEngine(reg, EngineConfig(max_batch=32), autostart=False)
    failed = engine.submit(X, model="bin")
    reg.remove("bin")
    engine.start()
    with pytest.raises(KeyError):
        failed.result(30)
    engine.stop()
    disp, queue = _spans(t0, "serve.dispatch", "serve.queue")
    assert len(disp) == len(queue) == 1
    assert queue["parent"][0] == disp["id"][0]


def test_submit_validates_shape(registry):
    with ServeEngine(registry, EngineConfig(max_batch=32)) as engine:
        with pytest.raises(ValueError, match="serves"):
            engine.submit(np.zeros((2, D + 1), np.float32))
        # zero-row requests complete immediately with empty margins
        assert engine.submit(np.zeros((0, D), np.float32)).result(5) \
            .shape == (0,)
        assert engine.submit(np.zeros((0, D), np.float32),
                             model="mc3").result(5).shape == (0, 3)


# ------------------------------------------------------------- metrics
def test_metrics_occupancy_and_percentiles():
    m = ServeMetrics()
    m.add(dispatches=2, dispatched_rows=48, padded_rows=64,
          coalesced_requests=6, submitted=8, rejected_full=2)
    assert m.occupancy() == 48 / 64
    assert m.requests_per_dispatch() == 3.0
    assert m.rejection_rate() == 0.25
    with pytest.raises(AttributeError):
        m.add(not_a_counter=1)
    p = percentiles([0.001] * 99 + [0.1])
    assert p["p50_ms"] == pytest.approx(1.0)
    assert p["p99_ms"] > 1.0
    assert percentiles([]) == {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0}


@pytest.mark.parametrize("n", [1, 2, 3, 99])
def test_percentiles_tiny_samples_clamp_to_observations(n):
    """On n < 100 samples the tail percentiles must be actual observations
    (the "higher" order statistic), clamped in range — never interpolated
    below the worst sample, never an out-of-range index. The failure this
    pins down: with one slow outlier among fast requests, interpolation
    reported a p99 ~equal to the median, silently erasing the tail a
    smoke-scale SLO run exists to measure."""
    slow, fast = 0.100, 0.001
    samples = [fast] * (n - 1) + [slow]
    p = percentiles(samples)
    assert p["p99_ms"] == pytest.approx(slow * 1e3)   # the worst REAL sample
    assert p["p95_ms"] in (pytest.approx(fast * 1e3), pytest.approx(slow * 1e3))
    if n == 1:
        # single sample: every percentile is that sample (no IndexError)
        assert p["p50_ms"] == p["p95_ms"] == p["p99_ms"] \
            == pytest.approx(slow * 1e3)
    if n >= 3:
        assert p["p50_ms"] == pytest.approx(fast * 1e3)
    # percentile ordering invariant
    assert p["p50_ms"] <= p["p95_ms"] <= p["p99_ms"]


def test_percentiles_n2_tail_is_not_the_median():
    # the regression shape: n=2 once reported p99 ≈ p50 via interpolation
    p = percentiles([0.001, 0.100])
    assert p["p99_ms"] == pytest.approx(100.0)
    assert p["p95_ms"] == pytest.approx(100.0)

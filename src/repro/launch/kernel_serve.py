"""Serving driver for saved KernelMachines over the repro.serve engine.

Loads checkpoints written by ``KernelMachine.save`` (any solver), registers
them in a :class:`repro.serve.ModelRegistry` (one bucketed jit-executable
cache per model, decide arms from the execution-plan registry — the same
engine ``decision_function`` uses, no private serving math), precompiles
every batch bucket (``warmup``; ``--no-warmup`` opts out), and drives a
concurrent synthetic client fleet through the asynchronous
continuous-batching :class:`repro.serve.ServeEngine`: queued rows from
many callers coalesce into ONE power-of-two-bucketed dispatch, multi-RHS
margins come back in one pass and are scattered to each caller's future.
Admission control (bounded queue, in-flight cap, per-request timeout)
turns overload into clean rejections.

A ``stream``-trained machine serves through the ``local`` decide arm by
default (request batches are small and in memory; the host-driven chunk
pipeline is for scoring datasets, not requests). Pass ``--plan`` to pick
any arm explicitly (e.g. ``otf_shard`` to serve huge-m machines without
ever materializing the request gram).

  # concurrent load against one machine (the default path)
  PYTHONPATH=src python -m repro.launch.kernel_serve --ckpt machine.npz \
      --clients 8 --requests 64 --max-batch 256

  # several checkpoints served side by side, traffic mixed across them
  PYTHONPATH=src python -m repro.launch.kernel_serve \
      --ckpt a.npz --ckpt b.npz

  # the old single-client request-at-a-time loop
  PYTHONPATH=src python -m repro.launch.kernel_serve --ckpt m.npz --serial

  # end-to-end self-test: train small machines (local + stream plans,
  # binary + multiclass), save, load, serve synchronously AND through the
  # concurrent engine, verify every response
  PYTHONPATH=src python -m repro.launch.kernel_serve --selftest
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import KernelMachine, MachineConfig
from repro.api.infer import BucketedDecider, bucket_rows
from repro.launch.cli import (BACKENDS, enable_compile_cache, plan_choices,
                              registry_epilog)
from repro.serve import (EngineConfig, ModelRegistry, ServeEngine,
                         baseline_target, engine_target, make_workload,
                         percentiles, run_load, serving_plan)
from repro.sharding import multihost

# back-compat aliases: tests and older scripts import these names from here
_bucket = bucket_rows
_serving_plan = serving_plan


class ServingEndpoint(BucketedDecider):
    """Deprecated single-caller shim over :class:`BucketedDecider`.

    The pre-engine synchronous endpoint: one caller, one request at a
    time. New code should register machines in a
    :class:`repro.serve.ModelRegistry` and serve through
    :class:`repro.serve.ServeEngine`; this class remains as the
    request-at-a-time baseline the SLO harness measures against.
    """

    def __init__(self, km: KernelMachine, max_batch: int = 256,
                 plan: Optional[str] = None, backend: Optional[str] = None):
        self.km = km
        self.plan = serving_plan(km, plan)
        super().__init__(km.decider(plan=self.plan, backend=backend),
                         max_batch=max_batch)


def _train_demo_machine(path: str, n: int = 2048, m: int = 64,
                        classes: int = 2, plan: str = "local") -> str:
    from repro.core import KernelSpec, TronConfig, random_basis
    from repro.data import make_classification, make_multiclass

    if classes > 2:    # integer labels -> one multi-RHS one-vs-rest fit
        X, y = make_multiclass(jax.random.PRNGKey(0), n, 16, classes,
                               clusters_per_class=2)
    else:
        X, y = make_classification(jax.random.PRNGKey(0), n, 16,
                                   clusters_per_class=4)
    basis = random_basis(jax.random.PRNGKey(1), X, m)
    config = MachineConfig(kernel=KernelSpec("gaussian", sigma=2.0), lam=1.0,
                           plan=plan, tron=TronConfig(max_iter=60))
    km = KernelMachine(config).fit(X, y, basis)
    km.save(path)
    print(f"[train] demo machine: m={m} classes={classes} plan={plan} "
          f"train_acc={km.score(X, y):.4f} -> {path}")
    return path


def serve_stream(km: KernelMachine, *, requests: int, max_batch: int,
                 seed: int = 0, d: Optional[int] = None,
                 plan: Optional[str] = None, backend: Optional[str] = None):
    """Single-client request-at-a-time loop; returns latency stats with
    tail percentiles (p50/p95/p99 via the shared serve-metrics helper, so
    this report and the SLO load harness can never disagree)."""
    if d is None:
        from repro.serve.registry import model_dim
        d = model_dim(km)
    endpoint = ServingEndpoint(km, max_batch=max_batch, plan=plan,
                               backend=backend)
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, max_batch + 1, size=requests)
    # warm every bucket so measured latencies are compile-free
    endpoint.warmup(d)
    lat = []
    for s in sizes:
        Xq = jnp.asarray(rng.standard_normal((int(s), d)), jnp.float32)
        t0 = time.perf_counter()
        jax.block_until_ready(endpoint(Xq))
        lat.append(time.perf_counter() - t0)
    stats = {
        "requests": requests,
        "rows": int(sizes.sum()),
        "plan": endpoint.plan,
        "executables": endpoint.n_executables,
        **percentiles(lat),
        "rows_per_s": float(sizes.sum() / max(sum(lat), 1e-9)),
    }
    return endpoint, stats


def build_registry(ckpts, *, max_batch: int, plan: Optional[str] = None,
                   backend: Optional[str] = None,
                   warmup: bool = True) -> ModelRegistry:
    """Load checkpoints into a registry (model names m0, m1, ... in CLI
    order) and optionally precompile every bucket of every model.
    ``backend`` overrides the gram/kmvp backend each machine was trained
    with (by default it serves through the same one)."""
    registry = ModelRegistry(max_batch=max_batch)
    for i, path in enumerate(ckpts):
        entry = registry.load(f"m{i}", path, plan=plan, backend=backend)
        beta = entry.km.state_["beta"]
        print(f"[load ] {entry.name}: {path} solver={entry.km.config.solver} "
              f"plan={entry.plan} "
              f"backend={backend or entry.km.config.backend} d={entry.d} "
              f"K={beta.shape[1] if beta.ndim == 2 else 1}")
    if warmup:
        t0 = time.perf_counter()
        counts = registry.warmup()
        print(f"[warm ] precompiled {sum(counts.values())} executables "
              f"across {len(counts)} models in {time.perf_counter() - t0:.2f}s"
              f" (first-request latency is compile-free)")
    return registry


def serve_concurrent(registry: ModelRegistry, *, clients: int, requests: int,
                     max_batch: int, engine_config: EngineConfig,
                     seed: int = 0):
    """Drive a concurrent mixed-size client fleet through the engine."""
    streams = make_workload(registry, clients=clients,
                            requests_per_client=requests,
                            max_rows=max_batch, seed=seed)
    with ServeEngine(registry, engine_config) as engine:
        report = run_load(engine_target(engine), streams, label="engine")
        snap = engine.metrics.snapshot()   # health read while still live
    stats = {**report.row(),
             "occupancy": round(snap["occupancy"], 4),
             "requests_per_dispatch": round(snap["requests_per_dispatch"], 2),
             "rejection_rate": round(snap["rejection_rate"], 4),
             "health": snap["health"],
             "breaker_opened": snap["breaker_opened"]}
    return report, stats


def _selftest():
    path = "/tmp/repro_kernel_serve_selftest.npz"
    _train_demo_machine(path, n=512, m=32)
    km = KernelMachine.load(path)
    endpoint, stats = serve_stream(km, requests=16, max_batch=64)
    Xq = jax.random.normal(jax.random.PRNGKey(9), (37, 16))
    served = endpoint(Xq)
    direct = km.decision_function(Xq)
    err = float(jnp.max(jnp.abs(served - direct)))
    assert err < 1e-5, f"served != direct decision_function (max {err})"
    print(f"[serve] {stats}")

    # a stream-trained machine must serve too: the endpoint flips its
    # host-driven chunk plan to the local decide arm, and the served
    # margins must match BOTH the local arm and the machine's own
    # (chunked) decision path — the plan-override symmetry in one check
    path_stream = "/tmp/repro_kernel_serve_selftest_stream.npz"
    _train_demo_machine(path_stream, n=512, m=32, plan="stream")
    km_stream = KernelMachine.load(path_stream)
    endpoint = ServingEndpoint(km_stream, max_batch=64)
    assert endpoint.plan == "local", endpoint.plan
    served = endpoint(Xq)
    local = km_stream.decision_function(Xq, plan="local")
    chunked = km_stream.decision_function(Xq)     # plan='stream' from config
    err_l = float(jnp.max(jnp.abs(served - local)))
    err_c = float(jnp.max(jnp.abs(served - jnp.asarray(chunked))))
    assert err_l < 1e-5, f"stream machine served != local arm ({err_l})"
    assert err_c < 1e-5, f"stream machine served != chunked arm ({err_c})"
    print(f"[serve] stream-plan machine served via local arm OK "
          f"(vs chunked decide max diff {err_c:.2e})")

    # multiclass round trip: checkpoint carries classes, served margins
    # are (b, K) from ONE multi-RHS evaluation, argmax labels match predict
    path_mc = "/tmp/repro_kernel_serve_selftest_mc.npz"
    _train_demo_machine(path_mc, n=512, m=32, classes=3)
    km_mc = KernelMachine.load(path_mc)
    endpoint = ServingEndpoint(km_mc, max_batch=64)
    served = endpoint(Xq)
    assert served.shape == (37, 3), served.shape
    labels = km_mc.state_["classes"][jnp.argmax(served, axis=-1)]
    assert bool(jnp.all(labels == km_mc.predict(Xq))), \
        "served argmax labels != km.predict"

    # concurrent engine: all three machines (binary, stream-trained,
    # multiclass) registered side by side, 4 client threads firing a few
    # hundred interleaved mixed-size mixed-K requests — every response
    # must exactly equal its precomputed synchronous reference, and the
    # batcher must actually coalesce (requests per dispatch > 1)
    registry = build_registry([path, path_stream, path_mc],
                              max_batch=64, warmup=True)
    report, cstats = serve_concurrent(
        registry, clients=4, requests=60, max_batch=64,
        engine_config=EngineConfig(max_batch=64, timeout_s=30.0))
    assert report.mismatches == 0, \
        f"{report.mismatches} concurrent responses mismatched their " \
        f"synchronous reference"
    assert report.completed == report.requests, (report.completed,
                                                 report.requests)
    assert cstats["requests_per_dispatch"] > 1.0, \
        f"engine never coalesced (requests/dispatch = " \
        f"{cstats['requests_per_dispatch']})"
    print(f"[serve] concurrent engine OK: {cstats}")

    print(f"[selftest] OK: served==direct (max diff {err:.2e}), "
          f"{stats['executables']} executables; stream-plan machine served; "
          f"multiclass (K=3) margins served + argmax labels verified; "
          f"concurrent engine served {report.completed} requests from "
          f"{report.clients} clients with 0 mismatches "
          f"({cstats['requests_per_dispatch']:.1f} requests/dispatch, "
          f"occupancy {cstats['occupancy']:.2f})")


def serve_multihost(path: str, *, requests: int, max_batch: int,
                    seed: int = 0):
    """One engine fronting the process-spanning mesh (multi-controller).

    Every process loads the same checkpoint and holds its 1/P block of the
    basis/beta rows; process 0 drives the request loop and verifies every
    served batch against a dense single-device reference at 1e-4 rel,
    followers run the lockstep :meth:`SpanningServer.follow` loop until
    released. Returns (served rounds, worst relative diff) — followers
    report (rounds, None).
    """
    from repro.kernels.ops import otf_kmvp_fwd
    from repro.sharding.multihost import SpanningServer
    km = KernelMachine.load(path)
    st = km.state_
    basis = np.asarray(st["basis"])
    beta = np.asarray(st["beta"])
    server = SpanningServer(basis, beta, km.config.kernel,
                            multihost.spanning_mesh(),
                            backend=km.config.backend, max_batch=max_batch)
    nb = server.collective_payload_bytes()
    if not multihost.is_primary():
        return server.follow(), None
    print(f"[load ] {path} solver={km.config.solver} "
          f"plan={km.config.plan} m={basis.shape[0]} d={basis.shape[1]} "
          f"K={beta.shape[1] if beta.ndim == 2 else 1} spanning "
          f"{multihost.process_count()} processes")
    rng = np.random.default_rng(seed)
    worst, rows = 0.0, 0
    for _ in range(requests):
        b = int(rng.integers(1, max_batch + 1))
        Xq = rng.standard_normal((b, server.d)).astype(server.dtype)
        o = np.asarray(server.margins(Xq))
        ref = np.asarray(otf_kmvp_fwd(
            jnp.asarray(Xq), jnp.asarray(basis), jnp.asarray(beta),
            kind=km.config.kernel.kind, sigma=km.config.kernel.sigma,
            backend="jnp", block_rows=None))
        scale = max(float(np.max(np.abs(ref))), 1e-12)
        worst = max(worst, float(np.max(np.abs(o - ref))) / scale)
        rows += b
    server.stop()
    if worst >= 1e-4:
        raise AssertionError(
            f"spanning engine served margins diverged from the dense "
            f"reference: max rel diff {worst:.2e} >= 1e-4")
    print(f"[serve] spanning engine OK: processes="
          f"{multihost.process_count()} requests={requests} rows={rows} "
          f"max_rel_diff={worst:.2e} xhost_bytes/eval={nb}")
    return requests, worst


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog=registry_epilog())
    ap.add_argument("--ckpt", action="append", default=None,
                    help="checkpoint path (repeat to serve several machines "
                         "side by side from one engine)")
    ap.add_argument("--requests", type=int, default=64,
                    help="requests per client (concurrent) / total (serial)")
    ap.add_argument("--clients", type=int, default=8,
                    help="concurrent client threads driving the engine")
    ap.add_argument("--max-batch", type=int, default=256,
                    help="rows per dispatch: the top batch bucket")
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="admission bound on waiting requests")
    ap.add_argument("--timeout", type=float, default=None,
                    help="per-request deadline seconds (default: none)")
    ap.add_argument("--plan", default=None, choices=plan_choices(),
                    help="decide arm override (default: each machine's own "
                         "plan; stream machines serve via 'local'; live "
                         "registry: %(choices)s)")
    ap.add_argument("--backend", default=None, choices=BACKENDS,
                    help="gram/kmvp backend override (default: the one each "
                         "machine was trained with, stored in its checkpoint)")
    ap.add_argument("--serial", action="store_true",
                    help="single-client request-at-a-time loop (the "
                         "pre-engine behavior) instead of the concurrent "
                         "engine")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip precompiling batch buckets at startup (first "
                         "request per bucket then pays its compile)")
    ap.add_argument("--train-if-missing", action="store_true")
    ap.add_argument("--selftest", action="store_true",
                    help="train->save->load->serve->verify (synchronous + "
                         "concurrent engine), tiny sizes")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="process 0's coordination address: serve one "
                         "machine from an engine spanning every process")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="total controller processes (hosts) in this run")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this host's index in [0, --num-processes)")
    args = ap.parse_args(argv)

    if args.num_processes > 1 and not args.coordinator:
        ap.error("--num-processes > 1 needs --coordinator host:port")
    enable_compile_cache()
    multihost.init(args.coordinator, args.num_processes, args.process_id)
    if multihost.active():
        if args.selftest or args.serial:
            ap.error("--selftest/--serial are single-process modes")
        if not args.ckpt or len(args.ckpt) != 1:
            ap.error("multi-controller serving fronts exactly one --ckpt")
        serve_multihost(args.ckpt[0], requests=args.requests,
                        max_batch=args.max_batch)
        return

    if args.selftest:
        _selftest()
        return

    import os
    ckpts = args.ckpt or ["/tmp/repro_kernel_machine.npz"]
    for path in ckpts:
        if not os.path.exists(path):
            if not args.train_if_missing:
                ap.error(f"{path} not found (pass --train-if-missing to "
                         f"bootstrap a demo machine)")
            _train_demo_machine(path)

    if args.serial:
        km = KernelMachine.load(ckpts[0])
        print(f"[load ] solver={km.config.solver} loss={km.config.loss} "
              f"state={ {k: tuple(v.shape) for k, v in km.state_.items()} }")
        # a failed dispatch raises out of serve_stream: a non-zero exit
        _, stats = serve_stream(km, requests=args.requests,
                                max_batch=args.max_batch, plan=args.plan,
                                backend=args.backend)
        print(f"[serve] {stats}")
        return

    registry = build_registry(ckpts, max_batch=args.max_batch,
                              plan=args.plan, backend=args.backend,
                              warmup=not args.no_warmup)
    report, stats = serve_concurrent(
        registry, clients=args.clients, requests=args.requests,
        max_batch=args.max_batch,
        engine_config=EngineConfig(max_batch=args.max_batch,
                                   max_queue=args.max_queue,
                                   timeout_s=args.timeout))
    print(f"[serve] {stats}")
    # admission-control rejections are allowed; a failed dispatch or a
    # response that differs from its synchronous reference is not
    if report.failed or report.mismatches or (
            report.completed + report.rejected != report.requests):
        raise SystemExit(
            f"[serve] FAILED: {report.failed} failed dispatches, "
            f"{report.mismatches} mismatched responses, "
            f"{report.completed}+{report.rejected} of {report.requests} "
            f"requests completed or rejected")


if __name__ == "__main__":
    main()

"""Worker program for the simulated multi-controller fleet (see rig.py).

Usage: ``worker.py <task> <num_processes> <process_id> <port> [extra...]``

The rig exports ``XLA_FLAGS=--xla_force_host_platform_device_count=K``
into this process's environment before Python starts, so plain jax
imports below already see K fake local devices; :func:`multihost.init`
then joins them into the ``num_processes * K``-device global mesh.

Process 0 prints ONE JSON line as its final stdout output — the task's
result payload the rig hands back to the test.
"""
import hashlib
import json
import sys
import time

import numpy as np

TASK, NPROC, PID, PORT = (sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
                          sys.argv[4])
EXTRA = sys.argv[5:]

from repro.sharding import multihost  # noqa: E402

multihost.init(f"127.0.0.1:{PORT}", NPROC, PID)

import jax  # noqa: E402

from repro.api import KernelMachine, MachineConfig  # noqa: E402
from repro.core import KernelSpec, TronConfig  # noqa: E402

M = 32


def _problem():
    """The conditioned parity problem: sigma=1 keeps the Nystrom W block
    near identity and lam=1e-1 keeps the objective strongly convex, so a
    1e-6 gradient tolerance pins beta well past the 1e-4 acceptance band
    (ill-conditioned problems amplify last-bit psum-association noise
    into macroscopic beta differences — that would test the conditioning,
    not the distribution)."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((512, 6)).astype(np.float32)
    w = rng.standard_normal(6)
    y = np.where(X @ w > 0, 1, -1).astype(np.int64)
    return X, y


def _config(plan, max_iter=200):
    return MachineConfig(kernel=KernelSpec("gaussian", sigma=1.0), lam=1e-1,
                         plan=plan, m=M,
                         tron=TronConfig(max_iter=max_iter, grad_rtol=1e-6))


def _beta_payload(km):
    beta32 = np.asarray(km.state_["beta"], np.float32)
    r = km.result_
    return {"beta": np.asarray(beta32, np.float64).ravel().tolist(),
            "beta_sha": hashlib.sha256(beta32.tobytes()).hexdigest(),
            "f": float(r.f), "n_iter": int(r.n_iter),
            "basis": np.asarray(km.state_["basis"], np.float64).tolist(),
            "n_devices": jax.device_count(),
            "num_processes": multihost.process_count()}


def task_fit(plan):
    X, y = _problem()
    km = KernelMachine(_config(plan), mesh=multihost.spanning_mesh())
    km.fit(X, y)
    return _beta_payload(km)


def task_ckpt(mode, ckpt_dir, head_iters):
    """Checkpointed stream fit: 'full' runs uninterrupted (writing steps),
    'head' stops after ``head_iters`` outer iterations, 'resume' restores
    the newest step and finishes — all over whatever process count this
    fleet was launched with (elastic restore across P != P')."""
    from repro.checkpoint import CheckpointConfig
    X, y = _problem()
    max_iter = int(head_iters) if mode == "head" else 200
    ck = CheckpointConfig(dir=ckpt_dir, interval=1, keep=0, background=False,
                          resume=(mode == "resume"),
                          write=multihost.is_primary())
    km = KernelMachine(_config("stream", max_iter=max_iter),
                       mesh=multihost.spanning_mesh())
    km.fit(X, y, checkpoint=ck)
    multihost.sync("ckpt-done")      # step files durable on every exit path
    return _beta_payload(km)


def task_payload():
    """Instrumentation-count the cross-host bytes of one chunk evaluation
    (training) and one served request (SpanningServer) on the real
    process-spanning mesh."""
    from repro.core.distributed import DistConfig, DistributedNystrom
    from repro.core.introspect import collective_payload_bytes_jaxpr
    from repro.data.chunks import ArrayChunkSource
    from repro.sharding.multihost import SpanningServer

    X, y = _problem()
    basis = X[:M].copy()
    mesh = multihost.spanning_mesh()
    kern = KernelSpec("gaussian", sigma=1.0)
    solver = DistributedNystrom(mesh, 1e-1, "squared_hinge", kern,
                                DistConfig(fused=True, materialize=False))
    sc = solver.make_stream_closures(ArrayChunkSource(X, y, chunk_rows=128),
                                     basis)
    cr, d = sc.chunk_rows, X.shape[1]
    f32 = np.float32

    def count(fn, *shapes):
        with mesh:
            closed = jax.make_jaxpr(fn)(
                *[jax.ShapeDtypeStruct(s, f32) for s in shapes])
        return collective_payload_bytes_jaxpr(closed.jaxpr)

    fg_bytes = count(sc.fg_chunk, (cr, d), (cr,), (cr,), (M, d), (M,))
    hd_bytes = count(sc.hd_chunk, (cr, d), (cr,), (M, d), (M,))
    server = SpanningServer(basis, np.zeros((M,), f32), kern, mesh,
                            max_batch=64)
    out = {"m": M, "chunk_rows": cr, "n_chunks": sc.n_chunks,
           "itemsize": 4, "max_batch": 64,
           "fg_chunk_bytes": int(fg_bytes),
           "hd_chunk_bytes": int(hd_bytes),
           "serve_request_bytes": int(server.collective_payload_bytes())}
    server.stop()
    return out


def task_spin():
    """Lockstep broadcast rounds for ~5 minutes: the fault-injection
    target. A SIGKILLed peer must surface as a fleet failure long before
    the rounds run out."""
    deadline = time.time() + 300
    i = 0
    while time.time() < deadline:
        multihost.broadcast_from_primary(np.asarray([i], np.int64))
        i += 1
    return {"rounds": i}


def main():
    if TASK == "fit":
        out = task_fit(EXTRA[0])
    elif TASK == "ckpt":
        out = task_ckpt(EXTRA[0], EXTRA[1], EXTRA[2] if len(EXTRA) > 2 else 3)
    elif TASK == "payload":
        out = task_payload()
    elif TASK == "spin":
        out = task_spin()
    else:
        raise SystemExit(f"unknown task {TASK!r}")
    multihost.sync("task-done")
    if multihost.is_primary():
        print(json.dumps(out))


if __name__ == "__main__":
    main()

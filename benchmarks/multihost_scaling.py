"""Multi-controller scaling: step time + measured cross-host bytes/eval.

The paper's distribution claim is that each TRON iteration moves O(m)
bytes between nodes regardless of n (the AllReduce of f/g/Hd partials),
so adding hosts buys data capacity at constant coordination cost. This
benchmark runs the SAME fused stream evaluation over 1, 2 and 4
controller processes on one machine (fake local devices keep the global
mesh at 4 devices throughout, so the math — and the flop count — is
identical; only the process partition changes) and reports:

  * eval_s          wall seconds of one f/g + Hd pass (the TRON step body)
  * xhost bytes     the per-chunk collective payload counted from the
                    traced jaxpr (instrumented, not claimed), and the
                    per-eval total = n_chunks x per-chunk

The per-eval bytes must be identical across process counts and a tiny
fraction of the partition size; step time may pick up the gloo hop cost
(cross-process TCP AllReduce vs XLA's shared-memory reduction) — that
gap IS the deployment price the paper's Table 4 slices, measured here.

Emits the repo-root ``BENCH_multihost.json`` trajectory record.

All fleet processes run on this one host, so this is a CPU simulation
only (``JAX_PLATFORMS=cpu``, fake devices): on a TPU host a chip belongs
to one process, and the times here are CPU times, not device metrics.

Run:  PYTHONPATH=src python -m benchmarks.multihost_scaling [--smoke]

(The module re-invokes itself with ``--worker`` for each fleet process;
XLA_FLAGS is set by the parent before each spawn.)
"""
import argparse
import json
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

parser = argparse.ArgumentParser()
parser.add_argument("--n", type=int, default=16384)
parser.add_argument("--d", type=int, default=32)
parser.add_argument("--m", type=int, default=256)
parser.add_argument("--chunk-rows", type=int, default=2048)
parser.add_argument("--evals", type=int, default=8,
                    help="timed f/g + Hd passes (min reported)")
parser.add_argument("--procs", type=int, nargs="*", default=[1, 2, 4],
                    help="process counts; each uses 4/P fake local devices")
parser.add_argument("--smoke", action="store_true",
                    help="small sizes for the verify.sh gate")
parser.add_argument("--out", default=None,
                    help="output JSON (default: <repo>/BENCH_multihost.json)")
parser.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
parser.add_argument("--fleet", type=int, default=0, help=argparse.SUPPRESS)
parser.add_argument("--pid", type=int, default=0, help=argparse.SUPPRESS)
parser.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
args = parser.parse_args()
if args.smoke:
    args.n, args.m, args.chunk_rows, args.evals = 2048, 64, 512, 3


# ------------------------------------------------------------ worker process
def worker():
    import numpy as np
    from repro.sharding import multihost

    multihost.init(f"127.0.0.1:{args.port}", args.fleet, args.pid)

    import jax
    from repro.core import KernelSpec
    from repro.core.distributed import DistConfig, DistributedNystrom
    from repro.core.introspect import collective_payload_bytes_jaxpr
    from repro.data.chunks import ArrayChunkSource

    rng = np.random.default_rng(0)
    X = rng.standard_normal((args.n, args.d)).astype(np.float32)
    y = np.where(X @ rng.standard_normal(args.d) > 0, 1, -1).astype(np.int64)
    basis = X[: args.m].copy()
    mesh = multihost.spanning_mesh()
    kern = KernelSpec("gaussian", sigma=2.0)
    solver = DistributedNystrom(mesh, 0.1, "squared_hinge", kern,
                                DistConfig(fused=True, materialize=False))
    sc = solver.make_stream_closures(
        ArrayChunkSource(X, y, chunk_rows=args.chunk_rows), basis)
    beta = np.zeros((args.m,), np.float32)

    f, g, aux = sc.fgrad(beta)           # warm: compile + first stream pass
    sc.hessd(aux, g)
    best = float("inf")
    for _ in range(args.evals):
        t0 = time.perf_counter()
        f, g, aux = sc.fgrad(beta)
        sc.hessd(aux, g)
        best = min(best, time.perf_counter() - t0)

    cr, d, m = sc.chunk_rows, args.d, args.m
    f32 = np.float32

    def count(fn, *shapes):
        with mesh:
            closed = jax.make_jaxpr(fn)(
                *[jax.ShapeDtypeStruct(s, f32) for s in shapes])
        return collective_payload_bytes_jaxpr(closed.jaxpr)

    fg_b = count(sc.fg_chunk, (cr, d), (cr,), (cr,), (m, d), (m,))
    hd_b = count(sc.hd_chunk, (cr, d), (cr,), (m, d), (m,))
    multihost.sync("bench-done")
    if multihost.is_primary():
        print(json.dumps({
            "num_processes": args.fleet, "n_devices": jax.device_count(),
            "eval_s": best, "n_chunks": sc.n_chunks, "chunk_rows": cr,
            "fg_chunk_bytes": int(fg_b), "hd_chunk_bytes": int(hd_b),
            "bytes_per_eval": int(sc.n_chunks * (fg_b + hd_b)),
            "partition_bytes": int(X.nbytes // args.fleet)}))


# ------------------------------------------------------------- fleet driver
def free_port():
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


def run_fleet(nproc):
    devs = 4 // nproc
    port = free_port()
    procs = []
    for p in range(nproc):
        env = dict(os.environ)
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devs}"
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PYTHONPATH"] = str(REPO_ROOT / "src") + os.pathsep \
            + env.get("PYTHONPATH", "")
        cmd = [sys.executable, "-m", "benchmarks.multihost_scaling",
               "--worker", "--fleet", str(nproc), "--pid", str(p),
               "--port", str(port),
               "--n", str(args.n), "--d", str(args.d), "--m", str(args.m),
               "--chunk-rows", str(args.chunk_rows),
               "--evals", str(args.evals)]
        procs.append(subprocess.Popen(
            cmd, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, env=env, cwd=str(REPO_ROOT)))
    outs = [pr.communicate()[0].decode(errors="replace") for pr in procs]
    for p, pr in enumerate(procs):
        if pr.returncode != 0:
            raise SystemExit(f"worker {p}/{nproc} failed rc={pr.returncode}:"
                             f"\n{outs[p][-2000:]}")
    return json.loads(outs[0].strip().splitlines()[-1])


def main():
    print(f"n={args.n} d={args.d} m={args.m} chunk_rows={args.chunk_rows} "
          f"evals={args.evals} (4 global devices throughout)")
    print("| procs | eval_s | bytes/eval | bytes/chunk (fg+hd) | "
          "partition MB |")
    print("|-------|--------|------------|---------------------|"
          "--------------|")
    results = []
    for nproc in args.procs:
        if 4 % nproc:
            raise SystemExit(f"--procs must divide 4, got {nproc}")
        row = run_fleet(nproc)
        results.append(row)
        print(f"| {nproc} | {row['eval_s']:.4f} | {row['bytes_per_eval']} "
              f"| {row['fg_chunk_bytes'] + row['hd_chunk_bytes']} "
              f"| {row['partition_bytes'] / 1e6:.1f} |", flush=True)

    # the instrumented O(m) claim, enforced at benchmark time too
    per_eval = {r["bytes_per_eval"] for r in results}
    assert len(per_eval) == 1, \
        f"cross-host bytes/eval changed with process count: {per_eval}"
    chunk_bytes = results[0]["fg_chunk_bytes"] + results[0]["hd_chunk_bytes"]
    assert chunk_bytes <= 8 * args.m * 4, \
        f"per-chunk payload {chunk_bytes}B is not O(m) (m={args.m})"

    from benchmarks.run import append_trajectory
    out = Path(args.out) if args.out else REPO_ROOT / "BENCH_multihost.json"
    append_trajectory(out, {
        "benchmark": "multihost_scaling",
        "run_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "config": {"n": args.n, "d": args.d, "m": args.m,
                   "chunk_rows": args.chunk_rows, "evals": args.evals,
                   "smoke": args.smoke},
        "results": results})
    print(f"appended {out}")


if __name__ == "__main__":
    worker() if args.worker else main()

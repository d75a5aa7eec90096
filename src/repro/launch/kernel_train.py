"""Distributed Nystrom kernel-machine training driver (the paper's system),
config-driven through the unified ``repro.api.KernelMachine``.

Single-host CPU example (1 device -> trivial mesh):
  PYTHONPATH=src python -m repro.launch.kernel_train --dataset covtype \
      --scale 0.01 --m 512 --basis auto --plan auto

Multi-device simulation:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.kernel_train --mesh 4,2 --plan shard_map

Out-of-core streaming from a shard directory (written by
``repro.data.chunks.save_chunks``; ``--export-chunks`` writes the chosen
synthetic dataset there first, so this one line is a full demo):
  PYTHONPATH=src python -m repro.launch.kernel_train --plan stream \
      --data-dir /tmp/covtype_shards --export-chunks --chunk-rows 8192

Multi-host (multi-controller): run the SAME command once per host with
``--coordinator host:port --num-processes P --process-id i`` — the mesh
then spans every process's devices, each host streams only its own
partition of the data, and process 0 owns checkpoints/saves/eval output.
``scripts/launch_multihost.sh`` wraps the local N-process simulation
(fake devices per process via ``--xla_force_host_platform_device_count``;
a CPU simulation only — on a TPU host one process drives every chip).

On a TPU, ``--backend pallas`` runs the fused Pallas kernels (the
default ``jnp`` is the row-chunked XLA fallback); the backend is saved in
the checkpoint. JAX's compile cache goes to ``JAX_COMPILATION_CACHE_DIR``
when it is set, else to ``.jax_cache`` at the repo root.

Any registered solver x plan combination is reachable from the CLI; the
``--solver``/``--plan`` choices below are read from the live registries in
``repro.api.registry``, so a newly registered entry shows up in ``--help``
without touching this file. ``--save`` writes a serving checkpoint for
``repro.launch.kernel_serve``.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api import (KernelMachine, MachineConfig, StreamConfig,
                       get_solver)
from repro.core import KernelSpec, TronConfig, select_basis
from repro.core.compat import make_mesh
from repro.data import PAPER_DATASETS, make_dataset, make_multiclass
from repro.data.chunks import (MmapChunkSource, is_partition_dir,
                               open_partition, save_chunks)
from repro.kernels.policy import POLICIES
from repro.launch.cli import (BACKENDS, enable_compile_cache, plan_choices,
                              registry_epilog, solver_choices)
from repro.sharding import multihost


def main(argv=None):
    """Parse ``argv`` (default ``sys.argv[1:]``), train, and return the
    fitted :class:`KernelMachine` (the supervising parent exits instead)."""
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog=registry_epilog())
    ap.add_argument("--dataset", default="covtype", choices=list(PAPER_DATASETS))
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--m", type=int, default=512)
    ap.add_argument("--basis", default="auto",
                    dest="strategy", choices=["auto", "random", "kmeans"])
    ap.add_argument("--mesh", default=None,
                    help="comma mesh shape, e.g. 4,2 -> (data, model)")
    ap.add_argument("--solver", default="tron", choices=solver_choices(),
                    help="optimization strategy (live registry: %(choices)s)")
    ap.add_argument("--plan", default="shard_map", choices=plan_choices(),
                    help="execution plan (live registry: %(choices)s)")
    ap.add_argument("--max-iter", type=int, default=200)
    ap.add_argument("--lam", type=float, default=None)
    ap.add_argument("--sigma", type=float, default=None)
    ap.add_argument("--classes", type=int, default=2,
                    help="class count: 2 trains the paper's binary problem; "
                         ">2 generates K-class data (integer labels) and "
                         "trains all one-vs-rest columns in ONE multi-RHS "
                         "TRON pass (solver 'tron' only)")
    ap.add_argument("--data-dir", default=None,
                    help="stream training data from this .npy/.npz shard "
                         "directory (plan 'stream'; see "
                         "repro.data.chunks.save_chunks)")
    ap.add_argument("--export-chunks", action="store_true",
                    help="write the synthetic --dataset into --data-dir as "
                         "mmap-able .npy shards before training")
    ap.add_argument("--chunk-rows", type=int, default=None,
                    help="rows streamed per step under plan 'stream' "
                         "(bounds every intermediate at chunk_rows x m)")
    ap.add_argument("--backend", default="jnp", choices=BACKENDS,
                    help="gram/kmvp implementation: 'pallas' runs the fused "
                         "Pallas kernels (compiled on a TPU, interpreted on "
                         "the CPU), 'jnp' the row-chunked XLA fallback; "
                         "saved into the checkpoint, so kernel_serve serves "
                         "through the same one")
    ap.add_argument("--policy", default="fp32",
                    choices=sorted(POLICIES),
                    help="dtype policy for the kernel compute path "
                         "(bf16/fp16 cut the tile matmul precision; "
                         "accumulation and TRON state stay fp32)")
    ap.add_argument("--quantize", default=None, choices=["int8"],
                    help="store the saved checkpoint's basis/beta as "
                         "symmetric per-column int8 (serving checkpoints "
                         "~4x smaller; load dequantizes transparently)")
    ap.add_argument("--save", default=None,
                    help="checkpoint path for repro.launch.kernel_serve")
    ap.add_argument("--ckpt-interval", type=int, default=0,
                    help="commit a preemption-safe in-training checkpoint "
                         "every N outer TRON iterations (0 = off; solver "
                         "'tron' only)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="step-file directory (default: <--save>.ckpt-steps)")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="retain only the newest N step files (0 = all)")
    ap.add_argument("--ckpt-sync", action="store_true",
                    help="commit checkpoints synchronously on the training "
                         "thread instead of the background writer")
    ap.add_argument("--resume", nargs="?", const="", default=None,
                    metavar="DIR",
                    help="restore the newest in-training checkpoint from DIR "
                         "(default: the --ckpt-dir / <--save>.ckpt-steps "
                         "directory) and continue training from it — "
                         "elastically: the device count may differ from the "
                         "run that wrote it")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="process 0's coordination address for "
                         "multi-controller runs (same value on every host)")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="total controller processes (hosts) in this run")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this host's index in [0, --num-processes)")
    ap.add_argument("--supervise", action="store_true",
                    help="run the fit under the fault-tolerant supervisor: "
                         "spawn --num-processes worker processes, restart "
                         "the fleet from the latest committed checkpoint "
                         "when a worker dies (capped exponential backoff + "
                         "jitter; shrinks the fleet after repeated failures "
                         "— requires --ckpt-interval). Every process runs on "
                         "this host, so --num-processes > 1 is a CPU "
                         "simulation only: a TPU chip belongs to one "
                         "process")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="restart budget under --supervise (0 = fail fast)")
    args = ap.parse_args(argv)

    if args.supervise:
        raise SystemExit(_supervise(ap, args, argv))

    if args.num_processes > 1 and not args.coordinator:
        ap.error("--num-processes > 1 needs --coordinator host:port")
    enable_compile_cache()
    multihost.init(args.coordinator, args.num_processes, args.process_id)
    # every process runs the same program; process 0 owns the console
    say = print if multihost.is_primary() else (lambda *a, **k: None)

    if multihost.active():
        if args.mesh:
            ap.error("--mesh conflicts with multi-controller runs: the "
                     "mesh always spans every process's devices")
        if args.strategy == "kmeans":
            ap.error("--basis kmeans is not routed multi-controller; use "
                     "--basis random (identical on every host)")
        mesh = multihost.spanning_mesh()
    elif args.mesh:
        shape = tuple(int(v) for v in args.mesh.split(","))
        names = ("data", "model")[: len(shape)]
        mesh = make_mesh(shape, names)
    else:
        shape, names = (len(jax.devices()),), ("data",)
        mesh = make_mesh(shape, names)
    model_axis = "model" if "model" in mesh.shape else None
    needs_basis = get_solver(args.solver).needs_basis
    if args.data_dir and args.plan != "stream":
        ap.error("--data-dir streams from disk and requires --plan stream")
    if args.classes > 2 and args.solver != "tron":
        ap.error(f"--classes {args.classes} trains one-vs-rest via the "
                 f"multi-RHS kmvp path, which only solver 'tron' supports")

    ckpt = None
    if args.ckpt_interval > 0 or args.resume is not None:
        from repro.checkpoint import (CheckpointConfig, load_latest,
                                      steps_dir_for)
        if args.solver != "tron":
            ap.error("--ckpt-interval/--resume snapshot TRON iterate state "
                     "and require --solver tron")
        ckpt_dir = args.resume or args.ckpt_dir \
            or (steps_dir_for(args.save) if args.save else None)
        if not ckpt_dir:
            ap.error("checkpointing needs a directory: pass --ckpt-dir, "
                     "--save (steps go next to it), or --resume DIR")
        ckpt = CheckpointConfig(
            dir=ckpt_dir,
            interval=args.ckpt_interval if args.ckpt_interval > 0 else 10,
            keep=args.ckpt_keep, background=not args.ckpt_sync,
            resume=args.resume is not None,
            write=multihost.is_primary())
        if ckpt.resume:
            rs = load_latest(ckpt.dir)   # fail fast, and announce the step
            say(f"[ckpt ] resuming from step {rs.step} ({rs.path})")
        else:
            import os
            if multihost.is_primary():
                os.makedirs(ckpt.dir, exist_ok=True)
            say(f"[ckpt ] step files -> {ckpt.dir} "
                f"every {ckpt.interval} iters "
                f"({'sync' if args.ckpt_sync else 'async'}, "
                f"keep={ckpt.keep})")

    def load_data(key):
        """(X, y, Xt, yt, spec): the paper's binary simulation, or K-class
        integer-label data when --classes > 2 (same mixture geometry)."""
        spec = PAPER_DATASETS[args.dataset]
        if args.classes <= 2:
            return make_dataset(args.dataset, key, scale=args.scale,
                                d_cap=784)
        n = max(int(spec.n * args.scale), 256)
        nt = max(int(spec.n_test * args.scale), 128)
        Xa, ya = make_multiclass(
            key, n + nt, min(spec.d, 784), args.classes,
            clusters_per_class=max(spec.clusters_per_class
                                   // args.classes, 2),
            margin=spec.margin)
        return Xa[:n], ya[:n], Xa[n:], ya[n:], spec

    def build_config(lam, sigma, m):
        return MachineConfig(
            kernel=KernelSpec("gaussian", sigma=sigma), lam=lam,
            solver=args.solver, plan=args.plan,
            tron=TronConfig(max_iter=args.max_iter),
            m=m, rff_features=m, model_axis=model_axis,
            backend=args.backend, dtype_policy=args.policy,
            stream=StreamConfig(chunk_rows=args.chunk_rows))

    # fail on an invalid solver/plan pair before any data work
    KernelMachine(build_config(1.0, 1.0, args.m), mesh=mesh)

    t0 = time.time()
    spec = PAPER_DATASETS[args.dataset]
    X = y = Xt = yt = None
    if args.data_dir and args.export_chunks:
        dd = Path(args.data_dir)
        if dd.is_dir() and (any(dd.glob("X_*.npy"))
                            or any(dd.glob("shard_*.npz"))):
            say(f"[export] {args.data_dir} already holds shards — "
                f"training on THOSE, not a fresh --dataset {args.dataset} "
                f"--scale {args.scale} export (delete the directory to "
                f"re-export)")
        elif multihost.is_primary():
            Xe, ye, _, _, _ = load_data(jax.random.PRNGKey(0))
            save_chunks(args.data_dir, Xe, ye)
            say(f"[export] wrote {Xe.shape[0]} rows to {args.data_dir} "
                f"({time.time() - t0:.2f}s)")
        multihost.sync("export-chunks")   # shards visible before any reader
    if args.data_dir:
        if is_partition_dir(args.data_dir):
            # this host's slice of a save_partition_dirs layout
            X = open_partition(args.data_dir)
            if args.chunk_rows:
                X = X.with_chunk_rows(args.chunk_rows)
            pid, nproc = X.process_span
            say(f"[step1] partition {args.data_dir}: process {pid}/{nproc} "
                f"of n={X.n} d={X.d} chunks={X.n_chunks} "
                f"({time.time() - t0:.2f}s)")
        else:
            # shared directory: multi-controller runs partition each chunk
            # row-wise per host inside make_stream_closures
            X = MmapChunkSource(args.data_dir, chunk_rows=args.chunk_rows)
            say(f"[step1] streaming {args.data_dir}: n={X.n} d={X.d} "
                f"chunks={X.n_chunks} ({time.time() - t0:.2f}s)")
    else:
        X, y, Xt, yt, spec = load_data(jax.random.PRNGKey(0))
        say(f"[step1] loaded {args.dataset}: n={X.shape[0]} d={X.shape[1]} "
            f"classes={args.classes} ({time.time() - t0:.2f}s)")
    lam = args.lam if args.lam is not None else max(spec.lam * args.scale, 1e-4)
    sigma = args.sigma if args.sigma is not None else max(spec.sigma, 1.0)

    if args.data_dir:
        Xs, ys = X, None           # plan 'stream' shards chunk by chunk
        n_dp = mesh.shape["data"]
        m = (args.m // n_dp) * n_dp if multihost.active() else args.m
    else:
        # keep shard sizes divisible for the in-memory distributed plans
        n_dp = mesh.shape["data"]
        n = (X.shape[0] // (n_dp * 8)) * n_dp * 8
        per = max(n_dp * mesh.shape.get("model", 1), 1)
        m = (args.m // per) * per
        X, y = X[:n], y[:n]
        if multihost.active():
            # leave X/y as host arrays: fit shards them globally, keeping
            # only this process's row block on its devices
            Xs, ys = np.asarray(X), np.asarray(y)
        else:
            Xs = jax.device_put(X, NamedSharding(mesh, P(("data",), None)))
            ys = jax.device_put(y, NamedSharding(mesh, P(("data",))))

    basis = None
    if needs_basis and not args.data_dir and not multihost.active():
        t0 = time.time()
        basis = select_basis(jax.random.PRNGKey(1), Xs, m,
                             strategy=args.strategy, mesh=mesh,
                             data_axes=("data",))
        basis.block_until_ready()
        say(f"[step2] basis: m={m} strategy={args.strategy} "
            f"({time.time() - t0:.2f}s)")
    elif needs_basis and multihost.active() and not args.data_dir:
        say(f"[step2] basis: m={m} sampled in-fit (deterministic on every "
            f"host)")

    km = KernelMachine(build_config(lam, sigma, m), mesh=mesh)

    t0 = time.time()
    km.fit(Xs, ys, basis,          # streaming fit samples a random basis
           checkpoint=ckpt)
    jax.block_until_ready(km.state_["beta"])
    r = km.result_
    say(f"[step3+4] {r.solver}/{r.plan}: f={r.f:.4f} iters={r.n_iter} "
        f"fg={r.n_fg} hd={r.n_hd} converged={r.converged} "
        f"({time.time() - t0:.2f}s)")
    if r.tron is not None:
        fh = np.asarray(r.tron.f_hist)[: r.n_iter + 1]
        fh = fh.reshape(fh.shape[0], -1).sum(axis=1)   # one-vs-rest: total
        say(f"[tron ] f per iteration: "
            f"{' '.join(f'{v:.8g}' for v in fh)}")
    if ckpt is not None:
        cs = r.extras["ckpt"]
        say(f"[ckpt ] wrote {cs['snapshots_written']} step files "
            f"({cs['bytes_written']} bytes, {cs['write_seconds']:.3f}s "
            f"{'sync' if args.ckpt_sync else 'async'}, "
            f"dropped={cs['snapshots_dropped']}, last_step={cs['last_step']}"
            f", errors={cs['errors']}, retries={cs.get('write_retries', 0)}"
            f", io_warnings={cs.get('io_warnings', 0)})")

    if multihost.active():
        _eval_multihost(km, X, y, mesh, args, say)
    elif args.data_dir:
        Xh, yh = X.chunk(0)        # held-in sample; no synthetic test split
        say(f"[eval ] train_acc(chunk0)={km.score(Xh, yh):.4f}")
    else:
        say(f"[eval ] train_acc={km.score(X, y):.4f} "
            f"test_acc={km.score(Xt, yt):.4f}")
    if args.save:
        if multihost.is_primary():
            print(f"[save ] {km.save(args.save, quantize=args.quantize)}")
        multihost.sync("save")     # checkpoint durable before anyone exits
    multihost.sync("done")
    return km


def _supervise(ap, args, argv=None) -> int:
    """The ``--supervise`` branch: relaunch this CLI under the supervisor.

    The parent never initializes a mesh — it is a pure process manager.
    Each worker is this same command line minus the supervision flags,
    plus per-process coordinator flags (multi-process fleets) and
    ``--resume`` once the checkpoint directory holds a committed step.
    """
    import sys

    from repro.sharding.supervisor import (Supervisor, SupervisorConfig,
                                           SupervisorError)

    if args.solver != "tron" or args.ckpt_interval <= 0:
        ap.error("--supervise restarts from committed checkpoints and "
                 "needs --solver tron with --ckpt-interval N")
    if args.process_id != 0 or args.coordinator:
        ap.error("--supervise owns the fleet topology; don't combine it "
                 "with --coordinator/--process-id")
    from repro.checkpoint import steps_dir_for
    ckpt_dir = args.ckpt_dir or (steps_dir_for(args.save) if args.save
                                 else None)
    if not ckpt_dir:
        ap.error("--supervise needs a checkpoint directory: pass "
                 "--ckpt-dir or --save")

    # Child argv = this argv minus the supervision/topology/resume flags
    # (the supervisor decides topology and resume per attempt).
    strip_valued = {"--max-restarts", "--coordinator", "--num-processes",
                    "--process-id"}
    argv = list(sys.argv[1:] if argv is None else argv)
    base, i = [], 0
    while i < len(argv):
        tok = argv[i]
        if tok == "--supervise":
            i += 1
        elif tok in strip_valued:
            i += 2
        elif tok == "--resume":
            i += 1
            if i < len(argv) and not argv[i].startswith("--"):
                i += 1                 # nargs="?": swallow the DIR value
        else:
            base.append(tok)
            i += 1

    def build_cmd(pid, nproc, port, resume):
        cmd = [sys.executable, "-m", "repro.launch.kernel_train", *base]
        if nproc > 1:
            cmd += ["--coordinator", f"127.0.0.1:{port}",
                    "--num-processes", str(nproc),
                    "--process-id", str(pid)]
        if resume:
            cmd += ["--resume", ckpt_dir]
        return cmd

    sup = Supervisor(build_cmd, num_processes=args.num_processes,
                     ckpt_dir=ckpt_dir,
                     config=SupervisorConfig(max_restarts=args.max_restarts))
    try:
        result = sup.run()
    except SupervisorError as err:
        print(err)
        return 1
    # surface the winning attempt's process-0 output (the say() lines a
    # non-supervised run would have printed)
    log0 = result.final_attempt["logs"][0]
    try:
        with open(log0, "r", errors="replace") as fh:
            tail = fh.read().splitlines()[-30:]
        for line in tail:
            print(line)
    except OSError:
        pass
    print(f"[supervise] done: restarts={result.restarts} "
          f"processes={result.final_processes}"
          f"{' (shrunk)' if result.shrunk else ''} "
          f"total={result.total_s:.1f}s logs={sup.log_dir}")
    return 0


def _eval_multihost(km, X, y, mesh, args, say) -> None:
    """Score a held-in sample through the process-spanning serving arm.

    The decider plans row-shard their outputs over local devices and so do
    not span processes; the :class:`SpanningServer` does — and doubles as
    a smoke test of the serving arm right after training. Every process
    enters the lockstep rounds with the identical (broadcast) batch, so no
    follower loop is needed.
    """
    from repro.sharding.multihost import SpanningServer
    st = km.state_
    if args.data_dir:
        Xh, yh = X.chunk(0)        # this host's block of global chunk 0
    else:
        Xh, yh = X, y
    Xh = np.asarray(Xh)
    yh = np.asarray(yh)
    ne = int(multihost.broadcast_from_primary(
        np.asarray([min(Xh.shape[0], 256)], np.int64))[0])
    xb = np.zeros((ne, Xh.shape[1]), Xh.dtype)
    xb[:min(ne, Xh.shape[0])] = Xh[:ne]
    yb = np.zeros((ne,), np.int64)
    yb[:min(ne, yh.shape[0])] = yh[:ne]
    Xh = multihost.broadcast_from_primary(xb)       # process 0's rows win
    yh = multihost.broadcast_from_primary(yb)
    server = SpanningServer(np.asarray(st["basis"]), np.asarray(st["beta"]),
                            km.config.kernel, mesh,
                            backend=km.config.backend,
                            max_batch=min(ne, 64))
    o = np.asarray(server.margins(Xh))
    if o.ndim == 2 and o.shape[1] > 1:
        pred = np.asarray(st["classes"])[np.argmax(o, axis=1)]
    else:
        pred = np.where(o.ravel() > 0, 1, -1)
    say(f"[eval ] train_acc({ne} rows via spanning server)="
        f"{float((pred == yh).mean()):.4f} "
        f"xhost_bytes/eval={server.collective_payload_bytes()}")


if __name__ == "__main__":
    main()

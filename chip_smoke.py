#!/usr/bin/env python3
"""Run the kernel machine's train -> serve path once on a TPU and check it.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the mesh plans, on a four-chip host

One chip, in this one process:

1. train: ``repro.launch.kernel_train`` (its ``main``, not a child process)
   fits the covtype-shaped problem at its published size (n=522,910, d=54,
   binary; m=16,384 random basis) under plan ``otf_shard`` with the Pallas
   kernels and the fp32 policy, 3 TRON iterations, and saves a checkpoint;
2. reference: on a 4096-row slice the machine's margins C(x, basis)·beta
   and C^T v through the kernels must match a float64 host computation to
   1e-4 of their largest magnitude, and both kernels must have compiled to
   ``tpu_custom_call`` (no interpret mode);
3. serve: ``kernel_serve``'s registry loads the checkpoint (it must come
   back on the Pallas backend), warms every bucket, and its engine answers
   320 requests from 8 concurrent clients; every response must equal the
   synchronous decider, no dispatch may fail, and margins served for a
   batch of test rows must match the float64 reference.

``--chips 4`` runs only what exists across chips: f, g and Hd of
formulation (4) at a seeded beta under ``otf_shard`` (Pallas) and
``shard_map`` (materialized C) on a (4,) data mesh, against the same on one
device to 1e-5, then 3 TRON iterations on four chips against one to 1e-4,
with each device's resident bytes.

Without a TPU it exits non-zero before any work. Any phase that fails
exits non-zero. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Wall and compile seconds printed on the way are set-up figures, not
device metrics. JAX's compile cache is placed as ``kernel_train`` places
it (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` here).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

MARGIN_TOL = 1e-4     # margins / C^T v against float64, of max magnitude
MESH_TOL = 1e-5       # 4-device f/g/Hd against one device
TRON_TOL = 1e-4       # objective after 3 TRON iterations, 4 chips vs 1


class Check(AssertionError):
    """A smoke check that did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Check(what)
    print(f"[check] ok: {what}", flush=True)


class Phase:
    """Times one phase: wall seconds, and seconds spent in XLA compiles
    (or compile-cache loads), counted through jax.monitoring once
    ``main`` has registered ``Phase.listen``."""

    compile_s = 0.0
    compiles = 0

    @classmethod
    def listen(cls, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            cls.compile_s += duration
            cls.compiles += 1

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        print(f"[phase] {self.name}", flush=True)
        self.t0 = time.perf_counter()
        self.c0, self.n0 = Phase.compile_s, Phase.compiles
        return self

    def __exit__(self, *exc):
        print(f"[phase] {self.name} done: wall_s="
              f"{time.perf_counter() - self.t0:.3f} compile_s="
              f"{Phase.compile_s - self.c0:.3f} compiles="
              f"{Phase.compiles - self.n0}", flush=True)


def rel_err(got, want) -> float:
    """max |got - want| over max |want| (float64)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def gram64(x, z, sigma: float) -> np.ndarray:
    """The gaussian gram on the host in float64 — the reference."""
    x = np.asarray(x, np.float64)
    z = np.asarray(z, np.float64)
    d2 = (np.sum(x * x, 1)[:, None] + np.sum(z * z, 1)[None, :]
          - 2.0 * x @ z.T)
    return np.exp(-np.maximum(d2, 0.0) / (2.0 * sigma ** 2))


def covtype(scale: float):
    """The covtype-shaped data exactly as ``kernel_train`` makes it."""
    from repro.data import make_dataset
    return make_dataset("covtype", jax.random.PRNGKey(0), scale=scale,
                        d_cap=784)


# ------------------------------------------------------------------ 1 chip
def one_chip(out: Path, *, scale: float = 1.0, m: int = 16384,
             rows: int = 4096, clients: int = 8, requests: int = 40,
             max_batch: int = 256, tpu: bool = True) -> None:
    from repro.kernels import ops
    from repro.launch import kernel_serve, kernel_train
    from repro.serve import EngineConfig, ServeEngine

    out.mkdir(parents=True, exist_ok=True)
    ckpt = out / "covtype.npz"
    with Phase("train"):
        km = kernel_train.main([
            "--dataset", "covtype", "--scale", str(scale), "--m", str(m),
            "--basis", "random", "--plan", "otf_shard", "--backend",
            "pallas", "--policy", "fp32", "--max-iter", "3",
            "--save", str(ckpt)])
        r = km.result_
        fh = np.asarray(r.tron.f_hist)[: r.n_iter + 1]
        print(f"[train] plan={r.plan} backend={km.config.backend} "
              f"m={r.m} iters={r.n_iter} fg={r.n_fg} hd={r.n_hd} "
              f"f_per_iter={fh.tolist()}", flush=True)
        check(np.all(np.isfinite(fh)) and fh[-1] < fh[0],
              f"TRON objective finite and decreasing over {r.n_iter} "
              f"iterations ({fh[0]:.8g} -> {fh[-1]:.8g})")

    with Phase("reference"):
        X, _, Xt, _, _ = covtype(scale)
        basis = km.state_["basis"]
        beta = np.asarray(km.state_["beta"], np.float64)
        sigma = km.config.kernel.sigma
        print(f"[data ] X={tuple(X.shape)} basis={tuple(basis.shape)} "
              f"sigma={sigma} lam={km.config.lam}", flush=True)
        xs = X[:rows]
        v = np.random.default_rng(0).standard_normal(rows).astype(np.float32)
        o = km.decision_function(xs)
        g = ops.otf_kmvp_t(xs, basis, v, sigma=sigma, backend="pallas")
        C64 = gram64(xs, basis, sigma)
        e_o = rel_err(o, C64 @ beta)
        e_g = rel_err(g, C64.T @ v.astype(np.float64))
        print(f"[ref  ] {rows}-row slice vs float64: margin_rel_err={e_o:.3e}"
              f" kmvp_t_rel_err={e_g:.3e}", flush=True)
        check(e_o <= MARGIN_TOL, f"margins within {MARGIN_TOL} of float64")
        check(e_g <= MARGIN_TOL, f"kmvp_t within {MARGIN_TOL} of float64")
        if tpu:
            check(ops._interpret_default() is False,
                  "Pallas kernels compile (no interpret mode)")
            bn, bm, bd = ops.otf_tiles(rows, basis.shape[0], basis.shape[1])
            tiles = dict(sigma=sigma, bn=bn, bm=bm, bd=bd)
            for name, fn, arg in (("kmvp_fwd", ops.kmvp_fwd, beta),
                                  ("kmvp_t", ops.kmvp_t, v)):
                hlo = fn.lower(xs, basis, np.asarray(arg, np.float32),
                               **tiles).compile().as_text()
                check("tpu_custom_call" in hlo,
                      f"{name} compiled to tpu_custom_call "
                      f"(tiles bn={bn} bm={bm} bd={bd})")
            hlo = jax.jit(km.decider()).lower(xs).compile().as_text()
            check("tpu_custom_call" in hlo,
                  "the machine's decide arm runs the Pallas kernel")

    with Phase("serve"):
        registry = kernel_serve.build_registry([str(ckpt)],
                                               max_batch=max_batch)
        entry = registry.get("m0")
        check(entry.km.config.backend == "pallas" and entry.plan ==
              "otf_shard", f"checkpoint serves on plan={entry.plan} "
              f"backend={entry.km.config.backend}")
        report, stats = kernel_serve.serve_concurrent(
            registry, clients=clients, requests=requests,
            max_batch=max_batch,
            engine_config=EngineConfig(max_batch=max_batch))
        print(f"[serve] {stats}", flush=True)
        check(report.completed == report.requests == clients * requests,
              f"{report.completed} of {report.requests} requests answered")
        check(report.failed == 0, "0 failed dispatches")
        check(report.mismatches == 0,
              "0 responses differ from the synchronous decider")
        xb = np.asarray(Xt[:200])
        with ServeEngine(registry, EngineConfig(max_batch=max_batch)) as eng:
            ob = eng(xb)
        e_b = rel_err(ob, gram64(xb, basis, sigma) @ beta)
        print(f"[serve] {xb.shape[0]}-row served batch vs float64: "
              f"rel_err={e_b:.3e}", flush=True)
        check(e_b <= MARGIN_TOL, f"served margins within {MARGIN_TOL} of "
              f"float64")


# ----------------------------------------------------------------- 4 chips
def _fg_hd(mesh, X, y, basis, beta, d, *, plan: str, backend: str, lam,
           sigma):
    """f, g, Hd of formulation (4) at (beta, d) under ``plan`` on ``mesh``,
    plus each device's resident bytes with the plan's data in place."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import DistConfig, DistributedNystrom, KernelSpec
    solver = DistributedNystrom(
        mesh, lam, "squared_hinge", KernelSpec("gaussian", sigma=sigma),
        DistConfig(materialize=plan == "shard_map",
                   fused=plan == "otf_shard", backend=backend))
    Xs = jax.device_put(X, NamedSharding(mesh, P("data", None)))
    ys = jax.device_put(y, NamedSharding(mesh, P("data")))
    if plan == "shard_map":
        make, data = solver.make_closures, (*solver.precompute(Xs, basis), ys)
    else:
        make, data = solver.make_fused_closures, (Xs, ys, basis)

    @jax.jit
    def evaluate(data, beta, d):
        fgrad, hessd = make(*data)
        f, g, D = fgrad(beta)
        return f, g, hessd(D, d)

    with mesh:
        f, g, h = jax.block_until_ready(evaluate(data, beta, d))
    used = [(dev.memory_stats() or {}).get("bytes_in_use")
            for dev in mesh.devices.flat]
    return float(f), np.asarray(g), np.asarray(h), used


def _tron3(mesh, X, y, basis, *, plan: str, backend: str, lam,
           sigma) -> float:
    """The objective after 3 TRON iterations through ``KernelMachine``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.api import KernelMachine, MachineConfig
    from repro.core import KernelSpec, TronConfig
    km = KernelMachine(MachineConfig(
        kernel=KernelSpec("gaussian", sigma=sigma), lam=lam, plan=plan,
        backend=backend, tron=TronConfig(max_iter=3)), mesh=mesh)
    km.fit(jax.device_put(X, NamedSharding(mesh, P("data", None))),
           jax.device_put(y, NamedSharding(mesh, P("data"))), basis)
    return float(km.result_.f)


def four_chips(*, scale: float = 1.0, m: int = 16384) -> None:
    """Both mesh plans on a (4,) data mesh against ``otf_shard`` on one
    device. Materialized C under ``shard_map`` is n/4 x m x 4 bytes, 8.6 GB
    per device at m=16384: it fits a v5e's 16 GB, so m is not cut."""
    from repro.core import random_basis
    from repro.core.compat import make_mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"{len(devices)} devices (need 4)")
    mesh4 = make_mesh((4,), ("data",), devices=devices[:4])
    mesh1 = make_mesh((1,), ("data",), devices=devices[:1])
    with Phase("data"):
        X, y, _, _, spec = covtype(scale)
        n = (X.shape[0] // 32) * 32       # 8-row tiles on each of 4 devices
        X, y = X[:n], y[:n]
        # kernel_train's defaults
        kw = dict(lam=max(spec.lam * scale, 1e-4), sigma=max(spec.sigma, 1.0))
        basis = random_basis(jax.random.PRNGKey(1), X, m)
        rng = np.random.default_rng(0)
        beta = (0.1 * rng.standard_normal(m)).astype(np.float32)
        d = rng.standard_normal(m).astype(np.float32)
        print(f"[data ] X=({n}, {X.shape[1]}) m={m} {kw}", flush=True)
    with Phase(f"otf_shard m={m} on one device"):
        f1, g1, h1, used1 = _fg_hd(mesh1, X, y, basis, beta, d,
                                   plan="otf_shard", backend="pallas", **kw)
        t1 = _tron3(mesh1, X, y, basis, plan="otf_shard", backend="pallas",
                    **kw)
    for plan, backend in (("otf_shard", "pallas"), ("shard_map", "jnp")):
        with Phase(f"{plan} m={m} on 4 devices"):
            f4, g4, h4, used4 = _fg_hd(mesh4, X, y, basis, beta, d,
                                       plan=plan, backend=backend, **kw)
            t4 = _tron3(mesh4, X, y, basis, plan=plan, backend=backend, **kw)
        errs = (abs(f4 - f1) / abs(f1), rel_err(g4, g1), rel_err(h4, h1))
        print(f"[mesh ] {plan} backend={backend}: f={f4:.9g} (1 device "
              f"{f1:.9g}) rel_err f={errs[0]:.3e} g={errs[1]:.3e} "
              f"Hd={errs[2]:.3e}", flush=True)
        print(f"[mesh ] bytes_in_use per device: 4 devices {used4}, "
              f"1 device {used1}", flush=True)
        check(max(errs) <= MESH_TOL,
              f"{plan} f/g/Hd on 4 devices within {MESH_TOL} of one device")
        e_t = abs(t4 - t1) / abs(t1)
        print(f"[tron ] {plan}: f after 3 iterations 4 devices {t4:.9g}, "
              f"1 device {t1:.9g}, rel_err={e_t:.3e}", flush=True)
        check(e_t <= TRON_TOL, f"{plan} TRON objective on 4 devices within "
              f"{TRON_TOL} of one device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train, check and serve on one chip; 4: the "
                         "mesh plans against one device")
    ap.add_argument("--out", default=str(ROOT / ".smoke"),
                    help="directory for the trained checkpoint")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {jax.devices()}); "
              f"nothing was run", file=sys.stderr)
        return 2
    from repro.launch.cli import enable_compile_cache
    jax.monitoring.register_event_duration_secs_listener(Phase.listen)
    print(f"[setup] devices={jax.devices()} compile_cache="
          f"{enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips()
        else:
            one_chip(Path(args.out))
    except Check as e:
        print(f"[check] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"[setup] total wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={Phase.compile_s:.3f} compiles={Phase.compiles}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

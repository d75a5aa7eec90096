"""Roofline for the PAPER's own workload: distributed TRON (Algorithm 1) at
full published scale — MNIST8m (n=8M, d=784) with m up to 51200 basis
points — lowered on the production 16x16 mesh with ShapeDtypeStructs.

Run standalone (sets the 512-device flag before jax import):
  PYTHONPATH=src python -m benchmarks.kernel_roofline

Compares three execution plans per (n, m):
  * shard_map  (faithful Algorithm 1, explicit psums)
  * auto       (XLA SPMD chooses the schedule)
  * otf        (materialize=False — C recomputed per matvec, the paper's
                kernel-caching idea; trades FLOPs for HBM capacity/traffic)
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import json
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import DistConfig, DistributedNystrom, KernelSpec, TronConfig
from repro.core.compat import make_mesh
from repro.core.tron import tron

RESULTS = Path(__file__).resolve().parent / "results" / "kernel_machine"
PEAK_FLOPS, HBM_BW, ICI_BW = 197e12, 819e9, 50e9

_COLL_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\(")
_DT = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "pred": 1, "f64": 8, "u32": 4}


def _coll_bytes(txt):
    out = {}
    for m in _COLL_RE.finditer(txt):
        b = _DT.get(m.group(1), 4)
        for d in m.group(2).split(","):
            if d.strip():
                b *= int(d)
        out[m.group(3)] = out.get(m.group(3), 0) + b
    return out


def lower_kernel_machine(n, m, d, mode, materialize, mesh, c_dtype=jnp.float32):
    kern = KernelSpec("gaussian", sigma=7.0)
    dc = DistConfig(data_axes=("data",), model_axis="model", mode=mode,
                    materialize=materialize)
    solver = DistributedNystrom(mesh, 8.0, "squared_hinge", kern, dc)
    sh = solver.shardings()
    X = jax.ShapeDtypeStruct((n, d), jnp.float32)
    y = jax.ShapeDtypeStruct((n,), jnp.float32)
    basis = jax.ShapeDtypeStruct((m, d), jnp.float32)
    cfg = TronConfig(max_iter=300)

    if materialize:
        C = jax.ShapeDtypeStruct((n, m), c_dtype)
        W = jax.ShapeDtypeStruct((m, m), c_dtype)

        def step(C, W, y, b0):
            # one TRON iteration's work: f/g + 3 Hd (paper's per-iter mix)
            fgrad, hessd = solver.make_closures(C, W, y)
            f, g, D = fgrad(b0)
            h = hessd(D, g)
            h = hessd(D, h)
            h = hessd(D, h)
            return f, g + h

        with mesh:
            lowered = jax.jit(step, in_shardings=(
                sh["c"], sh["w"], sh["y"], sh["rep"])).lower(
                C, W, y, jax.ShapeDtypeStruct((m,), jnp.float32))
    else:
        def step(X, y, basis, b0):
            fg, hd = solver.make_otf_closures(X, y, basis)
            f, g, D = fg(b0)
            h = hd(D, g)
            h = hd(D, h)
            h = hd(D, h)
            return f, g + h

        with mesh:
            lowered = jax.jit(step, in_shardings=(
                sh["x"], sh["y"], sh["rep"], sh["rep"])).lower(
                X, y, basis, jax.ShapeDtypeStruct((m,), jnp.float32))
    return lowered


def main():
    RESULTS.mkdir(parents=True, exist_ok=True)
    mesh = make_mesh((16, 16), ("data", "model"),
                     devices=jax.devices()[:256])
    n, d = 8_000_000, 784
    print("| n | m | plan | compute_s | memory_s (HLO ub) | stream_s (analytic) | "
          "collective_s | dominant | C bytes/dev |")
    print("|---|---|---|---|---|---|---|---|---|")
    for m in (10_240, 51_200):
        for plan, mode, mat in (("shard_map", "shard_map", True),
                                ("auto", "auto", True),
                                ("otf", "shard_map", False),
                                ("bf16C", "auto", True)):
            t0 = time.time()
            lowered = lower_kernel_machine(
                n, m, d, mode, mat, mesh,
                c_dtype=jnp.bfloat16 if plan == "bf16C" else jnp.float32)
            compiled = lowered.compile()
            cost = compiled.cost_analysis()
            colls = _coll_bytes(compiled.as_text())
            flops = float(cost.get("flops", 0))
            byts = float(cost.get("bytes accessed", 0))
            cb = float(sum(colls.values()))
            terms = dict(compute_s=flops / PEAK_FLOPS, memory_s=byts / HBM_BW,
                         collective_s=cb / ICI_BW)
            dom = max(terms, key=terms.get)
            c_bytes = n * m * (2 if plan == "bf16C" else 4) / 256 if mat else 0
            # analytic streaming floor for the 8-matvec TRON iteration mix:
            # materialized plans stream C per matvec; OTF streams X + basis
            # (the capacity-free regime of the fused Pallas kmvp)
            if mat:
                stream = 8 * c_bytes / HBM_BW
            else:
                per_dev = (n // 16) * d * 4 + m * d * 4
                stream = 8 * per_dev / HBM_BW
            terms["stream_s"] = stream
            print(f"| {n} | {m} | {plan} | {terms['compute_s']:.3e} | "
                  f"{terms['memory_s']:.3e} | {stream:.3e} | "
                  f"{terms['collective_s']:.3e} | "
                  f"{dom} | {c_bytes / 2**30:.2f} GiB |", flush=True)
            (RESULTS / f"n{n}_m{m}_{plan}.json").write_text(json.dumps(
                {"n": n, "m": m, "plan": plan, "roofline": terms,
                 "dominant": dom, "collectives": colls,
                 "compile_s": round(time.time() - t0, 1)}, indent=2))


if __name__ == "__main__":
    main()

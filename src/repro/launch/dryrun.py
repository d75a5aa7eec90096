import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination with ShapeDtypeStruct inputs (zero allocation), record
memory_analysis / cost_analysis / collective-bytes for the roofline.

The two lines above MUST precede any jax import: jax locks the device count
at first init. Do not set this flag globally — tests/benches see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]

Results cached as benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_arch
from repro.launch.mesh import make_production_mesh
from repro.models.common import unrolled_scans, unzip
from repro.models.config import INPUT_SHAPES, ArchConfig, ShapeSpec
from repro.models.registry import cache_specs, input_specs, make_model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.sharding.ctx import use_shard_hints
from repro.sharding.partitioning import (batch_specs, cache_pspecs,
                                         fsdp_axes, param_specs)
from repro.train.steps import make_prefill_step, make_serve_step, make_train_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# v5e hardware constants (roofline denominators)
PEAK_FLOPS = 197e12      # bf16 FLOP/s per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link
TRAIN_MICROBATCHES = 8   # gradient-accumulation factor for train shapes

_COLL_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\(")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "pred": 1, "s8": 1,
                "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str):
    """Sum RESULT bytes of every collective in the partitioned HLO (per-device
    program, consistent with cost_analysis being per-partition)."""
    per_kind = {}
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        for d in dims.split(","):
            if d.strip():
                nbytes *= int(d)
        per_kind[kind] = per_kind.get(kind, 0) + nbytes
    return per_kind


def adapt_for_shape(cfg: ArchConfig, shape: ShapeSpec) -> ArchConfig:
    """long_500k: full-attention families switch to the sliding-window
    variant (sub-quadratic decode via ring cache); ssm/hybrid run native.
    DESIGN.md §Arch-applicability records this policy."""
    if shape.name == "long_500k" and cfg.family in ("dense", "moe", "vlm", "audio"):
        return cfg.with_(attention_variant="sliding", window=8192)
    return cfg


def opt_config(n_params: int) -> AdamWConfig:
    """bf16 moments above 20B params so optimizer state fits 16GB/chip."""
    return AdamWConfig(state_dtype="bfloat16" if n_params > 20e9 else "float32")


def _tree_size(tree) -> int:
    import math
    return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))


def lower_step(cfg: ArchConfig, shape: ShapeSpec, mesh, micro_override=None):
    """Build shardings and lower the appropriate step. Returns jax Lowered."""
    model = make_model(cfg, max_dec_seq=shape.seq_len)
    annotated = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sds, axes = unzip(annotated)
    n_params = _tree_size(params_sds)
    p_specs = param_specs(axes, mesh)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                           is_leaf=lambda x: isinstance(x, P))

    batch_sds = input_specs(cfg, shape)
    fa = fsdp_axes(mesh)
    fsdp_size = 1
    for a in fa:
        fsdp_size *= mesh.shape[a]

    if shape.kind == "train":
        ocfg = opt_config(n_params)
        opt_sds = jax.eval_shape(lambda p: adamw_init(p, ocfg), params_sds)
        opt_shard = {"m": p_shard, "v": p_shard,
                     "step": NamedSharding(mesh, P())}
        b_specs = batch_specs(batch_sds, mesh)
        b_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs,
                               is_leaf=lambda x: isinstance(x, P))
        micro = TRAIN_MICROBATCHES if shape.global_batch % TRAIN_MICROBATCHES == 0 else 1
        if micro_override is not None:
            micro = micro_override
        acc_dt = jnp.bfloat16 if n_params > 20e9 else None
        step = make_train_step(model, ocfg, microbatches=micro,
                               acc_dtype=acc_dt)
        with mesh, use_shard_hints(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, opt_shard, b_shard),
                out_shardings=(p_shard, opt_shard, None),
                donate_argnums=(0, 1),
            ).lower(params_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        b_specs = batch_specs(batch_sds, mesh)
        b_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs,
                               is_leaf=lambda x: isinstance(x, P))
        step = make_prefill_step(model)
        with mesh, use_shard_hints(mesh):
            lowered = jax.jit(
                step, in_shardings=(p_shard, b_shard), out_shardings=None,
            ).lower(params_sds, batch_sds)
    else:  # decode
        cache_sds = cache_specs(cfg, shape)
        shard_seq = shape.global_batch < fsdp_size
        c_specs = cache_pspecs(cache_sds, mesh, shard_seq_over_fsdp=shard_seq)
        c_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), c_specs,
                               is_leaf=lambda x: isinstance(x, P))
        tok_spec = P(fa) if shape.global_batch >= fsdp_size else P()
        tok_shard = NamedSharding(mesh, P(*tok_spec, None))
        step = make_serve_step(model)
        with mesh, use_shard_hints(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, tok_shard, c_shard),
                out_shardings=(None, None, c_shard),
                donate_argnums=(2,),
            ).lower(params_sds, batch_sds["tokens"], cache_sds)
    return lowered, n_params


def _probe_cost(cfg: ArchConfig, shape: ShapeSpec, mesh, k_periods: int,
                micro_override: int | None = None):
    """Compile a k-period model with ALL scans unrolled -> exact op counts.

    Train shapes are probed with ONE microbatch at global_batch/micro and
    scaled back up (per-microbatch cost is shape-identical; only the tiny
    optimizer update is overcounted by the factor) — keeps the fully
    unrolled probe HLO ~8x smaller."""
    from repro.models.transformer import period_len
    pl_ = 1 if cfg.is_encdec else period_len(cfg)
    probe = cfg.with_(n_layers=pl_ * k_periods,
                      encoder_layers=k_periods if cfg.is_encdec else 0,
                      # per-period cost is pps-invariant (remat recomputes
                      # each period exactly once either way)
                      periods_per_scan_step=1)
    scale = 1
    pshape = shape
    eff_micro = micro_override or TRAIN_MICROBATCHES
    if shape.kind == "train" and shape.global_batch % eff_micro == 0:
        scale = eff_micro
        pshape = dataclasses.replace(
            shape, global_batch=shape.global_batch // eff_micro)
    with unrolled_scans():
        lowered, _ = lower_step(probe, pshape, mesh, micro_override=1)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = collective_bytes(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)) * scale,
        "bytes": float(cost.get("bytes accessed", 0.0)) * scale,
        "colls": {k: v * scale for k, v in colls.items()},
        "n_coll": len(_COLL_RE.findall(hlo)) * scale,
    }


def extrapolated_cost(cfg: ArchConfig, shape: ShapeSpec, mesh,
                      micro_override: int | None = None) -> dict:
    """cost(full depth) = c1 + (P-1) * (c2 - c1), exact if per-period cost is
    depth-invariant (it is: identical period structure)."""
    from repro.models.transformer import n_periods
    P_full = cfg.encoder_layers if cfg.is_encdec else n_periods(cfg)
    c1 = _probe_cost(cfg, shape, mesh, 1, micro_override=micro_override)
    c2 = _probe_cost(cfg, shape, mesh, 2, micro_override=micro_override)
    scale = P_full - 1
    kinds = set(c1["colls"]) | set(c2["colls"])
    colls = {k: max(c1["colls"].get(k, 0) +
                    scale * (c2["colls"].get(k, 0) - c1["colls"].get(k, 0)), 0)
             for k in kinds}
    return {
        "flops": max(c1["flops"] + scale * (c2["flops"] - c1["flops"]), 0.0),
        "bytes": max(c1["bytes"] + scale * (c2["bytes"] - c1["bytes"]), 0.0),
        "colls": colls,
        "n_coll": max(c1["n_coll"] + scale * (c2["n_coll"] - c1["n_coll"]), 0),
    }


def modeled_traffic(cfg: ArchConfig, shape: ShapeSpec, n_params: int,
                    n_chips: int) -> float:
    """Streaming LOWER BOUND on per-device HBM traffic for one step.

    The HLO 'bytes accessed' metric assumes every intermediate round-trips
    HBM (no fusion) — a loose upper bound. This models the minimum:
    parameters/optimizer state streamed once per use, one saved activation
    per period (remat), logits, KV/state cache read+write for decode.
    True traffic lies between the two; both are reported.
    """
    from repro.models.registry import cache_specs as _cs
    from repro.models.transformer import n_periods, period_len
    dt = 2 if cfg.dtype == "bfloat16" else 4
    pb = n_params * dt / n_chips
    B, S, d = shape.global_batch, shape.seq_len, cfg.d_model
    Pn = cfg.encoder_layers if cfg.is_encdec else n_periods(cfg)
    act = B * S * d * dt / n_chips
    if shape.kind == "train":
        ob = n_params * (2 if n_params > 20e9 else 4) * 2 / n_chips
        logits = B * S * cfg.vocab_padded * dt / n_chips
        # params: fwd read + bwd read + remat read + grad w/r + update write
        return pb * 6 + ob * 2 + act * Pn * 3 + logits * 3
    if shape.kind == "prefill":
        logits_last = B * cfg.vocab_padded * dt / n_chips
        return pb + act * Pn * 2 + logits_last
    # decode: params + cache r/w dominate
    import math
    cache = _cs(cfg, shape)
    cb = sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
             for x in jax.tree.leaves(cache)) / n_chips
    return pb + cb * 2 + B * d * dt * Pn * 2 / n_chips


def modeled_peak_gib(cfg: ArchConfig, shape: ShapeSpec, n_params: int,
                     mesh, micro: int | None = None) -> float:
    """Analytic per-device peak for TPU bf16 semantics.

    The XLA-CPU ``memory_analysis`` widens bf16 buffers to f32 (CPUs lack
    native bf16), overstating the remat-saved activation stacks ~2x; this
    model gives the TPU-accurate estimate (both are reported).
    Terms: params + optimizer moments + grad accumulator + per-micro grads
    + remat-saved carry stack (sharded over fsdp only) + logits + caches.
    """
    from repro.models.registry import cache_specs as _cs
    from repro.models.transformer import n_periods
    fa = fsdp_axes(mesh)
    fsdp_sz = 1
    for a in fa:
        fsdp_sz *= mesh.shape[a]
    chips = mesh.devices.size
    dt = 2
    B, S, d = shape.global_batch, shape.seq_len, cfg.d_model
    Pn = cfg.encoder_layers + cfg.n_layers if cfg.is_encdec else n_periods(cfg)
    pl_ = 1 if cfg.is_encdec else (cfg.attn_period if cfg.family == "hybrid" else 1)
    params = n_params * dt / chips
    total = params
    if shape.kind == "train":
        big = n_params > 20e9
        total += n_params * (2 if big else 4) * 2 / chips        # m, v
        total += n_params * (2 if big else 4) / chips            # grad acc
        total += params                                          # micro grads
        Bm = max(B // (micro or TRAIN_MICROBATCHES), 1)
        # saved carry stack: one h per pps periods; batch-sharded, plus the
        # model axis when cfg.shard_carry
        carry_div = fsdp_sz * (mesh.shape.get("model", 1)
                               if cfg.shard_carry else 1)
        pps = max(cfg.periods_per_scan_step, 1)
        total += Pn * pl_ * Bm * S * d * dt / carry_div / pps
        total += Bm * S * cfg.vocab_padded * dt / chips * 3      # logits f+b
        total += 2 * Bm * S * d * dt / fsdp_sz * 4               # live acts
    elif shape.kind == "prefill":
        total += 4 * B * S * d * dt / fsdp_sz                    # live acts
        total += B * cfg.vocab_padded * dt / chips
    else:
        import math
        cache = _cs(cfg, shape)
        total += sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
                     for x in jax.tree.leaves(cache)) / chips    # donated
    return round(total / 2 ** 30, 3)


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               verbose: bool = True, probe_costs: bool = True,
               cfg_override: dict | None = None,
               micro_override: int | None = None) -> dict:
    """cfg_override / micro_override: hillclimb knobs (EXPERIMENTS.md §Perf)."""
    shape = INPUT_SHAPES[shape_name]
    cfg = adapt_for_shape(get_arch(arch), shape)
    if cfg_override:
        cfg = cfg.with_(**cfg_override)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    t0 = time.time()
    lowered, n_params = lower_step(cfg, shape, mesh,
                                   micro_override=micro_override)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()

    if probe_costs:
        cost = extrapolated_cost(cfg, shape, mesh,
                                 micro_override=micro_override)
        flops_dev, bytes_dev = cost["flops"], cost["bytes"]
        colls, n_coll = cost["colls"], cost["n_coll"]
    else:   # raw (while bodies counted once) — kept for debugging
        ca = compiled.cost_analysis()
        flops_dev = float(ca.get("flops", 0.0))
        bytes_dev = float(ca.get("bytes accessed", 0.0))
        colls = collective_bytes(hlo)
        n_coll = len(_COLL_RE.findall(hlo))
    coll_dev = float(sum(colls.values()))
    mem_lb = modeled_traffic(cfg, shape, n_params, n_chips)

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "n_params": n_params,
        "n_chips": int(n_chips),
        "attention_variant": cfg.attention_variant,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": int(mem.argument_size_in_bytes),
            "output_bytes_per_device": int(mem.output_size_in_bytes),
            "temp_bytes_per_device": int(mem.temp_size_in_bytes),
            "alias_bytes_per_device": int(mem.alias_size_in_bytes),
            "peak_estimate_gib": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes +
                 mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3),
            "modeled_peak_gib_tpu": modeled_peak_gib(cfg, shape, n_params,
                                                     mesh, micro_override),
        },
        "cost": {
            "flops_per_device": flops_dev,
            "bytes_per_device": bytes_dev,
            "modeled_min_bytes_per_device": mem_lb,
            "collective_bytes_per_device": coll_dev,
            "collectives_by_kind": colls,
            "n_collective_ops": n_coll,
        },
        "roofline": {
            "compute_s": flops_dev / PEAK_FLOPS,
            "memory_s": mem_lb / HBM_BW,              # streaming lower bound
            "memory_s_upper": bytes_dev / HBM_BW,     # unfused HLO upper bound
            "collective_s": coll_dev / ICI_BW,
        },
    }
    r = result["roofline"]
    result["roofline"]["dominant"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: r[k])
    if verbose:
        print(json.dumps(result, indent=2))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    archs = list(ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                out = RESULTS_DIR / f"{tag}.json"
                if out.exists() and not args.force:
                    print(f"[skip] {tag} (cached)")
                    continue
                print(f"[run ] {tag}", flush=True)
                try:
                    res = dryrun_one(arch, shape, multi_pod=mp, verbose=False)
                    out.write_text(json.dumps(res, indent=2))
                    r = res["roofline"]
                    print(f"       ok: compile={res['compile_s']}s "
                          f"peak={res['memory']['peak_estimate_gib']}GiB "
                          f"(tpu-model {res['memory']['modeled_peak_gib_tpu']}GiB) "
                          f"dominant={r['dominant']}", flush=True)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((tag, repr(e)))
    if failures:
        print("FAILURES:")
        for tag, e in failures:
            print(" ", tag, e)
        raise SystemExit(1)
    print("all dry-runs passed")


if __name__ == "__main__":
    main()

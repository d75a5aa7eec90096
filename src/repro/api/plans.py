"""Execution plans: where (and how) a TRON solve runs.

Every plan has the same contract — take the global problem
``(X, y, basis, beta0)`` plus a :class:`MachineConfig`, return a
``TronResult`` — so solvers compose with plans without knowing which one
they got. Each registration also carries a ``decide`` arm
(:mod:`repro.api.infer`) executing the prediction map o(x) = k(x, basis)·β
under the same memory/distribution contract as the plan's training
closures — ``local`` materializes the dense test gram, the mesh plans
route through the fused kmvp dispatchers, ``stream`` scores chunk by
chunk from a :class:`~repro.data.chunks.ChunkSource`:

* ``local``     — one device, materialized (C, W), Formulation4 closures.
                  Accepts a precomputed ``CW`` cache (stage-wise growth
                  reuses every already-computed column of C).
* ``shard_map`` — the paper's Algorithm 1: explicit psum AllReduces, one
                  per paper step, via DistributedNystrom(mode="shard_map").
* ``auto``      — same math under jit with sharded operands; XLA SPMD picks
                  the collective schedule.
* ``otf``       — compute-on-the-fly: C is never *stored*, but each f/g/Hd
                  evaluation still rebuilds a transient (n/p, m) gram block
                  per shard before contracting it.
* ``otf_shard`` — mesh-sharded fully-fused on-the-fly: rows of X over the
                  data axes, full basis replicated; C beta / C^T D r / W
                  contractions run through the fused kmvp path (Pallas VMEM
                  tiles via ``config.backend="pallas"``, row-chunked jnp
                  recomputation otherwise), so no (n/p, m) array ever
                  exists on any device and each evaluation AllReduces one
                  m-vector.
* ``stream``    — out-of-core: X lives in a chunked source (in-memory
                  arrays or a directory of memory-mapped .npy shards) and
                  every f/g/Hd evaluation is *accumulated* chunk by chunk
                  through the fused kmvp path. TRON runs eagerly on the
                  host (``tron_host``); n may exceed host RAM.

Memory/flops/communication per f/g/Hd call (p devices, rows sharded):

                  plan        C bytes/device   extra flops    comms/eval
                  ----------  ---------------  -------------  -----------
                  shard_map   4 n m / p        0              O(m)
                  otf         4 n m / p (peak) O(n m d / p)   O(m)
                  otf_shard   tile (VMEM)      O(n m d / p)   O(m)
                  stream      tile (VMEM)      O(n m d / p)   O(m) / chunk

Distributed in-memory plans run on ``mesh`` (or a default all-devices data
mesh) and require n and m divisible by the data-axis extent — checked here
with a readable error instead of a shard_map trace failure. ``otf_shard``
and ``stream`` shard rows only (``model_axis`` must be None) and are
validated by shape instrumentation in tests: no intermediate reaches
n/p x m (respectively chunk_rows x m) elements. ``stream`` accepts any n —
ragged chunks are mask-padded exactly.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.api.infer import decide_fused, decide_local, decide_stream
from repro.api.registry import register_plan
from repro.core.compat import default_mesh
from repro.core.distributed import DistConfig, DistributedNystrom
from repro.core.formulation import Formulation4
from repro.core.nystrom import build_C, build_W
from repro.core.tron import TronResult, tron
from repro.data.chunks import as_chunk_source


@register_plan("local", decide=decide_local)
def plan_local(config, mesh, X, y, basis, beta0,
               CW: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
               classes=None, checkpoint=None, state0=None) -> TronResult:
    del mesh, classes   # multiclass y arrives pre-expanded to (n, K) ±1
    with obs.span("estimator.solve"):
        pol = None if config.dtype_policy == "fp32" else config.dtype_policy
        if CW is None:
            C = build_C(X, basis, config.kernel, config.backend, policy=pol)
            W = build_W(basis, config.kernel, config.backend, policy=pol)
        else:
            C, W = CW
        form = Formulation4(lam=config.lam, loss=config.get_loss())
        cfg = config.tron

        if checkpoint is not None or state0 is not None:
            # tron jits its own while_loop segments and snapshots between them;
            # an outer jit here would hide the state from the host
            return tron(lambda b: form.fgrad(C, W, y, b),
                        lambda D, d: form.hessd(C, W, D, d), beta0, cfg,
                        state0=state0,
                        snapshot_every=checkpoint.interval if checkpoint
                        else 0,
                        on_snapshot=checkpoint.on_snapshot if checkpoint
                        else None)

        @jax.jit
        def _run(C, W, y, beta0):
            return tron(lambda b: form.fgrad(C, W, y, b),
                        lambda D, d: form.hessd(C, W, D, d), beta0, cfg)

        return _run(C, W, y, beta0)


def _axis_extent(mesh, axes) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def _resolve_mesh(config, mesh):
    if mesh is not None:
        return mesh
    return default_mesh(config.data_axes, config.model_axis)


def _check_divisible(config, mesh, n: int, m: int, plan: str):
    dp = _axis_extent(mesh, config.data_axes)
    mp = mesh.shape[config.model_axis] if config.model_axis else 1
    if n % dp:
        raise ValueError(
            f"plan {plan!r}: n={n} rows must divide evenly over the data axes "
            f"{config.data_axes} (extent {dp}); truncate or pad the dataset")
    if m % (dp * mp) or (config.model_axis and m % mp):
        raise ValueError(
            f"plan {plan!r}: basis size m={m} must divide evenly over "
            f"data x model axes (extents {dp} x {mp}) for the 2-D (C, W) "
            f"partition; round m to a multiple of {dp * mp}")


def _distributed(config, mesh, X, y, basis, beta0, *, mode: str,
                 materialize: bool, plan: str, fused: bool = False,
                 checkpoint=None, state0=None) -> TronResult:
    mesh = _resolve_mesh(config, mesh)
    _check_divisible(config, mesh, X.shape[0], basis.shape[0], plan)
    dc = DistConfig(data_axes=config.data_axes, model_axis=config.model_axis,
                    mode=mode, materialize=materialize,
                    backend=config.backend, fused=fused,
                    block_rows=config.otf_block_rows,
                    policy=config.dtype_policy)
    solver = DistributedNystrom(mesh, config.lam, config.loss, config.kernel,
                                dc)
    return solver.solve(X, y, basis, beta0=beta0, cfg=config.tron,
                        checkpoint=checkpoint, state0=state0)


@register_plan("shard_map", decide=decide_fused)
def plan_shard_map(config, mesh, X, y, basis, beta0, CW=None,
                   classes=None, checkpoint=None, state0=None) -> TronResult:
    del CW, classes  # distributed plans build their own sharded (C, W);
    #                  multiclass y arrives pre-expanded to (n, K) ±1
    return _distributed(config, mesh, X, y, basis, beta0,
                        mode="shard_map", materialize=True, plan="shard_map",
                        checkpoint=checkpoint, state0=state0)


@register_plan("auto", decide=decide_fused)
def plan_auto(config, mesh, X, y, basis, beta0, CW=None,
              classes=None, checkpoint=None, state0=None) -> TronResult:
    del CW, classes
    return _distributed(config, mesh, X, y, basis, beta0,
                        mode="auto", materialize=True, plan="auto",
                        checkpoint=checkpoint, state0=state0)


@register_plan("otf", decide=decide_fused)
def plan_otf(config, mesh, X, y, basis, beta0, CW=None,
             classes=None, checkpoint=None, state0=None) -> TronResult:
    del CW, classes  # the whole point: C is never materialized
    return _distributed(config, mesh, X, y, basis, beta0,
                        mode="shard_map", materialize=False, plan="otf",
                        checkpoint=checkpoint, state0=state0)


@register_plan("stream", decide=decide_stream)
def plan_stream(config, mesh, X, y, basis, beta0, CW=None,
                classes=None, checkpoint=None, state0=None) -> TronResult:
    """Out-of-core accumulation: X may be an in-memory array (wrapped into
    an ArrayChunkSource), a ChunkSource, or a shard-directory path.

    Unlike the in-memory plans, a multiclass solve keeps the source's
    compact integer labels and receives ``classes``: each chunk is
    expanded into (chunk_rows, K) ±1 targets on the host right before
    transfer, so the one-vs-rest blow-up never exists at full n."""
    del CW  # recomputation leaves nothing to cache (same argument as
    #         otf_shard: growth re-streams, warm start carries the progress)
    if config.model_axis is not None:
        raise ValueError(
            "plan 'stream' shards rows only: chunks go through the fused "
            "kmvp kernels, which contract over all basis columns; set "
            "model_axis=None")
    mesh = _resolve_mesh(config, mesh)
    source = as_chunk_source(X, y, chunk_rows=config.stream.chunk_rows,
                             mmap=config.stream.mmap)
    dc = DistConfig(data_axes=config.data_axes, model_axis=None,
                    mode="shard_map", materialize=False,
                    backend=config.backend, fused=True,
                    block_rows=config.otf_block_rows,
                    policy=config.dtype_policy)
    solver = DistributedNystrom(mesh, config.lam, config.loss, config.kernel,
                                dc)
    return solver.solve_stream(source, basis, beta0=beta0, cfg=config.tron,
                               classes=classes,
                               cache_chunks=config.stream.cache_chunks,
                               prefetch=config.stream.prefetch,
                               checkpoint=checkpoint, state0=state0)


@register_plan("otf_shard", decide=decide_fused)
def plan_otf_shard(config, mesh, X, y, basis, beta0, CW=None,
                   classes=None, checkpoint=None, state0=None) -> TronResult:
    del CW, classes  # no (n/p, m) block exists to cache, let alone (C, W)
    if config.model_axis is not None:
        raise ValueError(
            "plan 'otf_shard' shards rows only: the fused kmvp kernels "
            "contract over all basis columns in VMEM, so a model_axis "
            "column partition does not apply; set model_axis=None (or use "
            "plan 'otf' for the 2-D on-the-fly partition)")
    return _distributed(config, mesh, X, y, basis, beta0,
                        mode="shard_map", materialize=False,
                        plan="otf_shard", fused=True,
                        checkpoint=checkpoint, state0=state0)

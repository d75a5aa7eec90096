"""Tiled Gaussian/linear gram-block Pallas kernel (paper Algorithm 1, step 3).

Kernel computation is the dominant cost for high-dimensional data (paper
Table 4, MNIST8m: step 3 ~ 10x step 4). On TPU the natural formulation is
MXU-friendly: the cross term x z^T is a matmul, so we tile

    grid = (n/bn, m/bm, d/bd)        # d innermost: accumulate sq-distances

with an (bn, bm) f32 VMEM scratch accumulating
``|x|^2 + |z|^2 - 2 x z^T`` over d-blocks, and the transcendental
``exp(-d2 / 2 sigma^2)`` applied once on the last d-step (VPU). Block sizes
keep the working set (bn*bd + bm*bd + bn*bm floats) inside VMEM and the
matmul dims MXU-aligned (multiples of 128 via caller padding).

This is the HBM->VMEM->MXU adaptation of the paper's node-local row-block
computation: one grid row block IS one 'node' share of C.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_kernel(x_ref, z_ref, o_ref, acc_ref, *, kind: str, sigma: float,
                 out_dtype, compute, accum, precision):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(compute)              # (bn, bd)
    z = z_ref[...].astype(compute)              # (bm, bd)
    xz = jax.lax.dot_general(x, z, (((1,), (1,)), ((), ())),
                             precision=precision,
                             preferred_element_type=accum)       # (bn, bm) MXU
    if kind == "linear":
        acc_ref[...] += xz
    else:
        xa = x.astype(accum)
        za = z.astype(accum)
        xx = jnp.sum(xa * xa, axis=1, keepdims=True)             # (bn, 1)
        zz = jnp.sum(za * za, axis=1, keepdims=True).T           # (1, bm)
        acc_ref[...] += xx + zz - 2.0 * xz

    @pl.when(k == nk - 1)
    def _finish():
        acc = acc_ref[...]
        if kind == "linear":
            o_ref[...] = acc.astype(out_dtype)
        else:
            d2 = jnp.maximum(acc, 0.0)
            o_ref[...] = jnp.exp(-d2 / (2.0 * sigma ** 2)).astype(out_dtype)


def gram_pallas(x: jnp.ndarray, z: jnp.ndarray, *, kind: str = "gaussian",
                sigma: float = 1.0, bn: int = 256, bm: int = 256,
                bd: int = 256, out_dtype=jnp.float32,
                interpret: bool = False,
                compute=jnp.float32, accum=jnp.float32,
                precision=jax.lax.Precision.HIGHEST) -> jnp.ndarray:
    """C = k(x, z) with explicit VMEM tiling. Shapes must divide the blocks
    (the ops.py wrapper pads/unpads arbitrary shapes). ``compute``/``accum``
    select the cross-term matmul and distance-accumulation dtypes,
    ``precision`` its MXU precision (``repro.kernels.policy``)."""
    n, d = x.shape
    m, d2 = z.shape
    assert d == d2, (d, d2)
    assert n % bn == 0 and m % bm == 0 and d % bd == 0, (x.shape, z.shape, (bn, bm, bd))
    grid = (n // bn, m // bm, d // bd)
    kernel = functools.partial(_gram_kernel, kind=kind, sigma=sigma,
                               out_dtype=out_dtype,
                               compute=jnp.dtype(compute),
                               accum=jnp.dtype(accum), precision=precision)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bd), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), out_dtype),
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.float32)],
        interpret=interpret,
    )(x, z)

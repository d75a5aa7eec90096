"""Fused on-the-fly kernel matvec (kmvp) Pallas kernels.

The paper (§3.1) notes that when the C row-block exceeds node memory,
kernel elements must be recomputed on the fly ('kernel caching ideas').
The TPU-native version of that idea is a FUSION: compute each (bn, bm)
gram tile in VMEM and immediately contract it against the vector, so C
never exists in HBM at all:

    kmvp_fwd : O = C(x, z) @ B           (TRON's  C beta)
    kmvp_t   : G = C(x, z)^T @ V         (TRON's  C^T D r)

HBM traffic drops from O(n m) (read a materialized C per matvec) to
O((n + m) d / bd') per call — arithmetic intensity rises by ~min(bn, bm),
moving the op from memory-bound to compute-bound (see EXPERIMENTS.md §Perf).

Both kernels take a *block* of right-hand sides: B is (m, k), V is (n, k).
Every k column shares one gram-tile recomputation: a K-class one-vs-rest
f/g/Hd costs one O(n m d) recompute pass, not K. How a finished tile E is
contracted against the block is picked from k, a static shape:

    k > 1 : on the MXU, a (bn, bm) @ (bm, k) matmul with k padded to 128
            lanes by the ops.py wrapper: up to 128 columns cost the MXU
            passes of one.
    k = 1 : on the VPU, as a broadcast multiply and a sum in f32 (binary
            fits and decides). Padded to 128 lanes, this contraction would
            issue as many MXU passes as a d = 128 cross term, 127 of its
            128 columns zero.
            fwd takes beta as a (1, m) row and adds the bm/128 lane-aligned
            slices of E * beta into a (bn, 128) scratch of lane partial
            sums, reduced across lanes once per row block into an (n, 1)
            output; t takes v as an (n, 1) column and adds the bn/8 sublane
            groups of E * v into an (8, m) output of sublane partial sums,
            which kmvp_t_pallas folds to (m, 1) outside the kernel.

Both paths round E and the RHS to the policy's compute dtype and
accumulate at accum f32, so they differ only in the order of the sums.

Grid layouts (sequential TPU grid => safe output accumulation):
    fwd: (i over n-blocks, j over m-blocks, l over d-blocks), O[i] += E_ij B_j
    t  : (j over m-blocks, i over n-blocks, l over d-blocks), G[j] += E_ij^T V_i
Both keep an (bn, bm) f32 VMEM scratch for the squared-distance accumulation
over d-blocks, applying exp once on the last step. The k axis is never
blocked: each RHS block rides whole in VMEM (k is small — classes, not
examples).
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tile(x_ref, z_ref, acc_ref, kind, compute, accum, precision):
    """Accumulate the gram tile over d-blocks; return E on the last step.

    ``compute`` is what the MXU multiplies (bf16 under the cheap policy),
    ``accum`` is the ``preferred_element_type`` of the cross-term matmul and
    the dtype the squared norms are summed in — the VMEM scratch holding the
    running distance is always ``accum`` (f32), so only the per-tile
    products are low-precision, never the accumulation over d-blocks.
    ``precision`` is the policy's (HIGHEST under fp32: see
    ``repro.kernels.policy.DtypePolicy.precision``).
    """
    x = x_ref[...].astype(compute)
    z = z_ref[...].astype(compute)
    xz = jax.lax.dot_general(x, z, (((1,), (1,)), ((), ())),
                             precision=precision,
                             preferred_element_type=accum)
    if kind == "linear":
        acc_ref[...] += xz
    else:
        xa = x.astype(accum)
        za = z.astype(accum)
        xx = jnp.sum(xa * xa, axis=1, keepdims=True)
        zz = jnp.sum(za * za, axis=1, keepdims=True).T
        acc_ref[...] += xx + zz - 2.0 * xz


def _finish_tile(acc_ref, kind, sigma):
    acc = acc_ref[...]
    if kind == "linear":
        return acc
    return jnp.exp(-jnp.maximum(acc, 0.0) / (2.0 * sigma ** 2))


def _widen(a, compute, accum):
    """``a`` rounded to ``compute`` and held at ``accum``: the factors the
    MXU path multiplies (a no-op under fp32). Under bf16 or fp16 the
    product of two such factors is exact at f32, so the VPU and MXU paths
    differ only in the order of their sums."""
    return a.astype(compute).astype(accum)


def _kmvp_fwd_kernel(x_ref, z_ref, b_ref, o_ref, acc_ref, *lane_ref, kind,
                     sigma, compute, accum, precision):
    """``lane_ref`` is the k = 1 path's (bn, 128) scratch of lane partial
    sums; without it the k columns contract on the MXU into ``o_ref``."""
    j, l = pl.program_id(1), pl.program_id(2)
    nj, nl = pl.num_programs(1), pl.num_programs(2)
    sum_ref = lane_ref[0] if lane_ref else o_ref

    @pl.when((j == 0) & (l == 0))
    def _init_out():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    @pl.when(l == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _tile(x_ref, z_ref, acc_ref, kind, compute, accum, precision)

    @pl.when(l == nl - 1)
    def _contract():
        E = _finish_tile(acc_ref, kind, sigma)                 # (bn, bm)
        if lane_ref:
            # k = 1: beta is a (1, bm) row broadcast down the sublanes.
            p = _widen(E, compute, accum) * _widen(b_ref[...], compute, accum)
            # Lane-aligned slices: plain vreg adds, no cross-lane work.
            w = sum_ref.shape[1]
            sum_ref[...] += functools.reduce(
                operator.add, [p[:, c:c + w] for c in range(0, p.shape[1], w)])
        else:
            # The finished tile is cast to compute (a no-op under fp32) so
            # the RHS contraction runs on the same MXU path; accumulate at
            # accum.
            sum_ref[...] += jax.lax.dot_general(
                E.astype(compute), b_ref[...].astype(compute),
                (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=accum)                   # (bn, k)

    if lane_ref:
        @pl.when((j == nj - 1) & (l == nl - 1))
        def _sum_lanes():
            o_ref[...] = jnp.sum(sum_ref[...], axis=1, keepdims=True)


def _kmvp_t_kernel(x_ref, z_ref, v_ref, g_ref, acc_ref, *, kind, sigma,
                   compute, accum, precision):
    """For k = 1 (a (bn, 1) ``v_ref``) ``g_ref`` is an (8, bm) block of
    sublane partial sums, folded to (bm,) by the caller; else (bm, k)."""
    i, l = pl.program_id(1), pl.program_id(2)
    nl = pl.num_programs(2)

    @pl.when((i == 0) & (l == 0))
    def _init_out():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(l == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _tile(x_ref, z_ref, acc_ref, kind, compute, accum, precision)

    @pl.when(l == nl - 1)
    def _contract():
        E = _finish_tile(acc_ref, kind, sigma)                 # (bn, bm)
        if v_ref.shape[1] == 1:
            # k = 1: v is a (bn, 1) column broadcast along the lanes.
            p = _widen(E, compute, accum) * _widen(v_ref[...], compute, accum)
            # Whole sublane groups: plain vreg adds, no cross-sublane work.
            g_ref[...] += p.reshape(-1, g_ref.shape[0], p.shape[1]).sum(0)
        else:
            g_ref[...] += jax.lax.dot_general(
                E.astype(compute), v_ref[...].astype(compute),
                (((0,), (0,)), ((), ())), precision=precision,
                preferred_element_type=accum)                   # (bm, k)


def _check_blocks(name: str, dims) -> None:
    """Readable divisibility errors instead of bare asserts: every dim the
    grid tiles must be a block multiple (the ops.py wrappers pad for you)."""
    for dim, size, block in dims:
        if block <= 0:
            raise ValueError(f"{name}: block b{dim}={block} must be positive")
        if size % block:
            raise ValueError(
                f"{name}: dim {dim}={size} is not divisible by its block "
                f"b{dim}={block}; pad {dim} to a multiple of {block} (the "
                f"repro.kernels.ops wrappers do this automatically)")


def _partial_width(block: int, align: int) -> int:
    """Width of the k = 1 path's partial sums along a blocked dim: one
    tile (``align``) when the block is a whole number of tiles, else the
    whole block (interpret mode's exact sizes)."""
    return align if block % align == 0 else block


def kmvp_fwd_pallas(x, z, beta, *, kind="gaussian", sigma=1.0,
                    bn=256, bm=256, bd=256, interpret=False,
                    compute=jnp.float32, accum=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST):
    """O = C(x, z) @ B, C never materialized. B: (m, k); O: (n, k).

    All k right-hand-side columns share each (bn, bm) gram tile — the
    recomputation cost is paid once per tile, not once per column.
    ``compute``/``accum``/``precision`` select the tile-matmul dtype, the
    accumulation dtype and the MXU precision (see ``repro.kernels.policy``);
    the output is always ``accum`` f32."""
    n, d = x.shape
    m, _ = z.shape
    k = beta.shape[1]
    _check_blocks("kmvp_fwd_pallas", [("n", n, bn), ("m", m, bm),
                                      ("d", d, bd)])
    grid = (n // bn, m // bm, d // bd)
    kernel = functools.partial(_kmvp_fwd_kernel, kind=kind, sigma=sigma,
                               compute=jnp.dtype(compute),
                               accum=jnp.dtype(accum), precision=precision)
    scratch = [pltpu.VMEM((bn, bm), jnp.float32)]
    if k == 1:
        beta = beta.reshape(1, m)
        b_spec = pl.BlockSpec((1, bm), lambda i, j, l: (0, j))
        scratch.append(pltpu.VMEM((bn, _partial_width(bm, 128)),
                                  jnp.float32))
    else:
        b_spec = pl.BlockSpec((bm, k), lambda i, j, l: (j, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, l: (i, l)),
            pl.BlockSpec((bm, bd), lambda i, j, l: (j, l)),
            b_spec,
        ],
        out_specs=pl.BlockSpec((bn, k), lambda i, j, l: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, z, beta)


def kmvp_t_pallas(x, z, v, *, kind="gaussian", sigma=1.0,
                  bn=256, bm=256, bd=256, interpret=False,
                  compute=jnp.float32, accum=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST):
    """G = C(x, z)^T @ V, C never materialized. V: (n, k); G: (m, k).

    Adjoint of :func:`kmvp_fwd_pallas` over the same implicit C; the k
    columns likewise share every gram-tile recomputation."""
    n, d = x.shape
    m, _ = z.shape
    k = v.shape[1]
    _check_blocks("kmvp_t_pallas", [("n", n, bn), ("m", m, bm),
                                    ("d", d, bd)])
    grid = (m // bm, n // bn, d // bd)
    kernel = functools.partial(_kmvp_t_kernel, kind=kind, sigma=sigma,
                               compute=jnp.dtype(compute),
                               accum=jnp.dtype(accum), precision=precision)
    if k == 1:      # sublane partial sums (s, m), folded to (m, 1) below
        s = _partial_width(bn, 8)
        g_spec = pl.BlockSpec((s, bm), lambda j, i, l: (0, j))
        g_shape = (s, m)
    else:
        g_spec = pl.BlockSpec((bm, k), lambda j, i, l: (j, 0))
        g_shape = (m, k)
    g = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda j, i, l: (i, l)),
            pl.BlockSpec((bm, bd), lambda j, i, l: (j, l)),
            pl.BlockSpec((bn, k), lambda j, i, l: (i, 0)),
        ],
        out_specs=g_spec,
        out_shape=jax.ShapeDtypeStruct(g_shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.float32)],
        interpret=interpret,
    )(x, z, v)
    return g.sum(axis=0).reshape(m, 1) if k == 1 else g

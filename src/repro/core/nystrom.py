"""Nystrom kernel-matrix pieces (paper §2.1).

``C[i,k] = k(x_i, xb_k)`` (n x m) and ``W[k,l] = k(xb_k, xb_l)`` (m x m).
The gram computation is pluggable: ``backend='jnp'`` is the reference path;
``backend='pallas'`` routes to the tiled TPU kernel in
``repro.kernels.ops`` (validated against the jnp oracle).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

# fp32 gram cross terms: at DEFAULT a TPU runs an f32 dot as one bf16 MXU
# pass, and the ||x||^2 + ||z||^2 - 2 x.z cancellation amplifies that error
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Kernel function spec. Gaussian is the paper's main kernel."""

    kind: str = "gaussian"  # gaussian | linear
    sigma: float = 1.0

    def __post_init__(self):
        if self.kind not in ("gaussian", "linear"):
            raise ValueError(f"unknown kernel kind {self.kind!r}")


def sqdist(x: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """Pairwise squared distances ||x_i - z_k||^2, (n, m)."""
    xx = jnp.sum(x * x, axis=-1, keepdims=True)          # (n, 1)
    zz = jnp.sum(z * z, axis=-1, keepdims=True).T        # (1, m)
    xz = jnp.matmul(x, z.T, precision=_HIGHEST)          # (n, m)
    return jnp.maximum(xx + zz - 2.0 * xz, 0.0)


def gram(x: jnp.ndarray, z: jnp.ndarray, kernel: KernelSpec,
         backend: str = "jnp", policy=None) -> jnp.ndarray:
    """Kernel block k(x_i, z_k) with the given backend.

    ``policy`` (name / DtypePolicy / None) selects the compute/accumulate
    dtypes; None is the fp32 default: the plain jnp expression tree, its
    cross-term dot at HIGHEST precision."""
    if backend == "pallas":
        from repro.kernels import ops as kops
        return kops.gram(x, z, kind=kernel.kind, sigma=kernel.sigma,
                         policy=policy)
    if policy is not None:
        from repro.kernels.policy import get_policy
        pol = get_policy(policy)
        if pol.compute != "float32":
            from repro.kernels.ops import gram_chunk_policy
            return gram_chunk_policy(x, z, kind=kernel.kind,
                                     sigma=kernel.sigma,
                                     pol=pol).astype(pol.accum_dtype)
    if kernel.kind == "linear":
        return jnp.matmul(x, z.T, precision=_HIGHEST)
    return jnp.exp(-sqdist(x, z) / (2.0 * kernel.sigma ** 2))


def build_C(x, basis, kernel: KernelSpec, backend: str = "jnp", policy=None):
    return gram(x, basis, kernel, backend, policy)


def build_W(basis, kernel: KernelSpec, backend: str = "jnp", policy=None):
    return gram(basis, basis, kernel, backend, policy)


def nystrom_approx_kernel(x, basis, kernel: KernelSpec,
                          jitter: float = 1e-6) -> jnp.ndarray:
    """K_tilde = C W^+ C^T (paper eq. 2) — reference only, O(n^2) memory.

    Used by tests to check approximation quality; the training path never
    forms this (that is the point of formulation (4)).
    """
    C = build_C(x, basis, kernel)
    W = build_W(basis, kernel)
    m = W.shape[0]
    Winv = jnp.linalg.pinv(W + jitter * jnp.eye(m, dtype=W.dtype))
    return C @ Winv @ C.T


def predict(x, basis, beta, kernel: KernelSpec, backend: str = "jnp"):
    """Classifier output o(x) = sum_k beta_k k(x, xb_k)."""
    return build_C(x, basis, kernel, backend) @ beta

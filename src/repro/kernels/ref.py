"""Pure-jnp oracles for the Pallas kernels. Ground truth for all sweeps.

Every contraction asks for ``Precision.HIGHEST``: on a TPU the default f32
dot is one bf16 pass, which no oracle may be."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def gram_ref(x, z, *, kind: str = "gaussian", sigma: float = 1.0):
    """C[i,k] = k(x_i, z_k); f32 accumulate regardless of input dtype."""
    x = x.astype(jnp.float32)
    z = z.astype(jnp.float32)
    if kind == "linear":
        return _dot(x, z.T)
    xx = jnp.sum(x * x, axis=-1, keepdims=True)
    zz = jnp.sum(z * z, axis=-1, keepdims=True).T
    d2 = jnp.maximum(xx + zz - 2.0 * _dot(x, z.T), 0.0)
    return jnp.exp(-d2 / (2.0 * sigma ** 2))


def kmvp_ref(x, z, beta, *, kind: str = "gaussian", sigma: float = 1.0):
    """o = C(x, z) @ beta without the caller holding C."""
    return _dot(gram_ref(x, z, kind=kind, sigma=sigma),
                beta.astype(jnp.float32))


def kmvp_t_ref(x, z, v, *, kind: str = "gaussian", sigma: float = 1.0):
    """g = C(x, z)^T @ v without the caller holding C."""
    return _dot(gram_ref(x, z, kind=kind, sigma=sigma).T,
                v.astype(jnp.float32))


def ssd_chunk_ref(Cc, Bc, dA, xdt):
    """Within-chunk SSD oracle. Cc/Bc: (G,Q,N); dA: (G,H,Q); xdt: (G,H,Q,P)."""
    import jax
    Cc = Cc.astype(jnp.float32)
    Bc = Bc.astype(jnp.float32)
    dA = dA.astype(jnp.float32)
    xdt = xdt.astype(jnp.float32)
    Q = Cc.shape[1]
    cs = jnp.cumsum(dA, axis=-1)                         # (G,H,Q) inclusive
    diff = cs[..., :, None] - cs[..., None, :]
    ii = jnp.arange(Q)
    mask = ii[:, None] >= ii[None, :]
    L = jnp.where(mask, jnp.exp(diff), 0.0)              # (G,H,Q,Q)
    scores = jnp.einsum("gqn,gkn->gqk", Cc, Bc)
    return jnp.einsum("ghqk,ghkp->ghqp", scores[:, None] * L, xdt)

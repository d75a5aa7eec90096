"""Plain float32 reference of what the benchmark's cells compute.

Formulation (4) of the Nystrom kernel machine with a gaussian kernel and
the squared hinge loss, written in straightforward ``jax.numpy`` from the
paper and independent of the program under test:

    f(beta) = lam/2 beta'W beta + sum_i 1/2 max(0, 1 - y_i c_i beta)^2
    g       = lam W beta + C'r,         r_i = (o_i - y_i) where 1 - y_i o_i > 0
    H d     = lam W d + C'(D C d),      D_i = 1 where 1 - y_i o_i > 0

with C = k(X, basis) and W = k(basis, basis). C is never held whole: each
evaluation builds it in blocks of rows and contracts every block at once.
TRON (trust-region Newton with Steihaug CG, liblinear's update rules) runs
on the host in float64 over the reference's float32 evaluations.

``mode`` picks the precision of every contraction: ``"highest"`` is float32
(``Precision.HIGHEST``); ``"bf16x3"`` is the three-pass bfloat16 split that
XLA calls ``Precision.HIGH`` on a TPU, written out so that it computes the
same on any backend. It is the control: the nearest precision below the
one the configurations state.

The float64 host helpers at the end (``gram64``, ``rel_err``) are the
reference's own check, copied from the repository's chip smoke test.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
BF16X3 = "bf16x3"
MODES = (HIGHEST, BF16X3)


def _dot(a, b, dims, mode: str):
    """``dot_general(a, b, dims)`` in float32 at the reference's precision."""
    if mode == HIGHEST:
        return jax.lax.dot_general(a, b, dims,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    if mode != BF16X3:
        raise ValueError(f"unknown precision mode {mode!r}; one of {MODES}")

    def split(v):
        hi = v.astype(jnp.bfloat16)
        return hi, (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def one(x, y):
        return jax.lax.dot_general(x, y, dims,
                                   preferred_element_type=jnp.float32)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    return (one(a_hi, b_lo) + one(a_lo, b_hi)) + one(a_hi, b_hi)


_MATMUL = (((1,), (0,)), ((), ()))      # (r, m) @ (m,)
_TMATMUL = (((0,), (0,)), ((), ()))     # (r, m)' @ (r,)
_NT = (((1,), (1,)), ((), ()))          # (r, d) @ (m, d)'
_VDOT = (((0,), (0,)), ((), ()))        # (m,) . (m,)


def gram(x, z, sigma: float, mode: str = HIGHEST):
    """exp(-|x_i - z_k|^2 / (2 sigma^2)) for row blocks x (r, d), z (m, d)."""
    xx = jnp.sum(x * x, axis=1)[:, None]
    zz = jnp.sum(z * z, axis=1)[None, :]
    d2 = xx + zz - 2.0 * _dot(x, z, _NT, mode)
    return jnp.exp(-jnp.maximum(d2, 0.0) / (2.0 * sigma * sigma))


def _blocks(a, block: int):
    """(rows, ...) -> (rows / block, block, ...), zero rows appended."""
    pad = (-a.shape[0]) % block
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a.reshape((-1, block) + a.shape[1:])


@functools.partial(jax.jit, static_argnames=("sigma", "mode", "block"))
def margins(x, z, beta, *, sigma: float, mode: str = HIGHEST,
            block: int = 2048):
    """o = k(x, z) beta for any number of rows, in row blocks."""
    n = x.shape[0]

    def one(xb):
        return _dot(gram(xb, z, sigma, mode), beta, _MATMUL, mode)

    return jax.lax.map(one, _blocks(x, block)).reshape(-1)[:n]


@dataclasses.dataclass(frozen=True)
class Problem:
    """Formulation (4) over rows X (n, d), labels y (n,) in {-1, +1} and a
    basis (m, d), evaluated at float32 in row blocks."""
    lam: float
    sigma: float
    mode: str = HIGHEST
    block: int = 4096

    def kernel_blocks(self, X, y):
        mask = jnp.ones((X.shape[0],), jnp.float32)
        return (_blocks(X, self.block), _blocks(y, self.block),
                _blocks(mask, self.block))

    @functools.partial(jax.jit, static_argnums=0)
    def w(self, basis):
        return jax.lax.map(lambda zb: gram(zb, basis, self.sigma, self.mode),
                           _blocks(basis, self.block)
                           ).reshape(-1, basis.shape[0])[:basis.shape[0]]

    @functools.partial(jax.jit, static_argnums=0)
    def fgrad(self, Xb, yb, mb, basis, W, beta):
        """(f, g, D) at beta; D is per block like Xb."""
        def body(g, blk):
            xb, yv, mv = blk
            C = gram(xb, basis, self.sigma, self.mode)
            o = _dot(C, beta, _MATMUL, self.mode)
            slack = 1.0 - yv * o
            act = (slack > 0.0) & (mv > 0.0)
            loss = jnp.sum(jnp.where(act, 0.5 * slack * slack, 0.0))
            r = jnp.where(act, o - yv, 0.0)
            g = g + _dot(C, r, _TMATMUL, self.mode)
            return g, (loss, act.astype(jnp.float32))

        gl, (losses, D) = jax.lax.scan(body, jnp.zeros_like(beta),
                                       (Xb, yb, mb))
        Wb = _dot(W, beta, _MATMUL, self.mode)
        f = 0.5 * self.lam * _dot(beta, Wb, _VDOT, self.mode) \
            + jnp.sum(losses)
        return f, self.lam * Wb + gl, D

    @functools.partial(jax.jit, static_argnums=0)
    def hessd(self, Xb, D, basis, W, d):
        def body(h, blk):
            xb, Db = blk
            C = gram(xb, basis, self.sigma, self.mode)
            o = _dot(C, d, _MATMUL, self.mode)
            return h + _dot(C, Db * o, _TMATMUL, self.mode), None

        h, _ = jax.lax.scan(body, jnp.zeros_like(d), (Xb, D))
        return self.lam * _dot(W, d, _MATMUL, self.mode) + h


@dataclasses.dataclass(frozen=True)
class Tron:
    """liblinear's TRON constants (Lin, Weng & Keerthi, ICML 2007)."""
    max_iter: int
    grad_rtol: float = 1e-3
    cg_rtol: float = 0.1
    cg_max_iter: int = 64
    eta0: float = 1e-4
    eta1: float = 0.25
    eta2: float = 0.75
    sigma1: float = 0.25
    sigma2: float = 0.5
    sigma3: float = 4.0


class Fit(NamedTuple):
    beta: np.ndarray      # float64 (m,)
    f_hist: np.ndarray    # objective at beta0 and after each iteration
    gnorm: float          # |g| at the final beta
    n_fg: int
    n_hd: int


def tron(fg, hd, beta0: np.ndarray, cfg: Tron) -> Fit:
    """Minimize f by trust-region Newton-CG. ``fg(beta) -> (f, g, aux)`` and
    ``hd(aux, d) -> H d`` are evaluated in float32; the m-vector algebra and
    the trust-region decisions are float64 on the host."""
    def evalf(b):
        f, g, aux = fg(b)
        return float(f), np.asarray(g, np.float64), aux

    def hvp(aux, d):
        return np.asarray(hd(aux, d), np.float64)

    beta = np.asarray(beta0, np.float64)
    f, g, aux = evalf(beta)
    gnorm0 = np.linalg.norm(g)
    delta = gnorm0
    hist, n_fg, n_hd = [f], 1, 0
    for it in range(cfg.max_iter):
        gnorm = np.linalg.norm(g)
        if not gnorm > cfg.grad_rtol * gnorm0:
            break
        s, r, steps = _steihaug(g, lambda d: hvp(aux, d), delta,
                                cfg.cg_rtol * gnorm, cfg.cg_max_iter)
        n_hd += steps
        snorm = np.linalg.norm(s)
        gs = g @ s
        prered = -0.5 * (gs - s @ r)
        f_new, g_new, aux_new = evalf(beta + s)
        n_fg += 1
        actred = f - f_new
        denom = f_new - f - gs
        alpha = cfg.sigma3 if denom <= 0 else max(cfg.sigma1,
                                                   -0.5 * (gs / denom))
        if it == 0:
            delta = min(delta, snorm)
        if actred < cfg.eta0 * prered:
            delta = min(max(alpha, cfg.sigma1) * snorm, cfg.sigma2 * delta)
        elif actred < cfg.eta1 * prered:
            delta = max(cfg.sigma1 * delta,
                        min(alpha * snorm, cfg.sigma2 * delta))
        elif actred < cfg.eta2 * prered:
            delta = max(cfg.sigma1 * delta,
                        min(alpha * snorm, cfg.sigma3 * delta))
        else:
            delta = max(delta, min(alpha * snorm, cfg.sigma3 * delta))
        f_old = f
        if actred > cfg.eta0 * prered:
            beta, f, g, aux = beta + s, f_new, g_new, aux_new
        hist.append(f)
        feps = abs(f_old) * 1e-12
        if prered <= 0 or (abs(actred) <= feps and abs(prered) <= feps):
            break
    return Fit(beta, np.asarray(hist), float(np.linalg.norm(g)), n_fg, n_hd)


def _steihaug(g, hvp, delta, tol, max_iter):
    """Steihaug-Toint CG for min g's + s'Hs/2 inside |s| <= delta. Returns
    (s, r = -g - H s, Hessian products used)."""
    s = np.zeros_like(g)
    r = -g
    d = -g
    rtr = g @ g
    it = 0
    while np.sqrt(rtr) > tol and it < max_iter:
        Hd = hvp(d)
        dHd = d @ Hd
        alpha = rtr / (dHd if dHd > 0 else 1.0)
        outside = np.linalg.norm(s + alpha * d) >= delta or dHd <= 0
        if outside:
            sd, dd, ss = s @ d, d @ d, s @ s
            rad = np.sqrt(max(sd * sd + dd * (delta * delta - ss), 0.0))
            step = (rad - sd) / (dd if dd > 0 else 1.0)
        else:
            step = alpha
        s = s + step * d
        r = r - step * Hd
        rtr_new = r @ r
        d = r + (rtr_new / (rtr if rtr > 0 else 1.0)) * d
        rtr = rtr_new
        it += 1
        if outside:
            break
    return s, r, it


def fit(X, y, basis, *, lam: float, sigma: float, cfg: Tron,
        mode: str = HIGHEST, block: int = 4096) -> Fit:
    """TRON on formulation (4) from beta = 0, all on the reference's side."""
    prob = Problem(lam=float(lam), sigma=float(sigma), mode=mode, block=block)
    Xb, yb, mb = prob.kernel_blocks(X, y)
    W = prob.w(basis)

    def fg(beta):
        return prob.fgrad(Xb, yb, mb, basis, W, jnp.asarray(beta,
                                                            jnp.float32))

    def hd(D, d):
        return prob.hessd(Xb, D, basis, W, jnp.asarray(d, jnp.float32))

    return tron(fg, hd, np.zeros(basis.shape[0]), cfg)


# --------------------------------------------------- float64 on the host
def gram64(x, z, sigma: float) -> np.ndarray:
    """The gaussian gram on the host in float64."""
    x = np.asarray(x, np.float64)
    z = np.asarray(z, np.float64)
    d2 = (np.sum(x * x, 1)[:, None] + np.sum(z * z, 1)[None, :]
          - 2.0 * x @ z.T)
    return np.exp(-np.maximum(d2, 0.0) / (2.0 * sigma ** 2))


def rel_err(got, want) -> float:
    """max |got - want| over max |want| (float64)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

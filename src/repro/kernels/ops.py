"""jit'd public wrappers for the Pallas kernels.

Handles arbitrary shapes/dtypes by zero-padding to block multiples (zero
rows/cols are exact no-ops for both the gaussian-distance accumulation and
the matvec contractions), picks VMEM-sane MXU-aligned block sizes, and runs
``interpret=True`` automatically on the CPU backend (the test suite) and
compiled kernels on a TPU; any other backend is refused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import gram as _gram
from repro.kernels import kmvp as _kmvp
from repro.kernels.policy import DtypePolicy, get_policy


def _interpret_default() -> bool:
    """Interpret mode on the CPU (the test suite), compiled kernels on a
    TPU; any other backend has no Mosaic lowering and no business running
    these kernels slowly in the interpreter without saying so."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and interpret on CPU; backend "
        f"{backend!r} is neither (use backend='jnp')")


def _sublane(dtype) -> int:
    """Minimum TPU sublane tile for a dtype: 8 rows at 4 bytes, 16 at 2
    (bf16/fp16), 32 at 1 (int8) — the row-padding alignment on hardware."""
    return max(8, 32 // max(jnp.dtype(dtype).itemsize, 1))


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def _block(size: int, want: int, align: int, interpret: bool = False) -> int:
    """Largest aligned block <= want that keeps padding small for tiny sizes.

    Off-TPU (``interpret``) there is no tiling constraint, so a tiny input
    uses its exact size as the block: a 1-row input must not round up to a
    full alignment block (8x wasted rows, 128x wasted lanes for the m/d
    dims). On hardware the minimum legal block is one alignment unit, but
    never more than ``want`` even when ``align > want``.
    """
    if size >= want:
        return want
    if interpret:
        return size
    return min(_round_up(size, align), max(want, align))


def _pad_rows(a, to):
    pad = to - a.shape[0]
    return a if pad == 0 else jnp.pad(a, ((0, pad), (0, 0)))


def _pad_cols(a, to):
    pad = to - a.shape[1]
    return a if pad == 0 else jnp.pad(a, ((0, 0), (0, pad)))


def _as_cols(v):
    """RHS as a (p, k) column block plus the flag to undo a 1-D squeeze.

    The kmvp entry points accept a single vector (the historical matvec
    call) or a block of k right-hand sides (multiclass one-vs-rest / CG
    over K columns); everything downstream is uniformly 2-D.
    """
    if v.ndim == 1:
        return v.reshape(-1, 1), True
    return v, False


def _pad_lanes(v, interpret: bool) -> jnp.ndarray:
    """Pad a block of k > 1 right-hand sides to the 128-lane width on
    hardware, where the kernels contract it on the MXU: up to 128 columns
    cost the MXU passes of one. A single column (k = 1) is not padded: the
    kernels contract it on the VPU, where padding would only add zero
    columns (``repro.kernels.kmvp``). Interpret mode keeps the exact k."""
    k = v.shape[1]
    return v if interpret or k == 1 else _pad_cols(v, _round_up(k, 128))


@functools.partial(jax.jit, static_argnames=("kind", "sigma", "bn", "bm", "bd",
                                             "interpret", "policy"))
def gram(x, z, *, kind: str = "gaussian", sigma: float = 1.0,
         bn: int = 256, bm: int = 256, bd: int = 256,
         interpret: bool | None = None, policy=None):
    """C[i,k] = k(x_i, z_k) via the tiled Pallas kernel. Any shapes/dtypes.

    ``policy`` (name or DtypePolicy) selects compute/accum dtypes and the
    dot precision (fp32 by default)."""
    if interpret is None:
        interpret = _interpret_default()
    pol = get_policy(policy)
    comp, acc = pol.compute_dtype, pol.accum_dtype
    n, d = x.shape
    m = z.shape[0]
    bn = _block(n, bn, _sublane(comp), interpret)
    bm = _block(m, bm, 128, interpret)
    bd = _block(d, bd, 128, interpret)
    np_, mp_, dp_ = _round_up(n, bn), _round_up(m, bm), _round_up(d, bd)
    xp = _pad_cols(_pad_rows(x.astype(comp), np_), dp_)
    zp = _pad_cols(_pad_rows(z.astype(comp), mp_), dp_)
    out = _gram.gram_pallas(xp, zp, kind=kind, sigma=sigma, bn=bn, bm=bm,
                            bd=bd, interpret=interpret, compute=comp,
                            accum=acc, precision=pol.precision)
    return out[:n, :m]


@functools.partial(jax.jit, static_argnames=("kind", "sigma", "bn", "bm", "bd",
                                             "interpret", "policy"))
def kmvp_fwd(x, z, beta, *, kind: str = "gaussian", sigma: float = 1.0,
             bn: int = 256, bm: int = 256, bd: int = 256,
             interpret: bool | None = None, policy=None):
    """o = C(x, z) @ beta with C fused away (never in HBM).

    ``beta`` may be a single (m,) vector or an (m, k) block of right-hand
    sides; the k columns share every gram-tile recomputation, so a K-class
    evaluation costs ~one recompute pass. Returns (n,) or (n, k) to match.
    ``policy`` selects compute/accum dtypes; output is always accum f32.
    """
    if interpret is None:
        interpret = _interpret_default()
    pol = get_policy(policy)
    comp, acc = pol.compute_dtype, pol.accum_dtype
    n, d = x.shape
    m = z.shape[0]
    bn = _block(n, bn, _sublane(comp), interpret)
    bm = _block(m, bm, 128, interpret)
    bd = _block(d, bd, 128, interpret)
    np_, mp_, dp_ = _round_up(n, bn), _round_up(m, bm), _round_up(d, bd)
    xp = _pad_cols(_pad_rows(x.astype(comp), np_), dp_)
    zp = _pad_cols(_pad_rows(z.astype(comp), mp_), dp_)
    b2, squeeze = _as_cols(beta)
    k = b2.shape[1]
    bp = _pad_lanes(_pad_rows(b2, mp_), interpret)  # zero padded basis rows
    out = _kmvp.kmvp_fwd_pallas(xp, zp, bp, kind=kind, sigma=sigma, bn=bn,
                                bm=bm, bd=bd, interpret=interpret,
                                compute=comp, accum=acc,
                                precision=pol.precision)
    return out[:n, 0] if squeeze else out[:n, :k]


@functools.partial(jax.jit, static_argnames=("kind", "sigma", "bn", "bm", "bd",
                                             "interpret", "policy"))
def kmvp_t(x, z, v, *, kind: str = "gaussian", sigma: float = 1.0,
           bn: int = 256, bm: int = 256, bd: int = 256,
           interpret: bool | None = None, policy=None):
    """g = C(x, z)^T @ v with C fused away (never in HBM).

    ``v`` may be (n,) or an (n, k) block; returns (m,) or (m, k).
    """
    if interpret is None:
        interpret = _interpret_default()
    pol = get_policy(policy)
    comp, acc = pol.compute_dtype, pol.accum_dtype
    n, d = x.shape
    m = z.shape[0]
    bn = _block(n, bn, _sublane(comp), interpret)
    bm = _block(m, bm, 128, interpret)
    bd = _block(d, bd, 128, interpret)
    np_, mp_, dp_ = _round_up(n, bn), _round_up(m, bm), _round_up(d, bd)
    xp = _pad_cols(_pad_rows(x.astype(comp), np_), dp_)
    zp = _pad_cols(_pad_rows(z.astype(comp), mp_), dp_)
    v2, squeeze = _as_cols(v)
    k = v2.shape[1]
    vp = _pad_lanes(_pad_rows(v2, np_), interpret)  # zero padded example rows
    out = _kmvp.kmvp_t_pallas(xp, zp, vp, kind=kind, sigma=sigma, bn=bn,
                              bm=bm, bd=bd, interpret=interpret,
                              compute=comp, accum=acc,
                              precision=pol.precision)
    return out[:m, 0] if squeeze else out[:m, :k]


# --------------------------------------------------------------------- on-the-
# fly helpers for the sharded plans. These are deliberately *not* jit'd:
# they are called inside shard_map bodies (per-shard shapes are concrete at
# trace time) and inline into the enclosing jit, so the chunk loop stays
# remat-friendly (jax.checkpoint on the chunk body: AD never saves a
# (block_rows x m) gram chunk) and donation of the enclosing buffers works.


def otf_block_rows(n: int, m: int, d: int, budget_bytes: int = 1 << 20,
                   itemsize: int = 4) -> int:
    """Row-chunk size for the jnp on-the-fly fallback, keyed on the
    *per-shard* row count n.

    Two ceilings: the transient (rows, m) gram chunk (``itemsize`` bytes
    per element — 2 under a bf16 policy, doubling the rows per chunk for
    the same budget) stays under ``budget_bytes``, and under ~1/8 of the
    shard's rows (so recomputation never quietly degenerates into
    materializing the full per-shard C block). Floor of 8 rows keeps the
    matmuls sane.
    """
    del d
    by_budget = max(budget_bytes // (itemsize * max(m, 1)), 8)
    by_fraction = _round_up(max(n // 8, 1), 8)
    return int(max(8, min(by_budget, by_fraction, _round_up(n, 8))))


def otf_tiles(n: int, m: int, d: int, k: int = 1,
              vmem_budget: int = 4 << 20) -> tuple[int, int, int]:
    """(bn, bm, bd) Pallas tile sizes keyed on the per-shard n: large shards
    take a taller bn (amortizes re-streaming z across the n-block loop),
    shrunk until the f32 working set (x, z, acc tiles plus the (bm, k) RHS
    and (bn, k) output blocks of the multi-RHS path) fits the budget."""
    interp = _interpret_default()
    kp = k if interp else _round_up(max(k, 1), 128)
    bn = _block(n, 512 if n >= 512 else 256, 8, interp)
    bm = _block(m, 256, 128, interp)
    bd = _block(d, 256, 128, interp)
    while bn > 8 and 4 * (bn * bd + bm * bd + bn * bm
                          + (bn + bm) * kp) > vmem_budget:
        bn = max(8, _round_up(bn // 2, 8))
    return bn, bm, bd


def gram_chunk_policy(c, z, *, kind: str, sigma: float, pol: DtypePolicy):
    """One (rows, m) gram chunk under a dtype policy — the jnp-fallback
    analogue of the Pallas ``_tile``/``_finish_tile`` sequence (satellite:
    the CPU fallback must exercise the *same* cast-compute/accumulate
    order, not silently promote everything to f32).

    The cross-term matmul runs at ``compute`` with ``accum`` accumulation;
    the squared norms and the distance combine at ``accum`` (mirroring the
    f32 VMEM scratch); the *finished* chunk is returned at ``compute`` — so
    the (rows, m) transient the introspect checks see under bf16 really is
    bf16, halving the fallback's peak bytes.
    """
    comp, acc = pol.compute_dtype, pol.accum_dtype
    cc = c.astype(comp)
    zc = z.astype(comp)
    xz = jax.lax.dot_general(cc, zc, (((1,), (1,)), ((), ())),
                             precision=pol.precision,
                             preferred_element_type=acc)
    if kind == "linear":
        return xz.astype(comp)
    ca = cc.astype(acc)
    za = zc.astype(acc)
    xx = jnp.sum(ca * ca, axis=1, keepdims=True)
    zz = jnp.sum(za * za, axis=1, keepdims=True).T
    d2 = jnp.maximum(xx + zz - 2.0 * xz, 0.0)
    return jnp.exp(-d2 / (2.0 * sigma ** 2)).astype(comp)


def kmvp_fwd_chunked(x, z, beta, *, kind: str = "gaussian", sigma: float = 1.0,
                     block_rows: int | None = None, policy=None):
    """o = C(x, z) @ beta via row-chunked recomputation (jnp fallback).

    Peak transient is one (block_rows, m) gram chunk — the fallback keeps
    the fused kernels' memory contract on backends without Pallas. ``beta``
    may be (m,) or (m, k); every RHS column contracts against the same
    recomputed gram chunk (one recompute pass per evaluation, not k).
    Under a low-precision ``policy`` the chunk is computed and held at the
    policy's compute dtype with f32 accumulation, exactly like the kernels.
    """
    from repro.kernels import ref
    pol = get_policy(policy)
    n, d = x.shape
    m = z.shape[0]
    b2, squeeze = _as_cols(beta)
    bn = block_rows or otf_block_rows(n, m, d)
    nb = -(-n // bn)
    if pol.compute == "float32":
        xp = _pad_rows(x, nb * bn).reshape(nb, bn, d)

        @jax.checkpoint
        def chunk(c):
            return jnp.matmul(ref.gram_ref(c, z, kind=kind, sigma=sigma),
                              b2.astype(jnp.float32), precision=pol.precision)
    else:
        comp, acc = pol.compute_dtype, pol.accum_dtype
        xp = _pad_rows(x.astype(comp), nb * bn).reshape(nb, bn, d)
        bc = b2.astype(comp)

        @jax.checkpoint
        def chunk(c):
            E = gram_chunk_policy(c, z, kind=kind, sigma=sigma, pol=pol)
            return jax.lax.dot_general(E, bc, (((1,), (0,)), ((), ())),
                                       precision=pol.precision,
                                       preferred_element_type=acc)

    out = jax.lax.map(chunk, xp).reshape(nb * bn, -1)[:n]
    return out[:, 0] if squeeze else out


def kmvp_t_chunked(x, z, v, *, kind: str = "gaussian", sigma: float = 1.0,
                   block_rows: int | None = None, policy=None):
    """g = C(x, z)^T @ v via row-chunked recomputation (jnp fallback).

    Padded x rows have nonzero gaussian kernel values against z, but their
    v entries are zero-padded, so their contribution to g vanishes exactly.
    ``v`` may be (n,) or (n, k); the accumulator contracts the k columns
    against each gram chunk without ever transposing it. The (k, m)
    accumulator carried across chunks always stays at accum f32.
    """
    from repro.kernels import ref
    pol = get_policy(policy)
    n, d = x.shape
    m = z.shape[0]
    v2, squeeze = _as_cols(v)
    k = v2.shape[1]
    bn = block_rows or otf_block_rows(n, m, d)
    nb = -(-n // bn)
    if pol.compute == "float32":
        xp = _pad_rows(x, nb * bn).reshape(nb, bn, d)
        vp = _pad_rows(v2.astype(jnp.float32), nb * bn).reshape(nb, bn, k)

        @jax.checkpoint
        def contrib(c, vc):
            E = ref.gram_ref(c, z, kind=kind, sigma=sigma)      # (bn, m)
            return jax.lax.dot_general(vc, E, (((0,), (0,)), ((), ())),
                                       precision=pol.precision)  # (k, m)
    else:
        comp, acc = pol.compute_dtype, pol.accum_dtype
        xp = _pad_rows(x.astype(comp), nb * bn).reshape(nb, bn, d)
        vp = _pad_rows(v2.astype(comp), nb * bn).reshape(nb, bn, k)

        @jax.checkpoint
        def contrib(c, vc):
            E = gram_chunk_policy(c, z, kind=kind, sigma=sigma, pol=pol)
            return jax.lax.dot_general(vc, E, (((0,), (0,)), ((), ())),
                                       precision=pol.precision,
                                       preferred_element_type=acc)

    def body(g, cv):
        return g + contrib(*cv), None

    g, _ = jax.lax.scan(body, jnp.zeros((k, m), jnp.float32), (xp, vp))
    return g[0] if squeeze else g.T


def otf_kmvp_fwd(x, z, beta, *, kind: str = "gaussian", sigma: float = 1.0,
                 backend: str = "jnp", block_rows: int | None = None,
                 policy=None):
    """Backend dispatch for o = C(x, z) @ beta with C never in HBM.

    ``pallas`` fuses the gram tile into the matvec in VMEM (tile sizes from
    :func:`otf_tiles`); ``jnp`` recomputes row chunks. Callable inside
    shard_map bodies — x is the per-shard row block there. ``beta`` may be
    (m,) or an (m, k) multi-RHS block on either backend. ``policy`` is
    honored identically by both backends.
    """
    if backend == "pallas":
        k = 1 if beta.ndim == 1 else beta.shape[1]
        bn, bm, bd = otf_tiles(x.shape[0], z.shape[0], x.shape[1], k)
        return kmvp_fwd(x, z, beta, kind=kind, sigma=sigma,
                        bn=bn, bm=bm, bd=bd, policy=policy)
    return kmvp_fwd_chunked(x, z, beta, kind=kind, sigma=sigma,
                            block_rows=block_rows, policy=policy)


def otf_kmvp_t(x, z, v, *, kind: str = "gaussian", sigma: float = 1.0,
               backend: str = "jnp", block_rows: int | None = None,
               policy=None):
    """Backend dispatch for g = C(x, z)^T @ v with C never in HBM.

    ``v`` may be (n,) or an (n, k) multi-RHS block on either backend."""
    if backend == "pallas":
        k = 1 if v.ndim == 1 else v.shape[1]
        bn, bm, bd = otf_tiles(x.shape[0], z.shape[0], x.shape[1], k)
        return kmvp_t(x, z, v, kind=kind, sigma=sigma, bn=bn, bm=bm, bd=bd,
                      policy=policy)
    return kmvp_t_chunked(x, z, v, kind=kind, sigma=sigma,
                          block_rows=block_rows, policy=policy)


@functools.partial(jax.jit, static_argnames=("interpret", "policy"))
def ssd_chunk(Cc, Bc, dA, xdt, *, interpret: bool | None = None, policy=None):
    """Mamba-2 SSD within-chunk term via the Pallas kernel (any shapes with
    Q multiple of 8 recommended; grid = (G, H))."""
    from repro.kernels import ssd as _ssd
    if interpret is None:
        interpret = _interpret_default()
    pol = get_policy(policy)
    return _ssd.ssd_chunk_pallas(Cc, Bc, dA, xdt, interpret=interpret,
                                 compute=pol.compute_dtype,
                                 accum=pol.accum_dtype)

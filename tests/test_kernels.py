"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_allclose_dtype
from repro.kernels import ops, ref

SHAPES = [(16, 8, 4), (64, 32, 16), (300, 130, 50), (512, 256, 256),
          (257, 129, 100), (1000, 333, 384)]
DTYPES = [jnp.float32, jnp.bfloat16]
KINDS = ["gaussian", "linear"]


def _data(n, m, d, dtype, seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k1, (n, d), dtype)
    z = jax.random.normal(k2, (m, d), dtype)
    beta = jax.random.normal(k3, (m,), jnp.float32)
    v = jax.random.normal(k4, (n,), jnp.float32)
    return x, z, beta, v


def _sigma(d):
    return float(np.sqrt(d))   # keep exp() in a meaningful range


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kind", KINDS)
def test_gram_matches_ref(shape, dtype, kind):
    n, m, d = shape
    x, z, _, _ = _data(n, m, d, dtype)
    got = ops.gram(x, z, kind=kind, sigma=_sigma(d))
    want = ref.gram_ref(x, z, kind=kind, sigma=_sigma(d))
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol)


@pytest.mark.parametrize("shape", SHAPES[:4])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kind", KINDS)
def test_kmvp_fwd_matches_ref(shape, dtype, kind):
    n, m, d = shape
    x, z, beta, _ = _data(n, m, d, dtype)
    got = ops.kmvp_fwd(x, z, beta, kind=kind, sigma=_sigma(d))
    want = ref.kmvp_ref(x, z, beta, kind=kind, sigma=_sigma(d))
    rtol = 3e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol * np.sqrt(m))


@pytest.mark.parametrize("shape", SHAPES[:4])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kind", KINDS)
def test_kmvp_t_matches_ref(shape, dtype, kind):
    n, m, d = shape
    x, z, _, v = _data(n, m, d, dtype)
    got = ops.kmvp_t(x, z, v, kind=kind, sigma=_sigma(d))
    want = ref.kmvp_t_ref(x, z, v, kind=kind, sigma=_sigma(d))
    rtol = 3e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol * np.sqrt(n))


# --------------------------------------------------------- parity test grid
# Deliberately odd, non-block-aligned shapes: every value in {1, 3, 127,
# 129, 257} appears in each of the n/m/d positions at least once, so the
# zero-padding claim in ops.py is a tested invariant, not a docstring.
ODD_SHAPES = [(1, 1, 1), (1, 3, 127), (3, 129, 1), (127, 1, 129),
              (129, 257, 3), (257, 127, 257)]


@pytest.mark.parametrize("shape", ODD_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kind", KINDS)
def test_parity_grid(shape, dtype, kind):
    """gram / kmvp_fwd / kmvp_t vs the dense ref.py path on one dataset."""
    n, m, d = shape
    x, z, beta, v = _data(n, m, d, dtype)
    kw = dict(kind=kind, sigma=_sigma(d))
    assert_allclose_dtype(ops.gram(x, z, **kw), ref.gram_ref(x, z, **kw),
                          dtype)
    assert_allclose_dtype(ops.kmvp_fwd(x, z, beta, **kw),
                          ref.kmvp_ref(x, z, beta, **kw), dtype)
    assert_allclose_dtype(ops.kmvp_t(x, z, v, **kw),
                          ref.kmvp_t_ref(x, z, v, **kw), dtype)


@pytest.mark.parametrize("shape", ODD_SHAPES)
@pytest.mark.parametrize("kind", KINDS)
def test_chunked_fallback_parity(shape, kind):
    """The jnp on-the-fly fallbacks match the dense path too."""
    n, m, d = shape
    x, z, beta, v = _data(n, m, d, jnp.float32)
    kw = dict(kind=kind, sigma=_sigma(d))
    assert_allclose_dtype(ops.kmvp_fwd_chunked(x, z, beta, **kw),
                          ref.kmvp_ref(x, z, beta, **kw), jnp.float32)
    assert_allclose_dtype(ops.kmvp_t_chunked(x, z, v, **kw),
                          ref.kmvp_t_ref(x, z, v, **kw), jnp.float32)
    # explicit chunk override exercises the padded-tail path
    assert_allclose_dtype(
        ops.kmvp_t_chunked(x, z, v, block_rows=8, **kw),
        ref.kmvp_t_ref(x, z, v, **kw), jnp.float32)


# ------------------------------------------------------- multi-RHS (m, k)
# k = 1 keeps the 2-D block shape (not the squeezed vector path), odd k
# exercises the 128-lane padding, k = 8 a real one-vs-rest class count.
MULTI_KS = [1, 3, 8]


def _multi_data(n, m, d, k, dtype, seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k1, (n, d), dtype)
    z = jax.random.normal(k2, (m, d), dtype)
    B = jax.random.normal(k3, (m, k), jnp.float32)
    V = jax.random.normal(k4, (n, k), jnp.float32)
    return x, z, B, V


@pytest.mark.parametrize("k", MULTI_KS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kind", KINDS)
def test_multirhs_parity_grid(k, dtype, kind):
    """(m, k) / (n, k) RHS blocks match the dense oracle — Pallas and the
    chunked jnp fallback — including non-block-aligned shapes."""
    for shape in [(64, 32, 16), (129, 257, 3)]:
        n, m, d = shape
        x, z, B, V = _multi_data(n, m, d, k, dtype)
        kw = dict(kind=kind, sigma=_sigma(d))
        G = ref.gram_ref(x, z, **kw)
        got_fwd = ops.kmvp_fwd(x, z, B, **kw)
        got_t = ops.kmvp_t(x, z, V, **kw)
        assert got_fwd.shape == (n, k) and got_t.shape == (m, k)
        assert_allclose_dtype(got_fwd, G @ B, dtype)
        assert_allclose_dtype(got_t, G.T @ V, dtype)
        if dtype == jnp.float32:
            assert_allclose_dtype(ops.kmvp_fwd_chunked(x, z, B, **kw),
                                  G @ B, dtype)
            assert_allclose_dtype(ops.kmvp_t_chunked(x, z, V, **kw),
                                  G.T @ V, dtype)


@pytest.mark.parametrize("k", MULTI_KS)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("impl", ["pallas", "chunked"])
def test_multirhs_column_independence(k, kind, impl):
    """Each column of a multi-RHS call equals the single-vector call on
    that column: the batched contraction is K independent matvecs sharing
    gram recomputation, never mixing columns."""
    n, m, d = 65, 40, 7
    x, z, B, V = _multi_data(n, m, d, k, jnp.float32)
    kw = dict(kind=kind, sigma=_sigma(d))
    fwd = ops.kmvp_fwd if impl == "pallas" else ops.kmvp_fwd_chunked
    t = ops.kmvp_t if impl == "pallas" else ops.kmvp_t_chunked
    O, G = fwd(x, z, B, **kw), t(x, z, V, **kw)
    for c in range(k):
        np.testing.assert_allclose(np.asarray(O[:, c]),
                                   np.asarray(fwd(x, z, B[:, c], **kw)),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(G[:, c]),
                                   np.asarray(t(x, z, V[:, c], **kw)),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", MULTI_KS)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("impl", ["pallas", "chunked"])
def test_multirhs_adjoint(k, kind, impl):
    """<kmvp_fwd(x,z,B), V>_F == <B, kmvp_t(x,z,V)>_F: the multi-RHS
    kernels stay adjoints of the same implicit C, column-batched."""
    n, m, d = 129, 64, 16
    x, z, B, V = _multi_data(n, m, d, k, jnp.float32)
    kw = dict(kind=kind, sigma=_sigma(d))
    if impl == "pallas":
        O, G = ops.kmvp_fwd(x, z, B, **kw), ops.kmvp_t(x, z, V, **kw)
    else:
        O = ops.kmvp_fwd_chunked(x, z, B, **kw)
        G = ops.kmvp_t_chunked(x, z, V, **kw)
    # Paired in float64, so the gap is the kernels' own: an f32 sum of
    # these 129·k cancelling terms rounds by about the tolerance itself.
    lhs = float(np.sum(np.asarray(O, np.float64) * np.asarray(V)))
    rhs = float(np.sum(np.asarray(B, np.float64) * np.asarray(G)))
    scale = max(1.0, abs(lhs), abs(rhs))
    assert abs(lhs - rhs) / scale < 1e-5, (lhs, rhs)


# ------------------------------------------- single RHS: the VPU contraction
# k = 1 contracts the gram tile on the VPU, k > 1 on the MXU. The extra
# shape has several n- and m-blocks with padded tails, so the lane and
# sublane partial sums carry across blocks.
SINGLE_RHS_CASES = [(shape, {}) for shape in ODD_SHAPES] + [
    ((40, 600, 130), dict(bn=16, bm=256, bd=128))]


def _kernel_dots(fn, *args):
    """How many dot_generals the Pallas kernels that ``fn`` calls hold."""
    from repro.core.introspect import _subjaxprs

    def count(jaxpr, in_kernel):
        total = 0
        for eqn in jaxpr.eqns:
            total += in_kernel and eqn.primitive.name == "dot_general"
            inner = in_kernel or eqn.primitive.name == "pallas_call"
            total += sum(count(sub, inner) for sub in _subjaxprs(eqn.params))
        return total

    return count(jax.make_jaxpr(fn)(*args).jaxpr, False)


@pytest.mark.parametrize("policy", ["fp32", "bf16"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape,tiles", SINGLE_RHS_CASES)
def test_single_rhs_vpu_contraction(shape, tiles, kind, policy):
    """k = 1 (1-D or one column) matches the dense oracle and column 0 of
    the k = 2 MXU path on the same data; its kernels hold only the cross
    term's dot, the k = 2 kernels the contraction's too."""
    n, m, d = shape
    x, z, B, V = _multi_data(n, m, d, 2, jnp.float32)
    kw = dict(kind=kind, sigma=_sigma(d), policy=policy, **tiles)
    comp = POLICY_COMPUTE.get(policy, jnp.float32)
    G = np.asarray(ref.gram_ref(x, z, kind=kind, sigma=_sigma(d)))
    mxu_fwd = ops.kmvp_fwd(x, z, B, **kw)[:, 0]
    mxu_t = ops.kmvp_t(x, z, V, **kw)[:, 0]
    for b, v in [(B[:, 0], V[:, 0]), (B[:, :1], V[:, :1])]:
        o, g = ops.kmvp_fwd(x, z, b, **kw), ops.kmvp_t(x, z, v, **kw)
        assert o.shape == (n,) + b.shape[1:] and g.shape == (m,) + v.shape[1:]
        o, g = o.reshape(n), g.reshape(m)
        assert_allclose_dtype(o, G @ np.asarray(B[:, 0]), comp)
        assert_allclose_dtype(g, G.T @ np.asarray(V[:, 0]), comp)
        assert_allclose_dtype(o, mxu_fwd, jnp.float32)
        assert_allclose_dtype(g, mxu_t, jnp.float32)
    fwd = functools.partial(ops.kmvp_fwd, **kw)
    t = functools.partial(ops.kmvp_t, **kw)
    assert _kernel_dots(fwd, x, z, B[:, 0]) == 1
    assert _kernel_dots(t, x, z, V[:, :1]) == 1
    assert _kernel_dots(fwd, x, z, B) == 2
    assert _kernel_dots(t, x, z, V) == 2


# ----------------------------------------------------- dtype-policy parity
# The policy axis is orthogonal to the input-dtype axis above: inputs stay
# fp32 and the *policy* decides what the tiles cast to / accumulate in.
POLICY_COMPUTE = {"bf16": jnp.bfloat16, "fp16": jnp.float16}


@pytest.mark.dtype
@pytest.mark.parametrize("shape", ODD_SHAPES)
@pytest.mark.parametrize("kind", KINDS)
def test_policy_fp32_bitwise(shape, kind):
    """policy='fp32' must be the identity: every cast is a trace-time
    no-op, so outputs are bitwise equal to the unpolicied call — the
    default-path guarantee the whole policy layer rests on."""
    n, m, d = shape
    x, z, beta, v = _data(n, m, d, jnp.float32)
    kw = dict(kind=kind, sigma=_sigma(d))
    pairs = [
        (ops.gram(x, z, **kw), ops.gram(x, z, policy="fp32", **kw)),
        (ops.kmvp_fwd(x, z, beta, **kw),
         ops.kmvp_fwd(x, z, beta, policy="fp32", **kw)),
        (ops.kmvp_t(x, z, v, **kw),
         ops.kmvp_t(x, z, v, policy="fp32", **kw)),
        (ops.kmvp_fwd_chunked(x, z, beta, **kw),
         ops.kmvp_fwd_chunked(x, z, beta, policy="fp32", **kw)),
        (ops.kmvp_t_chunked(x, z, v, **kw),
         ops.kmvp_t_chunked(x, z, v, policy="fp32", **kw)),
    ]
    for base, policied in pairs:
        assert np.array_equal(np.asarray(base), np.asarray(policied))


@pytest.mark.dtype
@pytest.mark.parametrize("k", MULTI_KS)
@pytest.mark.parametrize("policy", sorted(POLICY_COMPUTE))
@pytest.mark.parametrize("kind", KINDS)
def test_policy_parity_grid(k, policy, kind):
    """bf16/fp16 policies vs the fp32 dense oracle at per-dtype tolerance,
    Pallas and chunked-jnp backends, odd shapes x kinds x k."""
    comp = POLICY_COMPUTE[policy]
    for shape in [(1, 3, 127), (129, 257, 3), (257, 127, 129)]:
        n, m, d = shape
        x, z, B, V = _multi_data(n, m, d, k, jnp.float32)
        kw = dict(kind=kind, sigma=_sigma(d))
        G = np.asarray(ref.gram_ref(x, z, **kw))
        assert_allclose_dtype(ops.gram(x, z, policy=policy, **kw), G, comp)
        for fwd, t in [(ops.kmvp_fwd, ops.kmvp_t),
                       (ops.kmvp_fwd_chunked, ops.kmvp_t_chunked)]:
            O = fwd(x, z, B, policy=policy, **kw)
            Gt = t(x, z, V, policy=policy, **kw)
            assert O.dtype == jnp.float32 and Gt.dtype == jnp.float32
            assert_allclose_dtype(O, G @ np.asarray(B), comp)
            assert_allclose_dtype(Gt, G.T @ np.asarray(V), comp)


@pytest.mark.dtype
@pytest.mark.parametrize("policy", sorted(POLICY_COMPUTE))
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("impl", ["pallas", "chunked"])
def test_policy_adjoint(policy, kind, impl):
    """Adjointness under a low-precision policy holds to the compute
    dtype's tolerance: fwd rounds B while t rounds V, so the pairing is
    exact only up to one input-rounding step on each side. The gap is
    normalized by the term mass sum(|O.V|) + sum(|B.G|), not the (heavily
    cancelled) pairing value itself — rounding acts on the terms."""
    from conftest import _DTYPE_TOL
    n, m, d = 129, 64, 16
    x, z, B, V = _multi_data(n, m, d, 3, jnp.float32)
    kw = dict(kind=kind, sigma=_sigma(d), policy=policy)
    if impl == "pallas":
        O, G = ops.kmvp_fwd(x, z, B, **kw), ops.kmvp_t(x, z, V, **kw)
    else:
        O = ops.kmvp_fwd_chunked(x, z, B, **kw)
        G = ops.kmvp_t_chunked(x, z, V, **kw)
    lhs, rhs = float(jnp.sum(O * V)), float(jnp.sum(B * G))
    scale = max(1.0, float(jnp.sum(jnp.abs(O * V)))
                + float(jnp.sum(jnp.abs(B * G))))
    tol = _DTYPE_TOL[np.dtype(POLICY_COMPUTE[policy]).name]
    assert abs(lhs - rhs) / scale < tol, (lhs, rhs, scale)


@pytest.mark.dtype
def test_policy_otf_memory_contract():
    """Under bf16 the Pallas otf path keeps fp32 out of HBM entirely
    (the f32 accumulator is VMEM scratch); the jnp fallback's finished
    chunk materializes at bf16 — its only fp32 transient is the
    chunk-sized dot accumulator, never the full C block."""
    from repro.core.introspect import max_intermediate_elems_of_dtype
    n, d, m, br = 64, 8, 32, 16
    x, z, _, _ = _data(n, m, d, jnp.float32)
    v = jnp.ones((n, 1), jnp.float32)
    kw = dict(kind="gaussian", sigma=_sigma(d))

    def otf_pallas(x, z, v):
        return ops.otf_kmvp_t(x, z, v, backend="pallas", block_rows=br,
                              policy="bf16", **kw)

    def otf_jnp(x, z, v):
        return ops.kmvp_t_chunked(x, z, v, block_rows=br, policy="bf16",
                                  **kw)

    # pallas: strictly no fp32 (rows, m) block anywhere in HBM
    worst = max_intermediate_elems_of_dtype(otf_pallas, "float32", x, z, v)
    assert worst < br * m, worst
    # fallback: fp32 bounded by one chunk (full C forbidden), and the
    # finished chunk really exists at the compute dtype
    worst32 = max_intermediate_elems_of_dtype(otf_jnp, "float32", x, z, v)
    worst16 = max_intermediate_elems_of_dtype(otf_jnp, "bfloat16", x, z, v)
    assert worst32 <= br * m < n * m, worst32
    assert worst16 >= br * m, worst16


def test_kmvp_block_divisibility_errors():
    """The raw Pallas entry points reject non-divisible dims with errors
    naming the offending dim and block (the old bare asserts said nothing)."""
    from repro.kernels import kmvp
    x = jnp.zeros((100, 128))
    z = jnp.zeros((128, 128))
    b = jnp.zeros((128, 1))
    v = jnp.zeros((100, 1))
    with pytest.raises(ValueError, match=r"n=100.*bn=256"):
        kmvp.kmvp_fwd_pallas(x, z, b, bn=256, bm=128, bd=128)
    with pytest.raises(ValueError, match=r"m=128.*bm=96"):
        kmvp.kmvp_fwd_pallas(jnp.zeros((128, 128)), z, b,
                             bn=128, bm=96, bd=128)
    with pytest.raises(ValueError, match=r"d=128.*bd=100"):
        kmvp.kmvp_t_pallas(jnp.zeros((128, 128)), z, jnp.zeros((128, 1)),
                           bn=128, bm=128, bd=100)
    with pytest.raises(ValueError, match=r"kmvp_t_pallas.*n=100"):
        kmvp.kmvp_t_pallas(x, z, v, bn=256, bm=128, bd=128)
    with pytest.raises(ValueError, match=r"positive"):
        kmvp.kmvp_fwd_pallas(x, z, b, bn=0, bm=128, bd=128)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape", [(64, 32, 16), (129, 257, 3)])
@pytest.mark.parametrize("impl", ["pallas", "chunked"])
def test_kmvp_adjoint(shape, kind, impl):
    """<kmvp_fwd(x,z,b), v> == <b, kmvp_t(x,z,v)>: the two fused kernels
    are adjoints of the same implicit C and can never drift apart."""
    n, m, d = shape
    x, z, beta, v = _data(n, m, d, jnp.float32)
    kw = dict(kind=kind, sigma=_sigma(d))
    if impl == "pallas":
        o, g = ops.kmvp_fwd(x, z, beta, **kw), ops.kmvp_t(x, z, v, **kw)
    else:
        o = ops.kmvp_fwd_chunked(x, z, beta, **kw)
        g = ops.kmvp_t_chunked(x, z, v, **kw)
    lhs, rhs = float(o @ v), float(beta @ g)
    scale = max(1.0, abs(lhs), abs(rhs))
    assert abs(lhs - rhs) / scale < 1e-5, (lhs, rhs)


def test_block_tiny_size_regression():
    """_block must not balloon a 1-row input to a full alignment block."""
    assert ops._block(1, 256, 8, True) == 1        # interpret: exact size
    assert ops._block(3, 256, 128, True) == 3
    assert ops._block(1, 256, 8, False) == 8       # TPU: one align unit
    assert ops._block(1, 256, 128, False) == 128
    assert ops._block(2, 4, 8, False) == 8         # want < align stays legal
    assert ops._block(300, 256, 8, True) == 256    # large sizes unchanged
    # end-to-end: n=1 stays correct through the padding path
    x, z, beta, v = _data(1, 37, 5, jnp.float32)
    kw = dict(kind="gaussian", sigma=_sigma(5))
    assert ops.gram(x, z, **kw).shape == (1, 37)
    assert_allclose_dtype(ops.gram(x, z, **kw), ref.gram_ref(x, z, **kw),
                          jnp.float32)
    assert_allclose_dtype(ops.kmvp_fwd(x, z, beta, **kw),
                          ref.kmvp_ref(x, z, beta, **kw), jnp.float32)


def test_otf_block_heuristics():
    """Per-shard-n heuristics: aligned, bounded, never a full-C chunk."""
    for n in (8, 64, 256, 4096, 100_000):
        for m in (16, 128, 1024):
            bn = ops.otf_block_rows(n, m, 10)
            assert bn % 8 == 0 and bn >= 8
            assert bn * m * 4 <= max(1 << 20, 8 * m * 4)   # budget or floor
            if n >= 64:
                assert bn < n                               # real chunking
    bn, bm, bd = ops.otf_tiles(4096, 512, 784)
    assert bn % 8 == 0 and bm % 128 == 0 and bd % 128 == 0
    assert 4 * (bn * bd + bm * bd + bn * bm) <= 4 << 20


def test_block_shape_invariance():
    """Result must not depend on BlockSpec tile choice."""
    x, z, beta, v = _data(384, 256, 96, jnp.float32)
    base = ops.gram(x, z, sigma=10.0, bn=256, bm=256, bd=256)
    for bn, bm, bd in [(64, 128, 128), (8, 128, 256), (128, 256, 128)]:
        got = ops.gram(x, z, sigma=10.0, bn=bn, bm=bm, bd=bd)
        np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-6)


def test_gram_backend_integration():
    """core.nystrom routes backend='pallas' through the kernel."""
    from repro.core.nystrom import KernelSpec, gram
    x, z, _, _ = _data(100, 40, 12, jnp.float32)
    kern = KernelSpec("gaussian", sigma=3.0)
    np.testing.assert_allclose(gram(x, z, kern, "pallas"),
                               gram(x, z, kern, "jnp"), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape", [(2, 16, 8, 3, 4), (3, 64, 32, 2, 16),
                                   (1, 128, 64, 4, 32)])
def test_ssd_chunk_matches_ref(shape):
    """Pallas SSD within-chunk kernel vs jnp oracle."""
    G, Q, N, H, P = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    Cc = jax.random.normal(ks[0], (G, Q, N), jnp.float32)
    Bc = jax.random.normal(ks[1], (G, Q, N), jnp.float32)
    dA = -jnp.abs(jax.random.normal(ks[2], (G, H, Q), jnp.float32)) * 0.1
    xdt = jax.random.normal(ks[3], (G, H, Q, P), jnp.float32)
    got = ops.ssd_chunk(Cc, Bc, dA, xdt)
    want = ref.ssd_chunk_ref(Cc, Bc, dA, xdt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ssd_chunk_consistent_with_model_path():
    """Kernel output == the ssd_scan diagonal term used by the model."""
    from repro.models.ssm import _segsum
    G, Q, N, H, P = 2, 32, 16, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    Cc = jax.random.normal(ks[0], (G, Q, N), jnp.float32)
    Bc = jax.random.normal(ks[1], (G, Q, N), jnp.float32)
    dA = -jnp.abs(jax.random.normal(ks[2], (G, H, Q), jnp.float32)) * 0.1
    xdt = jax.random.normal(ks[3], (G, H, Q, P), jnp.float32)
    L = jnp.exp(_segsum(dA))
    scores = jnp.einsum("gqn,gkn->gqk", Cc, Bc)
    want = jnp.einsum("ghqk,ghkp->ghqp",
                      jnp.where(jnp.isfinite(L), scores[:, None] * L, 0.0), xdt)
    got = ops.ssd_chunk(Cc, Bc, dA, xdt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)

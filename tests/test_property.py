"""Hypothesis property tests on system invariants."""
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dependency: property tests need hypothesis")
import hypothesis
import hypothesis.extra.numpy as hnp
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core import Formulation4, KernelSpec, build_C, build_W, get_loss
from repro.core.tron import TronConfig, tron
from repro.kernels import ops, ref

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=20,
    suppress_health_check=[hypothesis.HealthCheck.too_slow])
hypothesis.settings.load_profile("ci")

finite_f32 = st.floats(-5.0, 5.0, allow_nan=False, width=32)


@given(hnp.arrays(np.float32, hnp.array_shapes(min_dims=2, max_dims=2,
                                               min_side=2, max_side=24),
                  elements=finite_f32))
def test_gaussian_gram_range_and_symmetry(x):
    """0 < W_kl <= 1, W symmetric, diag == 1 (gaussian kernel axioms).

    The diagonal is exp(-d2 / 2 sigma^2) with d2 = |x|^2 + |x|^2 - 2 x.x
    formed in f32: each d-term sum carries at most gamma_d = d u / (1 - d u)
    relative error (u = eps / 2; Higham, Accuracy and Stability, 3.1), so
    |d2| <= 2 gamma_d (|x|^2 + |x|^2) + 3 u (|x|^2 + |x|^2), and the
    diagonal may sit that much, over 2 sigma^2, below 1."""
    sigma = 1.5
    kern = KernelSpec("gaussian", sigma=sigma)
    W = np.asarray(build_W(jnp.asarray(x), kern))
    assert (W >= 0).all() and (W <= 1.0 + 1e-6).all()  # exp may underflow to 0
    np.testing.assert_allclose(W, W.T, rtol=1e-5, atol=1e-6)
    u = np.finfo(np.float32).eps / 2
    d = x.shape[1]
    gamma = d * u / (1 - d * u)
    sq = 2.0 * np.sum(np.asarray(x, np.float64) ** 2, axis=1)
    tol = (2 * gamma + 3 * u) * sq / (2 * sigma ** 2) + u
    assert np.all(np.abs(np.diag(W) - 1.0) <= tol), (np.diag(W), tol)


@given(hnp.arrays(np.float32, (12, 6), elements=finite_f32),
       hnp.arrays(np.float32, (5, 6), elements=finite_f32))
def test_gram_psd_nystrom(x, z):
    """W must be PSD (it is a Gram matrix) — min eigenvalue >= -eps."""
    W = np.asarray(build_W(jnp.asarray(z), KernelSpec("gaussian", sigma=2.0)))
    evals = np.linalg.eigvalsh(W)
    assert evals.min() > -1e-4


@given(st.integers(1, 40), st.integers(1, 30), st.integers(1, 20),
       st.sampled_from(["gaussian", "linear"]))
def test_pallas_gram_any_shape(n, m, d, kind):
    """Pallas gram == oracle for arbitrary (unaligned) shapes."""
    k = jax.random.PRNGKey(n * 1000 + m * 10 + d)
    x = jax.random.normal(k, (n, d), jnp.float32)
    z = jax.random.normal(jax.random.fold_in(k, 1), (m, d), jnp.float32)
    got = ops.gram(x, z, kind=kind, sigma=float(np.sqrt(d)))
    want = ref.gram_ref(x, z, kind=kind, sigma=float(np.sqrt(d)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@given(hnp.arrays(np.float32, (16,), elements=finite_f32),
       hnp.arrays(np.float32, (16,), elements=st.floats(-1, 1, width=32)))
def test_kmvp_linearity(beta1, beta2):
    """kmvp(beta1 + beta2) == kmvp(beta1) + kmvp(beta2) (linear operator)."""
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (24, 8), jnp.float32)
    z = jax.random.normal(jax.random.fold_in(k, 1), (16, 8), jnp.float32)
    o12 = ops.kmvp_fwd(x, z, jnp.asarray(beta1 + beta2), sigma=3.0)
    o1 = ops.kmvp_fwd(x, z, jnp.asarray(beta1), sigma=3.0)
    o2 = ops.kmvp_fwd(x, z, jnp.asarray(beta2), sigma=3.0)
    np.testing.assert_allclose(o12, o1 + o2, rtol=1e-3, atol=1e-3)


@given(st.sampled_from(["squared_hinge", "logistic", "squared"]),
       hnp.arrays(np.float32, (9,), elements=finite_f32))
def test_loss_gauss_newton_diag_nonneg(loss_name, o):
    """D >= 0 — required for the Gauss-Newton Hd to be PSD (CG validity)."""
    loss = get_loss(loss_name)
    y = jnp.asarray(np.sign(np.arange(9) % 2 - 0.5), jnp.float32)
    D = np.asarray(loss.diag(jnp.asarray(o), y))
    assert (D >= 0).all()


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10)
def test_tron_objective_never_increases(seed):
    """Final objective <= initial objective for any PSD quadratic."""
    key = jax.random.PRNGKey(seed)
    A = jax.random.normal(key, (12, 12))
    H = A @ A.T + 0.1 * jnp.eye(12)
    b = jax.random.normal(jax.random.fold_in(key, 1), (12,))
    x0 = jax.random.normal(jax.random.fold_in(key, 2), (12,))
    fgrad = lambda x: (0.5 * x @ (H @ x) - b @ x, H @ x - b, jnp.zeros(()))
    res = tron(fgrad, lambda a, d: H @ d, x0, TronConfig(max_iter=30))
    f0 = 0.5 * x0 @ (H @ x0) - b @ x0
    assert float(res.f) <= float(f0) + 1e-5

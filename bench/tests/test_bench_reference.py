"""bench/reference.py against float64 on the host, and its control."""
import bench_tiny  # noqa: F401
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as ref


def _problem(n=300, m=40, d=54, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 0.5, (n, d)).astype(np.float32)
    y = np.where(rng.random(n) < 0.5, 1.0, -1.0).astype(np.float32)
    Z = X[rng.choice(n, m, replace=False)]
    return X, y, Z


def _f64(X, y, Z, beta, lam, sigma):
    C, W = ref.gram64(X, Z, sigma), ref.gram64(Z, Z, sigma)
    o = C @ beta
    slack = 1 - y * o
    act = slack > 0
    f = 0.5 * lam * beta @ W @ beta + 0.5 * np.sum(np.where(act, slack, 0)
                                                   ** 2)
    g = lam * W @ beta + C.T @ np.where(act, o - y, 0)
    return f, g, act.astype(np.float64), C, W


def test_fgrad_hessd_margins_match_float64():
    X, y, Z = _problem()
    lam, sigma = 0.01, 1.0
    beta = np.random.default_rng(1).normal(0, 0.1, Z.shape[0])
    d = np.random.default_rng(2).normal(0, 1.0, Z.shape[0])
    prob = ref.Problem(lam=lam, sigma=sigma, block=128)
    Xb, yb, mb = prob.kernel_blocks(jnp.asarray(X), jnp.asarray(y))
    W = prob.w(jnp.asarray(Z))
    f, g, D = prob.fgrad(Xb, yb, mb, jnp.asarray(Z), W,
                         jnp.asarray(beta, jnp.float32))
    f64, g64, D64, C, W64 = _f64(X, y, Z, beta, lam, sigma)
    assert float(f) == pytest.approx(f64, rel=1e-5)
    assert ref.rel_err(g, g64) < 1e-5
    np.testing.assert_array_equal(np.asarray(D).reshape(-1)[:len(y)], D64)
    h = prob.hessd(Xb, D, jnp.asarray(Z), W, jnp.asarray(d, jnp.float32))
    assert ref.rel_err(h, lam * W64 @ d + C.T @ (D64 * (C @ d))) < 1e-5
    o = ref.margins(jnp.asarray(X), jnp.asarray(Z),
                    jnp.asarray(beta, jnp.float32), sigma=sigma, block=128)
    assert ref.rel_err(o, C @ beta) < 1e-5


def test_control_is_three_bf16_passes():
    """bf16x3 is coarser than float32 and far finer than one bf16 pass."""
    X, _, Z = _problem()
    g32 = np.asarray(ref.gram(jnp.asarray(X), jnp.asarray(Z), 1.0))
    g3 = np.asarray(ref.gram(jnp.asarray(X), jnp.asarray(Z), 1.0,
                             ref.BF16X3))
    g64 = ref.gram64(X, Z, 1.0)
    e32, e3 = ref.rel_err(g32, g64), ref.rel_err(g3, g64)
    # one bf16 pass reads about 2e-2 on these rows
    assert e32 < 1e-5
    assert 10 * e32 < e3 < 1e-3
    with pytest.raises(ValueError, match="unknown precision"):
        ref._dot(jnp.ones(2), jnp.ones(2), ref._VDOT, "high")


def test_tron_matches_the_program_on_the_cpu():
    """The reference's TRON walks the program's trajectory (local plan)."""
    import repro.api as api
    from repro.core import KernelSpec, TronConfig
    X, y, Z = _problem(n=512, m=32)
    km = api.KernelMachine(api.MachineConfig(
        kernel=KernelSpec("gaussian", sigma=1.0), lam=0.01, plan="local",
        tron=TronConfig(max_iter=3, grad_rtol=1e-6)))
    km.fit(jnp.asarray(X), jnp.asarray(y), jnp.asarray(Z))
    r = km.result_
    fit = ref.fit(jnp.asarray(X), jnp.asarray(y), jnp.asarray(Z), lam=0.01,
                  sigma=1.0, cfg=ref.Tron(max_iter=3, grad_rtol=1e-6),
                  block=128)
    assert (fit.n_fg, fit.n_hd) == (r.n_fg, r.n_hd)
    np.testing.assert_allclose(fit.f_hist, np.asarray(r.tron.f_hist),
                               rtol=1e-5)
    assert ref.rel_err(km.state_["beta"], fit.beta) < 1e-3

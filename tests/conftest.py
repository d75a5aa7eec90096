import os
import signal
import sys

# NOTE: do NOT set --xla_force_host_platform_device_count here (brief:
# smoke tests run on 1 device; multi-device tests spawn subprocesses).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# The suite keeps no persistent compile cache; the launchers' cache helper
# (repro.launch.cli.enable_compile_cache) honours this, and subprocesses
# the tests start inherit it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)
jax.config.update("jax_enable_compilation_cache", False)


# --------------------------------------------------------------- device gating
def _device_capability() -> int:
    """Devices a test (or its subprocess) can get on this host. The
    multi-device suites run in subprocesses that force
    --xla_force_host_platform_device_count, which works on any CPU-backed
    host for any count; on accelerators the real device count is the cap."""
    if jax.default_backend() == "cpu":
        return 1 << 30
    return jax.device_count()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "requires_devices(k): skip (not error) when fewer than k devices "
        "are available or simulatable (CPU hosts can fake any count in a "
        "subprocess via --xla_force_host_platform_device_count)")
    config.addinivalue_line(
        "markers",
        "requires_multiprocess(timeout=900): spawns a jax.distributed "
        "subprocess fleet; wall-clock guarded by SIGALRM so a hung "
        "collective fails the test instead of the session")


def pytest_runtest_setup(item):
    marker = item.get_closest_marker("requires_devices")
    if marker is not None:
        k = int(marker.args[0])
        have = _device_capability()
        if have < k:
            pytest.skip(f"needs {k} devices; this host has "
                        f"{jax.device_count()} and cannot simulate more")
    if item.get_closest_marker("requires_multiprocess") is not None \
            and not hasattr(signal, "SIGALRM"):
        pytest.skip("requires_multiprocess needs SIGALRM for its hang "
                    "guard (POSIX only)")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Wall-clock guard for ``requires_multiprocess`` tests: a fleet whose
    collective hangs (e.g. every worker blocked on a dead peer) raises in
    THIS process instead of stalling the whole pytest session. The rig has
    its own (tighter) watchdog; this alarm is the backstop above it."""
    marker = item.get_closest_marker("requires_multiprocess")
    if marker is None or not hasattr(signal, "SIGALRM"):
        yield
        return
    budget = int(marker.kwargs.get("timeout", 900))

    def _alarm(signum, frame):
        raise TimeoutError(
            f"requires_multiprocess test exceeded its {budget}s wall "
            f"budget — subprocess fleet presumed hung")

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(budget)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


# ------------------------------------------------------- shared parity asserts
_DTYPE_TOL = {"float32": 2e-4, "bfloat16": 3e-2, "float16": 4e-3}


def assert_allclose_dtype(got, want, dtype, *, rtol=None, atol=None):
    """allclose with per-dtype tolerances for the kernel parity sweeps.

    ``dtype`` is the *input* dtype of the kernel under test (accumulation
    is always f32, so bf16 inputs dominate the error). The default atol
    scales with the magnitude of ``want`` so linear-kernel outputs (which
    grow with d and m) and unit-range gaussian outputs share one helper.
    """
    want = np.asarray(want)
    tol = _DTYPE_TOL[np.dtype(dtype).name]
    if rtol is None:
        rtol = tol
    if atol is None:
        atol = tol * max(1.0, float(np.max(np.abs(want))) if want.size else 1.0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=rtol, atol=atol)


@pytest.fixture
def allclose_dtype():
    """Fixture view of :func:`assert_allclose_dtype` for tests that prefer
    injection over the conftest import."""
    return assert_allclose_dtype


# --------------------------------------------------- f32 solver resolution
def f32_plateau_rtol(f, lam, basis, sigma, beta):
    """How far apart two f32 TRON solves of formulation (4) may stop, as a
    fraction of ||beta|| (per column for an (m, K) beta).

    TRON accepts a step only when the objective's f32 decrease is
    resolvable, so a solve stops somewhere in {b : f(b) - f* <= eps |f*|}
    (eps = f32 machine epsilon): past that, actual and predicted
    reductions are rounding. Formulation (4) is mu-strongly convex with
    mu = lam * lambda_min(W) (the loss term is convex), so that set lies
    within sqrt(2 eps |f*| / mu) of the unique optimum, and two solves lie
    within twice that of each other. ``f`` is the objective a solve
    reported, ``basis``/``sigma`` give the gaussian W."""
    z = np.asarray(basis, np.float64)
    sq = np.sum(z * z, axis=1)
    W = np.exp(-np.maximum(sq[:, None] + sq[None, :] - 2.0 * z @ z.T, 0.0)
               / (2.0 * sigma ** 2))
    mu = lam * np.linalg.eigvalsh(W)[0]
    eps = np.finfo(np.float32).eps
    radius = np.sqrt(2.0 * eps * np.abs(np.asarray(f, np.float64)) / mu)
    return 2.0 * radius / np.linalg.norm(np.asarray(beta, np.float64), axis=0)

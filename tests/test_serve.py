"""kernel_serve internals: bucketing, executable-cache bounds, plan
routing, and multiclass label fidelity.

The serving driver rides the shared plan-registry inference engine
(``KernelMachine.decider``) — these tests pin the pieces the ``--selftest``
smoke exercises only end-to-end: power-of-two bucket arithmetic at its
boundaries, the jit-cache staying bounded under a mixed-size request
stream, the stream->local plan flip for out-of-core-trained machines, and
served multiclass argmax labels equalling ``predict``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KernelMachine, MachineConfig, StreamConfig
from repro.core import KernelSpec, TronConfig, random_basis
from repro.data import make_classification, make_multiclass
from repro.launch.kernel_serve import ServingEndpoint, _bucket, _serving_plan

N, D, M = 512, 12, 32
CFG = MachineConfig(kernel=KernelSpec("gaussian", sigma=2.0), lam=1.0,
                    tron=TronConfig(max_iter=60),
                    stream=StreamConfig(chunk_rows=128))


@pytest.fixture(scope="module")
def km():
    X, y = make_classification(jax.random.PRNGKey(0), N, D,
                               clusters_per_class=4)
    basis = random_basis(jax.random.PRNGKey(1), X, M)
    return KernelMachine(CFG).fit(X, y, basis)


@pytest.fixture(scope="module")
def km_mc():
    X, y = make_multiclass(jax.random.PRNGKey(0), N, D, 3,
                           clusters_per_class=2)
    basis = random_basis(jax.random.PRNGKey(1), X, M)
    return KernelMachine(CFG).fit(X, y, basis)


# ------------------------------------------------------------------ buckets
def test_bucket_boundaries():
    # floor is MIN_BUCKET=2: a (1, d) dispatch lowers to a different XLA
    # dot strategy than multi-row shapes, and the resulting one-ULP drift
    # would break the serve engine's coalescing determinism contract
    assert _bucket(1, 256) == 2
    assert _bucket(2, 256) == 2
    assert _bucket(3, 256) == 4          # just above a bucket -> next pow2
    assert _bucket(64, 256) == 64        # exact power of two: no padding
    assert _bucket(65, 256) == 128
    assert _bucket(256, 256) == 256      # n == max_batch: top bucket
    assert _bucket(257, 256) == 256      # capped (caller splits oversize)


def test_endpoint_boundary_batches(km):
    """n == 1, n == max_batch, and n just above a bucket all serve and
    match the direct decision path."""
    endpoint = ServingEndpoint(km, max_batch=64)
    for n in (1, 2, 3, 63, 64, 65):
        Xq = jax.random.normal(jax.random.PRNGKey(n), (n, D))
        served = endpoint(Xq)
        assert served.shape == (n,)
        direct = km.decision_function(Xq)
        assert float(jnp.max(jnp.abs(served - direct))) < 1e-5, n


def test_endpoint_splits_oversize_requests(km):
    endpoint = ServingEndpoint(km, max_batch=64)
    Xq = jax.random.normal(jax.random.PRNGKey(3), (150, D))  # 64+64+22
    served = endpoint(Xq)
    assert served.shape == (150,)
    direct = km.decision_function(Xq)
    assert float(jnp.max(jnp.abs(served - direct))) < 1e-5
    # oversize splitting reuses the same buckets, so 64 and 32 only
    assert endpoint.n_executables <= 2


def test_executable_cache_bounded_under_mixed_sizes(km):
    """A mixed-size request stream compiles at most log2(max_batch)+1
    executables — the whole point of bucketing."""
    endpoint = ServingEndpoint(km, max_batch=64)
    rng = np.random.default_rng(0)
    for s in rng.integers(1, 65, size=40):
        endpoint(jnp.zeros((int(s), D)))
    assert endpoint.n_executables <= 7    # {1,2,4,8,16,32,64}
    # replaying the same stream adds nothing
    before = endpoint.n_executables
    for s in rng.integers(1, 65, size=40):
        endpoint(jnp.zeros((int(s), D)))
    assert endpoint.n_executables == before


# ----------------------------------------------------------- plan routing
def test_serving_plan_resolution(km):
    assert _serving_plan(km, None) == "local"
    assert _serving_plan(km, "otf_shard") == "otf_shard"
    stream_km = KernelMachine(CFG.replace(plan="stream"))
    stream_km.state_ = km.state_          # plan routing only reads config
    assert _serving_plan(stream_km, None) == "local"
    assert _serving_plan(stream_km, "stream") == "local"


def test_stream_trained_machine_serves(km):
    """The plan-override symmetry: a stream-trained machine serves small
    batches through the local decide arm, matching its own chunked path."""
    X, y = make_classification(jax.random.PRNGKey(0), N, D,
                               clusters_per_class=4)
    basis = random_basis(jax.random.PRNGKey(1), X, M)
    skm = KernelMachine(CFG.replace(plan="stream")).fit(X, y, basis)
    endpoint = ServingEndpoint(skm, max_batch=64)
    assert endpoint.plan == "local"
    Xq = jax.random.normal(jax.random.PRNGKey(5), (37, D))
    served = endpoint(Xq)
    chunked = skm.decision_function(Xq)        # config plan: stream
    assert float(np.max(np.abs(np.asarray(served) -
                               np.asarray(chunked)))) < 1e-5


def test_endpoint_fused_plan_arm(km):
    """Serving through a mesh decide arm (otf_shard) matches local."""
    endpoint = ServingEndpoint(km, max_batch=64, plan="otf_shard")
    Xq = jax.random.normal(jax.random.PRNGKey(6), (21, D))
    direct = km.decision_function(Xq, plan="local")
    assert float(jnp.max(jnp.abs(endpoint(Xq) - direct))) < 1e-5


# ------------------------------------------------------------- multiclass
def test_served_multiclass_labels_equal_predict(km_mc):
    """Served (b, K) margins come from ONE multi-RHS evaluation and their
    argmax labels equal the direct predict path, across bucket sizes."""
    endpoint = ServingEndpoint(km_mc, max_batch=64)
    for n in (1, 37, 64):
        Xq = jax.random.normal(jax.random.PRNGKey(n), (n, D))
        served = endpoint(Xq)
        assert served.shape == (n, 3)
        labels = km_mc.state_["classes"][jnp.argmax(served, axis=-1)]
        np.testing.assert_array_equal(np.asarray(labels),
                                      np.asarray(km_mc.predict(Xq)))


# ------------------------------------------------------------- CLI exit code
def test_kernel_serve_exits_nonzero_on_failed_dispatch(km, tmp_path):
    """A dispatch that raises fails only its batch inside the engine, but
    the serving run as a whole must not report success: kernel_serve
    counts the failed request and exits non-zero. The same run without
    the fault exits cleanly."""
    from repro.faults import FaultPlan
    from repro.launch import kernel_serve
    path = str(tmp_path / "m.npz")
    km.save(path)
    argv = ["--ckpt", path, "--clients", "2", "--requests", "4",
            "--max-batch", "16"]
    kernel_serve.main(argv)                       # no fault: returns
    with FaultPlan().inject("serve.dispatch", exc="RuntimeError", times=1):
        with pytest.raises(SystemExit) as ei:
            kernel_serve.main(argv)
    assert "1 failed dispatches" in str(ei.value.code), ei.value.code


def test_kernel_serve_backend_override(km, tmp_path):
    """--backend replaces the backend a machine was trained with (jnp
    here) for every served model, and margins agree across the two."""
    from repro.launch.kernel_serve import build_registry
    path = str(tmp_path / "m.npz")
    km.save(path)
    Xq = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (9, D)))
    trained = build_registry([path], max_batch=16, warmup=False).get("m0")
    pallas = build_registry([path], max_batch=16, backend="pallas",
                            warmup=False).get("m0")
    np.testing.assert_allclose(pallas.decider(Xq), trained.decider(Xq),
                               rtol=1e-5, atol=1e-6)

"""Execution-plan equivalence and the otf_shard memory contract.

The plan matrix iterates the *registry*, so a newly registered plan is
automatically held to the same standard: same small problem, same config,
beta agreeing with every other plan within tolerance. The memory tests
use jaxpr shape instrumentation (repro.core.introspect) to prove the
fused plan never materializes a C block — the claim that distinguishes
``otf_shard`` from ``otf``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KernelMachine, MachineConfig, StreamConfig, available_plans
from repro.core import KernelSpec, TronConfig, random_basis
from repro.core.compat import make_mesh
from repro.core.distributed import DistConfig, DistributedNystrom
from repro.core.introspect import (assert_max_intermediate_below,
                                   max_intermediate_elems)
from repro.data import ArrayChunkSource, make_classification
from conftest import f32_plateau_rtol

N, M, D = 256, 32, 8
CHUNK = 64          # stream plan chunking for this fixture (4 chunks)


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    X, y = make_classification(key, N, D, clusters_per_class=2)
    basis = random_basis(jax.random.PRNGKey(2), X, M)
    return X, y, basis


@pytest.fixture(scope="module")
def config():
    # tight grad_rtol: plans must agree at the *optimum*, not merely at a
    # loose early stop where near-flat directions of W leave beta slack
    return MachineConfig(kernel=KernelSpec("gaussian", sigma=2.0), lam=0.5,
                         tron=TronConfig(max_iter=300, grad_rtol=1e-6),
                         stream=StreamConfig(chunk_rows=CHUNK))


@pytest.fixture(scope="module")
def fits(problem, config):
    X, y, basis = problem
    out = {}
    for plan in available_plans():
        km = KernelMachine(config.replace(plan=plan)).fit(X, y, basis)
        out[plan] = np.asarray(km.state_["beta"])
    return out


def test_matrix_covers_registry(fits):
    assert set(fits) == set(available_plans())
    assert "otf_shard" in fits
    assert "stream" in fits             # the plan this PR adds is registered


@pytest.mark.parametrize("plan", available_plans())
def test_plan_matches_every_other(plan, fits):
    """Pairwise beta agreement across the whole registry."""
    b = fits[plan]
    scale = max(np.max(np.abs(v)) for v in fits.values())
    for other, bo in fits.items():
        assert np.max(np.abs(b - bo)) / scale < 5e-4, (plan, other)


def test_otf_shard_matches_local_tight(fits):
    """Acceptance: otf_shard's beta within 1e-4 relative of local's."""
    b, bl = fits["otf_shard"], fits["local"]
    assert np.linalg.norm(b - bl) / np.linalg.norm(bl) < 1e-4


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_otf_shard_never_materializes_C(problem, backend):
    """No intermediate of the fused f/g/Hd closures reaches n x m elements;
    the non-fused otf path (which rebuilds the per-shard block) is the
    positive control proving the instrumentation sees gram blocks."""
    X, y, basis = problem
    mesh = make_mesh((1,), ("data",))
    kern = KernelSpec("gaussian", sigma=2.0)
    beta = jnp.zeros((M,), X.dtype)
    D = jnp.ones((N,), X.dtype)

    fused = DistributedNystrom(
        mesh, 0.5, "squared_hinge", kern,
        DistConfig(materialize=False, fused=True, backend=backend))
    fg, hd = fused.make_fused_closures(X, y, basis)
    with mesh:
        assert_max_intermediate_below(fg, N * M, beta)
        assert_max_intermediate_below(hd, N * M, D, beta)

    control = DistributedNystrom(mesh, 0.5, "squared_hinge", kern,
                                 DistConfig(materialize=False))
    fg_c, _ = control.make_otf_closures(X, y, basis)
    with mesh:
        assert max_intermediate_elems(fg_c, beta) >= N * M


def test_otf_shard_partial_fit_growth(problem, config):
    """Stage-wise basis growth under otf_shard: recomputation makes growth
    trivially correct — the grown machine must land on the same optimum as
    a fresh local fit on the full basis, warm start included."""
    X, y, basis = problem
    ref = KernelMachine(config).fit(X, y, basis)
    km = KernelMachine(config.replace(plan="otf_shard"))
    km.partial_fit(X, y, basis[: M // 2]).partial_fit(X, y, basis[M // 2:])
    assert len(km.history_) == 2
    assert km.state_["beta"].shape == (M,)
    b, br = np.asarray(km.state_["beta"]), np.asarray(ref.state_["beta"])
    assert np.linalg.norm(b - br) / np.linalg.norm(br) < 1e-3
    # the warm-started second stage must keep the fitted objective value
    assert abs(km.result_.f - ref.result_.f) / abs(ref.result_.f) < 1e-4


def test_otf_shard_rejects_model_axis(problem):
    X, y, basis = problem
    cfg = MachineConfig(plan="otf_shard", model_axis="model")
    with pytest.raises(ValueError, match="rows only"):
        KernelMachine(cfg).fit(X, y, basis)


def test_stream_matches_local_tight(fits):
    """Acceptance: stream's beta within 1e-4 relative of local's."""
    b, bl = fits["stream"], fits["local"]
    assert np.linalg.norm(b - bl) / np.linalg.norm(bl) < 1e-4


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_stream_never_materializes_chunk_gram(problem, backend):
    """Memory contract: no intermediate of the per-chunk f/g/Hd bodies
    reaches chunk_rows x m elements — the streamed gram is contracted
    through the fused kmvp path, never built."""
    X, y, basis = problem
    mesh = make_mesh((1,), ("data",))
    kern = KernelSpec("gaussian", sigma=2.0)
    solver = DistributedNystrom(
        mesh, 0.5, "squared_hinge", kern,
        DistConfig(materialize=False, fused=True, backend=backend))
    src = ArrayChunkSource(np.asarray(X), np.asarray(y), CHUNK)
    sc = solver.make_stream_closures(src, np.asarray(basis))
    cr = sc.chunk_rows
    Xc = jnp.zeros((cr, D))
    yc = jnp.zeros((cr,))
    wc = jnp.ones((cr,))
    beta = jnp.zeros((M,))
    Dl = jnp.ones((cr,))
    with mesh:
        assert_max_intermediate_below(sc.fg_chunk, cr * M, Xc, yc, wc,
                                      jnp.asarray(basis), beta)
        assert_max_intermediate_below(sc.hd_chunk, cr * M, Xc, Dl,
                                      jnp.asarray(basis), beta)


def test_stream_partial_fit_growth(problem, config):
    """Stage-wise growth under stream: like otf_shard, recomputation makes
    growth trivially correct — the grown machine lands on the fresh-fit
    optimum, warm start and all."""
    X, y, basis = problem
    ref = KernelMachine(config).fit(X, y, basis)
    km = KernelMachine(config.replace(plan="stream"))
    km.partial_fit(X, y, basis[: M // 2]).partial_fit(X, y, basis[M // 2:])
    assert len(km.history_) == 2
    assert km.state_["beta"].shape == (M,)
    b, br = np.asarray(km.state_["beta"]), np.asarray(ref.state_["beta"])
    assert np.linalg.norm(b - br) / np.linalg.norm(br) < 1e-3
    assert abs(km.result_.f - ref.result_.f) / abs(ref.result_.f) < 1e-4


def test_stream_ragged_n_and_chunking_invariance(problem, config):
    """n not divisible by the chunk size (mask-padded ragged last chunk)
    must give the same optimum as any other chunking of the same data."""
    X, y, basis = problem
    X, y = X[:200], y[:200]            # 200 = 3 x 64 + 8: ragged
    ref = KernelMachine(config).fit(X, y, basis)
    km = KernelMachine(config.replace(
        plan="stream", stream=StreamConfig(chunk_rows=56))).fit(X, y, basis)
    b, br = np.asarray(km.state_["beta"]), np.asarray(ref.state_["beta"])
    assert np.linalg.norm(b - br) / np.linalg.norm(br) < 1e-4


def test_stream_rejects_model_axis(problem):
    X, y, basis = problem
    cfg = MachineConfig(plan="stream", model_axis="model")
    with pytest.raises(ValueError, match="rows only"):
        KernelMachine(cfg).fit(X, y, basis)


def test_otf_shard_rff_solver(problem, config):
    """The validity matrix re-examination: rff composes with otf_shard via
    the exact linear-kernel reduction and matches rff under local."""
    X, y, _ = problem
    base = config.replace(solver="rff", rff_features=32)
    b_local = KernelMachine(base.replace(plan="local")).fit(X, y).state_["beta"]
    b_fused = KernelMachine(base.replace(plan="otf_shard")).fit(X, y).state_["beta"]
    assert np.max(np.abs(np.asarray(b_fused) - np.asarray(b_local))) < 5e-4


# -------------------------------------------- multiclass one-vs-rest (multi-RHS)
KCLS = 3


@pytest.fixture(scope="module")
def mc_problem():
    """K-class integer-label problem + its explicit ±1 one-vs-rest targets."""
    from repro.data import make_multiclass
    from repro.data.chunks import ovr_targets
    X, yi = make_multiclass(jax.random.PRNGKey(0), N, D, KCLS,
                            clusters_per_class=4)
    basis = random_basis(jax.random.PRNGKey(2), X, M)
    Y = ovr_targets(np.asarray(yi), np.arange(KCLS))
    return X, yi, Y, basis


@pytest.fixture(scope="module")
def mc_config(config):
    # lam high enough that every one-vs-rest column is well conditioned;
    # rtol 1e-5 is where the f32 one-vs-rest problems reliably terminate
    return config.replace(lam=8.0,
                          tron=TronConfig(max_iter=300, grad_rtol=1e-5))


@pytest.fixture(scope="module")
def mc_fits(mc_problem, mc_config):
    """One multi-RHS fit per registered plan on the SAME integer labels."""
    X, yi, _, basis = mc_problem
    out = {}
    for plan in available_plans():
        out[plan] = KernelMachine(mc_config.replace(plan=plan)).fit(X, yi,
                                                                    basis)
    return out


def test_multiclass_matrix_covers_registry(mc_fits, mc_problem):
    """Every plan fits integer labels as one (m, K) multi-RHS solve with
    classes in the state and label-space predictions."""
    X, yi, _, _ = mc_problem
    assert set(mc_fits) == set(available_plans())
    for plan, km in mc_fits.items():
        assert km.state_["beta"].shape == (M, KCLS), plan
        np.testing.assert_array_equal(np.asarray(km.state_["classes"]),
                                      np.arange(KCLS))
        o = km.decision_function(X[:16])
        assert o.shape == (16, KCLS), plan
        preds = np.asarray(km.predict(X))
        assert set(np.unique(preds)) <= set(range(KCLS)), plan
        assert km.score(X, yi) > 0.8, plan


def test_multiclass_plans_agree(mc_fits):
    """Pairwise beta agreement of the multi-RHS fits across the registry.

    Looser than the binary matrix (5e-4): the one-vs-rest hinge problems
    sit on wider f32 stagnation plateaus; the objective-level test below
    pins the tight equivalence."""
    betas = {p: np.asarray(km.state_["beta"]) for p, km in mc_fits.items()}
    scale = max(np.max(np.abs(b)) for b in betas.values())
    for p1, b1 in betas.items():
        for p2, b2 in betas.items():
            assert np.max(np.abs(b1 - b2)) / scale < 2e-3, (p1, p2)


@pytest.mark.parametrize("plan", ["stream", "otf_shard"])
def test_multiclass_matches_sequential_fits(plan, mc_problem, mc_config):
    """Acceptance: one multi-RHS fit == K sequential single-RHS fits, per
    column, at a matched iteration budget. The stream driver agrees to
    rounding at this budget (under 1e-6 of ||beta||) and is held to 1e-4.
    The traced driver reduces an (n, K) block where the solo run reduces
    (n,), so its rounding differs from the first evaluation on and grows
    along the trajectory; it is held to the f32 plateau tolerance
    (conftest.f32_plateau_rtol; by the 4th iteration both solves are where
    f32 no longer resolves the objective's decrease)."""
    X, yi, Y, basis = mc_problem
    cfg = mc_config.replace(plan=plan,
                            tron=TronConfig(max_iter=4, grad_rtol=1e-6))
    multi = np.asarray(KernelMachine(cfg).fit(X, yi, basis).state_["beta"])
    for k in range(KCLS):
        solo = KernelMachine(cfg).fit(X, jnp.asarray(Y[:, k]), basis)
        beta = np.asarray(solo.state_["beta"])
        rel = np.linalg.norm(multi[:, k] - beta) / np.linalg.norm(beta)
        if plan == "stream":
            assert rel < 1e-4, (plan, k, rel)
            continue
        rtol = f32_plateau_rtol(solo.result_.f, cfg.lam, basis,
                                cfg.kernel.sigma, beta)
        assert rel < rtol, (plan, k, rel, rtol)


def test_multiclass_objective_matches_sequential(mc_problem, mc_config,
                                                 mc_fits):
    """Full-convergence equivalence: each column of the multi-RHS solve
    reaches the same objective value as its standalone single-RHS fit
    (the per-column f is the invariant the plateau cannot blur)."""
    X, _, Y, basis = mc_problem
    f_multi = np.asarray(mc_fits["stream"].result_.tron.f)
    assert f_multi.shape == (KCLS,)
    for k in range(KCLS):
        km = KernelMachine(mc_config.replace(plan="stream")).fit(
            X, jnp.asarray(Y[:, k]), basis)
        f_solo = float(km.result_.f)
        assert abs(f_multi[k] - f_solo) / abs(f_solo) < 1e-5, (k, f_multi[k],
                                                              f_solo)


def test_multiclass_fused_memory_contract_k_aware(mc_problem):
    """No intermediate of the K=8 multi-RHS fused f/g/Hd bodies reaches
    n x m elements (fused_contract_limit guards that the bound still
    separates legal (n, K) blocks from the forbidden gram block)."""
    from repro.core.introspect import fused_contract_limit
    X, _, _, basis = mc_problem
    K = 8
    mesh = make_mesh((1,), ("data",))
    kern = KernelSpec("gaussian", sigma=2.0)
    Y8 = jnp.ones((N, K))
    beta = jnp.zeros((M, K))
    D8 = jnp.ones((N, K))
    fused = DistributedNystrom(
        mesh, 0.5, "squared_hinge", kern,
        DistConfig(materialize=False, fused=True))
    fg, hd = fused.make_fused_closures(X, Y8, basis)
    limit = fused_contract_limit(N, M, K)
    with mesh:
        assert_max_intermediate_below(fg, limit, beta)
        assert_max_intermediate_below(hd, limit, D8, beta)
    with pytest.raises(ValueError, match="vacuous"):
        fused_contract_limit(N, M, k=M)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_stream_multirhs_memory_contract(mc_problem, backend):
    """The stream chunk bodies keep the chunk_rows x m bound with K=8
    right-hand sides — including the cached-chunk path (the cache holds
    (chunk_rows, d) X blocks, which the walker sees as inputs, not
    intermediates; what matters is no gram chunk appears)."""
    from repro.core.introspect import fused_contract_limit
    X, yi, _, basis = mc_problem
    K = 8
    mesh = make_mesh((1,), ("data",))
    kern = KernelSpec("gaussian", sigma=2.0)
    solver = DistributedNystrom(
        mesh, 0.5, "squared_hinge", kern,
        DistConfig(materialize=False, fused=True, backend=backend))
    src = ArrayChunkSource(np.asarray(X), np.asarray(yi), CHUNK)
    sc = solver.make_stream_closures(src, np.asarray(basis),
                                     classes=np.arange(K))
    cr = sc.chunk_rows
    limit = fused_contract_limit(cr, M, K)
    Xc = jnp.zeros((cr, D))
    Yc = jnp.zeros((cr, K))
    wc = jnp.ones((cr,))
    beta = jnp.zeros((M, K))
    Dl = jnp.ones((cr, K))
    with mesh:
        assert_max_intermediate_below(sc.fg_chunk, limit, Xc, Yc, wc,
                                      jnp.asarray(basis), beta)
        assert_max_intermediate_below(sc.hd_chunk, limit, Xc, Dl,
                                      jnp.asarray(basis), beta)


# ------------------------------------------------- plan-aware inference
def _fitted_for(solver, problem, config):
    """One machine per solver, trained under its cheapest valid plan."""
    from repro.api import get_solver
    X, y, basis = problem
    cfg = config.replace(solver=solver, plan="local", rff_features=M,
                         ppack_epochs=1)
    entry = get_solver(solver)
    return KernelMachine(cfg).fit(X, y, basis if entry.needs_basis else None)


@pytest.mark.parametrize("solver", ["tron", "linearized", "rff", "ppacksvm"])
def test_decision_plan_matrix_parity(solver, problem, config):
    """Every registered (solver, plan) pair's decision_function matches the
    local dense reference at 1e-5 — including pairs whose TRAINING
    composition is invalid (linearized/ppacksvm are local-pinned solvers,
    but o(x) is one kmvp, valid under every decide arm)."""
    X, _, _ = problem
    km = _fitted_for(solver, problem, config)
    Xt = X[:100]                      # ragged vs chunk_rows AND mesh extent
    ref = np.asarray(km.decision_function(Xt, plan="local"))
    scale = max(np.max(np.abs(ref)), 1e-6)
    for plan in available_plans():
        o = np.asarray(km.decision_function(Xt, plan=plan))
        assert o.shape == ref.shape, (solver, plan)
        assert np.max(np.abs(o - ref)) / scale < 1e-5, (solver, plan)


def test_decision_unknown_plan_rejected(problem, config):
    km = _fitted_for("tron", problem, config)
    with pytest.raises(KeyError, match="unknown execution plan"):
        km.decision_function(problem[0][:8], plan="no_such_plan")


def test_multiclass_decision_plan_parity(mc_fits, mc_problem):
    """The (n, K) multi-RHS margin block survives every decide arm: same
    one-multi-RHS-evaluation margins, same argmax labels."""
    X, _, _, _ = mc_problem
    km = mc_fits["local"]
    ref = np.asarray(km.decision_function(X[:50], plan="local"))
    for plan in available_plans():
        o = np.asarray(km.decision_function(X[:50], plan=plan))
        assert o.shape == (50, KCLS), plan
        assert np.max(np.abs(o - ref)) / np.max(np.abs(ref)) < 1e-5, plan
        np.testing.assert_array_equal(
            np.asarray(km.predict(X[:50], plan=plan)),
            np.asarray(km.predict(X[:50], plan="local")))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fused_decision_never_materializes_test_gram(problem, config,
                                                     backend):
    """Inference keeps the training-side memory contract: no intermediate
    of the fused margin body reaches n x m elements; the dense local arm
    is the positive control proving the walker sees test grams."""
    from repro.api.infer import DecisionSpec, make_margin_body
    from repro.core.nystrom import gram as dense_gram
    X, _, basis = problem
    mesh = make_mesh((1,), ("data",))
    kern = KernelSpec("gaussian", sigma=2.0)
    beta = jnp.zeros((M,), X.dtype)
    spec = DecisionSpec(map_x=lambda x: x, basis=basis, beta=beta,
                        kernel=kern, backend=backend)
    body = make_margin_body(config, mesh, spec)
    with mesh:
        assert_max_intermediate_below(body, N * M, X, basis, beta)
    control = lambda Xq: dense_gram(Xq, basis, kern, "jnp") @ beta
    assert max_intermediate_elems(control, X) >= N * M


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_stream_decision_memory_contract(mc_problem, config, backend):
    """Acceptance: the stream decide arm's per-chunk body stays under
    chunk_rows x m elements with a K=8 multi-RHS beta block
    (fused_contract_limit guards the bound still separates)."""
    from repro.api.infer import DecisionSpec, make_stream_decider
    from repro.core.introspect import fused_contract_limit
    X, _, _, basis = mc_problem
    K = 8
    mesh = make_mesh((1,), ("data",))
    spec = DecisionSpec(map_x=lambda x: x, basis=jnp.asarray(basis),
                        beta=jnp.zeros((M, K)),
                        kernel=KernelSpec("gaussian", sigma=2.0),
                        backend=backend)
    src = ArrayChunkSource(np.asarray(X), np.zeros((N,), np.float32), CHUNK)
    sd = make_stream_decider(config, mesh, spec, src)
    cr = sd.chunk_rows
    shapes = (jax.ShapeDtypeStruct((cr, D), jnp.float32),
              jax.ShapeDtypeStruct((M, D), jnp.float32),
              jax.ShapeDtypeStruct((M, K), jnp.float32))
    with mesh:
        assert_max_intermediate_below(sd.o_chunk,
                                      fused_contract_limit(cr, M, K), *shapes)


@pytest.mark.parametrize("solver", ["rff", "linearized", "ppacksvm"])
def test_multiclass_rejected_by_binary_solvers(mc_problem, solver):
    """Integer multiclass labels route to tron's multi-RHS path; the
    binary-only solvers refuse them with a pointer instead of silently
    fitting garbage."""
    X, yi, _, basis = mc_problem
    cfg = MachineConfig(solver=solver, plan="local")
    with pytest.raises(ValueError, match="binary-only"):
        KernelMachine(cfg).fit(X, yi,
                               basis if solver == "linearized" else None)


# ----------------------------------------- multi-controller plan validation
def test_multihost_rejects_materializing_plans_at_construction():
    """Every plan outside MULTIHOST_PLANS must fail a multi-process
    topology check with a message that names the plan, says why, and
    lists the plans that DO work — at construction, not deep in a trace."""
    from repro.sharding import multihost
    bad = sorted(set(available_plans()) - multihost.MULTIHOST_PLANS)
    assert bad, "no materializing plans left to reject?"
    for plan in bad:
        with pytest.raises(ValueError) as ei:
            multihost.check_plan(plan, num_processes=2)
        msg = str(ei.value)
        assert plan in msg                      # names the offender
        assert "stream" in msg and "otf_shard" in msg   # names the fix
        assert "multi-controller" in msg        # names the context


def test_multihost_plans_accepted_and_single_process_unconstrained():
    from repro.sharding import multihost
    for plan in sorted(multihost.MULTIHOST_PLANS):
        multihost.check_plan(plan, num_processes=4)     # no raise
    for plan in available_plans():
        multihost.check_plan(plan, num_processes=1)     # no raise


def test_multihost_machine_construction_fails_under_live_topology():
    """With an active 2-process topology, KernelMachine construction
    itself (registry validate) rejects non-partitionable plans; the
    multihost-safe plans still construct."""
    from repro.sharding import multihost
    assert multihost.current_span() is None, "test leaked a topology"
    try:
        multihost._SPAN = multihost.HostSpan(0, 2)
        with pytest.raises(ValueError, match="multi-controller"):
            KernelMachine(MachineConfig(plan="shard_map"))
        KernelMachine(MachineConfig(plan="stream"))      # constructs fine
        KernelMachine(MachineConfig(plan="otf_shard"))
    finally:
        multihost._reset_for_tests()


def test_multihost_span_and_mesh_validation():
    from types import SimpleNamespace
    from repro.sharding import multihost
    with pytest.raises(ValueError, match="out of range"):
        multihost.HostSpan(process_id=2, num_processes=2)
    with pytest.raises(ValueError, match="num_processes"):
        multihost.HostSpan(process_id=0, num_processes=0)
    # a mesh that does not cover the global device list is rejected with
    # a pointer at spanning_mesh (stub: check_mesh_spans reads .size only)
    with pytest.raises(ValueError, match="spanning_mesh"):
        multihost.check_mesh_spans(
            SimpleNamespace(size=jax.device_count() + 1), num_processes=2)
    multihost.check_mesh_spans(SimpleNamespace(size=1), num_processes=1)

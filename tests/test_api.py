"""Unified KernelMachine API: registries, parity with legacy entrypoints,
save/load round-trips, stage-wise partial_fit."""
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.api import (KernelMachine, MachineConfig, available_plans,
                       available_solvers, get_solver, valid_combinations,
                       validate)
from repro.core import KernelSpec, TronConfig, get_loss, random_basis
from repro.data import make_classification

KERN = KernelSpec("gaussian", sigma=2.0)
CFG = MachineConfig(kernel=KERN, lam=0.5, tron=TronConfig(max_iter=60),
                    rff_features=64)


@pytest.fixture(scope="module")
def data():
    X_all, y_all = make_classification(jax.random.PRNGKey(0), 1280, 12,
                                       clusters_per_class=4, margin=1.0)
    return X_all[:1024], y_all[:1024], X_all[1024:], y_all[1024:]


@pytest.fixture(scope="module")
def basis(data):
    return random_basis(jax.random.PRNGKey(1), data[0], 64)


# ---------------------------------------------------------------- registries
def test_registries_populated():
    assert set(available_solvers()) == {"tron", "linearized", "rff",
                                        "ppacksvm"}
    assert set(available_plans()) == {"local", "shard_map", "auto", "otf",
                                      "otf_shard", "stream"}


def test_invalid_composition_raises_at_construction():
    with pytest.raises(ValueError, match="does not support execution plan"):
        KernelMachine(CFG.replace(solver="ppacksvm", plan="shard_map"))
    with pytest.raises(KeyError, match="unknown solver"):
        validate("no_such_solver", "local")
    with pytest.raises(KeyError, match="unknown execution plan"):
        validate("tron", "no_such_plan")


@pytest.mark.parametrize("solver,plan", valid_combinations())
def test_every_valid_combination_trains(data, basis, solver, plan):
    """Registry round-trip: every solver x valid plan fits synthetic data.

    rff draws 256 features here, not CFG's 64: over eight seeds 64 random
    features score 0.904 +- 0.031 on this split (seed 0: 0.844), so a
    fixed 0.85 bar tested the draw; 256 score 0.978 +- 0.010. The other
    solvers ignore rff_features."""
    X, y, Xt, yt = data
    km = KernelMachine(CFG.replace(solver=solver, plan=plan,
                                   rff_features=256))
    km.fit(X, y, basis if get_solver(solver).needs_basis else None)
    assert km.result_.solver == solver and km.result_.plan == plan
    assert km.score(Xt, yt) > 0.85
    assert km.decision_function(Xt).shape == (Xt.shape[0],)


# ------------------------------------------------------------ legacy parity
def test_fit_matches_legacy_solve_every_solver(data, basis):
    """beta parity vs the pre-API entrypoints at 1e-5."""
    X, y, _, _ = data
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from repro.core import solve
        from repro.core.rff import solve_rff
    from repro.core.linearized import solve_linearized
    from repro.core import ppacksvm as pps

    km = KernelMachine(CFG).fit(X, y, basis)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        mach = solve(X, y, basis, lam=0.5, kernel=KERN,
                     cfg=TronConfig(max_iter=60))
    assert float(jnp.max(jnp.abs(km.state_["beta"] - mach.beta))) < 1e-5

    km = KernelMachine(CFG.replace(solver="rff", seed=3)).fit(X, y)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        rff = solve_rff(jax.random.PRNGKey(3), X, y, 64, lam=0.5, sigma=2.0,
                        cfg=TronConfig(max_iter=60))
    assert float(jnp.max(jnp.abs(km.state_["beta"] - rff.w))) < 1e-5

    km = KernelMachine(CFG.replace(solver="linearized")).fit(X, y, basis)
    res = solve_linearized(X, y, basis, lam=0.5, loss=get_loss("squared_hinge"),
                           kernel=KERN, cfg=TronConfig(max_iter=60))
    assert float(jnp.max(jnp.abs(km.state_["beta"] - res.beta))) < 1e-5

    km = KernelMachine(CFG.replace(solver="ppacksvm", seed=5)).fit(X, y)
    res = pps.ppacksvm(jax.random.PRNGKey(5), X, y, lam=0.5, kernel=KERN,
                       epochs=1, pack_size=64)
    assert float(jnp.max(jnp.abs(km.state_["beta"] - res.alpha))) < 1e-5


@pytest.mark.parametrize("plan", available_plans())
def test_same_fit_call_under_every_plan(data, basis, plan):
    """Acceptance: identical call site, plan swapped by config only."""
    X, y, _, _ = data
    km_ref = KernelMachine(CFG).fit(X, y, basis)
    km = KernelMachine(CFG.replace(plan=plan)).fit(X, y, basis)
    # same optimum: objective match tight, beta match loose (otf recomputes
    # gram tiles in a different association order)
    assert abs(km.result_.f - km_ref.result_.f) / abs(km_ref.result_.f) < 1e-4
    assert float(jnp.max(jnp.abs(km.state_["beta"] -
                                 km_ref.state_["beta"]))) < 1e-2


@pytest.mark.parametrize("plan", ["local", "shard_map", "otf_shard"])
def test_fit_records_one_machine_fit_with_estimator_children(data, basis,
                                                             plan):
    X, y, _, _ = data
    km = KernelMachine(CFG.replace(plan=plan, tron=TronConfig(max_iter=3)))
    t0 = obs.clock()
    km.fit(X, y, basis)
    km.fit(X, y, basis)
    t1 = obs.clock()
    fits = obs.spans("machine.fit", t0, t1)
    solve = obs.spans("estimator.solve", t0, t1)
    wait = obs.spans("estimator.wait", t0, t1)
    assert len(fits) == len(solve) == len(wait) == 2
    assert list(solve["parent"]) == list(wait["parent"]) == list(fits["id"])
    for f, s, w in zip(fits, solve, wait):
        assert f["start"] <= s["start"] <= s["end"] <= w["start"] \
            <= w["end"] <= f["end"]


# ---------------------------------------------------------------- save/load
@pytest.mark.parametrize("solver", ["tron", "linearized", "rff", "ppacksvm"])
def test_save_load_identical_decisions(tmp_path, data, basis, solver):
    X, y, Xt, _ = data
    km = KernelMachine(CFG.replace(solver=solver)).fit(
        X, y, basis if get_solver(solver).needs_basis else None)
    path = str(tmp_path / f"{solver}.npz")
    km.save(path)
    km2 = KernelMachine.load(path)
    assert km2.config == km.config
    o1, o2 = km.decision_function(Xt), km2.decision_function(Xt)
    assert float(jnp.max(jnp.abs(o1 - o2))) == 0.0


def test_load_rejects_foreign_checkpoint(tmp_path):
    from repro.checkpoint import save_checkpoint
    path = str(tmp_path / "foreign.npz")
    save_checkpoint(path, {"w": jnp.ones((3,))}, metadata={"other": 1})
    with pytest.raises(ValueError, match="not a KernelMachine checkpoint"):
        KernelMachine.load(path)


# --------------------------------------------------------------- partial_fit
def test_partial_fit_matches_one_shot(data, basis):
    """Stage-wise growth reaches the one-shot optimum (paper §3)."""
    X, y, _, _ = data
    cfg = CFG.replace(tron=TronConfig(max_iter=80, grad_rtol=1e-4))
    km = KernelMachine(cfg)
    km.partial_fit(X, y, basis[:16]).partial_fit(X, y, basis[16:40])
    km.partial_fit(X, y, basis[40:])
    ref = KernelMachine(cfg).fit(X, y, basis)
    assert [r.m for r in km.history_] == [16, 40, 64]
    fs = [r.f for r in km.history_]
    assert fs[0] >= fs[1] >= fs[2]          # objective falls as basis grows
    assert abs(fs[-1] - ref.result_.f) / abs(ref.result_.f) < 1e-2
    assert km.state_["beta"].shape == (64,)


def test_partial_fit_after_fit_grows_basis(data, basis):
    X, y, _, _ = data
    km = KernelMachine(CFG).fit(X, y, basis[:32])
    km.partial_fit(X, y, basis[32:])
    assert km.state_["basis"].shape == basis.shape
    assert len(km.history_) == 2


def test_partial_fit_detects_swapped_same_shape_data(data, basis):
    """Regression: the local-plan (C, W) growth cache used to be keyed on
    X.shape alone, so growing a basis after swapping X for *different*
    data of the same shape silently reused stale kernel columns. The cache
    is now keyed on a sampled-checksum fingerprint: the grown machine must
    land on the optimum of the data it actually saw."""
    X, y, _, _ = data
    # a different dataset of the SAME shape (fresh draw, same generator)
    from repro.data import make_classification
    X2_all, y2_all = make_classification(jax.random.PRNGKey(7), 1280, 12,
                                         clusters_per_class=4, margin=1.0)
    X2, y2 = X2_all[:1024], y2_all[:1024]
    assert X2.shape == X.shape

    cfg = CFG.replace(tron=TronConfig(max_iter=120, grad_rtol=1e-5))
    km = KernelMachine(cfg)
    km.partial_fit(X, y, basis[:32])      # builds the (C, W) cache on X
    km.partial_fit(X2, y2, basis[32:])    # swapped data: must rebuild

    # reference: the identical call sequence with the cache force-cleared
    ref = KernelMachine(cfg)
    ref.partial_fit(X, y, basis[:32])
    ref._cw = ref._cw_key = None
    ref.partial_fit(X2, y2, basis[32:])
    assert float(jnp.max(jnp.abs(km.state_["beta"] -
                                 ref.state_["beta"]))) == 0.0
    assert km.result_.f == ref.result_.f

    # and the fast path still holds: growing on the SAME data reuses the
    # cache — the old basis columns of C are never rebuilt
    import repro.api.machine as machine_mod
    km2 = KernelMachine(cfg)
    km2.partial_fit(X, y, basis[:32])
    orig_build_C, rebuilds = machine_mod.build_C, []
    machine_mod.build_C = lambda *a, **k: (rebuilds.append(1),
                                           orig_build_C(*a, **k))[1]
    try:
        km2.partial_fit(X, y, basis[32:40])
    finally:
        machine_mod.build_C = orig_build_C
    assert not rebuilds                       # cache hit: no full C rebuild
    assert km2._cw[0].shape == (1024, 40)     # grew FROM the cached block


def test_partial_fit_rejected_for_non_growing_solver(data):
    X, y, _, _ = data
    km = KernelMachine(CFG.replace(solver="ppacksvm"))
    with pytest.raises(ValueError, match="stage-wise"):
        km.partial_fit(X, y, X[:8])


def test_stagewise_shim_accepts_loss_string(data, basis):
    """The satellite fix: stagewise accepts loss by name like everyone else."""
    X, y, _, _ = data
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from repro.core.stagewise import stagewise_solve
        results = stagewise_solve(X, y, [basis[:32], basis[32:]], lam=0.5,
                                  loss="squared_hinge", kernel=KERN,
                                  cfg=TronConfig(max_iter=40))
    assert [r.m for r in results] == [32, 64]
    assert results[0].f >= results[1].f


def test_solve_shim_accepts_custom_loss_object(data, basis):
    """Legacy solve() took ANY Loss object; the shim must keep that working
    by auto-registering it for the name-keyed config."""
    from repro.core.losses import SQUARED, Loss
    X, y, _, _ = data
    custom = Loss("custom_squared_for_test", SQUARED.value, SQUARED.grad,
                  SQUARED.diag)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from repro.core import solve
        mach = solve(X, y, basis, lam=0.5, loss=custom, kernel=KERN,
                     cfg=TronConfig(max_iter=30))
    assert mach.beta.shape == (64,)


# ------------------------------------------------------------------- config
def test_config_json_round_trip():
    cfg = CFG.replace(solver="rff", plan="auto", model_axis="model",
                      linearized_rank=16)
    import json
    back = MachineConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert back == cfg


def test_unknown_loss_rejected_at_config_time():
    with pytest.raises(KeyError, match="unknown loss"):
        MachineConfig(loss="hinge3")


def test_unfitted_machine_raises():
    km = KernelMachine(CFG)
    with pytest.raises(RuntimeError, match="not fitted"):
        km.decision_function(jnp.zeros((2, 12)))

"""Infrastructure tests: optimizer, checkpoint, microbatching, sharding rules."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs import ARCHS
from repro.models.common import unzip
from repro.models.registry import make_model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.sgd import sgd_init, sgd_update
from repro.sharding.partitioning import DEFAULT_RULES, FSDP, spec_for_axes
from repro.train.steps import make_train_step


def test_adamw_matches_reference_scalar():
    """One AdamW step against the textbook update."""
    cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0)
    p = {"w": jnp.array([2.0])}
    g = {"w": jnp.array([0.5])}
    st = adamw_init(p, cfg)
    p2, st2 = adamw_update(g, st, p, cfg)
    m = 0.1 * 0.5
    v = 0.01 * 0.25
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.99)
    want = 2.0 - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(p2["w"], want, rtol=1e-6)
    assert int(st2["step"]) == 1


def test_adamw_bf16_state_dtype():
    cfg = AdamWConfig(state_dtype="bfloat16")
    p = {"w": jnp.ones((4,), jnp.bfloat16)}
    st = adamw_init(p, cfg)
    assert st["m"]["w"].dtype == jnp.bfloat16
    p2, st2 = adamw_update({"w": jnp.ones((4,), jnp.bfloat16)}, st, p, cfg)
    assert st2["v"]["w"].dtype == jnp.bfloat16
    assert p2["w"].dtype == jnp.bfloat16


def test_sgd_descends_quadratic():
    p = {"w": jnp.array(4.0)}
    st = sgd_init(p)
    for _ in range(200):
        g = {"w": 2 * p["w"]}
        p, st = sgd_update(g, st, p, lr=0.02, momentum=0.5)
    assert abs(float(p["w"])) < 0.1


def test_checkpoint_roundtrip():
    cfg = ARCHS["tinyllama-1.1b"].reduced()
    model = make_model(cfg)
    params, _ = unzip(model.init(jax.random.PRNGKey(0)))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.npz")
        save_checkpoint(path, params, {"arch": cfg.name})
        loaded = load_checkpoint(path, params)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), params, loaded)


def test_microbatched_train_step_matches_full_batch():
    """Gradient accumulation over 4 microbatches == one full-batch step."""
    cfg = ARCHS["tinyllama-1.1b"].reduced(dtype="float32")
    model = make_model(cfg)
    params, _ = unzip(model.init(jax.random.PRNGKey(0)))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                          cfg.vocab),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0,
                                          cfg.vocab)}
    # A first AdamW step moves each weight by lr*g/(|g|+eps), whose slope
    # in g is lr/eps. Accumulating over microbatches reorders the f32 sums,
    # moving g by up to ~eps_f32*max|g| (4e-9 here); with the default
    # eps=1e-8 a weight whose gradient is itself rounding-sized (3e-9 in
    # ffn w2) then moves by 2e-4, which compares rounding, not the
    # accumulation. eps=1e-6 bounds that term by lr*4e-9/1e-6 = 4e-6.
    ocfg = AdamWConfig(lr=1e-3, eps=1e-6)
    opt = adamw_init(params, ocfg)
    p1, _, m1 = jax.jit(make_train_step(model, ocfg))(params, opt, batch)
    p4, _, m4 = jax.jit(make_train_step(model, ocfg, microbatches=4))(
        params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-4)
    diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p4)
    assert max(jax.tree.leaves(diffs)) < 1e-4


def test_sharding_rules_cover_all_model_axes():
    """Every logical axis used by any arch's params has a rule."""
    for name, cfg in ARCHS.items():
        model = make_model(cfg.reduced())
        ann = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        _, axes = unzip(ann)
        for t in jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)):
            for ax in t:
                assert ax in DEFAULT_RULES, f"{name}: unknown axis {ax!r}"


def test_spec_for_axes_fsdp_resolution():
    from repro.core.compat import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    spec = spec_for_axes(("embed", "ffn"), mesh)
    assert spec == jax.sharding.PartitionSpec(("data",), "model")


def test_sharded_loader_and_kernel_dataset():
    from repro.data.pipeline import ShardedLoader, shard_kernel_dataset, synthetic_lm_loader
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((1, 1), ("data", "model"))
    cfg = ARCHS["tinyllama-1.1b"].reduced()
    loader = synthetic_lm_loader(mesh, cfg, batch=2, seq=16)
    it = iter(loader)
    b1 = next(it)
    b2 = next(it)
    assert b1["tokens"].shape == (2, 16)
    assert not bool(jnp.all(b1["tokens"] == b2["tokens"]))  # streams differ
    # kernel dataset sharding truncates to divisible rows
    X = jnp.ones((10, 4)); y = jnp.ones((10,))
    Xs, ys = shard_kernel_dataset(mesh, X, y)
    assert Xs.shape[0] == 10 and ys.shape == (10,)

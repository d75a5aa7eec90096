"""Exercises the dry-run lowering path at small scale in a subprocess
(8 fake devices, reduced configs) — validates shardings/lowering machinery
without the 512-device production compile (run via repro.launch.dryrun)."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow,  # 8-fake-device subprocess, min. of compiles
              pytest.mark.requires_devices(8)]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import ARCHS
from repro.models.common import unzip
from repro.models.config import ShapeSpec
from repro.models.registry import cache_specs, input_specs, make_model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.sharding.ctx import use_shard_hints
from repro.sharding.partitioning import batch_specs, cache_pspecs, param_specs
from repro.train.steps import make_serve_step, make_train_step

from repro.core.compat import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
out = {}
for name in ("tinyllama-1.1b", "mamba2-1.3b", "grok-1-314b",
             "deepseek-v2-236b", "whisper-small"):
    cfg = ARCHS[name].reduced(vocab=256)
    model = make_model(cfg, max_dec_seq=64)
    ann = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sds, axes = unzip(ann)
    p_specs = param_specs(axes, mesh)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                           is_leaf=lambda x: isinstance(x, P))
    shape = ShapeSpec("t", 96 if cfg.is_encdec else 32, 8, "train")
    batch_sds = input_specs(cfg, shape)
    b_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           batch_specs(batch_sds, mesh),
                           is_leaf=lambda x: isinstance(x, P))
    ocfg = AdamWConfig()
    opt_sds = jax.eval_shape(lambda p: adamw_init(p, ocfg), params_sds)
    opt_shard = {"m": p_shard, "v": p_shard, "step": NamedSharding(mesh, P())}
    step = make_train_step(model, ocfg, microbatches=2)
    with mesh, use_shard_hints(mesh):
        lowered = jax.jit(step, in_shardings=(p_shard, opt_shard, b_shard),
                          out_shardings=(p_shard, opt_shard, None),
                          donate_argnums=(0, 1)).lower(
            params_sds, opt_sds, batch_sds)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    # decode path
    dshape = ShapeSpec("d", 64, 8, "decode")
    cache_sds = cache_specs(cfg, dshape)
    c_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           cache_pspecs(cache_sds, mesh),
                           is_leaf=lambda x: isinstance(x, P))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    serve = make_serve_step(model)
    with mesh, use_shard_hints(mesh):
        lc = jax.jit(serve,
                     in_shardings=(p_shard, NamedSharding(mesh, P(("data",), None)), c_shard),
                     out_shardings=(None, None, c_shard),
                     donate_argnums=(2,)).lower(params_sds, tok, cache_sds)
        cc = lc.compile()
    out[name] = {"train_flops": float(cost.get("flops", 0)),
                 "decode_ok": True}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", ["tinyllama-1.1b", "mamba2-1.3b",
                                  "grok-1-314b", "deepseek-v2-236b",
                                  "whisper-small"])
def test_lowering_compiles_on_mesh(results, name):
    assert results[name]["decode_ok"]
    assert results[name]["train_flops"] > 0

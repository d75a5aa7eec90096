"""chip_smoke.py's phases, rehearsed on the CPU at a tiny size.

The script refuses to run without a TPU, so these tests call its phase
functions directly: the one-chip train -> reference -> serve sequence in
this process (Pallas kernels in interpret mode), and the four-chip mesh
comparison on four virtual CPU devices in a subprocess. They prove the
control flow and the checks, not anything about the chip.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def test_refuses_without_a_tpu():
    """No TPU: a non-zero exit that says so, and no result line."""
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


CACHE_PROBE = """
import json, os, sys
import jax, jax.numpy as jnp
from repro.launch.cli import REPO_CACHE_DIR, enable_compile_cache
got = enable_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 2.0 + 1.0)(jnp.arange(3.0)).block_until_ready()
print(json.dumps({"dir": got, "repo_dir": str(REPO_CACHE_DIR)}))
"""


def _cache_probe(mode, cache_dir=None, enable="true"):
    env = _env(JAX_ENABLE_COMPILATION_CACHE=enable)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    proc = subprocess.run([sys.executable, "-c", CACHE_PROBE, mode],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_placement(tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the one place entries land;
    unset, the cache is the repo's fixed .jax_cache (only placed here,
    nothing compiled into it); the suite's JAX_ENABLE_COMPILATION_CACHE=
    false keeps it off."""
    repo = ROOT / ".jax_cache"

    def listing():
        return sorted(os.listdir(repo)) if repo.exists() else None

    before = listing()
    got = _cache_probe("compile", cache_dir=tmp_path)
    assert got["dir"] == str(tmp_path) and os.listdir(tmp_path)
    assert listing() == before
    got = _cache_probe("place")
    assert got["dir"] == got["repo_dir"] == str(repo)
    assert _cache_probe("place", enable="false")["dir"] is None


def test_one_chip_phases(tmp_path, capsys):
    chip_smoke.one_chip(tmp_path, scale=0.002, m=64, rows=64, clients=2,
                        requests=4, max_batch=16, tpu=False)
    out = capsys.readouterr().out
    assert "[check] ok: checkpoint serves on plan=otf_shard " \
           "backend=pallas" in out
    assert "[tron ] f per iteration:" in out
    assert "FAILED" not in out


def test_check_failure_is_an_error():
    with pytest.raises(chip_smoke.Check, match="margins"):
        chip_smoke.check(False, "margins within 1e-4")


def test_rel_err_and_float64_gram():
    x = np.array([[0.0, 0.0], [1.0, 0.0]], np.float32)
    np.testing.assert_allclose(chip_smoke.gram64(x, x, 1.0),
                               [[1.0, np.exp(-0.5)], [np.exp(-0.5), 1.0]])
    assert chip_smoke.rel_err([1.0, 2.1], [1.0, 2.0]) == pytest.approx(0.05)


def test_four_chip_phases_on_virtual_devices():
    """The --chips 4 comparison on four virtual CPU devices: both mesh
    plans agree with one device and every check passes."""
    code = ("import chip_smoke, json; chip_smoke.four_chips(scale=0.002, "
            "m=64); print(json.dumps({'done': True}))")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=str(ROOT), capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {"done": True}
    for plan in ("otf_shard", "shard_map"):
        assert f"[check] ok: {plan} f/g/Hd on 4 devices" in proc.stdout
        assert f"[check] ok: {plan} TRON objective on 4 devices" \
            in proc.stdout

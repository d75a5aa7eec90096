"""bench/run.py as the driver calls it: without a chip it exits non-zero
and prints no result, and it needs the program beside it."""
import json
import os
import shutil
import subprocess
import sys

import bench_tiny

REPO = bench_tiny.REPO
ARGS = ["--workload", "covtype_otf.fit", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_exits_nonzero_without_a_tpu(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    p = _run(REPO, env)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "needs 1 TPU chip" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".runs", ".jax_cache",
                                                  "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)

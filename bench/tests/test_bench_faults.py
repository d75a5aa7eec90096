"""A whole run on the CPU, with the chip check skipped and the timed path
broken underneath, must come out not correct; and correct when sound."""
import json
import os
import subprocess
import sys

import bench_tiny
import pytest

from bench import faults


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload,fault", [
    ("covtype_otf.fit", None),
    ("covtype_otf.fit", "unchanged"),
    ("covtype_otf.fit", "half_batch"),
    ("covtype_otf.serve_poisson", None),
    ("covtype_otf.serve_poisson", "altered_answer"),
    ("covtype_otf.serve_poisson", "half_served"),
])
def test_fault_is_caught(root, capsys, workload, fault):
    if fault is None:
        res = bench_tiny.run(root, workload, capsys=capsys)
    else:
        with faults.ALL[fault]():
            res = bench_tiny.run(root, workload, capsys=capsys)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


_FOUR = """
import json, sys
sys.path[:0] = [{tests!r}]
import bench_tiny
from bench import faults
root = bench_tiny.make({root!r}, four_chips=True)
from bench import run
for fault in (None, "no_exchange", "half_batch"):
    import contextlib
    ctx = faults.ALL[fault]() if fault else contextlib.nullcontext()
    with ctx:
        rc = run.run_cell("covtype_shardmap4.fit", 2 ** 31 + 11, 1.0, False,
                          root=root, require_tpu=False)
    assert rc == 0
"""


def test_four_chip_faults_are_caught(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR.format(tests=os.path.dirname(__file__),
                        root=str(tmp_path / "bench"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert [r["correct"] for r in lines] == [True, False, False]
    assert all(r["device"]["count"] == 4 for r in lines)

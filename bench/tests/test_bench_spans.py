"""The readers of the program's spans (``"source": "program_span"``) on a
tiny CPU cell: finite where the cell records their spans, None where it
records none or where the program has no recorder."""
import dataclasses
import json
import math
import os
import subprocess
import sys

import bench_tiny
import pytest

from bench import spec

READERS = ("serve.queue_p50_ms", "serve.dispatch_p50_ms",
           "infer.decide_p50_ms", "estimator.host_ms_per_fit")
SERVE = set(READERS[:3])
FIT = {"estimator.host_ms_per_fit"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make(tmp_path_factory.mktemp("bench"))


def _with_readers(monkeypatch, root):
    """Let an untraced run report the four readers beside its end-to-end
    metrics, whatever their ``workloads`` lists say."""
    load = spec.load_cell
    extra = tuple(spec.Metric(name, "ms", spec._reader(
        root / "bench" / "layers" / f"{name}.py")) for name in READERS)

    def load_cell(workload, root=spec.ROOT):
        cell = load(workload, root)
        return dataclasses.replace(cell, end_to_end=cell.end_to_end + extra)

    monkeypatch.setattr(spec, "load_cell", load_cell)


@pytest.mark.parametrize("workload,present", [
    ("covtype_otf.fit", FIT), ("covtype_otf.serve_poisson", SERVE)])
def test_readers_on_a_tiny_cell(root, capsys, monkeypatch, workload,
                                present):
    _with_readers(monkeypatch, root)
    res = bench_tiny.run(root, workload, capsys=capsys)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items() if k in READERS}
    assert set(got) == present, got
    assert all(math.isfinite(v) and v > 0 for v in got.values()), got


def test_readers_give_none_without_spans_or_recorder(root, monkeypatch):
    import repro
    from repro import obs
    readers = {n: spec._reader(root / "bench" / "layers" / f"{n}.py")
               for n in READERS}
    for name in ("serve.queue", "serve.dispatch", "infer.decide",
                 "estimator.solve"):
        obs.record(name, 1.0, 1.25, obs.next_id())
    rec = {"window": {"start": 1.0, "end": 2.0},
           "fits": [{"t0": 1.0, "t1": 2.0}], "serve": {}}
    assert all(r(rec) >= 250.0 for r in readers.values())
    outside = dict(rec, window={"start": 1.1, "end": 2.0})
    assert {n: r(outside) for n, r in readers.items()} == dict.fromkeys(
        READERS)
    monkeypatch.delattr(repro, "obs")                # a program without
    monkeypatch.setitem(sys.modules, "repro.obs", None)   # the recorder
    assert {n: r(rec) for n, r in readers.items()} == dict.fromkeys(READERS)



def test_readers_give_none_where_the_ring_lost_window_records(
        root, monkeypatch):
    """A ring that overwrote records the window may have held gives None;
    one whose oldest kept record ended before the window still reads."""
    from repro import obs
    small = obs.Recorder(capacity=4)
    monkeypatch.setattr(obs, "spans", small.spans)
    monkeypatch.setattr(obs, "dropped", small.dropped)
    read = spec._reader(root / "bench" / "layers" / "serve.queue_p50_ms.py")
    for i in range(6):                       # 0.0-0.5 ... 5.0-5.5
        small.record("serve.queue", float(i), i + 0.5, i + 1)
    assert small.dropped("serve.queue") == 2
    assert read({"window": {"start": 3.0, "end": 6.0}}) == 500.0
    assert read({"window": {"start": 2.0, "end": 6.0}}) is None
    assert read({"window": {"start": 0.0, "end": 6.0}}) is None


_FOUR = """
import dataclasses, json, sys
sys.path[:0] = [{tests!r}]
import bench_tiny
from bench import run, spec
root = bench_tiny.make({root!r}, four_chips=True)
load = spec.load_cell
def load_cell(workload, root=spec.ROOT):
    cell = load(workload, root)
    return dataclasses.replace(cell,
                               end_to_end=cell.end_to_end + cell.per_layer)
spec.load_cell = load_cell
rc = run.run_cell("covtype_shardmap4.fit", 2 ** 31 + 13, 1.0, False,
                  root=root, require_tpu=False)
assert rc == 0
"""


def test_estimator_reader_on_the_four_chip_cell(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR.format(tests=os.path.dirname(__file__),
                        root=str(tmp_path / "bench"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["device"]["count"] == 4
    v = res["metrics"]["estimator.host_ms_per_fit"]["value"]
    assert math.isfinite(v) and v > 0
    assert not SERVE & set(res["metrics"])

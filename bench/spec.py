"""Finds a cell's parts by name, so that a later change adds files and
edits none.

    BENCHMARK.json                 the cells, metrics and configurations
    bench/configs/<config>.json    a deployment (the file BENCHMARK.json names)
    bench/traffic/<traffic>.json   a traffic mix, read by ``bench.loadgen``
    bench/limits/<workload>.json   the limits of the numbers ``correct`` compares
    bench/metrics/<metric>.py      reader of an end-to-end metric
    bench/layers/<metric>.py       reader of a per-layer metric

A reader module defines ``read(record) -> float | None``; it returns None
where its cell gives it nothing to read, and the harness then leaves the
metric out.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable[[dict], Optional[float]]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reader(path: Path) -> Callable[[dict], Optional[float]]:
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric: {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_reader_{path.parent.name}_{path.stem.replace('.', '_')}",
        path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` in ``root``/BENCHMARK.json, with every
    file it needs read."""
    root = Path(root)
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = configs[w["config"]]
    e2e = tuple(Metric(m["name"], m["unit"],
                       _reader(root / "bench" / "metrics" / f"{m['name']}.py"))
                for m in bench["end_to_end"] if _reports(m, workload))
    layers = tuple(Metric(m["name"], m["unit"],
                          _reader(root / "bench" / "layers"
                                  / f"{m['name']}.py"))
                   for m in bench["per_layer"] if _reports(m, workload))
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=_json(root / cfg["file"]),
                traffic_name=w["traffic"],
                traffic=_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                limits=_json(root / "bench" / "limits" / f"{workload}.json"),
                end_to_end=e2e, per_layer=layers)

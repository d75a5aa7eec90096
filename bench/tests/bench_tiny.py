"""A copy of the benchmark at a size the CPU runs in seconds, for tests.

``make(dst)`` writes a root like the repository's (BENCHMARK.json and
bench/{configs,traffic,limits,metrics,layers}) with every configuration cut
to ``n`` rows and an ``m``-point basis (widths kept), the open-loop mixes
cut to ``rate`` requests per second, and the limits of ``LIMITS``.

The limits are read at this size on the CPU (14 seeds, program against
the reference and the three-pass bfloat16 control), not on the chip: the
gaps of sound runs depend on the size. Program, largest: loss_gap 6.2e-8,
grad_gap 1.2e-3, change_gap 3.5e-6, margin_gap 0 (bitwise). Control,
smallest: loss_gap 0, grad_gap 1.2e-4, change_gap 8.1e-5, margin_gap
8.5e-6. So change_gap and margin_gap separate the control here; loss_gap
and grad_gap are held only against the faults.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)


LIMITS = {"fit": {"loss_gap": 1e-6, "grad_gap": 5e-3, "change_gap": 1.5e-5},
          "serve": {"margin_gap": 2e-6, "unanswered": 0}}


FOUR = {"config": {"name": "covtype_shardmap4_m16k",
                   "source": "https://arxiv.org/abs/1405.4543",
                   "file": "bench/configs/covtype_shardmap4_m16k.json",
                   "reduced": ["n"], "why": "materialized C over 4 chips"},
        "workload": {"name": "covtype_shardmap4.fit",
                     "config": "covtype_shardmap4_m16k",
                     "traffic": "fit_repeat", "chips": 4,
                     "why": "the psum between chips"}}


def make(dst, *, n: int = 4096, m: int = 128, rate: float = 200.0,
         backend: str = "jnp", four_chips: bool = False) -> Path:
    """``four_chips`` adds the shard_map cell on a (4,) mesh where
    BENCHMARK.json does not list it (its configuration is in bench/configs)."""
    dst = Path(dst)
    (dst / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    for sub in ("traffic", "metrics", "layers", "limits"):
        shutil.copytree(REPO / "bench" / sub, dst / "bench" / sub,
                        dirs_exist_ok=True)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    if four_chips and FOUR["workload"]["name"] not in {
            w["name"] for w in bench["workloads"]}:
        bench["configs"].append(FOUR["config"])
        bench["workloads"].append(FOUR["workload"])
    for c in bench["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        cfg.update(n=n, n_test=256, m=m,
                   backend="jnp" if cfg["chips"] > 1 else backend)
        (dst / c["file"]).write_text(json.dumps(cfg))
    for f in (dst / "bench" / "traffic").glob("*.json"):
        tr = json.loads(f.read_text())
        if tr["kind"] == "open_loop":
            tr["rate_profile"] = [[1.0, rate]]
            f.write_text(json.dumps(tr))
    for w in bench["workloads"]:
        kind = "fit" if w["name"].endswith(".fit") else "serve"
        (dst / "bench" / "limits" / f"{w['name']}.json").write_text(
            json.dumps(LIMITS[kind]))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def run(root, workload: str, seed: int = 2 ** 31 + 7, seconds: float = 1.0,
        capsys=None) -> dict:
    """One run of ``workload`` in ``root`` on the CPU; its result line."""
    from bench import run as runmod
    rc = runmod.run_cell(workload, seed, seconds, False, root=root,
                         require_tpu=False)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])

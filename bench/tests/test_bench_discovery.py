"""A configuration, a traffic mix and a metric are found by name: a new
cell needs new files and no edit to any file that is there."""
import json

import bench_tiny
import pytest

from bench import spec


def test_load_cell_reads_every_part(tmp_path):
    root = bench_tiny.make(tmp_path)
    cell = spec.load_cell("covtype_otf.fit", root)
    assert cell.chips == 1 and cell.config_name == "covtype_otf_m16k"
    assert cell.traffic == {"kind": "fit_repeat"}
    assert set(cell.limits) == {"loss_gap", "grad_gap", "change_gap"}
    assert {m.name for m in cell.end_to_end} == {"fit_s", "setup_s"}
    assert "kmvp_roofline" in {m.name for m in cell.per_layer}
    assert "serve.occupancy_pct" not in {m.name for m in cell.per_layer}


def test_new_config_traffic_and_metric_files_are_found(tmp_path, capsys):
    root = bench_tiny.make(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}
    cfg = json.loads((root / "bench/configs/covtype_otf_m16k.json")
                     .read_text())
    cfg.update(n=512, m=32, lam=0.01)
    (root / "bench/configs/tiny_new.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/steady_small.json").write_text(json.dumps(
        {"kind": "open_loop", "rate_profile": [[1.0, 100.0]],
         "rows": {"median": 2, "log_sigma": 0.5, "min": 1, "max": 8}}))
    (root / "bench/limits/tiny_new.serve.json").write_text(json.dumps(
        {"margin_gap": 1e-4, "unanswered": 0}))
    (root / "bench/layers/serve.rows_per_request.py").write_text(
        "def read(rec):\n"
        "    s = rec.get('serve')\n"
        "    return None if s is None else "
        "s['dispatched_rows'] / rec['attempted']\n")
    # BENCHMARK.json gains entries; no file that was there changes
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_new", "source": "test",
                             "file": "bench/configs/tiny_new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_new.serve",
                               "config": "tiny_new",
                               "traffic": "steady_small", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "serve.rows_per_request",
                               "unit": "rows", "better": "higher",
                               "source": "program_counter",
                               "layer": "serve engine",
                               "moves": "serve_p50_ms",
                               "workloads": ["tiny_new.serve"]})
    for m in bench["end_to_end"]:
        if m["name"].startswith("serve_"):
            m["workloads"].append("tiny_new.serve")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("tiny_new.serve", root)
    assert cell.config["m"] == 32 and cell.traffic["rate_profile"] == [
        [1.0, 100.0]]
    assert [m.name for m in cell.per_layer] == ["serve.rows_per_request"]
    assert cell.per_layer[0].read({"serve": {"dispatched_rows": 10},
                                   "attempted": 5}) == 2.0
    res = bench_tiny.run(root, "tiny_new.serve", capsys=capsys)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"serve_p50_ms", "setup_s"}
    assert res["attempted"] == 100
    for p, content in before.items():
        assert p.read_bytes() == content, p


def test_unknown_workload_and_missing_reader(tmp_path):
    root = bench_tiny.make(tmp_path)
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("nope.fit", root)
    (root / "bench/layers/kmvp_roofline.py").unlink()
    with pytest.raises(FileNotFoundError, match="kmvp_roofline"):
        spec.load_cell("covtype_otf.fit", root)

"""A cell, a configuration and a per-layer metric are each found by
name from their own files: adding one edits no file that is there."""
import hashlib
import json
import shutil
from pathlib import Path

from bench import spec

ROOT = Path(__file__).resolve().parents[2]


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted((root / "bench").rglob("*"))
            if p.is_file()}


def _copy(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_every_cell_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        assert cell.traffic_name == w["traffic"]
        assert {"update", "selection"} <= set(cell.limits) <= {
            "probe", "update", "selection", "local_steps", "model"}
        assert spec.reference_model(cell).n_params(cell.config) \
            == cell.config["n_params"]
        assert set(spec.metric_readers(cell)) == {
            m["name"] for m in bench["per_layer"]
            if w["name"] in m.get("workloads", [w["name"]])}


def test_a_new_cell_config_and_metric_need_only_new_files(tmp_path):
    root = _copy(tmp_path)
    before = _digest(root)

    # a new configuration: its file of sizes and its reference beside it
    cfg_dir = root / "bench" / "configs" / "cnn_wide"
    shutil.copytree(root / "bench" / "configs" / "cnn_mnist", cfg_dir)
    cfg = json.loads((cfg_dir / "config.json").read_text())
    cfg.update(name="cnn_wide", d_fc=256)
    (cfg_dir / "config.json").write_text(json.dumps(cfg))
    # a new traffic mix, the limits of the new cell, a new metric
    traffic = json.loads(
        (root / "bench" / "traffic" / "s3500_k20.json").read_text())
    traffic.update(clients=2000, select=50)
    (root / "bench" / "traffic" / "s2k_k50.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "limits" / "cnn_wide.s2k_k50.json").write_text(
        json.dumps({"limits": {"probe": 1e-3, "update": 0.1,
                               "selection": 0}}))
    (root / "bench" / "metrics" / "round.selection_ms.py").write_text(
        "def read(ctx):\n    return None\n")

    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "cnn_wide", "source": "x",
                             "file": "bench/configs/cnn_wide/config.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "cnn_wide.s2k_k50",
                               "config": "cnn_wide", "traffic": "s2k_k50",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "round.selection_ms", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "core.round",
                               "moves": "device_rounds_per_s",
                               "workloads": ["cnn_wide.s2k_k50"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("cnn_wide.s2k_k50", root)
    assert cell.config["d_fc"] == 256
    assert cell.traffic["clients"] == 2000 and cell.chips == 1
    assert cell.limits["update"] == 0.1
    assert "round.selection_ms" in spec.metric_readers(cell)
    old = spec.load_cell("cnn_mnist.s3500_k20", root)
    assert "round.selection_ms" not in spec.metric_readers(old)
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before

"""Find a cell's configuration, traffic and metric readers by name.

Everything the harness runs is named in `BENCHMARK.json`: a workload
names its configuration (whose `file` is a JSON of sizes with a plain
reference beside it), its traffic mix (`traffic/<name>.json`), the
limits of its comparison (`limits/<workload>.json`) and the per-layer
metrics it reports (`metrics/<name>.py`, one reader each). A
new cell, configuration or metric is a new file and an entry; no code
here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    config_dir: Path
    traffic: dict
    traffic_name: str
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: Dict[str, float]
    root: Path = ROOT


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_path = root / configs[w["config"]]["file"]
    traffic_path = (root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads(cfg_path.read_text()), config_dir=cfg_path.parent,
        traffic=json.loads(traffic_path.read_text()),
        traffic_name=w["traffic"],
        end_to_end=[m for m in bench["end_to_end"]
                    if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        limits=json.loads((root / "bench" / "limits" / f"{workload}.json"
                           ).read_text())["limits"],
        root=root)


def reference_model(cell: Cell):
    """The configuration's plain reference module (`reference` key):
    `init(key, cfg)`, `per_sample_loss(params, x, y, dtype)`,
    `n_params(cfg)`, the parameters a device uploads (the `trainable`
    leaves where the configuration declares them, else all), and the
    per-sample `forward_flops(cfg)` and `train_flops(cfg)`."""
    return load_module(cell.config_dir / cell.config["reference"],
                       f"bench_ref_{cell.config['name']}")


def metric_readers(cell: Cell) -> Dict[str, object]:
    """{metric name: reader module}, one file per per-layer metric."""
    return {m["name"]: load_module(
        cell.root / "bench" / "metrics" / f"{m['name']}.py",
        "bench_metric_" + m["name"].replace(".", "_"))
        for m in cell.per_layer}

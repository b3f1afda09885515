"""Find everything a cell needs by the names in ``BENCHMARK.json``.

- a cell: its entry under ``workloads``;
- its configuration: the ``configs`` entry of that name, whose ``file``
  holds the sizes; the file's ``arch`` names the program's graph builder
  ``bench/models/<arch>.py`` and the plain reference
  ``bench/reference/<arch>.py``;
- its traffic mix: ``bench/traffic/<traffic>.json``;
- a per-layer metric: its reader ``bench/metrics/<name>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Dict, List

from bench.loadgen import validate

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_module(path: str, name: str) -> ModuleType:
    """Import the file ``path`` as module ``name`` (once per process)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file's contents
    traffic: dict          # the traffic file's contents
    end_to_end: List[dict]
    per_layer: List[dict]

    def model(self) -> ModuleType:
        return load_module(os.path.join(
            BENCH, "models", self.config["arch"] + ".py"),
            "bench_model_" + self.config["arch"])

    def reference(self) -> ModuleType:
        return load_module(os.path.join(
            BENCH, "reference", self.config["arch"] + ".py"),
            "bench_reference_" + self.config["arch"])


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = validate(json.load(f))
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in manifest["end_to_end"] if _reports(m, name)],
                [m for m in manifest["per_layer"] if _reports(m, name)])


def metric_readers(metrics: List[dict]) -> Dict[str, ModuleType]:
    """Reader module of each per-layer metric, by name."""
    return {m["name"]: load_module(
        os.path.join(BENCH, "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_")) for m in metrics}

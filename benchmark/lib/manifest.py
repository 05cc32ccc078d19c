"""Find a cell's files by the names ``BENCHMARK.json`` gives.

Nothing about a cell is written in code: its configuration is
``<config file>``, its traffic mix ``benchmark/traffic/<traffic>.json``,
each per-layer metric ``benchmark/layer_metrics/<name>.json`` with a
``reader`` ``<file>.<function>`` under ``benchmark/readers/``, and its
plain reference ``benchmark/reference/<reference>.py``.  Code files are
loaded by path under the benchmark's own root, so a copy of the benchmark
elsewhere (a test's, the driver's) finds its own files.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_code(root: Path, kind: str, stem: str):
    """``benchmark/<kind>/<stem>.py`` under ``root`` as a module."""
    path = root / "benchmark" / kind / f"{stem}.py"
    name = f"_bench_{kind}_{stem}_{abs(hash(str(path)))}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]                  # the cell's, setup_s included
    per_layer: List[dict]                   # BENCHMARK.json entries
    readers: Dict[str, Callable]            # metric name -> reader

    def reference(self):
        return load_code(self.root, "reference", self.config["reference"])


def load_manifest(root: Path) -> dict:
    return _json(Path(root) / "BENCHMARK.json")


def load_cell(root: Path, workload: str) -> Cell:
    root = Path(root)
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"({sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in man["configs"]}[w["config"]]
    config = _json(root / cfg_entry["file"])
    traffic = _json(root / "benchmark" / "traffic" / f"{w['traffic']}.json")
    per_layer = [m for m in man["per_layer"] if _applies(m, workload)]
    readers = {}
    for m in per_layer:
        spec = _json(root / "benchmark" / "layer_metrics"
                     / f"{m['name']}.json")
        stem, _, fn = spec["reader"].rpartition(".")
        readers[m["name"]] = getattr(load_code(root, "readers", stem), fn)
    return Cell(root, workload, int(w["chips"]), w["config"], config,
                w["traffic"], traffic,
                [m for m in man["end_to_end"] if _applies(m, workload)],
                per_layer, readers)

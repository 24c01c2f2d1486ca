"""Find a cell's files by the names ``BENCHMARK.json`` gives."""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> Dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(man: Dict, workload: str) -> Dict:
    for w in man["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"BENCHMARK.json has no workload {workload!r}; it has "
                   f"{[w['name'] for w in man['workloads']]}")


def config_of(man: Dict, name: str) -> Dict:
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(ROOT, c["file"]))
    raise KeyError(f"BENCHMARK.json has no config {name!r}")


def traffic_of(name: str) -> Dict:
    return load_json(os.path.join(BENCH, "traffic", name + ".json"))


def metric_of(name: str) -> Dict:
    return load_json(os.path.join(BENCH, "metrics", name + ".json"))


def metrics_for(man: Dict, workload: str, group: str) -> List[Dict]:
    """Entries of ``group`` ("end_to_end" / "per_layer") this cell reports."""
    return [m for m in man[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py``, found by name."""
    path = os.path.join(BENCH, folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {folder[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module

"""Finds a cell's pieces by the names in ``BENCHMARK.json``:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and one
reader ``bench/metrics/<metric>.py`` per per-layer metric."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    deploy: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(work)}")
    w = work[name]
    cfg = _load(os.path.join(root, "bench", "configs", w["config"] + ".json"))
    mix = _load(os.path.join(root, "bench", "traffic",
                             w["traffic"] + ".json"))
    deploy = dict(cfg["deployment"], **mix.get("engine", {}))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name)
             and ("workloads" in m or m["moves"] in moved)]
    return Cell(name, w["chips"], cfg, mix, deploy, e2e, layer)


def reader(metric: str, root: str = ROOT) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def readers(cell: Cell, root: str = ROOT) -> Dict[str, Callable]:
    return {m["name"]: reader(m["name"], root) for m in cell.per_layer}

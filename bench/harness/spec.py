"""Finds a cell's pieces by the names in ``BENCHMARK.json``:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``, one
reader ``bench/metrics/<metric>.py`` per per-layer metric, and the model
family ``bench/families/<model_type>.py`` that the configuration's
published ``model_type`` names."""
from __future__ import annotations

import importlib.util
import json
import os
import sys
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


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: str = ROOT) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    return _module(path, f"bench_metric_{metric.replace('.', '_')}").read


def family(cfg: dict, root: str = ROOT):
    """The module ``bench/families/<model_type>.py`` of a configuration,
    with its ``arch_config``, ``Reference`` and ``shapes``."""
    folder = os.path.join(root, "bench", "families")
    known = sorted(f[:-3] for f in os.listdir(folder)
                   if f.endswith(".py") and not f.startswith("_"))
    kind = cfg.get("model_type")
    if kind not in known:
        raise KeyError(f"no family for model_type {kind!r} of "
                       f"{cfg.get('name')!r}; known: {known}")
    return _module(os.path.join(folder, kind + ".py"),
                   f"bench_family_{kind.replace('.', '_')}")


def readers(cell: Cell, root: str = ROOT) -> Dict[str, Callable]:
    return {m["name"]: reader(m["name"], root) for m in cell.per_layer}

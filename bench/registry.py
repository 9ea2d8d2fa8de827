"""Finds a cell's pieces by name, so that later cells only add files.

    configs/<config>.json    model configuration, as run
    mixes/<traffic>.json     traffic mix; names its driver
    drivers/<driver>.py      run(ctx) -> common.Outcome
    metrics/<metric>.py      read(outcome, ctx) -> number or None; a
                             metric "a.b" falls back to metrics/a.py
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict

ROOT = pathlib.Path(__file__).resolve().parent


def _json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(repo_root: pathlib.Path = ROOT.parent) -> Dict[str, Any]:
    return _json(repo_root / "BENCHMARK.json")


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _json(root / "configs" / f"{name}.json")


def mix(name: str, root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _json(root / "mixes" / f"{name}.json")


def _module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def driver(name: str, root: pathlib.Path = ROOT):
    return _module(root / "drivers" / f"{name}.py")


def metric_module(name: str, root: pathlib.Path = ROOT):
    path = root / "metrics" / f"{name}.py"
    if not path.exists():
        path = root / "metrics" / f"{name.split('.', 1)[0]}.py"
    return _module(path)


def metric_reader(name: str, root: pathlib.Path = ROOT) -> Callable:
    return metric_module(name, root).read

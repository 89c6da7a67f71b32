"""BENCHMARK.json and the data files it names. JAX-free.

A cell is found by its name in ``workloads``; its configuration by
``configs[].file``; its traffic mix at ``<root>/traffic/<traffic>.json``;
its own numbers (the fixed rate of an open-loop cell) at
``<root>/cells/<cell>.json``, which may be absent; a per-layer metric's
reader at ``<root>/layer_metrics/<metric>.py``. ``<root>`` is the first
of the manifest's ``paths``. A later PR adds a cell by adding files and
manifest entries; no file that is there needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ManifestError(Exception):
    pass


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    why: str
    config_name: str
    config: dict            # the configuration file, whole
    config_file: str        # its path
    traffic_name: str
    traffic: dict           # the traffic file with the cell's overrides
    end_to_end: list        # manifest entries of the metrics it reports
    per_layer: list
    run_seconds: int
    root: str               # directory that holds the data files
    extra: dict = field(default_factory=dict)   # the cell file, whole


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            obj = json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read {path}: {e}") from None
    except ValueError as e:
        raise ManifestError(f"{path} is not JSON: {e}") from None
    if not isinstance(obj, dict):
        raise ManifestError(f"{path} must hold a JSON object")
    return obj


def load_manifest(repo: str = REPO) -> dict:
    return _read_json(os.path.join(repo, "BENCHMARK.json"))


def _for_cell(metrics: list, cell: str) -> list:
    """The metrics a cell reports: all that list no ``workloads``, and
    those that list this cell."""
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, repo: str = REPO,
              manifest: Optional[dict] = None) -> Cell:
    man = manifest if manifest is not None else load_manifest(repo)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"workload {name!r} names configuration "
                            f"{w['config']!r}, which BENCHMARK.json lacks")
    root = os.path.join(repo, man["paths"][0])
    config_file = os.path.join(repo, configs[w["config"]]["file"])
    config = _read_json(config_file)
    traffic = _read_json(os.path.join(root, "traffic",
                                      w["traffic"] + ".json"))
    cell_file = os.path.join(root, "cells", name + ".json")
    extra = _read_json(cell_file) if os.path.exists(cell_file) else {}
    traffic = {**traffic, **extra.get("traffic", {})}
    return Cell(name=name, chips=int(w["chips"]), why=w["why"],
                config_name=w["config"], config=config,
                config_file=config_file,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=_for_cell(man["end_to_end"], name),
                per_layer=_for_cell(man["per_layer"], name),
                run_seconds=int(man["run_seconds"]), root=root,
                extra=extra)


def load_reader(root: str, metric: str) -> Callable:
    """The ``read(obs)`` function of a per-layer metric's reader file."""
    path = os.path.join(root, "layer_metrics", metric + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"per-layer metric {metric!r} has no reader "
                            f"at {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + metric.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise ManifestError(f"{path} defines no read(obs)")
    return mod.read

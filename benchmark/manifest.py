"""BENCHMARK.json and the data files it names. JAX-free.

A cell is found by its name in ``workloads``; its configuration by
``configs[].file``; its traffic mix at ``<root>/traffic/<traffic>.json``;
its own numbers (the fixed rate of an open-loop cell) at
``<root>/cells/<cell>.json``, which may be absent; a per-layer metric's
reader at ``<root>/layer_metrics/<metric>.py``; its family's
architecture file at ``<root>/architectures/<architecture>.py``, named
by the configuration file's ``"architecture"`` key (absent: ``mistral``).
``<root>`` is the first of the manifest's ``paths``. A later PR adds a
cell, or a configuration of a family the harness has not seen, by adding
files and manifest entries; no file that is there needs an edit.

**The architecture file's contract.** Whatever is particular to a model
family lives in that one file; serve_cell.py, the child that holds the
chip, loads it and knows no field or leaf name itself.

``model_config(cfg) -> dict``
    Keyword arguments for the program's ``ModelConfig``, from the
    family's published key names in the configuration file ``cfg``.
    A keyword ``ModelConfig`` does not have is a :class:`ManifestError`
    that names the keyword and the file.
``engine_weights(sched) -> weights``
    Reads the tree the scheduler serves from (``sched._params``,
    ``sched.config``, ``sched.mesh``: under a mesh the tree is laid out
    differently) and returns whatever ``forward`` wants: float32
    weights handed over one layer (one expert) at a time, so that the
    reference fits beside a serving model. The tree is an argument of
    every jitted function, never a closure (a closure bakes gigabytes
    of constants into the program).
``forward(cfg, tokens, weights) -> (logits, facts)``
    The plain reference: float32, ``jax.default_matmul_precision(
    "highest")``, no kernels, no cache, no batching. ``logits`` is
    [B, T, V] for ``tokens`` [B, T]; ``facts`` is a dict of whatever
    ``compare`` reads (routing margins, say).
``compare(system, reference_logits, facts, cfg) -> dict``
    The verdict: ``ok``, the numbers behind it, and ``tolerance`` with
    the reason for it in the file. The harness adds ``n_prefill`` to
    ``facts`` first: the first ``n_prefill`` positions of each sequence
    went through the prefill program, the rest through decode steps.
``system_logits(sched, tokens, n_prefill) -> logits``  (optional)
    For a family whose cache is not ``KVCache`` + ``PagedKVCache``;
    absent, serve_cell.py's own is used. It must drive ``sched._model``'s
    functions on ``sched._params`` under ``sched.mesh``: the programs
    the scheduler serves with, not a path of the file's own.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_ARCHITECTURE = "mistral"
ARCHITECTURE_FUNCTIONS = ("model_config", "engine_weights", "forward",
                          "compare")


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


def _load_module(path: str, tag: str):
    spec = importlib.util.spec_from_file_location(
        tag.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: str, metric: str) -> Callable:
    """The ``read(obs)`` function of a per-layer metric's reader file."""
    path = os.path.join(root, "layer_metrics", metric + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"per-layer metric {metric!r} has no reader "
                            f"at {path}")
    mod = _load_module(path, "benchmark_layer_metric_" + metric)
    if not callable(getattr(mod, "read", None)):
        raise ManifestError(f"{path} defines no read(obs)")
    return mod.read


def load_architecture(root: str, name: str = DEFAULT_ARCHITECTURE):
    """The module of a family's architecture file (the contract is in
    this module's docstring). It imports JAX: only the child loads it."""
    path = os.path.join(root, "architectures", name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"architecture {name!r} has no file at {path}")
    mod = _load_module(path, "benchmark_architecture_" + name)
    for fn in ARCHITECTURE_FUNCTIONS:
        if not callable(getattr(mod, fn, None)):
            raise ManifestError(f"{path} defines no {fn}()")
    return mod

"""What the rehearsals share: a benchmark of tiny cells made of new files
in a temporary directory, and the pin of its child onto the CPU.

The test, not run.py, pins the child onto the CPU (there is no flag for
it): ``on_cpu`` replaces ``run.child_env`` and ``run.check_device``. A
cell that asks for four chips gets four virtual CPU devices. A number
from such a run is not a device metric and is compared with nothing.
"""

import argparse
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, roofline, run  # noqa: E402

HEAD = ("You are a helpful assistant. Draft a concise, friendly reply to "
        "the following message:\n\n")


def tiny(name: str, experts: int = 0, **over) -> dict:
    """A configuration file of the Mistral family at a toy size."""
    cfg = {"name": name, "source": "tests", "hidden_size": 128,
           "intermediate_size": 256, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 32, "vocab_size": 512,
           "max_position_embeddings": 256, "rope_theta": 10000.0,
           "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
           "stack": {"SERVE_QUANT": "int8", "SERVE_KV": "paged",
                     "SERVE_KV_QUANT": "int8", "SERVE_SLOTS": "4",
                     "SERVE_MAX_SEQ": "256", "SERVE_FUSE": "4",
                     "SERVE_PREFILL_CHUNK": "256"}}
    if experts:
        cfg.update(num_local_experts=experts, num_experts_per_tok=2,
                   moe_capacity_factor=2.0)
    cfg.update(over)
    return cfg


def write_benchmark(root, configs: list, chips: int = 1,
                    copied: tuple = ("layer_metrics", "architectures"),
                    architectures: dict = None) -> str:
    """A benchmark of one ``<config>.tiny-open`` cell for each of
    ``configs``, made of new files only under ``root``; of the real
    benchmark only the directories ``copied`` are there.
    ``architectures``: name -> text of an architecture file to write."""
    b = root / "benchmark"
    for d in copied:
        shutil.copytree(os.path.join(ROOT, "benchmark", d), b / d)
    for d in ("configs", "traffic", "cells", "layer_metrics",
              "architectures"):
        (b / d).mkdir(parents=True, exist_ok=True)
    (b / "layer_metrics" / "requests_ok.py").write_text(
        '"""A metric a later PR might add: requests that ended well."""\n'
        "def read(obs):\n    return float(len(obs.counted_ok()))\n")
    for name, text in (architectures or {}).items():
        (b / "architectures" / (name + ".py")).write_text(text)
    (b / "traffic" / "tiny-open.json").write_text(json.dumps({
        "loop": "open", "rate_rps": None,
        "prompt": {"head": HEAD, "tail": "\n\nReply:", "body_tokens": {
            "dist": "lognormal", "median": 30, "sigma": 0.5, "min": 8,
            "max": 90}},
        "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
        "options": {"temperature": 0}, "warmup_buckets": [128, 256]}))
    names = [c["name"] for c in configs]
    for c in configs:
        (b / "configs" / (c["name"] + ".json")).write_text(json.dumps(c))
        (b / "cells" / (c["name"] + ".tiny-open.json")).write_text(
            json.dumps({"traffic": {"rate_rps": 6.0}}))
    real = manifest.load_manifest(ROOT)
    man = dict(real)
    man["paths"] = ["benchmark"]
    man["configs"] = [
        {"name": n, "source": "tests", "file": f"benchmark/configs/{n}.json",
         "reduced": [], "why": "tiny"} for n in names]
    man["workloads"] = [
        {"name": f"{n}.tiny-open", "config": n, "traffic": "tiny-open",
         "chips": chips, "why": "rehearsal"} for n in names]
    strip = lambda ms: [{k: v for k, v in m.items() if k != "workloads"}
                        for m in ms]
    man["end_to_end"] = strip(real["end_to_end"])
    man["per_layer"] = strip(real["per_layer"]) + [
        {"name": "requests_ok", "unit": "requests", "better": "higher",
         "source": "host_clock", "layer": "load generator (benchmark)",
         "moves": "tpot_p50_ms"}]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return str(root)


@pytest.fixture()
def on_cpu(monkeypatch):
    real_env = run.child_env

    def env(cell, port, traced):
        e = real_env(cell, port, traced)
        e["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1:
            e["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                              f"{cell.chips}")
        return e

    monkeypatch.setattr(run, "child_env", env)
    monkeypatch.setattr(run, "check_device", lambda device, labels, cell:
                        roofline.peaks_for("TPU v5 lite"))
    monkeypatch.setattr(run, "RAMP_S", 1.0)
    monkeypatch.setattr(run, "TRACE_STRETCH_S", 1.0)


def run_args(cell: str, trace: int, seconds: float = 4.0
             ) -> argparse.Namespace:
    return argparse.Namespace(workload=cell, seed=7, seconds=seconds,
                              trace=trace, sample=False)

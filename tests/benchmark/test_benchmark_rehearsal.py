"""A rehearsal of a whole run on the CPU at a tiny size, and the proof that
a new cell is data: a configuration, a traffic mix, a cell and a
per-layer metric are loaded from a temporary directory.

The test, not run.py, pins the child onto the CPU (there is no flag for
it): it replaces ``run.child_env`` and ``run.check_device``. A number
from this run is not a device metric and is compared with nothing.
"""

import argparse
import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, roofline, run  # noqa: E402

HEAD = ("You are a helpful assistant. Draft a concise, friendly reply to "
        "the following message:\n\n")


def _tiny(name: str, experts: int = 0) -> dict:
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
    return cfg


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    """A benchmark of one tiny cell, made of new files only; the readers
    of the real per-layer metrics are copied beside one new one."""
    root = tmp_path_factory.mktemp("bench")
    b = root / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark", "layer_metrics"),
                    b / "layer_metrics")
    for d in ("configs", "traffic", "cells"):
        (b / d).mkdir()
    (b / "layer_metrics" / "requests_ok.py").write_text(
        '"""A metric a later PR might add: requests that ended well."""\n'
        "def read(obs):\n    return float(len(obs.counted_ok()))\n")
    (b / "configs" / "tiny-dense.json").write_text(json.dumps(
        _tiny("tiny-dense")))
    (b / "configs" / "tiny-routed.json").write_text(json.dumps(
        _tiny("tiny-routed", experts=4)))
    (b / "traffic" / "tiny-open.json").write_text(json.dumps({
        "loop": "open", "rate_rps": None,
        "prompt": {"head": HEAD, "tail": "\n\nReply:", "body_tokens": {
            "dist": "lognormal", "median": 30, "sigma": 0.5, "min": 8,
            "max": 90}},
        "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
        "options": {"temperature": 0}, "warmup_buckets": [128, 256]}))
    (b / "cells" / "tiny-dense.tiny-open.json").write_text(json.dumps(
        {"traffic": {"rate_rps": 6.0}}))
    (b / "cells" / "tiny-routed.tiny-open.json").write_text(json.dumps(
        {"traffic": {"rate_rps": 6.0}}))
    real = manifest.load_manifest(ROOT)
    man = dict(real)
    man["paths"] = ["benchmark"]
    man["configs"] = [
        {"name": n, "source": "tests", "file": f"benchmark/configs/{n}.json",
         "reduced": [], "why": "tiny"}
        for n in ("tiny-dense", "tiny-routed")]
    man["workloads"] = [
        {"name": f"{n}.tiny-open", "config": n, "traffic": "tiny-open",
         "chips": 1, "why": "rehearsal"}
        for n in ("tiny-dense", "tiny-routed")]
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
        return e

    monkeypatch.setattr(run, "child_env", env)
    monkeypatch.setattr(run, "check_device", lambda device, labels, cell:
                        roofline.peaks_for("TPU v5 lite"))
    monkeypatch.setattr(run, "RAMP_S", 1.0)
    monkeypatch.setattr(run, "TRACE_STRETCH_S", 1.0)


def _args(cell: str, trace: int) -> argparse.Namespace:
    return argparse.Namespace(workload=cell, seed=7, seconds=4.0,
                              trace=trace, sample=False)


def test_new_cell_is_data_only(data_root):
    cell = manifest.load_cell("tiny-routed.tiny-open", data_root)
    assert cell.config["num_local_experts"] == 4
    assert cell.traffic["rate_rps"] == 6.0
    assert cell.traffic["loop"] == "open"
    assert "requests_ok" in [m["name"] for m in cell.per_layer]
    assert callable(manifest.load_reader(cell.root, "requests_ok"))
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no-such-cell", data_root)


@pytest.mark.parametrize("cell", ["tiny-dense.tiny-open",
                                  "tiny-routed.tiny-open"])
def test_timed_run_on_tiny(cell, data_root, on_cpu, tmp_path, capsys):
    last = run.run_cell(_args(cell, 0), time.monotonic(),
                        data_root=data_root, out_root=str(tmp_path))
    earlier = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    ref = next(x["reference"] for x in earlier if "reference" in x)
    assert ref["ok"], ref
    assert last["correct"], earlier
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["failed"] == 0 and last["attempted"] >= 10
    assert set(last["metrics"]) == {
        m["name"] for m in manifest.load_manifest(data_root)["end_to_end"]}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    # One streamed character is one token: the client read exactly what
    # every request asked for.
    with open(os.path.join(str(tmp_path), "benchmark",
                           f"{cell}.seed7.trace0", "records.json")) as f:
        records = json.load(f)
    assert records and all(
        sum(r["chunk_tokens"]) == r["num_predict"]
        == r["final"]["eval_count"] for r in records)
    assert all(r["final"]["prompt_eval_count"] == r["prompt_bytes"] + 1
               for r in records)


def test_traced_run_without_a_chip_is_refused(data_root, on_cpu, tmp_path):
    """The traced path runs to its end on the CPU (profiler, spans,
    samples) and is then refused for the right reason: no operation ran
    on a device, so there is no device metric to report."""
    with pytest.raises(run.RunFailure, match="no operation on the device"):
        run.run_cell(_args("tiny-dense.tiny-open", 1), time.monotonic(),
                     data_root=data_root, out_root=str(tmp_path))


def test_steadiness_plays_windows_on_one_boot(data_root, on_cpu, tmp_path,
                                              capsys):
    """Two windows on one boot, the second with a variant laid over the
    mix: a row and a records file each, then each variant's spread."""
    from benchmark import steadiness
    rc = steadiness.main(
        ["--workload", "tiny-dense.tiny-open", "--windows", "2",
         "--seconds", "3", "--seed", "5",
         "--variants", '[{}, {"rate_rps": 4.0}]'],
        data_root=data_root, out_root=str(tmp_path))
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert rc == 0
    assert [r["seed"] for r in rows[:2]] == [5, 6]
    assert rows[0]["attempted"] > rows[1]["attempted"] >= 8
    assert all(r["failed"] == 0 and r["itl_p50_ms"] > 0 for r in rows[:2])
    out = os.path.join(str(tmp_path), "benchmark",
                       "tiny-dense.tiny-open.steadiness")
    with open(os.path.join(out, "window1.json")) as f:
        w = json.load(f)
    assert w["traffic"]["rate_rps"] == 4.0
    assert len(w["records"]) >= w["row"]["attempted"]

"""A rehearsal of a whole run on the CPU at a tiny size, and the proof that
a new cell is data: a configuration, a traffic mix, a cell, a per-layer
metric and the family's architecture file are loaded from a temporary
directory (rehearsal_files.py makes it and pins the child onto the CPU).
"""

import json
import os
import time

import pytest

from rehearsal_files import (on_cpu, run_args as _args,  # noqa: F401
                             tiny, write_benchmark)

from benchmark import manifest, run  # noqa: E402


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    """Two tiny cells, made of new files only; the readers of the real
    per-layer metrics (beside one new one) and the real architecture
    files are copied."""
    return write_benchmark(tmp_path_factory.mktemp("bench"),
                           [tiny("tiny-dense"),
                            tiny("tiny-routed", experts=4)])


def test_new_cell_is_data_only(data_root):
    cell = manifest.load_cell("tiny-routed.tiny-open", data_root)
    assert cell.config["num_local_experts"] == 4
    assert cell.traffic["rate_rps"] == 6.0
    assert cell.traffic["loop"] == "open"
    assert "requests_ok" in [m["name"] for m in cell.per_layer]
    assert callable(manifest.load_reader(cell.root, "requests_ok"))
    arch = manifest.load_architecture(cell.root)
    assert arch.__file__.startswith(data_root)
    assert arch.model_config(cell.config)["num_experts"] == 4
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

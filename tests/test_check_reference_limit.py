"""tools/check_reference_limit.py at test size: the sound reading passes
the family's limit and every wrong model reads far above it (the tool's
chip readings at the published widths are in PERF.md section 6, PR 26)."""

import json
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
sys.path.insert(0, ROOT)

from rehearsal_files import tiny  # noqa: E402

from tools import check_reference_limit as tool  # noqa: E402


def test_fake_quant_rounds_each_column_to_its_own_grid():
    w = np.asarray([[1.0, -0.07], [-7.0, 0.02], [3.4, 0.035]], np.float32)
    got = np.asarray(tool.fake_quant(w, 4))
    np.testing.assert_allclose(got[:, 0], [1.0, -7.0, 3.0])
    np.testing.assert_allclose(got[:, 1], [-0.07, 0.02, 0.04], atol=1e-7)
    assert not np.asarray(tool.fake_quant(np.zeros((2, 2)), 4)).any()


def test_tool_separates_the_sound_model_from_the_wrong_ones(tmp_path):
    cfg = tiny("tiny-olmoe-limit", architecture="olmoe", model_type="olmoe",
               norm_topk_prob=False, num_key_value_heads=4,
               intermediate_size=64)
    cfg.update(num_experts=8, num_experts_per_tok=4,
               moe_capacity_factor=None)
    path = tmp_path / "tiny-olmoe-limit.json"
    path.write_text(json.dumps(cfg))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "check_reference_limit.py"),
         str(path), "--seeds", "53", "--wrong-seeds", "53"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    by = {r["model"]: r for r in map(json.loads, (
        x for x in done.stdout.splitlines() if x.startswith("{")))}
    assert set(by) == {"sound", "renormalised", "no_q_norm", "int4_weights"}
    sound = by["sound"]
    assert sound["ok"] and sound["overflow_pairs"] == 0
    limit = sound["tolerance"]["median"]
    assert sound["median"] < limit / 2
    assert all(by[m]["median"] > 2 * limit for m in by if m != "sound")

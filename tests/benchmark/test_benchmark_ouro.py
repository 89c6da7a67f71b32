"""The Ouro family (Ouro-2.6B, whole) in the benchmark: its architecture
file, its configuration (against the catalog's published keys), its
traffic mix and cell, and the two readers that came with it. Every
manifest entry is found BY NAME and by presence and held to what it
holds, never to where it stands or how many there are: a later PR appends
behind these.

A rehearsal cell of the family's published key names at a toy size runs
whole on the CPU through benchmark/architectures/ouro.py (prefill, the
install into 12 page layers, fused decode steps at the cell's slots,
logits and exit distribution against the plain reference) and is
``correct``. (The wrong models and what each cache holds are in
tests/test_ouro_parity.py and tests/test_engine_ouro.py.)
"""

import json
import os
import time
import types

import numpy as np
import pytest

from rehearsal_files import (ROOT, on_cpu, run_args, tiny,  # noqa: F401
                             write_benchmark)

from benchmark import manifest, metrics, roofline, run

NAME = "ouro-2.6b"
CELL = NAME + ".problem-backlog"
BENCH = os.path.join(ROOT, "benchmark")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def by_name(entries: list, name: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def tiny_ouro(name: str) -> dict:
    """The family's published keys at a toy size: three layers of plain
    multi-head attention (4 heads of 32) walked four times."""
    cfg = tiny(name, architecture="ouro", model_type="ouro")
    cfg.update(num_hidden_layers=3, num_key_value_heads=4,
               rms_norm_eps=1e-6, total_ut_steps=4, early_exit_threshold=1)
    cfg["stack"] = {**cfg["stack"], "SERVE_PREFILL_CHUNK": "32",
                    "SERVE_PREFIX": "1", "SERVE_PAGE_SIZE": "16"}
    return cfg


def arch():
    return manifest.load_architecture(BENCH, "ouro")


@pytest.fixture(scope="module")
def ouro_root(tmp_path_factory):
    return write_benchmark(tmp_path_factory.mktemp("ouro"),
                           [tiny_ouro("tiny-ouro-cell")])


def test_rehearsal_cell_runs_whole_and_is_correct(ouro_root, on_cpu,
                                                  tmp_path, capsys):
    cell = manifest.load_cell("tiny-ouro-cell.tiny-open", ouro_root)
    assert cell.config["architecture"] == "ouro"
    last = run.run_cell(run_args(cell.name, 0, 4.0), time.monotonic(),
                        data_root=ouro_root, out_root=str(tmp_path))
    earlier = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    ref = next(x["reference"] for x in earlier if "reference" in x)
    a = arch()
    assert ref["ok"], ref
    assert ref["tolerance"] == {"median": a.TOL_MEDIAN,
                                "long_median": a.TOL_MEDIAN,
                                "decode_max": a.TOL_DECODE,
                                "long_decode_max": a.TOL_DECODE,
                                "exit_max": a.TOL_EXIT}
    for name in ("median", "long_median", "decode_max", "long_decode_max",
                 "exit_max"):
        assert 0 < ref[name] <= ref["tolerance"][name], name
    # Twelve layer applications amplify little: the toy reads a tenth of
    # the limits the published depth set.
    assert ref["median"] < 0.1 and ref["exit_max"] < 0.03
    assert ref["positions"] == 2 * (128 + 8)
    assert sum(ref["exit_mean"]) == pytest.approx(1.0, abs=1e-4)
    assert last["correct"] and last["failed"] == 0
    assert last["attempted"] >= 10


def obs_of(cell, start, end, **kw):
    return metrics.Observations(
        records=kw.pop("records", []), ramp_s=0.0, window_s=51.0, cell=cell,
        counters_start=start, counters_end=end,
        peaks=roofline.peaks_for("TPU v5 lite"), **kw)


def test_the_two_readers_on_recorded_counters():
    """19 rows at a context of 450 for 1,700 steps, on made-up
    observations: the re-read weights are three fifths of a step, the
    pages of 192 cache layers the rest."""
    cell = manifest.load_cell(CELL, ROOT)
    cfg, a = cell.config, arch()
    steps, rows, ctx = 1700.0, 19, 450
    rec = types.SimpleNamespace(ok=True, prompt_bytes=ctx - 161, tokens=320,
                                due_t=1.0, chunk_t=[1.0],
                                chunk_tokens=[rows * steps])
    ticks = {"serve_decode_ticks_total": steps / 4,
             "decode_fused_ticks_total": steps / 4,
             "decode_fused_steps_total": steps}

    def read(name, moved: dict):
        start = {**dict.fromkeys(moved, 5.0), **dict.fromkeys(ticks, 0.0)}
        end = {**{k: 5.0 + v for k, v in moved.items()}, **ticks}
        return manifest.load_reader(cell.root, name)(
            obs_of(cell, start, end, records=[rec]))

    step = a.decode_step_bytes(cfg, rows, ctx)
    weights = read("loop_weight_share", {
        "serve_loop_weight_bytes_total": steps * 4 * a.stack_bytes(cfg)})
    assert weights == pytest.approx(100 * 4 * a.stack_bytes(cfg) / step)
    assert 55 < weights < 63
    pages = read("page_step_share", {
        "serve_page_kv_bytes_total": steps * rows * ctx * 811008})
    assert pages == pytest.approx(100 * rows * ctx * 811008 / step)
    assert weights + pages == pytest.approx(
        100 - 100 * (a._q8(2048, 49152) + rows * 4096) / step)
    starved = read("page_starved_share", {
        "serve_page_starved_iterations_total": 900.0,
        "serve_loop_iterations_total": 1000.0})
    assert starved == pytest.approx(90.0)


def test_the_two_readers_end_at_the_last_sample_inside_the_window():
    """A traced run's closing scrape waits for ``stop_trace`` (77 s in
    this cell) and then holds the drain behind the window: as many steps
    again, of few rows, in which nobody waits. The readers take the
    counters of the last 2 Hz sample inside the window and the records up
    to it: the share the window's own arithmetic gives."""
    cell = manifest.load_cell(CELL, ROOT)
    cfg, a = cell.config, arch()
    steps, rows, ctx = 850.0, 19, 450
    rec = types.SimpleNamespace(
        ok=True, prompt_bytes=ctx - 161, tokens=320, due_t=1.0,
        chunk_t=[1.0, 40.0], chunk_tokens=[rows * steps, rows * steps])

    def counters(n, starved, iters):
        return {"serve_decode_ticks_total": n / 4,
                "decode_fused_ticks_total": n / 4,
                "decode_fused_steps_total": n,
                "serve_loop_weight_bytes_total": n * 4 * a.stack_bytes(cfg),
                "serve_page_starved_iterations_total": starved,
                "serve_loop_iterations_total": iters}

    late = counters(4 * steps, 180.0, 800.0)
    obs = obs_of(cell, counters(0, 0.0, 0.0), late, records=[rec],
                 samples=[(12.5, counters(steps / 2, 90.0, 100.0)),
                          (25.5, counters(steps, 180.0, 200.0)),
                          (110.0, late)])
    want = 100 * 4 * a.stack_bytes(cfg) / a.decode_step_bytes(cfg, rows, ctx)
    assert manifest.load_reader(cell.root, "loop_weight_share")(obs) \
        == pytest.approx(want)
    assert manifest.load_reader(cell.root, "page_starved_share")(obs) \
        == pytest.approx(90.0)
    # The closing scrape alone (an untraced run keeps no samples) reads
    # what it always read.
    obs.samples = []
    assert manifest.load_reader(cell.root, "page_starved_share")(obs) \
        == pytest.approx(22.5)
    assert manifest.load_reader(cell.root, "loop_weight_share")(obs) > want


def test_readers_read_nothing_from_a_program_without_the_counters():
    """Laid over the parent's program (which cannot run the cell, and has
    no such counter) the readers return None and do not raise."""
    cell = manifest.load_cell(CELL, ROOT)
    obs = obs_of(cell, {"serve_decode_row_steps_total": 0.0},
                 {"serve_decode_row_steps_total": 50.0})
    for name in ("loop_weight_share", "page_starved_share",
                 "page_step_share"):
        assert manifest.load_reader(cell.root, name)(obs) is None, name
    # A counter that did not move is no share of nothing.
    still = obs_of(cell, {"serve_page_starved_iterations_total": 3.0,
                          "serve_loop_iterations_total": 7.0},
                   {"serve_page_starved_iterations_total": 3.0,
                    "serve_loop_iterations_total": 7.0})
    assert manifest.load_reader(cell.root, "page_starved_share")(still) \
        is None


def test_configuration_is_the_catalogs_published_keys_whole():
    """Every key of the catalog entry's ``config`` with its value: nothing
    is reduced; every assumption named."""
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Ouro-2.6B")
    cfg = manifest.load_cell(CELL, ROOT).config
    assert cfg["source"] == entry["source_url"]
    assert {k for k, v in entry["config"].items() if cfg.get(k) != v} == set()
    assert cfg["reduced"] == {}
    assert cfg["architecture"] == "ouro"
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"],
            cfg["early_exit_threshold"]) == (48, 4, 1)
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (2048, 5632, 49152, 16, 16, 128)
    assert set(cfg["assumed"]) >= {
        "origin", "sandwich_norm", "final_norm_every_pass", "exit_gate",
        "last_pass_logits", "cache_per_pass", "ignore_eos"}
    assert "it is right and this file" in cfg["assumed"]["origin"]
    assert cfg["stands_for"].startswith("the whole model on one chip, as it "
                                        "is deployed")
    stack = dict(cfg["stack"])
    pages = int(stack.pop("SERVE_PAGES"))
    assert 161 <= pages <= 193
    assert stack == {
        "SERVE_QUANT": "int8", "SERVE_KV": "paged",
        "SERVE_KV_QUANT": "int8", "SERVE_PREFIX": "1", "SERVE_FUSE": "4",
        "SERVE_PREFILL_CHUNK": "256", "SERVE_SLOTS": "32",
        "SERVE_MAX_SEQ": "2048", "SERVE_PAGE_SIZE": "64"}


def test_cell_mix_and_manifest_entries_by_name():
    man = manifest.load_manifest(ROOT)
    cell = manifest.load_cell(CELL, ROOT)
    entry = by_name(man["configs"], NAME)
    assert entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == cell.config["source"]
    w = by_name(man["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (NAME,
                                                       "problem-backlog", 1)
    assert len(w["why"]) <= 200 and len(entry["why"]) <= 200
    t = cell.traffic
    assert (t["loop"], t["clients"]) == ("closed", 28)
    assert len(t["prompt"]["head"]) == 88
    assert t["prompt"]["body_tokens"] == {
        "dist": "lognormal", "median": 160, "sigma": 0.6, "min": 48,
        "max": 480}
    out = dict(t["output_tokens"])
    # The one change the issue permits: a shorter median, not below 192.
    assert 192 <= out.pop("median") <= 320
    assert out == {"dist": "lognormal", "sigma": 0.35, "min": 160,
                   "max": 640}
    assert t["options"] == {"temperature": 0}
    assert t["warmup_buckets"] == [256, 512, 1024]
    assert (t["stratify"], t["design_seed"]) == (32, 22)
    assert t["seed_jitter"] == {"arrival_s": 0.05, "length": 0.03}
    # The longest request fits a row's budget and the pool; the callers'
    # mean reservation does not: pages bind, rows do not.
    stack = cell.config["stack"]
    longest = (88 + 1 + t["prompt"]["body_tokens"]["max"]
               + len(t["prompt"]["tail"]) + t["output_tokens"]["max"])
    assert longest + 1 <= int(stack["SERVE_MAX_SEQ"])
    pool = int(stack["SERVE_PAGES"]) - 1
    assert -(-(longest + 1) // 64) <= pool
    from benchmark import traffic as traffic_mod
    d = traffic_mod.describe(t, 1, 500)
    mean_pages = (d["mean_prompt_bytes"] + 1 + d["mean_num_predict"]
                  + 1) / 64 + 0.5
    assert t["clients"] <= int(stack["SERVE_SLOTS"])
    assert t["clients"] * mean_pages > 1.25 * pool
    assert not os.path.exists(os.path.join(BENCH, "cells", CELL + ".json"))
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms",
                                                    "setup_s"}
    assert CELL in by_name(man["end_to_end"], "tpot_p50_ms")["workloads"]
    names = {m["name"] for m in cell.per_layer}
    assert {"out_tok_s", "kv_pages_peak", "tick_ms", "pallas_share",
            "device_idle", "hbm_peak_gb", "prefill_pad_share",
            "device_wait_share", "prefill_device_share",
            "attn_ctx_mean", "decode_bw_util_family", "prefill_flops_util",
            "page_step_share", "chunk_step_ms",
            "chunk_step_share", "padded_step_share",
            "admit_step_share", "req_cut_share_p50", "window_compile_s",
            "sample_sort_share", "loop_weight_share",
            "page_starved_share"} <= names
    assert any(n.startswith("decode_step") for n in names)
    # Not ``padded_step_ms``: a suffix here is 58-490 tokens, so a ladder
    # is one or two 256-token chunks up to the longest row's bucket and
    # its last chunk always holds tokens; the padded class books no step
    # (``padded_step_share`` reads 0) and the reader has nothing to read.
    assert (t["prompt"]["body_tokens"]["max"] + len(t["prompt"]["tail"])
            + 1 <= 2 * int(stack["SERVE_PREFILL_CHUNK"]))
    assert "padded_step_ms" not in names
    # Not ``attn_walk_share``, though the flash-append kernel serves this
    # cell from W 512: an older test pins that metric's LAST cell
    # (tests/benchmark/test_benchmark_keye.py), and a file the benchmark
    # has is not this PR's to edit (PERF.md section 7).
    assert not names & {"moe_drop_share", "index_step_share",
                        "sparse_keep_share", "attn_walk_share"}
    for name, layer in (("loop_weight_share", "model programs models/"),
                        ("page_starved_share",
                         "scheduler serve/scheduler.py")):
        m = by_name(man["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
        assert (m["source"], m["unit"], m["layer"]) == (
            "program_counter", "%", layer)
    for m in cell.per_layer:
        manifest.load_reader(cell.root, m["name"])


def test_architecture_file_keeps_the_contract_and_imports_no_program():
    a = arch()
    for fn in manifest.ARCHITECTURE_FUNCTIONS + ("system_logits",
                                                 "wrong_models"):
        assert callable(getattr(a, fn)), fn
    assert set(a.WRONG) >= {"one_pass", "three_passes", "shared_cache",
                            "final_norm_once", "pre_norms_only",
                            "no_rotation", "int4_weights"}
    with open(a.__file__) as f:
        text = f.read()
    # The reference is its own: the program's model code is driven by
    # system_logits alone, through the scheduler's module.
    assert "import llama" not in text and "models import" not in text
    assert "rms_norm" in text and "models.layers import rms" not in text
    cfg = manifest.load_cell(CELL, ROOT).config
    kw = a.model_config(cfg)
    assert (kw["num_layers"], kw["ut_steps"], kw["sandwich_norm"]) == (
        48, 4, True)
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]) == (
        16, 16, 128)
    assert kw["rope_theta"] == 1e6 and kw["rms_norm_eps"] == 1e-6
    assert kw["max_seq_len"] == 65536 and not kw["tie_embeddings"]
    assert kw["eos_token_ids"] == ()
    # A row that leaves the loop early is not built: refused by name.
    with pytest.raises(manifest.ManifestError,
                       match="early_exit_threshold 0.9"):
        a.model_config({**cfg, "early_exit_threshold": 0.9})
    # The program's ModelConfig has every keyword (the parent's lacks
    # ``ut_steps``: its child refuses the cell at boot, a ManifestError).
    from benchmark import serve_cell
    config = serve_cell.model_config(cfg)
    assert (config.cache_layers, config.q_dim, config.kv_dim) == (192, 2048,
                                                                  2048)


def test_engine_weights_hand_the_tree_back():
    import jax
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.models import family_for, llama
    config = serve_cell.model_config(tiny_ouro("t"))
    assert family_for(config) is llama
    p = llama.init_params_quantized(config, jax.random.PRNGKey(3))
    weights = arch().engine_weights(types.SimpleNamespace(
        _params=p, config=config, mesh=None))
    deq = lambda w, *at: np.asarray(w.q[at], np.float32) * np.asarray(
        w.s[at], np.float32)
    w = weights.layer(2)
    assert set(w) == {"attn_norm", "attn_out_norm", "mlp_norm",
                      "mlp_out_norm", "wq", "wk", "wv", "wo", "w_gate",
                      "w_up", "w_down"}
    qkv = deq(p["layers"]["wqkv"], 2)
    np.testing.assert_array_equal(w["wq"], qkv[:, :128])
    np.testing.assert_array_equal(w["wv"], qkv[:, 256:])
    np.testing.assert_array_equal(w["w_up"],
                                  deq(p["layers"]["wgu"], 2)[:, 256:])
    np.testing.assert_array_equal(
        w["mlp_out_norm"],
        np.asarray(p["layers"]["mlp_out_norm"][2], np.float32))
    np.testing.assert_array_equal(
        weights.gate_w, np.asarray(p["exit_gate_w"], np.float32)[:, 0])
    assert weights.gate_b.shape == () and weights.lm_head.shape == (128, 512)


def test_costs_are_the_issues_arithmetic():
    """``decode_step_bytes`` and ``prefill_flops`` at the published
    widths, against the numbers ISSUE 53 states."""
    a = arch()
    cfg = manifest.load_cell(CELL, ROOT).config
    # 4 x 2,048^2 + 3 x 2,048 x 5,632 = 51.4 M a layer, 2,467 M the stack.
    per_layer = sum(i * o for i, o in a.layer_shapes(cfg))
    assert per_layer == 4 * 2048 ** 2 + 3 * 2048 * 5632 == 51_380_224
    assert a.stack_bytes(cfg) == pytest.approx(2.467e9, rel=2e-3)
    # 4,224 B a token a cache layer, x 192 = 811,008 B a token.
    assert a.page_token_bytes(cfg) == 192 * (16 * 128 * 2 + 16 * 2 * 4) \
        == 811_008
    # A step reads 9.97 GB of weights whatever the batch...
    none = a.decode_step_bytes(cfg, 0, 0)
    assert none == pytest.approx(9.97e9, rel=2e-3)
    assert none == 4 * a.stack_bytes(cfg) + a._q8(2048, 49152)
    # ... and about 19 x 450 x 0.811 MB = 6.9 GB of pages.
    step = a.decode_step_bytes(cfg, 19, 450)
    assert step - none == 19 * 450 * 811_008 + 19 * 2 * 2048
    assert step - none == pytest.approx(6.9e9, rel=1e-2)
    assert 100 * 4 * a.stack_bytes(cfg) / step == pytest.approx(58.5, abs=1)
    # 19.7 GFLOP a token; a 256-token chunk is 5 TFLOP.
    token = a.prefill_flops(cfg, 1, 0)
    assert token == 2 * 4 * 48 * per_layer
    assert token == pytest.approx(19.7e9, rel=5e-3)
    chunk = a.prefill_flops(cfg, 256, 256 * 257 / 2)
    assert chunk == pytest.approx(5.05e12, rel=2e-2)
    assert chunk - 256 * token == 192 * (256 * 257 / 2) * 4 * 16 * 128

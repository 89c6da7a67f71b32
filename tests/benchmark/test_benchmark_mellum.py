"""The Mellum 2 family (Mellum2-12B-A2.5B-Instruct, 16 of its 28 layers) in
the benchmark: its architecture file, its configuration (against the
catalog's published keys), its traffic mix and cell, and the reader that
came with it. Every manifest entry is found BY NAME and held to what it
holds, never to where it stands or how many there are: a later PR appends
behind these.

A rehearsal cell of the family's published key names at a toy size runs
whole on the CPU through benchmark/architectures/mellum.py (both of its
samples: a chunk ladder with a padded last chunk past three windows, then
decode through rings and pages, against the plain reference) and is
``correct``. (The wrong models, the YaRN table and what each cache holds
are in tests/test_mellum_parity.py.)
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

NAME = "mellum2-12b-a2.5b-instruct-l16"
CELL = NAME + ".code-context"
BENCH = os.path.join(ROOT, "benchmark")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = "page_step_share"
RINGS = "window_step_share"


def by_name(entries: list, name: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def tiny_mellum(name: str) -> dict:
    """The family's published keys at a toy size: two periods, a window
    of 8, 8 experts of which a token keeps 4 (so that one expert flipped
    by bfloat16's rounding is the weakest of four, not one of two)."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        real = json.load(f)
    cfg = tiny(name, architecture="mellum", model_type="mellum")
    cfg.pop("rope_theta")
    cfg.update(
        num_hidden_layers=8, layer_types=real["layer_types"][:8],
        mlp_layer_types=real["mlp_layer_types"][:8], sliding_window=8,
        moe_intermediate_size=64, num_experts=8, num_experts_per_tok=4,
        norm_topk_prob=True, rms_norm_eps=1e-6,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                "original_max_position_embeddings": 16, "beta_fast": 2,
                "beta_slow": 0.02},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000}},
        assumed=real["assumed"])
    cfg["stack"] = {**cfg["stack"], "SERVE_PREFILL_CHUNK": "32",
                    "SERVE_PREFIX": "1"}
    return cfg


def arch():
    return manifest.load_architecture(BENCH, "mellum")


@pytest.fixture(scope="module")
def mellum_root(tmp_path_factory):
    return write_benchmark(tmp_path_factory.mktemp("mellum"),
                           [tiny_mellum("tiny-mellum-cell")])


def test_rehearsal_cell_runs_whole_and_is_correct(mellum_root, on_cpu,
                                                  tmp_path, capsys):
    cell = manifest.load_cell("tiny-mellum-cell.tiny-open", mellum_root)
    assert cell.config["architecture"] == "mellum"
    last = run.run_cell(run_args(cell.name, 0, 4.0), time.monotonic(),
                        data_root=mellum_root, out_root=str(tmp_path))
    earlier = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    ref = next(x["reference"] for x in earlier if "reference" in x)
    a = arch()
    assert ref["ok"], ref
    assert 0 < ref["median"] <= ref["tolerance"]["median"] == a.TOL_MEDIAN
    assert 0 < ref["long_median"] <= a.TOL_MEDIAN
    assert abs(ref["window_edge"]) < ref["tolerance"]["window_edge"]
    assert last["correct"] and last["failed"] == 0
    assert last["attempted"] >= 10


def obs_of(cell, start, end, **kw):
    return metrics.Observations(
        records=kw.pop("records", []), ramp_s=0.0, window_s=51.0, cell=cell,
        counters_start=start, counters_end=end,
        peaks=roofline.peaks_for("TPU v5 lite"), **kw)


def test_the_two_shares_are_counter_bytes_over_the_steps_bytes():
    """20 rows at a context of 5,800 for 1,000 steps, on made-up
    observations: twelve rings honoured at 1,024 positions read a few
    percent of the step, rings that grew with their context 5.7 times
    that; four page layers at 5,800 positions outweigh the twelve
    rings."""
    cell = manifest.load_cell(CELL, ROOT)
    cfg, a = cell.config, arch()
    steps, rows, ctx = 1000.0, 20, 5800
    pos = a.window_position_bytes(cfg)
    rec = types.SimpleNamespace(ok=True, prompt_bytes=ctx - 101, tokens=200,
                                due_t=1.0, chunk_t=[1.0],
                                chunk_tokens=[rows * steps])
    ticks = {"serve_decode_ticks_total": steps / 4,
             "decode_fused_ticks_total": steps / 4,
             "decode_fused_steps_total": steps}

    def read(name, counter, moved):
        obs = obs_of(cell, {counter: 5.0, **dict.fromkeys(ticks, 0.0)},
                     {counter: 5.0 + moved, **ticks}, records=[rec])
        return manifest.load_reader(cell.root, name)(obs)

    step = a.decode_step_bytes(cfg, rows, ctx)
    honoured = read(RINGS, "serve_window_bytes_total",
                    steps * rows * 12 * 1024 * pos)
    assert honoured == pytest.approx(100 * rows * 12 * 1024 * pos / step)
    assert 2 < honoured < 5
    grown = read(RINGS, "serve_window_bytes_total",
                 steps * rows * 12 * ctx * pos)
    assert grown == pytest.approx(honoured * ctx / 1024)
    pages = read(NEW, "serve_page_kv_bytes_total",
                 steps * rows * 4 * ctx * pos)
    assert pages == pytest.approx(100 * rows * 4 * ctx * pos / step)
    assert honoured < pages < grown


def test_new_reader_reads_nothing_from_a_program_without_the_counter():
    """Laid over the parent's program (no such counter) the new reader
    returns None and does not raise."""
    cell = manifest.load_cell(CELL, ROOT)
    obs = obs_of(cell, {"serve_decode_row_steps_total": 0.0},
                 {"serve_decode_row_steps_total": 50.0})
    for name in (NEW, RINGS):
        assert manifest.load_reader(cell.root, name)(obs) is None, name


def test_configuration_is_the_catalogs_published_keys():
    """Every key of the catalog entry's ``config`` with its value but the
    depth and the two per-layer lists cut with it; no width, no expert,
    no vocabulary reduced; every assumption named."""
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Mellum2-12B-A2.5B-Instruct")
    cfg = manifest.load_cell(CELL, ROOT).config
    assert cfg["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types"}
    assert cfg["num_hidden_layers"] == 16
    for key in ("layer_types", "mlp_layer_types"):
        assert cfg[key] == entry["config"][key][:16]
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 4
    assert (cfg["hidden_size"], cfg["vocab_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["sliding_window"]) == (2304, 98304, 64, 8, 896, 1024)
    assert set(cfg["assumed"]) >= {
        "origin", "qk_norm", "rotary_layout", "window", "yarn", "routing",
        "mtp_head", "biases", "ignore_eos"}
    assert cfg["stands_for"].startswith("the first of two pipeline stages")
    stack = dict(cfg["stack"])
    # The two the sweep on the chip chose (PERF.md section 6, PR 40): a
    # chunk that divides every warmed bucket past the smallest, and the
    # fused steps between two chunks.
    chunk = int(stack.pop("SERVE_PREFILL_CHUNK"))
    assert chunk in (512, 1024, 2048) and 16384 % chunk == 0
    assert int(stack.pop("SERVE_FUSE")) in (1, 2, 4)
    assert stack == {
        "SERVE_QUANT": "int8", "SERVE_KV": "paged",
        "SERVE_KV_QUANT": "int8", "SERVE_PREFIX": "1", "SERVE_SLOTS": "32",
        "SERVE_MAX_SEQ": "16384", "SERVE_PAGE_SIZE": "64",
        "SERVE_PAGES": str(32 * 16384 // 64 + 1)}


def test_cell_mix_and_manifest_entries_by_name():
    man = manifest.load_manifest(ROOT)
    cell = manifest.load_cell(CELL, ROOT)
    entry = by_name(man["configs"], NAME)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "mlp_layer_types"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == cell.config["source"]
    w = by_name(man["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        NAME, "code-context", 1)
    assert len(w["why"]) <= 200 and len(entry["why"]) <= 200
    t = cell.traffic
    assert (t["loop"], t["clients"]) == ("closed", 24)
    assert len(t["prompt"]["head"]) == 88
    assert t["prompt"]["body_tokens"] == {
        "dist": "lognormal", "median": 5000, "sigma": 0.6, "min": 1024,
        "max": 14336}
    assert t["output_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.4, "min": 96,
        "max": 384}
    assert t["options"] == {"temperature": 0}
    assert set(t["warmup_buckets"]) >= {2048, 4096, 8192, 16384}
    assert (t["stratify"], t["design_seed"]) == (32, 22)
    # The longest prompt and its output fit the serving budget.
    assert (88 + 1 + t["prompt"]["body_tokens"]["max"]
            + len(t["prompt"]["tail"]) + t["output_tokens"]["max"]
            <= int(cell.config["stack"]["SERVE_MAX_SEQ"]))
    assert not os.path.exists(os.path.join(BENCH, "cells", CELL + ".json"))
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms",
                                                    "setup_s"}
    assert CELL in by_name(man["end_to_end"], "tpot_p50_ms")["workloads"]
    names = {m["name"] for m in cell.per_layer}
    assert {RINGS, "attn_ctx_mean", "decode_bw_util_family",
            "prefill_flops_util", "moe_drop_share", "out_tok_s",
            "kv_pages_peak", "tick_ms", "pallas_share", "device_idle",
            "hbm_peak_gb", "prefill_pad_share", "device_wait_share",
            "prefill_device_share", NEW} <= names
    assert any(n.startswith("decode_step") for n in names)
    # Not the five host-side metrics of PR 34, whose lists of cells
    # tests/benchmark/test_benchmark_host_side.py pins (ROADMAP S1b); nor
    # decode_rows_mean, which is the steady cells' and moves itl_p50_ms.
    # The rings report under window_step_share, the one metric of a ring
    # (ISSUE 40), though test_benchmark_phi4flash.py pins its list to its
    # own cell and now fails by construction (PERF.md section 7 (xv)).
    assert not names & {"admit_host_ms", "launch_ms", "loop_offcpu_share",
                        "launch_starved_share", "stream_handoff_ms",
                        "decode_rows_mean", "shared_kv_step_share",
                        "state_step_share"}
    m = by_name(man["per_layer"], NEW)
    assert CELL in m["workloads"] and m["unit"] == "%"
    assert (m["moves"], m["source"]) == ("tpot_p50_ms", "program_counter")
    assert m["layer"] == by_name(man["per_layer"],
                                 "shared_kv_step_share")["layer"]
    assert CELL in by_name(man["per_layer"], RINGS)["workloads"]
    for m in cell.per_layer:
        manifest.load_reader(cell.root, m["name"])


def test_architecture_file_keeps_the_contract_and_imports_no_program():
    a = arch()
    for fn in manifest.ARCHITECTURE_FUNCTIONS + ("system_logits",
                                                 "wrong_models"):
        assert callable(getattr(a, fn)), fn
    assert callable(a.decode_step_bytes) and callable(a.prefill_flops)
    assert set(a.WRONG) >= {
        "yarn_on_window_layers", "plain_on_full_layers", "factor_dropped",
        "factor_once", "window_one_short", "window_one_long",
        "window_unbounded", "weights_not_renormalised", "int4_weights"}
    # The reference shares nothing with the program's models: the only
    # imports from the package are the cache classes system_logits
    # drives and the dataclass model_config fills.
    with open(a.__file__) as f:
        text = f.read()
    assert "p2p_llm_chat_tpu.models.nemotron_h" not in text
    assert "models.layers" not in text and "models import" not in text
    cfg = manifest.load_cell(CELL, ROOT).config
    assert a.layer_kinds(cfg) == ["window", "window", "window", "full"] * 4
    assert a.layer_counts(cfg) == {"window": 12, "full": 4}
    assert a.pattern(cfg) == "wEwEwE*E" * 4
    with pytest.raises(ValueError, match="do not describe"):
        a.layer_kinds({**cfg, "num_hidden_layers": 28})
    # The long sample at the candidate chunks: whole chunks and 11/16 of
    # a padded one, at least 3.32 windows of 1,024; 8 decode steps.
    assert a.long_shape(2048, 1024) == (3456, 8)
    assert a.long_shape(1024, 1024) == (3776, 8)
    assert a.long_shape(512, 1024) == (3424, 8)
    at = a.long_positions(1024, 1024)
    assert at[0] == 0 and list(at[-9:]) == list(range(3775, 3784))
    # Dense just past the first window and just past each wrap.
    for edge in (1024, 2048, 3072):
        assert set(range(edge, edge + 16)) <= set(at.tolist())
    assert len(at) < 600


def test_the_yarn_table_is_the_closed_form_without_jax():
    """``rope_table`` in plain Python at the published numbers: low 18,
    high 35, frequencies 0-18 untouched, 35-63 divided by 16, the ramp
    linear between, the factor 0.1 ln 16 + 1; the window layers' table
    plain."""
    import math
    cfg = manifest.load_cell(CELL, ROOT).config
    a = arch()
    assert a.yarn_ramp(cfg["rope_parameters"]["full_attention"], 128) == (
        18, 35)
    full, factor = a.rope_table(cfg, "full")
    window, one = a.rope_table(cfg, "window")
    assert one == 1.0 and factor == 1.2772588722239782
    assert factor == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    plain = [500000.0 ** (-2.0 * i / 128) for i in range(64)]
    assert window == plain
    assert full[:19] == plain[:19]
    assert full[35:] == pytest.approx([f / 16 for f in plain[35:]])
    for i in range(19, 35):
        r = (i - 18) / 17
        assert full[i] == pytest.approx(plain[i] * ((1 - r) + r / 16))
    # Without attention_factor in the file it is computed.
    rp = {**cfg["rope_parameters"]}
    rp["full_attention"] = {k: v for k, v in rp["full_attention"].items()
                            if k != "attention_factor"}
    assert a.rope_table({**cfg, "rope_parameters": rp}, "full")[1] == \
        pytest.approx(factor, rel=1e-12)


def test_engine_weights_hand_the_tree_back():
    import jax
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.models import family_for
    config = serve_cell.model_config(tiny_mellum("t"))
    model = family_for(config)
    p = model.init_params_quantized(config, jax.random.PRNGKey(3))
    weights = arch().engine_weights(types.SimpleNamespace(
        _params=p, config=config, mesh=None))
    deq = lambda w, *at: np.asarray(w.q[at], np.float32) * np.asarray(
        w.s[at], np.float32)
    w = weights.layer(3)                    # the first full layer
    np.testing.assert_array_equal(w["attn"]["wqkv"], deq(p["attn"]["wqkv"],
                                                         3))
    np.testing.assert_array_equal(w["attn"]["wo"], deq(p["attn"]["wo"], 3))
    np.testing.assert_array_equal(
        w["moe"]["router"], np.asarray(p["moe"]["router"][3], np.float32))
    assert set(w["moe"]) == {"norm", "router"}
    wgu, wd = weights.expert(5, 6)
    np.testing.assert_array_equal(wgu, deq(p["moe"]["wgu_e"], 5, 6))
    np.testing.assert_array_equal(wd, deq(p["moe"]["w_down"], 5, 6))
    assert wgu.shape == (128, 128) and wd.shape == (64, 128)
    q, s = weights.lm_head
    assert q.shape == (128, 512) and q.dtype == np.int8


def test_counts_are_the_hand_arithmetics():
    """ISSUE 40's table: a layer is 21.23 M (attention) + 64 x 6.19 M +
    0.15 M; 16 of them 6.68 G; a ring position and a page token 1,056
    bytes; a decode step at 20 rows and 5,800 of context reads the whole
    6.9 GB of layers and head, 0.26 GB of rings and 0.49 GB of pages."""
    cfg = manifest.load_cell(CELL, ROOT).config
    a = arch()
    shapes = a.layer_shapes(cfg)
    params = lambda kind: sum(i * o for i, o in shapes[kind])
    assert params("attn") == 2304 * 5120 + 4096 * 2304 == 21_233_664
    assert params("expert") == 3 * 2304 * 896 == 6_193_152
    layer = params("attn") + 64 * params("expert") + 2304 * 64
    assert layer == pytest.approx(417.7e6, rel=0.001)
    whole = 28 * layer + 2 * 98304 * 2304
    assert whole == pytest.approx(12.15e9, rel=0.002)
    active = 28 * (params("attn") + 8 * params("expert") + 2304 * 64) \
        + 2 * 98304 * 2304
    assert active == pytest.approx(2.44e9, rel=0.005)
    assert a.page_token_bytes(cfg) == a.window_position_bytes(cfg) == \
        2 * 4 * (128 + 4) == 1056
    step = a.decode_step_bytes(cfg, 20, 5800)
    layers = 16 * layer
    head = 2304 * 98304
    rings = 12 * 20 * 1024 * 1056
    pages = 4 * 20 * 5800 * 1056
    assert layers + head == pytest.approx(6.9e9, rel=0.005)
    assert rings == pytest.approx(0.26e9, rel=0.01)
    assert pages == pytest.approx(0.49e9, rel=0.01)
    # int8 with a float32 scale a column, the router float32: within half
    # a percent of a byte a parameter.
    assert step == pytest.approx(layers + head + rings + pages, rel=0.005)
    # A step of few rows reaches rows x 8 experts a layer, not all 64.
    few = a.decode_step_bytes(cfg, 2, 0)
    assert few == pytest.approx(
        16 * (params("attn") + 16 * params("expert")) + head, rel=0.01)
    # A window layer's read stops growing at 1,024; the pages' does not.
    assert (a.decode_step_bytes(cfg, 20, 9000)
            - a.decode_step_bytes(cfg, 20, 8000)) == pytest.approx(
        4 * 20 * 1000 * 1056)
    assert (a.decode_step_bytes(cfg, 20, 900)
            - a.decode_step_bytes(cfg, 20, 800)) == pytest.approx(
        16 * 20 * 100 * 1056)
    # A prompt token: two FLOPs a parameter it reaches (8 experts); a
    # pair: 32 heads x 128 x 4.
    per_token = a.prefill_flops(cfg, 1, 0)
    assert per_token == pytest.approx(
        2 * 16 * (params("attn") + 8 * params("expert") + 2304 * 64))
    assert a.prefill_flops(cfg, 1, 10) - per_token == pytest.approx(
        10 * 16 * 32 * 128 * 4)
    assert a.prefill_flops(cfg, 1, 5000) - per_token == pytest.approx(
        (4 * 5000 + 12 * 1024) * 32 * 128 * 4)

"""The LFM2 family (LFM2-8B-A1B, whole) in the benchmark: its architecture
file, its configuration (against the catalog's published keys), its
traffic mix and cell. Every manifest entry is found BY NAME and held to
what it holds, never to where it stands, how many there are or what a
whole list equals: a later PR appends behind these.

A rehearsal cell of the family's published key names at a toy size runs
whole on the CPU through benchmark/architectures/lfm2.py (both of its
samples: a chunk ladder with a padded last chunk, then decode through
convolution windows and pages, against the plain reference) and is
``correct``. (The wrong models, the router's rule and what each cache
holds are in tests/test_lfm2_parity.py.)
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

NAME = "lfm2-8b-a1b"
CELL = NAME + ".thread-recap"
BENCH = os.path.join(ROOT, "benchmark")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def by_name(entries: list, name: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def tiny_lfm2(name: str) -> dict:
    """The family's published keys at a toy size: two dense layers, then
    attention at layers 2, 5, 8, 11 and 13 of 15 (three whole periods, so
    one scan, and a short tail), a head of 64, 8 experts of which a token
    keeps 4 (so that one expert flipped by bfloat16's rounding is the
    weakest of four, not one of two)."""
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        real = json.load(f)
    cfg = tiny(name, architecture="lfm2", model_type="lfm2_moe")
    for key in ("rms_norm_eps", "tie_word_embeddings"):
        cfg.pop(key)
    conv, attn = "conv", "full_attention"
    cfg.update(
        num_hidden_layers=15, num_attention_heads=8, num_key_value_heads=4,
        head_dim=64, intermediate_size=192, moe_intermediate_size=64,
        layer_types=([conv, conv, attn] * 4 + [conv, attn, conv]),
        num_dense_layers=2, num_experts=8, num_experts_per_tok=4,
        norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1,
        conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
        assumed=real["assumed"])
    cfg["stack"] = {**cfg["stack"], "SERVE_PREFILL_CHUNK": "32",
                    "SERVE_PREFIX": "1"}
    return cfg


def arch():
    return manifest.load_architecture(BENCH, "lfm2")


@pytest.fixture(scope="module")
def lfm2_root(tmp_path_factory):
    root = write_benchmark(tmp_path_factory.mktemp("lfm2"),
                           [tiny_lfm2("tiny-lfm2-cell")])
    # The long sample at toy size: 100 positions and more, not 3,500.
    path = os.path.join(root, "benchmark", "architectures", "lfm2.py")
    with open(path) as f:
        text = f.read()
    assert text.count("LONG_MIN = 3500") == 1
    with open(path, "w") as f:
        f.write(text.replace("LONG_MIN = 3500", "LONG_MIN = 100"))
    return root


def test_rehearsal_cell_runs_whole_and_is_correct(lfm2_root, on_cpu,
                                                  tmp_path, capsys):
    cell = manifest.load_cell("tiny-lfm2-cell.tiny-open", lfm2_root)
    assert cell.config["architecture"] == "lfm2"
    last = run.run_cell(run_args(cell.name, 0, 4.0), time.monotonic(),
                        data_root=lfm2_root, out_root=str(tmp_path))
    earlier = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    ref = next(x["reference"] for x in earlier if "reference" in x)
    a = arch()
    assert ref["ok"], ref
    # The routers' choices were handed out and replayed, and every limit
    # of the family's own was read and held.
    assert ref["replayed"]
    limits = ref["tolerance"]
    assert limits["median"] == limits["long_median"] == a.TOL_MEDIAN
    assert limits["max"] == limits["long_max"] == a.TOL_MAX
    for name in ("median", "long_median", "max", "long_max"):
        assert 0 < ref[name] <= limits[name], name
    assert 0 < max(ref["decode_max"], ref["starts_max"]) <= a.TOL_MAX
    assert 0 <= ref["flips"] <= limits["flips"] == a.TOL_FLIPS
    assert last["correct"] and last["failed"] == 0
    assert last["attempted"] >= 10


def obs_of(cell, start, end, **kw):
    return metrics.Observations(
        records=kw.pop("records", []), ramp_s=0.0, window_s=51.0, cell=cell,
        counters_start=start, counters_end=end,
        peaks=roofline.peaks_for("TPU v5 lite"), **kw)


def test_the_shares_are_counter_bytes_over_the_steps_bytes():
    """28 rows at a context of 4,800 for 1,000 steps, on made-up
    observations: six page layers read a tenth of the step, the eighteen
    windows (read and written for every slot's row) a thousandth."""
    cell = manifest.load_cell(CELL, ROOT)
    cfg, a = cell.config, arch()
    steps, rows, ctx = 1000.0, 28, 4800
    rec = types.SimpleNamespace(ok=True, prompt_bytes=ctx - 257, tokens=512,
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
    token = a.page_token_bytes(cfg)
    pages = read("page_step_share", "serve_page_kv_bytes_total",
                 steps * rows * 6 * ctx * token)
    assert pages == pytest.approx(100 * rows * 6 * ctx * token / step)
    assert 8 < pages < 11
    state = read("state_step_share", "serve_state_bytes_total",
                 steps * 2 * 32 * a.window_row_bytes(cfg))
    assert state == pytest.approx(
        100 * 2 * 32 * a.window_row_bytes(cfg) / step)
    assert 0.05 < state < 0.2


def test_readers_read_nothing_from_a_program_without_the_counters():
    """Laid over the parent's program (which cannot run the cell, and has
    no such counter for it) the readers the cell reports under return
    None and do not raise."""
    cell = manifest.load_cell(CELL, ROOT)
    obs = obs_of(cell, {"serve_decode_row_steps_total": 0.0},
                 {"serve_decode_row_steps_total": 50.0})
    for name in ("page_step_share", "state_step_share", "state_live_share",
                 "attn_ctx_mean", "moe_drop_share"):
        assert manifest.load_reader(cell.root, name)(obs) is None, name


def test_configuration_is_the_catalogs_published_keys():
    """Every key of the catalog entry's ``config`` with its value; nothing
    reduced; every assumption named."""
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "LFM2-8B-A1B")
    cfg = manifest.load_cell(CELL, ROOT).config
    assert cfg["source"] == entry["source_url"]
    assert {k for k, v in entry["config"].items() if cfg.get(k) != v} == set()
    assert cfg["reduced"] == {}
    assert cfg["architecture"] == "lfm2"
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert cfg["layer_types"].count("conv") == 18
    assert (cfg["hidden_size"], cfg["vocab_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"], cfg["num_dense_layers"],
            cfg["conv_L_cache"]) == (2048, 65536, 32, 4, 1792, 7168, 2, 3)
    assert set(cfg["assumed"]) >= {
        "origin", "tied_head", "embedding_norm", "gate_order", "tap_order",
        "no_activation", "qk_norm", "rotary_layout", "dense_layers",
        "routing", "biases", "ignore_eos"}
    assert cfg["stands_for"].startswith("one whole replica on one chip")
    stack = dict(cfg["stack"])
    chunk = int(stack.pop("SERVE_PREFILL_CHUNK"))
    assert chunk in (512, 1024) and 16384 % chunk == 0
    assert int(stack.pop("SERVE_FUSE")) in (2, 4)
    # Pages for 32 rows at the mix's longest context at least.
    pages = int(stack.pop("SERVE_PAGES"))
    assert 6700 <= pages <= 32 * 16384 // 64 + 1
    assert stack == {
        "SERVE_QUANT": "int8", "SERVE_KV": "paged",
        "SERVE_KV_QUANT": "int8", "SERVE_PREFIX": "1", "SERVE_SLOTS": "32",
        "SERVE_MAX_SEQ": "16384", "SERVE_PAGE_SIZE": "64"}


def test_cell_mix_and_manifest_entries_by_name():
    man = manifest.load_manifest(ROOT)
    cell = manifest.load_cell(CELL, ROOT)
    entry = by_name(man["configs"], NAME)
    assert entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == cell.config["source"]
    w = by_name(man["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        NAME, "thread-recap", 1)
    assert len(w["why"]) <= 200 and len(entry["why"]) <= 200
    assert "1-13 K" in w["why"] and "25 live rows" in w["why"]
    assert "nothing cut" in w["why"]
    t = cell.traffic
    assert (t["loop"], t["clients"]) == ("closed", 36)
    assert len(t["prompt"]["head"]) == 88
    assert t["prompt"]["body_tokens"] == {
        "dist": "lognormal", "median": 4000, "sigma": 0.55, "min": 1024,
        "max": 12288}
    assert t["output_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.35, "min": 256,
        "max": 1024}
    assert t["options"] == {"temperature": 0}
    assert set(t["warmup_buckets"]) >= {2048, 4096, 8192, 16384}
    assert (t["stratify"], t["design_seed"]) == (32, 22)
    assert t["seed_jitter"] == {"arrival_s": 0.05, "length": 0.03}
    assert "16,384 bucket" in t["what"]
    # The longest prompt and its output fit the serving budget, and 32 of
    # them the page pool.
    longest = (88 + 1 + t["prompt"]["body_tokens"]["max"]
               + len(t["prompt"]["tail"]) + t["output_tokens"]["max"])
    stack = cell.config["stack"]
    assert longest <= int(stack["SERVE_MAX_SEQ"])
    assert 32 * -(-(longest + 1) // 64) < int(stack["SERVE_PAGES"])
    assert not os.path.exists(os.path.join(BENCH, "cells", CELL + ".json"))
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms",
                                                    "setup_s"}
    assert CELL in by_name(man["end_to_end"], "tpot_p50_ms")["workloads"]
    names = {m["name"] for m in cell.per_layer}
    assert {"out_tok_s", "kv_pages_peak", "tick_ms", "pallas_share",
            "device_idle", "hbm_peak_gb", "prefill_pad_share",
            "device_wait_share", "prefill_device_share",
            "moe_drop_share", "attn_ctx_mean", "prefill_flops_util",
            "page_step_share", "state_step_share", "state_live_share",
            "boot_compile_s"} <= names
    assert any(n.startswith("decode_step") for n in names)
    # Not ``decode_bw_util_family`` (ISSUE 45 asked for it): its reader
    # hands ``decode_step_bytes`` rows and a context and no count of the
    # experts a step reached, and here a step reaches far from all of
    # them, so the share over-reads (PERF.md section 7(xviii)).
    assert "decode_bw_util_family" not in names
    # Not the five host-side metrics of PR 34, whose lists of cells
    # tests/benchmark/test_benchmark_host_side.py pins (ROADMAP S1b); nor
    # the steady cells', nor a ring's or a shared page layer's.
    assert not names & {"admit_host_ms", "launch_ms", "loop_offcpu_share",
                        "launch_starved_share", "stream_handoff_ms",
                        "decode_rows_mean", "window_step_share",
                        "shared_kv_step_share", "moe_local_share"}
    for name in ("page_step_share", "state_step_share", "state_live_share"):
        m = by_name(man["per_layer"], name)
        assert CELL in m["workloads"] and m["moves"] == "tpot_p50_ms"
    for m in cell.per_layer:
        manifest.load_reader(cell.root, m["name"])
    # No reader came with this PR: the trace's reduction keeps the ten
    # longest operations by HLO line, not a kernel's time by its name
    # (PERF.md section 7).
    assert not [m for m in man["per_layer"]
                if m["name"] == "page_attn_bw_util"]


def test_architecture_file_keeps_the_contract_and_imports_no_program():
    a = arch()
    for fn in manifest.ARCHITECTURE_FUNCTIONS + ("system_logits",
                                                 "wrong_models"):
        assert callable(getattr(a, fn)), fn
    assert callable(a.decode_step_bytes) and callable(a.prefill_flops)
    for fn in ("flash_append_cost", "page_token_bytes", "parameter_count"):
        assert callable(getattr(a, fn)), fn
    assert set(a.WRONG) >= {
        "no_expert_bias", "biased_scores_as_weights", "taps_reversed",
        "window_of_one", "window_of_three", "c_gate_left_out",
        "qk_norm_left_out", "qk_norm_whole_projection", "int4_weights"}
    with open(a.__file__) as f:
        text = f.read()
    assert "p2p_llm_chat_tpu.models.nemotron_h" not in text
    assert "models.layers" not in text and "models import" not in text
    assert "models.pangu" not in text
    cfg = manifest.load_cell(CELL, ROOT).config
    kinds = a.layer_kinds(cfg)
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [
        2, 6, 10, 14, 18, 21]
    assert a.layer_counts(cfg) == {"conv": 18, "attn": 6, "dense": 2,
                                   "routed": 22}
    assert a.pattern(cfg) == ("c-c-*E" + "cEcEcE*E" * 4 + "cEcE*E"
                              + "cEcE")
    assert a.head_dim(cfg) == 64
    with pytest.raises(ValueError, match="does not describe"):
        a.layer_kinds({**cfg, "num_hidden_layers": 16})
    with pytest.raises(ValueError, match="no bias"):
        a.model_config({**cfg, "conv_bias": True})
    # The long sample at the candidate chunks: whole chunks and 11/16 of
    # a padded one, at least 3,500 positions; 8 decode steps.
    assert a.long_shape(1024) == (3776, 8)
    assert a.long_shape(512) == (3936, 8)
    at = a.long_positions(1024)
    assert at[0] == 0 and list(at[-9:]) == list(range(3775, 3784))
    for start in (1024, 2048, 3072):        # where a chunk reads the carry
        assert {start, start + 1, start + 2} <= set(at.tolist())
    assert len(at) < 500


def test_engine_weights_hand_the_tree_back():
    import jax
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.models import family_for
    config = serve_cell.model_config(tiny_lfm2("t"))
    model = family_for(config)
    p = model.init_params_quantized(config, jax.random.PRNGKey(3))
    weights = arch().engine_weights(types.SimpleNamespace(
        _params=p, config=config, mesh=None))
    deq = lambda w, *at: np.asarray(w.q[at], np.float32) * np.asarray(
        w.s[at], np.float32)
    w = weights.layer(0)                    # conv, dense
    np.testing.assert_array_equal(w["op"]["w_in"], deq(p["conv"]["w_in"], 0))
    assert set(w["op"]) == {"norm", "conv_w", "w_in", "w_out"}
    assert w["op"]["conv_w"].shape == (3, 128)
    np.testing.assert_array_equal(w["ff"]["w_gu"], deq(p["mlp"]["w_gu"], 0))
    w = weights.layer(5)                    # the second attention, routed
    np.testing.assert_array_equal(w["op"]["wqkv"], deq(p["attn"]["wqkv"], 1))
    assert w["op"]["q_norm"].shape == w["op"]["k_norm"].shape == (64,)
    np.testing.assert_array_equal(
        w["ff"]["router"], np.asarray(p["moe"]["router"][3], np.float32))
    assert set(w["ff"]) == {"norm", "router", "router_bias"}
    w = weights.layer(3)                    # the third conv layer
    np.testing.assert_array_equal(w["op"]["w_out"],
                                  deq(p["conv"]["w_out"], 2))
    wgu, wd = weights.expert(5, 6)          # layer 5 is routed layer 3
    np.testing.assert_array_equal(wgu, deq(p["moe"]["wgu_e"], 3, 6))
    np.testing.assert_array_equal(wd, deq(p["moe"]["w_down"], 3, 6))
    assert wgu.shape == (128, 128) and wd.shape == (64, 128)
    # The tied head: an int8 copy of the embedding transposed.
    q, s = weights.lm_head
    assert q.shape == (128, 512) and q.dtype == np.int8
    head = np.asarray(q, np.float32) * np.asarray(s, np.float32)
    np.testing.assert_allclose(head, np.asarray(p["embed"], np.float32).T,
                               atol=float(np.asarray(s).max()))


def test_counts_are_the_hand_arithmetics():
    """ISSUE 45's table: 22 x 352.3 M experts + 18 x 16.8 M conv + 6 x
    10.5 M attention + 2 x 44.0 M dense + 134 M embedding = 8.34 G, about
    1.5 G active; a page token 1,056 bytes; a decode step at 28 rows and
    4,800 of context reads 8.4 GB of weights and 0.85 GB of pages."""
    cfg = manifest.load_cell(CELL, ROOT).config
    a = arch()
    shapes = a.layer_shapes(cfg)
    params = lambda kind: sum(i * o for i, o in shapes[kind])
    assert params("conv") == 2048 * 6144 + 2048 * 2048 == 16_777_216
    assert params("attn") == 2048 * 3072 + 2048 * 2048 == 10_485_760
    assert params("dense") == 3 * 2048 * 7168 == 44_040_192
    assert params("expert") == 3 * 2048 * 1792 == 11_010_048
    assert 32 * params("expert") == pytest.approx(352.3e6, rel=0.001)
    whole = (22 * 32 * params("expert") + 18 * params("conv")
             + 6 * params("attn") + 2 * params("dense") + 65536 * 2048)
    assert whole == pytest.approx(8.34e9, rel=0.002)
    total, active = a.parameter_count(cfg)
    # The function adds the routers, the taps and the norms: 1.7 M more.
    assert total == pytest.approx(whole, rel=0.0005) and total > whole
    assert total - active == 22 * 28 * params("expert")
    assert active == pytest.approx(1.5e9, rel=0.05)
    assert a.page_token_bytes(cfg) == 2 * (8 * 64 + 4 * 4) == 1056
    assert a.window_row_bytes(cfg) == 18 * 2 * 2048 * 2
    rows, ctx = 28, 4800
    step = a.decode_step_bytes(cfg, rows, ctx)
    weights = whole - 65536 * 2048 + 65536 * 2048     # head int8 once
    pages = 6 * rows * ctx * 1056
    assert pages == pytest.approx(0.85e9, rel=0.01)
    # int8 with a float32 scale a column, the routers float32, the rows'
    # embeddings and windows: within half a percent of a byte a
    # parameter and the pages.
    assert step == pytest.approx(weights + pages, rel=0.005)
    assert step / 819e9 == pytest.approx(11.3e-3, rel=0.02)
    # A step of few rows reaches rows x 4 experts a layer, not all 32.
    few = a.decode_step_bytes(cfg, 2, 0)
    assert few == pytest.approx(
        22 * 8 * params("expert") + 18 * params("conv") + 6 * params("attn")
        + 2 * params("dense") + 65536 * 2048, rel=0.01)
    # The pages' read grows with the context in six layers; the windows'
    # does not grow at all.
    assert (a.decode_step_bytes(cfg, 28, 9000)
            - a.decode_step_bytes(cfg, 28, 8000)) == pytest.approx(
        6 * 28 * 1000 * 1056)
    # A prompt token: two FLOPs a parameter it reaches (4 experts) and
    # the convolution's three taps and two gates; a pair: 32 heads x 64 x
    # 4 in each of six layers.
    per_token = a.prefill_flops(cfg, 1, 0)
    assert per_token == pytest.approx(2 * (
        18 * (params("conv") + 4 * 2048) + 6 * params("attn")
        + 2 * params("dense")
        + 22 * (2048 * 32 + 4 * params("expert"))))
    assert per_token == pytest.approx(2.85e9, rel=0.01)     # 3.1 with the head
    assert a.prefill_flops(cfg, 1, 10) - per_token == pytest.approx(
        10 * 6 * 32 * 64 * 4)
    # 4.6 K of prompt: the issue's 15 TFLOP.
    assert a.prefill_flops(cfg, 4600, 4600 * 4601 / 2) == pytest.approx(
        15e12, rel=0.1)
    # The kernel on the paired pool: every cached position's K, V and
    # scales once, and each query's dots over a pair's 128-wide row.
    flops, nbytes = a.flash_append_cost(cfg, 28, 4800)
    assert nbytes == pages
    assert flops == 6 * 28 * 4800 * 4 * 32 * 128

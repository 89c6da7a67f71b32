"""The Granite 4.0-H family (granite-4.0-h-micro, whole, at 64 slots) in
the benchmark: its architecture file, its configuration (against the
catalog's published keys, key by key), its traffic mix and cell, and the
two readers that came with it. Every manifest entry is found BY NAME and
by presence and held to what it holds, never to where it stands or how
many there are: a later PR appends behind these.

A rehearsal cell of the family's published key names at a toy size runs
whole on the CPU through benchmark/architectures/granite_hybrid.py (a
chunk ladder, the install into page pool and state pool, decode steps,
both samples against the plain reference) and is ``correct``. (The wrong
models and what each cache holds are in tests/test_granite_parity.py and
tests/test_engine_granite.py.)
"""

import json
import os
import time

import pytest

from rehearsal_files import (ROOT, on_cpu, run_args, tiny,  # noqa: F401
                             write_benchmark)

from benchmark import manifest, metrics, roofline, run

NAME = "granite-4.0-h-micro"
CELL = NAME + ".draft-crowd"
BENCH = os.path.join(ROOT, "benchmark")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = 36 * (2_097_152 + 26_112)     # a row's state and window, all layers
POOL = {"serve_state_pool_bytes": 65.0 * ROW}   # 64 slots and the garbage row


def by_name(entries: list, name: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def tiny_granite(name: str) -> dict:
    """The family's published keys at a toy size: two periods of three
    Mamba-2 layers to one attention layer at a head of 64."""
    cfg = tiny(name, architecture="granite_hybrid",
               model_type="granitemoehybrid")
    for key in ("head_dim", "rope_theta"):
        cfg.pop(key)
    cfg.update(
        hidden_size=256, num_hidden_layers=8,
        layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
        intermediate_size=192, shared_intermediate_size=192,
        num_local_experts=0, num_experts_per_tok=0,
        position_embedding_type="nope", rope_theta=10000,
        mamba_n_heads=4, mamba_d_head=16, mamba_n_groups=1,
        mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=16,
        embedding_multiplier=6, residual_multiplier=0.3,
        attention_multiplier=0.03125, logits_scaling=4,
        tie_word_embeddings=True)
    cfg["stack"] = {**cfg["stack"], "SERVE_PREFILL_CHUNK": "32",
                    "SERVE_PREFIX": "1", "SERVE_PAGE_SIZE": "16",
                    "SERVE_SLOTS": "6"}
    return cfg


def arch():
    return manifest.load_architecture(BENCH, "granite_hybrid")


@pytest.fixture(scope="module")
def granite_root(tmp_path_factory):
    return write_benchmark(tmp_path_factory.mktemp("granite"),
                           [tiny_granite("tiny-granite-cell")])


def test_rehearsal_cell_runs_whole_and_is_correct(granite_root, on_cpu,
                                                  tmp_path, capsys):
    cell = manifest.load_cell("tiny-granite-cell.tiny-open", granite_root)
    assert cell.config["architecture"] == "granite_hybrid"
    last = run.run_cell(run_args(cell.name, 0, 4.0), time.monotonic(),
                        data_root=granite_root, out_root=str(tmp_path))
    earlier = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    ref = next(x["reference"] for x in earlier if "reference" in x)
    a = arch()
    assert ref["ok"], ref
    assert ref["tolerance"] == {
        "median": a.TOL_MEDIAN, "max": a.TOL_MAX,
        "long_median": a.TOL_MEDIAN, "long_max": a.TOL_MAX,
        "state_error": a.TOL_STATE, "scale_edge": a.TOL_EDGE}
    for name in ("median", "max", "long_median", "long_max",
                 "state_error"):
        assert 0 < ref[name] <= ref["tolerance"][name], name
    assert abs(ref["scale_edge"]) < 0.1
    assert ref["positions"] == 2 * (128 + 8)
    assert last["correct"] and last["failed"] == 0
    assert last["attempted"] >= 10


def obs_of(cell, start, end, **kw):
    return metrics.Observations(
        records=kw.pop("records", []), ramp_s=0.0, window_s=51.0, cell=cell,
        counters_start=start, counters_end=end,
        peaks=roofline.peaks_for("TPU v5 lite"), **kw)


def test_the_two_readers_on_recorded_counters():
    """2,000 steps of 64 rows in 51 s, 300 admissions of two entries, on
    made-up observations."""
    cell = manifest.load_cell(CELL, ROOT)
    steps, rows, admits = 2000.0, 64, 300
    moved = 2 * rows * ROW * steps
    installed = 2 * admits * ROW
    start = {"serve_state_bytes_total": 7.0, **POOL,
             "serve_admit_rows_padded_total": 3.0}
    end = {"serve_state_bytes_total": 7.0 + moved, **POOL,
           "serve_admit_rows_padded_total": 3.0 + 2 * admits}
    obs = obs_of(cell, start, end)
    bw = obs.peaks["hbm_bytes_per_s"]
    util = manifest.load_reader(cell.root, "state_bw_util")(obs)
    assert util == pytest.approx(100 * moved / (51.0 * bw))
    assert 45 < util < 48
    share = manifest.load_reader(cell.root, "state_install_share")(obs)
    assert share == pytest.approx(100 * installed / (installed + moved))
    assert 0.2 < share < 0.25
    # A step cannot move 64 rows faster than the chip's bandwidth lets
    # it, so a window of nothing but such steps reads 100 and no more:
    # 51 s x 819 GB/s / (2 x 64 x a row) steps.
    most = 51.0 * bw / (2 * rows * ROW)
    full = obs_of(cell, {"serve_state_bytes_total": 0.0},
                  {"serve_state_bytes_total": 2 * rows * ROW * most})
    assert manifest.load_reader(cell.root, "state_bw_util")(full) \
        == pytest.approx(100.0)
    assert most / 51.0 == pytest.approx(83.7, abs=0.1)   # 11.9 ms a step


def test_the_two_readers_end_at_the_last_sample_inside_the_window():
    """A traced run's closing scrape waits for ``stop_trace`` and then
    holds the drain behind the window: steps the window's seconds did not
    pay for. The readers take the last 2 Hz sample inside the window and
    the seconds up to it, so the share of a bandwidth cannot pass 100 by
    counting a drain."""
    cell = manifest.load_cell(CELL, ROOT)
    per_s = 2 * 64 * ROW * 40.0             # 40 steps of 64 rows a second

    def counters(t, admits):
        return {"serve_state_bytes_total": per_s * t, **POOL,
                "serve_admit_rows_padded_total": 2.0 * admits}

    late = counters(90.0, 700)              # 39 s of drain behind 51
    obs = obs_of(cell, counters(0.0, 0), late,
                 samples=[(25.0, counters(25.0, 250)),
                          (50.5, counters(50.5, 505)), (110.0, late)])
    bw = obs.peaks["hbm_bytes_per_s"]
    util = manifest.load_reader(cell.root, "state_bw_util")
    share = manifest.load_reader(cell.root, "state_install_share")
    assert util(obs) == pytest.approx(100 * per_s / bw)
    assert share(obs) == pytest.approx(
        100 * 2 * 505 * ROW / (2 * 505 * ROW + per_s * 50.5))
    # The closing scrape alone (an untraced run keeps no samples) reads
    # the drain into the window's seconds: what the samples are for.
    obs.samples = []
    assert util(obs) == pytest.approx(100 * per_s * 90 / 51 / bw)


def test_readers_read_nothing_from_a_program_without_the_counters():
    """Laid over the parent's program (which cannot run the cell, and has
    no such counter) the readers return None and do not raise."""
    cell = manifest.load_cell(CELL, ROOT)
    obs = obs_of(cell, {"serve_decode_row_steps_total": 0.0},
                 {"serve_decode_row_steps_total": 50.0})
    for name in ("state_bw_util", "state_install_share"):
        assert manifest.load_reader(cell.root, name)(obs) is None, name
    # A program without recurrent state admits rows and has no pool.
    half = obs_of(cell, {"serve_admit_rows_padded_total": 0.0},
                  {"serve_admit_rows_padded_total": 9.0})
    for name in ("state_bw_util", "state_install_share"):
        assert manifest.load_reader(cell.root, name)(half) is None, name
    # Counters that did not move are no share of nothing.
    still = {"serve_state_bytes_total": 4.0, **POOL,
             "serve_admit_rows_padded_total": 2.0}
    assert manifest.load_reader(cell.root, "state_install_share")(
        obs_of(cell, still, dict(still))) is None


def test_configuration_is_the_catalogs_published_keys_whole():
    """Every key of the catalog entry's ``config`` with its value, key by
    key: nothing is reduced; every assumption named."""
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == NAME)
    cfg = manifest.load_cell(CELL, ROOT).config
    assert cfg["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        assert cfg.get(key) == value, key
    assert cfg["reduced"] == {}
    assert cfg["architecture"] == "granite_hybrid"
    assert cfg["layer_types"] == (["mamba"] * 5 + ["attention"]
                                  + ["mamba"] * 4) * 4
    assert (cfg["embedding_multiplier"], cfg["residual_multiplier"],
            cfg["attention_multiplier"], cfg["logits_scaling"]) == (
                12, 0.22, 0.015625, 8)
    assert set(cfg["assumed"]) >= {
        "origin", "layer", "scalars", "head_dim", "position", "mamba",
        "state_precision", "tied_head", "ignore_eos"}
    assert "it is right and this file" in cfg["assumed"]["origin"]
    assert cfg["stands_for"].startswith("the whole model on one chip, as "
                                        "deployed")
    assert cfg["stack"] == {
        "SERVE_QUANT": "int8", "SERVE_KV": "paged",
        "SERVE_KV_QUANT": "int8", "SERVE_PREFIX": "1", "SERVE_FUSE": "4",
        "SERVE_PREFILL_CHUNK": "256", "SERVE_SLOTS": "64",
        "SERVE_MAX_SEQ": "4096", "SERVE_PAGE_SIZE": "64",
        "SERVE_PAGES": "4097"}


def test_cell_mix_and_manifest_entries_by_name():
    man = manifest.load_manifest(ROOT)
    cell = manifest.load_cell(CELL, ROOT)
    entry = by_name(man["configs"], NAME)
    assert entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == cell.config["source"]
    w = by_name(man["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (NAME, "draft-crowd",
                                                       1)
    assert len(w["why"]) <= 200 and len(entry["why"]) <= 200
    assert all(x["chips"] == 1 for x in man["workloads"])
    t = cell.traffic
    backlog = manifest.load_cell("mixtral-8x7b-v0.1-l6.chat-backlog",
                                 ROOT).traffic
    assert (t["loop"], t["clients"]) == ("closed", 96)
    assert t["clients"] == int(cell.config["stack"]["SERVE_SLOTS"]) + 32
    # The co-pilot's head and tail, letter for letter: every request hits
    # the head's prefix entry.
    assert t["prompt"]["head"] == backlog["prompt"]["head"]
    assert t["prompt"]["tail"] == backlog["prompt"]["tail"]
    assert t["prompt"]["body_tokens"] == {
        "dist": "lognormal", "median": 200, "sigma": 0.8, "min": 32,
        "max": 1500}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.4, "min": 256, "max": 1024}
    assert t["options"] == {"temperature": 0}
    assert t["warmup_buckets"] == [128, 256, 512, 1024, 2048]
    assert (t["stratify"], t["design_seed"]) == (32, 22)
    assert t["seed_jitter"] == backlog["seed_jitter"]
    # The longest request fits a row's budget, and the pool holds every
    # caller's longest at once: rows bind here, pages never.
    stack = cell.config["stack"]
    longest = (len(t["prompt"]["head"]) + 1
               + t["prompt"]["body_tokens"]["max"]
               + len(t["prompt"]["tail"]) + t["output_tokens"]["max"])
    assert longest + 1 <= int(stack["SERVE_MAX_SEQ"])
    assert int(stack["SERVE_SLOTS"]) * -(-(longest + 1) // 64) \
        <= int(stack["SERVE_PAGES"]) - 1
    assert not os.path.exists(os.path.join(BENCH, "cells", CELL + ".json"))
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms",
                                                    "setup_s"}
    assert CELL in by_name(man["end_to_end"], "tpot_p50_ms")["workloads"]
    names = {m["name"] for m in cell.per_layer}
    assert {"out_tok_s", "kv_pages_peak", "tick_ms", "pallas_share",
            "device_idle", "hbm_peak_gb", "prefill_pad_share",
            "device_wait_share", "prefill_device_share",
            "attn_ctx_mean", "decode_bw_util_family", "prefill_flops_util",
            "chunk_step_ms", "chunk_step_share", "padded_step_share",
            "admit_step_share", "admit_step_ms", "req_cut_share_p50",
            "window_compile_s",
            "sample_sort_share", "state_step_share", "state_live_share",
            "state_bw_util", "state_install_share", "boot_load_s",
            "boot_warmup_s", "boot_compile_s"} <= names
    # (graftcheck reads a literal that opens with ``decode_`` as a
    # /metrics series: the benchmark's metric of that name is spelt so.)
    assert any(n.startswith("decode_step") for n in names)
    # Not ``padded_step_ms``: a chunk that computes nothing is 1% of this
    # cell's intervals, and a window without one would leave the line
    # short (the share is on the line). Not ``attn_walk_share`` nor the
    # five readers of the host's loop (``admit_host_ms``, ``launch_ms``,
    # ``launch_starved_share``, ``loop_offcpu_share``,
    # ``stream_handoff_ms``), though each finds its counters here: tests
    # that stand hold their lists to the cells they had (PERF.md section
    # 7(xxiv) has the cell's readings of them).
    assert not names & {"loop_weight_share", "page_starved_share",
                        "page_step_share", "moe_drop_share",
                        "moe_local_share", "window_step_share",
                        "attn_walk_share", "padded_step_ms",
                        "admit_host_ms", "launch_ms"}
    for name, better in (("state_bw_util", "higher"),
                         ("state_install_share", "lower")):
        m = by_name(man["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
        assert (m["source"], m["unit"], m["better"], m["layer"]) == (
            "program_counter", "%", better, "state pool ops/state_pool.py")
    assert by_name(man["per_layer"], "state_step_share")["layer"] \
        == "state pool ops/state_pool.py"
    for m in cell.per_layer:
        manifest.load_reader(cell.root, m["name"])


def test_architecture_file_keeps_the_contract_and_imports_no_program():
    a = arch()
    for fn in manifest.ARCHITECTURE_FUNCTIONS + (
            "system_logits", "wrong_models", "prefill_flops"):
        assert callable(getattr(a, fn)), fn
    assert callable(a.decode_step_bytes)
    assert set(a.WRONG) >= {"bf16_state", "scale_rsqrt_d", "residual_one",
                            "embedding_unscaled", "logits_undivided",
                            "int4_weights"}
    with open(a.__file__) as f:
        text = f.read()
    # The reference is its own: the program's model code is driven by
    # system_logits alone, through the scheduler's module.
    assert "import nemotron_h" not in text and "models import" not in text
    assert "models.layers" not in text and "ssd_scan" not in text
    cfg = manifest.load_cell(CELL, ROOT).config
    kw = a.model_config(cfg)
    assert kw["hybrid_pattern"] == "M-M-M-M-M-*-M-M-M-M-" * 4
    assert (kw["num_layers"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"]) == (40, 32, 8, 64)
    assert (kw["mamba_num_heads"], kw["mamba_head_dim"],
            kw["ssm_state_size"], kw["ssm_groups"], kw["ssm_chunk"]) == (
                64, 64, 128, 1, 256)
    assert (kw["embedding_multiplier"], kw["residual_multiplier"],
            kw["attention_multiplier"], kw["logits_scaling"]) == (
                12.0, 0.22, 0.015625, 8.0)
    assert kw["tie_embeddings"] and not kw["attn_rope"]
    assert kw["eos_token_ids"] == ()
    with pytest.raises(ValueError, match="dense members"):
        a.model_config({**cfg, "num_local_experts": 8})
    # The program's ModelConfig has every keyword. The parent's lacks the
    # four scalars: its child refuses the cell at boot, a ManifestError
    # that names them.
    from benchmark import serve_cell
    config = serve_cell.model_config(cfg)
    assert (config.ssm_layers, config.cache_layers, config.kv_paired) == (
        36, 4, True)
    import dataclasses
    from p2p_llm_chat_tpu.models.configs import ModelConfig
    older = dataclasses.make_dataclass("ModelConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(ModelConfig)
        if "multiplier" not in f.name and f.name != "logits_scaling"],
        frozen=True)
    import p2p_llm_chat_tpu.models.configs as configs
    real, configs.ModelConfig = configs.ModelConfig, older
    try:
        with pytest.raises(manifest.ManifestError,
                           match="attention_multiplier.*embedding_multiplier"
                                 ".*logits_scaling.*residual_multiplier"):
            serve_cell.model_config(cfg)
    finally:
        configs.ModelConfig = real


def test_costs_are_the_issues_arithmetic():
    """``decode_step_bytes`` and its parts against ISSUE 55's hand count:
    a Mamba layer 76.2 M parameters, an attention layer 60.8 M, 2.98 B in
    the stack and 205.5 M in the tied embedding; a row 75.5 MB of state
    and 0.9 MB of window; a full step 9.7 GB of state against 3.2 GB of
    weights and 0.2 GB of head."""
    a = arch()
    cfg = manifest.load_cell(CELL, ROOT).config
    shapes = a.layer_shapes(cfg)
    per = {k: sum(i * o for i, o in v) for k, v in shapes.items()}
    assert shapes["mamba"] == [(2048, 8512), (4096, 2048)]
    assert per["mamba"] + per["mlp"] == 76_152_832
    assert per["attention"] + per["mlp"] == 60_817_408
    assert a.layer_counts(cfg) == {"mamba": 36, "attention": 4}
    assert a.parameter_count(cfg) == 36 * 76_152_832 + 4 * 60_817_408 \
        + 100_352 * 2048
    assert round(a.parameter_count(cfg) / 1e9, 2) == 3.19
    assert a.state_row_bytes(cfg) == 2_097_152 + 26_112
    assert 36 * a.state_row_bytes(cfg) == ROW
    assert a.page_token_bytes(cfg) == 1056
    q8 = a._q8
    weights = (40 * (q8(2048, 16384) + q8(8192, 2048))
               + 36 * (q8(2048, 8512) + q8(4096, 2048))
               + 4 * (q8(2048, 3072) + q8(2048, 2048)))
    assert round(weights / 1e9, 2) == 2.99
    head = q8(2048, 100_352)
    assert round(head / 1e9, 2) == 0.21
    rows, ctx = 64, 700
    step = a.decode_step_bytes(cfg, rows, ctx)
    state = 2 * rows * ROW
    assert round(state / 1e9, 1) == 9.8          # the issue's 9.7: 75.5 MB
    pages = 4 * rows * ctx * 1056
    assert step == weights + head + rows * 4096 + state + pages
    assert 0.72 < state / step < 0.76            # three quarters of a step
    # At the chip's 819 GB/s a full step cannot take less than 16 ms.
    assert 15.5 < step / 819e9 * 1e3 < 17
    # A step of no rows moves the matrices alone.
    assert a.decode_step_bytes(cfg, 0, 0) == weights + head
    # A prompt token: two operations a matrix parameter, the recurrence
    # and the convolution in 36 layers; a causal pair 4 x 32 x 64 in 4.
    flops = a.prefill_flops(cfg, 1.0, 0.0)
    assert flops == 2 * (a.parameter_count(cfg) - 100_352 * 2048) \
        + 36 * (4 * 64 * 64 * 128 + 2 * 4 * 4352)
    assert a.prefill_flops(cfg, 0.0, 10.0) == 10 * 4 * 4 * 32 * 64

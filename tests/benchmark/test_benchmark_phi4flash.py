"""The SambaY family (Phi-4-mini-flash-reasoning) in the benchmark: its
architecture file, its configuration (against the catalog's published
keys), its traffic mix and cell, and the two readers that came with it.
Every manifest entry is found BY NAME: a later PR appends behind these.

A rehearsal cell of the family's published key names at a toy size runs
whole on the CPU through benchmark/architectures/phi4flash.py (both of
its samples: a chunk ladder with a padded last chunk past several
windows, then decode through ring, page pool and state pool, against the
sequential-recurrence reference) and is ``correct``. (The wrong models
and what each cache holds are in tests/test_phi4flash_parity.py.)
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

NAME = "phi-4-mini-flash-reasoning"
CELL = NAME + ".reasoning-backlog"
BENCH = os.path.join(ROOT, "benchmark")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("window_step_share", "shared_kv_step_share")


def by_name(entries: list, name: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def tiny_phi(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        real = json.load(f)
    cfg = tiny(name, architecture="phi4flash", model_type="phi4flash")
    for k in ("head_dim", "rope_theta", "rms_norm_eps"):
        cfg.pop(k)
    cfg.update(num_hidden_layers=8, num_attention_heads=8,
               num_key_value_heads=4, sliding_window=8, mb_per_layer=2,
               layer_norm_eps=1e-5, tie_word_embeddings=True,
               assumed={**real["assumed"], "mamba_dt_rank": 8})
    cfg["stack"] = {**cfg["stack"], "SERVE_PREFILL_CHUNK": "32",
                    "SERVE_PREFIX": "1"}
    return cfg


def arch():
    return manifest.load_architecture(BENCH, "phi4flash")


@pytest.fixture(scope="module")
def phi_root(tmp_path_factory):
    return write_benchmark(tmp_path_factory.mktemp("phi4flash"),
                           [tiny_phi("tiny-phi-cell")])


def test_rehearsal_cell_runs_whole_and_is_correct(phi_root, on_cpu, tmp_path,
                                                  capsys):
    cell = manifest.load_cell("tiny-phi-cell.tiny-open", phi_root)
    assert cell.config["architecture"] == "phi4flash"
    last = run.run_cell(run_args(cell.name, 0, 4.0), time.monotonic(),
                        data_root=phi_root, out_root=str(tmp_path))
    earlier = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    ref = next(x["reference"] for x in earlier if "reference" in x)
    a = arch()
    assert ref["ok"], ref
    assert 0 < ref["median"] <= ref["tolerance"]["median"] == a.TOL_MEDIAN
    assert 0 < ref["long_median"] <= a.TOL_MEDIAN
    assert 0 < ref["state_error"] <= ref["tolerance"]["state_error"]
    assert abs(ref["window_edge"]) < ref["tolerance"]["window_edge"]
    assert last["correct"] and last["failed"] == 0
    assert last["attempted"] >= 10


def obs_of(cell, start, end, **kw):
    return metrics.Observations(
        records=kw.pop("records", []), ramp_s=0.0, window_s=51.0, cell=cell,
        counters_start=start, counters_end=end,
        peaks=roofline.peaks_for("TPU v5 lite"), **kw)


def test_the_two_shares_are_counter_bytes_over_the_steps_bytes():
    """32 rows at a context of 2,200 for 1,000 steps, on made-up
    observations: a ring honoured at 512 positions reads about 6% of the
    step, one that grew with its context over 20%; eight readers of one
    page layer about a quarter."""
    cell = manifest.load_cell(CELL, ROOT)
    cfg, a = cell.config, arch()
    steps = 1000.0
    pos = a.window_position_bytes(cfg)
    rec = types.SimpleNamespace(ok=True, prompt_bytes=1649, tokens=1100,
                                due_t=1.0, chunk_t=[1.0],
                                chunk_tokens=[32 * steps])
    ticks = {"serve_decode_ticks_total": steps / 4,
             "decode_fused_ticks_total": steps / 4,
             "decode_fused_steps_total": steps}

    def read(name, counter, moved):
        obs = obs_of(cell, {counter: 5.0, **dict.fromkeys(ticks, 0.0)},
                     {counter: 5.0 + moved, **ticks}, records=[rec])
        return manifest.load_reader(cell.root, name)(obs)

    step = a.decode_step_bytes(cfg, 32, 2200)
    honoured = read("window_step_share", "serve_window_bytes_total",
                    steps * 32 * 8 * 512 * pos)
    assert honoured == pytest.approx(100 * 32 * 8 * 512 * pos / step)
    assert 4 < honoured < 8
    grown = read("window_step_share", "serve_window_bytes_total",
                 steps * 32 * 8 * 2200 * pos)
    assert 20 < grown < 30
    shared = read("shared_kv_step_share", "serve_shared_kv_bytes_total",
                  steps * 32 * 8 * 2200 * pos)
    assert shared == pytest.approx(grown)
    assert 20 < shared < 30


def test_new_readers_read_nothing_from_a_program_without_the_counters():
    """Laid over the parent's program (no such counters) each new reader
    returns None and does not raise."""
    cell = manifest.load_cell(CELL, ROOT)
    obs = obs_of(cell, {"serve_decode_row_steps_total": 0.0},
                 {"serve_decode_row_steps_total": 50.0})
    for name in NEW:
        assert manifest.load_reader(cell.root, name)(obs) is None, name


def test_configuration_is_the_catalogs_published_keys():
    """Every key of the catalog entry's ``config`` with its value,
    nothing reduced, and every assumption named."""
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Phi-4-mini-flash-reasoning")
    cfg = manifest.load_cell(CELL, ROOT).config
    assert cfg["source"] == entry["source_url"]
    assert {k for k, v in entry["config"].items() if cfg.get(k) != v} == set()
    assert cfg["reduced"] == {}
    assert (cfg["num_hidden_layers"], cfg["vocab_size"],
            cfg["hidden_size"]) == (32, 200064, 2560)
    assert set(cfg["assumed"]) >= {
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
        "mamba", "layer_kinds", "memory", "differential_attention",
        "biases", "positional_encoding", "window", "mlp", "head",
        "state_precision", "ignore_eos", "origin"}
    assert cfg["stands_for"].startswith("the whole model on one chip")
    assert cfg["stack"] == {
        "SERVE_QUANT": "int8", "SERVE_KV": "paged",
        "SERVE_KV_QUANT": "int8", "SERVE_PREFIX": "1", "SERVE_FUSE": "4",
        "SERVE_PREFILL_CHUNK": "256", "SERVE_SLOTS": "32",
        "SERVE_MAX_SEQ": "4096", "SERVE_PAGE_SIZE": "64",
        "SERVE_PAGES": "2049"}


def test_cell_mix_and_manifest_entries_by_name():
    man = manifest.load_manifest(ROOT)
    cell = manifest.load_cell(CELL, ROOT)
    entry = by_name(man["configs"], NAME)
    assert entry["reduced"] == [] and entry["file"] == \
        f"benchmark/configs/{NAME}.json"
    assert entry["source"] == cell.config["source"]
    w = by_name(man["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        NAME, "reasoning-backlog", 1)
    assert len(w["why"]) <= 200 and len(entry["why"]) <= 200
    t = cell.traffic
    assert (t["loop"], t["clients"]) == ("closed", 36)
    assert len(t["prompt"]["head"]) == 88
    assert t["prompt"]["body_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.4, "min": 600,
        "max": 2000}
    assert t["output_tokens"]["median"] == 1100
    assert (t["output_tokens"]["min"], t["output_tokens"]["max"]) == (768,
                                                                      1536)
    assert t["options"] == {"temperature": 0}
    assert set(t["warmup_buckets"]) >= {1024, 2048}
    # What follows the head fits the largest warmed bucket, so a prefix
    # hit is never bypassed for want of room under SERVE_MAX_SEQ.
    assert (t["prompt"]["body_tokens"]["max"] + len(t["prompt"]["tail"])
            <= max(t["warmup_buckets"]))
    assert not os.path.exists(os.path.join(BENCH, "cells", CELL + ".json"))
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms",
                                                    "setup_s"}
    assert CELL in by_name(man["end_to_end"], "tpot_p50_ms")["workloads"]
    names = {m["name"] for m in cell.per_layer}
    assert {"out_tok_s", "kv_pages_peak", "tick_ms", "pallas_share",
            "device_idle", "hbm_peak_gb", "prefill_pad_share",
            "device_wait_share", "prefill_device_share",
            "attn_ctx_mean", "decode_bw_util_family", "prefill_flops_util",
            "state_step_share", "state_live_share", *NEW} <= names
    # The five host-side metrics of PR 34 are not listed for this cell:
    # tests/benchmark/test_benchmark_host_side.py pins their lists of
    # cells, and a `model_config` PR may not edit it (ROADMAP S1b).
    assert any(n.startswith("decode_step") for n in names)
    for name in NEW:
        m = by_name(man["per_layer"], name)
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "tpot_p50_ms"
        assert m["source"] == "program_counter"
        assert m["layer"] in {x["layer"] for x in man["per_layer"]
                              if x["name"] not in NEW}
    for m in cell.per_layer:
        manifest.load_reader(cell.root, m["name"])


def test_the_file_builds_the_registered_configuration():
    import dataclasses
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.models.configs import get_config
    cfg = manifest.load_cell(CELL, ROOT).config
    assert dataclasses.asdict(serve_cell.model_config(cfg)) == \
        dataclasses.asdict(get_config(NAME).with_(eos_token_ids=()))


def test_architecture_file_keeps_the_contract():
    a = arch()
    for fn in manifest.ARCHITECTURE_FUNCTIONS + ("system_logits",
                                                 "wrong_models"):
        assert callable(getattr(a, fn)), fn
    assert callable(a.decode_step_bytes) and callable(a.prefill_flops)
    assert set(a.WRONG) >= {
        "rotary_applied", "lam_zero", "lam0_of_next_layer", "no_sub_norm",
        "no_lam0_factor", "own_value_head", "window_one_short",
        "window_unbounded", "m_after_gate", "m_without_d_skip",
        "cross_reads_last_window_layer", "rms_for_layer_norm", "bf16_state",
        "int4_weights"}
    cfg = manifest.load_cell(CELL, ROOT).config
    kinds = a.layer_kinds(cfg)
    assert kinds[:4] == ["mamba", "window", "mamba", "window"]
    assert kinds[14:20] == ["mamba", "window", "mamba", "full", "gmu",
                            "cross"]
    assert a.layer_counts(cfg) == {"mamba": 9, "window": 8, "full": 1,
                                   "gmu": 7, "cross": 7}
    assert a.pattern(cfg) == "1-w-" * 8 + "Y-*-" + "g-x-" * 7
    assert a.mamba_dims(cfg) == (5120, 16, 4, 160)
    # The long sample at the cell's chunk: 1,712 positions, three windows
    # and a third, a last chunk of 176 padded to 256; 8 decode steps.
    assert a.long_shape(256) == (1712, 8)
    at = a.long_positions(256)
    assert at[0] == 0 and list(at[-9:]) == list(range(1711, 1720))


def test_engine_weights_hand_the_tree_back():
    import jax
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.models import family_for
    config = serve_cell.model_config(tiny_phi("t"))
    model = family_for(config)
    p = model.init_params_quantized(config, jax.random.PRNGKey(3))
    weights = arch().engine_weights(types.SimpleNamespace(
        _params=p, config=config, mesh=None))
    deq = lambda w, *at: np.asarray(w.q[at], np.float32) * np.asarray(
        w.s[at], np.float32)
    m = weights.layer(4)                    # the publishing Mamba layer
    for n in ("w_in", "w_x", "w_dt", "w_out"):
        np.testing.assert_array_equal(m["mixer"][n],
                                      deq(p["mamba1"][n], 2))
    np.testing.assert_array_equal(
        m["mixer"]["A_log"], np.asarray(p["mamba1"]["A_log"][2]).T)
    np.testing.assert_array_equal(m["mlp"]["w_gu"], deq(p["mlp"]["w_gu"], 4))
    full = weights.layer(5)                 # after two window layers
    np.testing.assert_array_equal(full["mixer"]["wqkv"],
                                  deq(p["attn"]["wqkv"], 2))
    np.testing.assert_array_equal(
        full["mixer"]["bqkv"], np.asarray(p["attn"]["bqkv"][2], np.float32))
    cross = weights.layer(7)
    np.testing.assert_array_equal(cross["mixer"]["wq"],
                                  deq(p["cross"]["wq"], 0))
    np.testing.assert_array_equal(weights.layer(6)["mixer"]["w_in"],
                                  deq(p["gmu"]["w_in"], 0))
    # A tied head: the int8 copy of the embedding, transposed.
    q, s = weights.lm_head
    assert q.shape == (128, 512) and q.dtype == np.int8
    embed = np.asarray(p["embed"], np.float32)
    assert np.max(np.abs(np.asarray(q, np.float32) * np.asarray(s)
                         - embed.T)) <= np.max(np.abs(embed)) / 127


def test_counts_are_the_hand_arithmetics():
    """ISSUE 38's table: parameters by kind of layer (3.85 G), what a row
    keeps, and a decode step's bytes at 32 rows and a context of 2,200:
    layers 3.3 GB, shared pages 1.4, rings 0.34, state 0.21, and the head
    0.51 (an int8 copy of the tied embedding; the issue's 6.3 GB counted
    it in bf16 at 1.02, so the whole is held to 6.3 - 0.51)."""
    cfg = manifest.load_cell(CELL, ROOT).config
    a = arch()
    shapes = a.layer_shapes(cfg)
    params = lambda kind: sum(i * o for i, o in shapes[kind])
    assert params("mlp") == 2560 * 20480 + 10240 * 2560 == 78_643_200
    assert params("mamba") == 41_123_840
    assert params("window") == params("full") == 19_660_800
    assert params("gmu") == 26_214_400
    assert params("cross") == 13_107_200
    n = a.layer_counts(cfg)
    total = (32 * params("mlp") + sum(n[k] * params(k) for k in n)
             + 200064 * 2560)
    assert 3.84e9 < total < 3.86e9
    assert a.state_row_bytes(cfg) == 4 * 5120 * 16 + 2 * 3 * 5120
    assert 9 * a.state_row_bytes(cfg) == pytest.approx(3.2e6, rel=0.02)
    assert a.page_token_bytes(cfg) == 2 * (20 * 64 + 4) == 2568
    assert 8 * 512 * a.window_position_bytes(cfg) == pytest.approx(
        10.5e6, rel=0.07)
    step = a.decode_step_bytes(cfg, 32, 2200)
    layers = 32 * params("mlp") + sum(n[k] * params(k) for k in n)
    assert layers == pytest.approx(3.3e9, rel=0.02)
    pages = 8 * 32 * 2200 * 2568
    rings = 8 * 32 * 512 * 2568
    state = 2 * 32 * 9 * a.state_row_bytes(cfg)
    assert pages == pytest.approx(1.4e9, rel=0.1)
    assert rings == pytest.approx(0.34e9, rel=0.07)
    assert state == pytest.approx(0.21e9, rel=0.02)
    head = 2560 * 200064
    assert step == pytest.approx(layers + head + pages + rings + state,
                                 rel=0.005)
    assert step == pytest.approx(6.3e9 - 0.51e9, rel=0.05)
    # A window layer's read stops growing at 512; the pages' does not.
    assert (a.decode_step_bytes(cfg, 32, 4000)
            - a.decode_step_bytes(cfg, 32, 3000)) == pytest.approx(
        8 * 32 * 1000 * 2568)
    # A prompt token: two FLOPs a matrix parameter, the recurrence and
    # convolution of nine Mamba layers; a pair: 40 heads x 64 x 4.
    per_token = a.prefill_flops(cfg, 1, 0)
    assert per_token == pytest.approx(
        2 * layers + 9 * (4 * 5120 * 16 + 2 * 4 * 5120), rel=1e-9)
    # Ten pairs of one token: in the full and the seven cross layers, and
    # (under the window's 512 a token) in the eight window layers.
    assert a.prefill_flops(cfg, 1, 10) - per_token == pytest.approx(
        10 * (8 + 8) * 40 * 64 * 4)
    assert a.prefill_flops(cfg, 1, 1000) - per_token == pytest.approx(
        (8 * 1000 + 8 * 512) * 40 * 64 * 4)

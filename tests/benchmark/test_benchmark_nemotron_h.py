"""The Nemotron-H family in the benchmark: its architecture file, its
configuration (against the catalog's published keys) and cell, and the
two readers that came with it.

A rehearsal cell of the family's published key names at a toy size runs
whole on the CPU through benchmark/architectures/nemotron_h.py (prefill
in two chunks with carried state, then decode through both pools,
against the sequential-recurrence reference) and is ``correct``;
``engine_weights`` hands the engine's tree back; the byte and FLOP
counts are the issue's hand arithmetic. (The wrong models, the share
test and the router's rule are in tests/test_nemotron_h_parity.py.)
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

NAME = "nemotron-3-super-120b-a12b-l22e128"
CELL = NAME + ".chat-backlog"
BENCH = os.path.join(ROOT, "benchmark")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_nemotron(name: str) -> dict:
    cfg = tiny(name, architecture="nemotron_h", model_type="nemotron_h")
    for k in ("intermediate_size", "rms_norm_eps"):
        cfg.pop(k)
    cfg.update(num_hidden_layers=11, hybrid_override_pattern="MEMEM*EMEME",
               mamba_num_heads=8, mamba_head_dim=16, n_groups=2,
               ssm_state_size=16, conv_kernel=4, chunk_size=16,
               norm_eps=1e-5, moe_intermediate_size=96, moe_latent_size=64,
               moe_shared_expert_intermediate_size=192, n_shared_experts=1,
               n_routed_experts=16, n_held_experts=4, num_experts_per_tok=3,
               norm_topk_prob=True, routed_scaling_factor=5.0,
               mlp_hidden_act="relu2", moe_capacity_factor=None)
    return cfg


def arch():
    return manifest.load_architecture(BENCH, "nemotron_h")


@pytest.fixture(scope="module")
def nemotron_root(tmp_path_factory):
    return write_benchmark(tmp_path_factory.mktemp("nemotron"),
                           [tiny_nemotron("tiny-nemotron-cell")])


def test_rehearsal_cell_runs_whole_and_is_correct(nemotron_root, on_cpu,
                                                  tmp_path, capsys):
    cell = manifest.load_cell("tiny-nemotron-cell.tiny-open", nemotron_root)
    assert cell.config["architecture"] == "nemotron_h"
    last = run.run_cell(run_args(cell.name, 0, 4.0), time.monotonic(),
                        data_root=nemotron_root, out_root=str(tmp_path))
    earlier = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    ref = next(x["reference"] for x in earlier if "reference" in x)
    assert ref["ok"] and 0 < ref["median"] <= ref["tolerance"]["median"], (
        ref["median"], ref["state_error"])
    assert ref["tolerance"]["median"] == arch().TOL_MEDIAN
    assert 0 < ref["state_error"] <= ref["tolerance"]["state_error"]
    assert 0 < ref["local_share"] < 1
    assert last["correct"] and last["failed"] == 0
    assert last["attempted"] >= 10


def obs_of(cell, start, end, **kw):
    return metrics.Observations(
        records=kw.pop("records", []), ramp_s=0.0, window_s=51.0, cell=cell,
        counters_start=start, counters_end=end,
        peaks=roofline.peaks_for("TPU v5 lite"), **kw)


def test_new_readers_are_window_differences_of_the_new_counters():
    cell = manifest.load_cell(CELL, ROOT)
    start = {"serve_state_row_steps_total": 320.0,
             "serve_state_row_steps_live_total": 300.0,
             "serve_state_bytes_total": 1e9}
    end = {"serve_state_row_steps_total": 320.0 + 32 * 1000,
           "serve_state_row_steps_live_total": 300.0 + 31 * 1000,
           "serve_state_bytes_total": 1e9 + 2.7e12}
    obs = obs_of(cell, start, end)
    read = lambda name: manifest.load_reader(cell.root, name)(obs)
    assert read("state_live_share") == pytest.approx(100.0 * 31 / 32)
    # No decode step and no finished request in the observations: the
    # share of a step has nothing to divide by.
    assert read("state_step_share") is None


def test_state_step_share_is_state_bytes_over_the_steps_bytes():
    """32 rows whose state moves every step, against the architecture
    file's step at 32 rows: the widths' own share, about 27%."""
    cell = manifest.load_cell(CELL, ROOT)
    a = arch()
    steps = 1000.0
    row = 10 * a.state_row_bytes(cell.config)
    rec = types.SimpleNamespace(ok=True, prompt_bytes=300, tokens=96,
                                due_t=1.0, chunk_t=[1.0],
                                chunk_tokens=[32 * steps])
    obs = obs_of(cell,
                 {"serve_state_bytes_total": 0.0,
                  "serve_decode_ticks_total": 0.0,
                  "decode_fused_ticks_total": 0.0,
                  "decode_fused_steps_total": 0.0},
                 {"serve_state_bytes_total": 2 * 32 * steps * row,
                  "serve_decode_ticks_total": steps / 4,
                  "decode_fused_ticks_total": steps / 4,
                  "decode_fused_steps_total": steps},
                 records=[rec])
    got = manifest.load_reader(cell.root, "state_step_share")(obs)
    want = 100.0 * 2 * 32 * row / a.decode_step_bytes(cell.config, 32, 349)
    assert got == pytest.approx(want)
    assert 25 < got < 30


def test_new_readers_read_nothing_from_a_program_without_the_counters():
    """Laid over the parent's program (no such counters) each new reader
    returns None and does not raise."""
    cell = manifest.load_cell(CELL, ROOT)
    obs = obs_of(cell, {"serve_decode_row_steps_total": 0.0},
                 {"serve_decode_row_steps_total": 50.0})
    for name in ("state_step_share", "state_live_share"):
        assert manifest.load_reader(cell.root, name)(obs) is None, name


def test_configuration_is_the_catalogs_published_keys():
    """Every number of the catalog entry's ``config`` under the same key;
    what differs is listed in ``reduced`` and is no width."""
    with open(CATALOG) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    cfg = manifest.load_cell(CELL, ROOT).config
    assert cfg["source"] == entry["source_url"]
    differs = {k for k, v in entry["config"].items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "hybrid_override_pattern",
                       "vocab_size", "num_nextn_predict_layers"}
    assert differs | {"n_routed_experts"} == set(cfg["reduced"])
    assert cfg["hybrid_override_pattern"] == \
        entry["config"]["hybrid_override_pattern"][:22]
    assert (cfg["num_hidden_layers"], cfg["n_held_experts"],
            cfg["vocab_size"]) == (22, 128, 32768)
    for width in ("hidden_size", "moe_intermediate_size", "moe_latent_size",
                  "moe_shared_expert_intermediate_size", "mamba_num_heads",
                  "mamba_head_dim", "ssm_state_size", "n_groups",
                  "head_dim", "num_experts_per_tok", "expand"):
        assert width not in cfg["reduced"]
        assert cfg[width] == entry["config"][width]


def test_cell_and_manifest_entries():
    man = manifest.load_manifest(ROOT)
    cell = manifest.load_cell(CELL, ROOT)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic_name == "chat-backlog"
    assert cell.traffic["loop"] == "closed"
    assert cell.traffic["clients"] == 64
    assert len(cell.traffic["prompt"]["head"]) == 88
    assert man["workloads"][-1]["name"] == CELL
    entry = man["configs"][-1]
    assert entry["name"] == NAME
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"state_step_share", "state_live_share", "moe_local_share",
            "decode_bw_util_family", "prefill_flops_util", "attn_ctx_mean",
            "out_tok_s", "kv_pages_peak", "tick_ms", "pallas_share",
            "device_idle", "hbm_peak_gb", "prefill_pad_share",
            "device_wait_share", "prefill_device_share",
            "moe_drop_share"} <= names
    assert any(n.startswith("decode_step") for n in names)
    assert [m["name"] for m in man["per_layer"][-2:]] == [
        "state_step_share", "state_live_share"]
    for m in man["per_layer"][-2:]:
        assert m["workloads"] == [CELL]
        assert m["layer"] == "state pool ops/state_pool.py"
        assert m["moves"] == "tpot_p50_ms"
    for m in cell.per_layer:
        manifest.load_reader(cell.root, m["name"])
    from benchmark import serve_cell
    config = serve_cell.model_config(cfg)
    from p2p_llm_chat_tpu.models.configs import get_config
    import dataclasses
    assert dataclasses.asdict(config) == dataclasses.asdict(
        get_config(NAME).with_(eos_token_ids=(),
                               max_seq_len=cfg["max_position_embeddings"]))
    assert cfg["stack"] == {
        "SERVE_QUANT": "int8", "SERVE_KV": "paged",
        "SERVE_KV_QUANT": "int8", "SERVE_PREFIX": "1", "SERVE_FUSE": "4",
        "SERVE_PREFILL_CHUNK": "256", "SERVE_SLOTS": "32",
        "SERVE_MAX_SEQ": "2048", "SERVE_PAGE_SIZE": "64",
        "SERVE_PAGES": "1025"}


def test_architecture_file_keeps_the_contract():
    a = arch()
    for fn in manifest.ARCHITECTURE_FUNCTIONS + ("system_logits",
                                                 "wrong_models"):
        assert callable(getattr(a, fn)), fn
    assert callable(a.decode_step_bytes) and callable(a.prefill_flops)
    assert set(a.WRONG) >= {"bf16_state", "no_d_skip", "no_conv_bias",
                            "norm_before_gate", "no_selection_bias",
                            "gated_experts", "rotary_applied"}
    assert a.tree_index("MEMEM*EMEME", 5) == ("attn", 0)
    assert a.tree_index("MEMEM*EMEME", 6) == ("moe", 2)
    assert a.tree_index("MEMEM*EMEME", 9) == ("mamba", 4)


def test_engine_weights_hand_the_tree_back():
    import jax
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.models import family_for
    config = serve_cell.model_config(tiny_nemotron("t"))
    model = family_for(config)
    p = model.init_params_quantized(config, jax.random.PRNGKey(3))
    weights = arch().engine_weights(types.SimpleNamespace(
        _params=p, config=config, mesh=None))
    deq = lambda w, *at: np.asarray(w.q[at], np.float32) * np.asarray(
        w.s[at], np.float32)
    m = weights.layer(4)                    # the third Mamba layer
    np.testing.assert_array_equal(m["w_in"], deq(p["mamba"]["w_in"], 2))
    np.testing.assert_array_equal(m["w_out"], deq(p["mamba"]["w_out"], 2))
    for n in ("norm", "conv_w", "conv_b", "dt_bias", "A_log", "D", "gnorm"):
        np.testing.assert_array_equal(
            m[n], np.asarray(p["mamba"][n][2], np.float32))
    a = weights.layer(5)
    np.testing.assert_array_equal(a["wqkv"], deq(p["attn"]["wqkv"], 0))
    e = weights.layer(8)                    # the fourth routed layer
    np.testing.assert_array_equal(e["w_fc1"], deq(p["moe"]["w_fc1"], 3))
    np.testing.assert_array_equal(e["w_up_s"], deq(p["moe"]["w_up_s"], 3))
    np.testing.assert_array_equal(
        e["router"], np.asarray(p["moe"]["router"][3], np.float32))
    np.testing.assert_array_equal(
        e["router_bias"], np.asarray(p["moe"]["router_bias"][3]))
    up, down = weights.expert(8, 2)
    np.testing.assert_array_equal(up, deq(p["moe"]["w_up_e"], 3, 2))
    np.testing.assert_array_equal(down, deq(p["moe"]["w_down"], 3, 2))


def test_counts_are_the_hand_arithmetics():
    """The issue's table: parameters a layer, the bytes of the cut, the
    state a row keeps, a decode step's bytes and a prompt token's
    FLOPs."""
    cfg = manifest.load_cell(CELL, ROOT).config
    a = arch()
    p = a.layer_params(cfg)
    assert p["mamba"] == 4096 * 18560 + 8192 * 4096 == 109_576_192
    assert p["attn"] == 4096 * 4608 + 4096 * 4096 == 35_651_584
    assert p["latent"] == 2 * 4096 * 1024 == 8_388_608
    assert p["shared"] == 2 * 4096 * 5376 == 44_040_192
    assert p["latent"] + p["shared"] == 52_428_800
    assert p["router"] == 4096 * 512 == 2_097_152
    assert p["expert"] == 2 * 1024 * 2688 == 5_505_024
    assert a.layer_counts(cfg) == {"M": 10, "E": 10, "*": 2}
    held = (10 * p["mamba"] + 2 * p["attn"]
            + 10 * (p["latent"] + p["shared"] + 4 * p["router"]
                    + 128 * p["expert"])
            + 32768 * 4096 * 2 + 32768 * 4096)      # bf16 embed, int8 head
    assert 9.2e9 < held < 9.25e9
    # A row of a Mamba layer: 128 x 64 x 128 float32 and 3 x 10,240 bf16.
    assert a.state_row_bytes(cfg) == 4_194_304 + 61_440
    assert 33 * 10 * a.state_row_bytes(cfg) == pytest.approx(1.404e9,
                                                             rel=1e-3)
    assert a.page_token_bytes(cfg) == 2 * 2 * (128 + 4) == 528
    # A step of 32 rows at a 350-token context: the state 2.72 GB, the
    # experts 128 x (1 - (490/512)^32) = 96.6 a layer, 5.3 GB.
    touched = 128 * (1 - (490 / 512) ** 32)
    assert abs(touched - 96.6) < 0.1
    step = a.decode_step_bytes(cfg, 32, 350)
    state = 2 * 32 * 10 * a.state_row_bytes(cfg)
    assert state == pytest.approx(2.72e9, rel=2e-3)
    by_hand = (10 * 109.6e6 + 2 * 35.7e6 + 10 * (52.4e6 + 8.4e6)
               + 10 * touched * 5.51e6 + 134.2e6 + state
               + 32 * 350 * 2 * 528)
    assert abs(step - by_hand) / by_hand < 0.005
    assert 9.8e9 < step < 10.1e9
    assert 0.26 < state / step < 0.28
    # A prompt token: 10 Mamba layers' projections and recurrence, 2
    # attention layers, 10 x (latent + shared + router + 22 x 128 / 512 =
    # 5.5 experts); a pair: 32 heads x (128 + 128) x 2.
    assert a.attention_pair_flops(cfg) == 16_384
    per_token = a.prefill_flops(cfg, 1, 0)
    by_hand = (10 * (2 * 109.6e6 + 4 * 128 * 64 * 128 + 2 * 4 * 10240)
               + 2 * 2 * 35.7e6
               + 10 * 2 * (52.4e6 + 2.1e6 + 5.5 * 5.505e6))
    assert abs(per_token - by_hand) / by_hand < 0.005
    assert a.prefill_flops(cfg, 0, 10) == 10 * 2 * 16_384

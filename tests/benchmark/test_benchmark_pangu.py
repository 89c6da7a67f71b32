"""The openPangu-Ultra-MoE family in the benchmark: its architecture
file, its configuration and cell, and the four readers that came with
it.

A rehearsal cell of the family's published key names at a toy size runs
whole on the CPU through benchmark/architectures/pangu_ultra_moe.py
(chunked prefill then decode through the int8 latent pool, against the
expanded plain reference) and is ``correct``; each wrong model of
``wrong_models`` fails the limit on the same system logits;
``engine_weights`` hands the engine's tree back; the chip's share of the
experts adds up; the byte and FLOP counts are the hand arithmetic's.
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

CELL = "openpangu-ultra-moe-718b-l9e16.long-context"
BENCH = os.path.join(ROOT, "benchmark")


def tiny_pangu(name: str, held: int = 4, routed: int = 16) -> dict:
    cfg = tiny(name, architecture="pangu_ultra_moe",
               model_type="pangu_ultra_moe")
    for k in ("head_dim", "num_key_value_heads"):
        cfg.pop(k)
    cfg.update(num_hidden_layers=3, first_k_dense_replace=1,
               intermediate_size=256, moe_intermediate_size=64,
               n_routed_experts=routed, n_held_experts=held,
               n_shared_experts=1, num_experts_per_tok=4,
               norm_topk_prob=True, routed_scaling_factor=2.5,
               q_lora_rank=48, kv_lora_rank=64, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=32, sandwich_norm=True,
               num_key_value_heads=4, moe_capacity_factor=None)
    return cfg


def arch():
    return manifest.load_architecture(BENCH, "pangu_ultra_moe")


@pytest.fixture(scope="module")
def pangu_root(tmp_path_factory):
    return write_benchmark(tmp_path_factory.mktemp("pangu"),
                           [tiny_pangu("tiny-pangu-cell")])


def test_rehearsal_cell_runs_whole_and_is_correct(pangu_root, on_cpu,
                                                  tmp_path, capsys):
    cell = manifest.load_cell("tiny-pangu-cell.tiny-open", pangu_root)
    assert cell.config["architecture"] == "pangu_ultra_moe"
    last = run.run_cell(run_args(cell.name, 0, 4.0), time.monotonic(),
                        data_root=pangu_root, out_root=str(tmp_path))
    earlier = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    ref = next(x["reference"] for x in earlier if "reference" in x)
    assert ref["ok"] and 0 < ref["median"] <= ref["tolerance"]["median"]
    assert ref["tolerance"]["median"] == arch().TOL_MEDIAN
    assert 0 < ref["local_share"] < 1
    assert last["correct"] and last["failed"] == 0
    assert last["attempted"] >= 10


def test_new_readers_are_window_differences_of_the_new_counters():
    """(A traced run cannot end on the CPU; tests/test_engine_pangu.py
    holds the scheduler to exporting these series.)"""
    cell = manifest.load_cell(CELL, ROOT)
    start = {"serve_attn_context_tokens_total": 1000.0,
             "serve_decode_row_steps_total": 10.0,
             "serve_moe_local_pairs_total": 5.0,
             "serve_moe_routed_pairs_total": 100.0,
             "serve_prefill_tokens_total": 0.0,
             "serve_prefill_context_pairs_total": 0.0}
    end = {"serve_attn_context_tokens_total": 301000.0,
           "serve_decode_row_steps_total": 110.0,
           "serve_moe_local_pairs_total": 55.0,
           "serve_moe_routed_pairs_total": 900.0,
           "serve_prefill_tokens_total": 30000.0,
           "serve_prefill_context_pairs_total": 30000.0 * 1500}
    obs = metrics.Observations(
        records=[], ramp_s=0.0, window_s=51.0, cell=cell,
        counters_start=start, counters_end=end,
        peaks=roofline.peaks_for("TPU v5 lite"))
    read = lambda name: manifest.load_reader(cell.root, name)(obs)
    assert read("attn_ctx_mean") == 3000.0
    assert read("moe_local_share") == 100.0 * 50 / 800
    a = arch()
    want = 100.0 * a.prefill_flops(cell.config, 30000.0, 45e6) / (
        51.0 * 197e12)
    assert read("prefill_flops_util") == pytest.approx(want)
    assert 0 < want < 100


@pytest.fixture(scope="module")
def tiny_system():
    """A scheduler's worth of the tiny model (int8 weights, int8 latent
    pool), the system's logits on one sample, and the reference's."""
    import jax
    import jax.numpy as jnp
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.models import family_for
    cfg = tiny_pangu("t")
    config = serve_cell.model_config(cfg)
    model = family_for(config)
    params = model.init_params_quantized(config, jax.random.PRNGKey(3))
    sched = types.SimpleNamespace(
        _params=params, config=config, mesh=None, _model=model,
        page_size=16, kv_quant=True, _dtype=params["embed"].dtype)
    tokens = jnp.asarray(np.random.default_rng(53).integers(
        0, config.vocab_size, size=(2, 128 + 8)), jnp.int32)
    a = arch()
    system = a.system_logits(sched, tokens, 128)
    weights = a.engine_weights(sched)
    return cfg, sched, tokens, system, weights


def test_system_agrees_with_the_reference_and_not_with_wrong_models(
        tiny_system):
    """The limit at the small size: the sound reference passes it; every
    wrong model reads at least twice the sound median and fails it."""
    cfg, sched, tokens, system, weights = tiny_system
    a = arch()
    ref, facts = a.forward(cfg, tokens, weights)
    sound = a.compare(system, ref, {**facts, "n_prefill": 128}, cfg)
    assert sound["ok"], sound
    wrong = a.wrong_models(cfg, weights)
    assert set(wrong) == {"softmax_router", "scale_1", "no_kv_a_norm",
                          "no_post_norms", "rope_on_nope",
                          "absorbed_without_wuv", "int4_weights"}
    for name, (wcfg, w) in wrong.items():
        wref, wfacts = a.forward(wcfg, tokens, w)
        got = a.compare(system, wref, {**wfacts, "n_prefill": 128}, wcfg)
        assert not got["ok"], (name, got)
        assert got["median"] > 2 * sound["median"], (name, got, sound)


def test_engine_weights_hand_the_tree_back(tiny_system):
    cfg, sched, _, _, weights = tiny_system
    c, p = sched.config, sched._params
    deq = lambda w, *at: np.asarray(w.q[at], np.float32) * np.asarray(
        w.s[at], np.float32)
    dense, moe = weights.layer(0), weights.layer(2)
    ql, r, dr = c.q_lora_rank, c.kv_lora_rank, c.qk_rope_head_dim
    Hq, dn = c.num_heads, c.qk_nope_head_dim
    qkva = deq(p["layers"]["wqkva"], 1)
    np.testing.assert_array_equal(moe["wqa"], qkva[:, :ql])
    np.testing.assert_array_equal(moe["wkva"], qkva[:, ql: ql + r + dr])
    wqb = deq(p["layers"]["wqb"], 1)
    np.testing.assert_array_equal(
        np.asarray(moe["wqb"])[:, 2, :dn], wqb[:, 2 * dn: 3 * dn])
    np.testing.assert_array_equal(
        np.asarray(moe["wqb"])[:, 2, dn:],
        wqb[:, Hq * dn + 2 * dr: Hq * dn + 3 * dr])
    np.testing.assert_array_equal(
        np.asarray(moe["wkvb"]).reshape(r, -1), deq(p["layers"]["wkvb"], 1))
    wgu = deq(p["dense_layers"]["wgu"], 0)
    np.testing.assert_array_equal(dense["w_gate"], wgu[:, :256])
    np.testing.assert_array_equal(dense["w_up"], wgu[:, 256:])
    np.testing.assert_array_equal(
        moe["router"], np.asarray(p["layers"]["router"][1], np.float32))
    for n in a_norms():
        np.testing.assert_array_equal(
            moe[n], np.asarray(p["layers"][n][1], np.float32))
    gate, up, down = weights.expert(2, 3)
    np.testing.assert_array_equal(
        np.concatenate([gate, up], -1), deq(p["layers"]["wgu_e"], 1, 3))
    np.testing.assert_array_equal(down, deq(p["layers"]["w_down"], 1, 3))


def a_norms():
    return arch().NORMS


def test_the_shares_routed_parts_and_the_shared_expert_add_up():
    """The guide's share test, on the program's own routed MLP: four
    chips, each holding 4 of 16 experts (its own first in its router's
    order, as every share's are), compute their parts of the routed sum;
    the four parts add up to what one chip holding all 16 computes."""
    import jax
    import jax.numpy as jnp
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.models import pangu
    whole = serve_cell.model_config(tiny_pangu("w", held=16))
    share = serve_cell.model_config(tiny_pangu("s", held=4))
    H, F, NE = whole.hidden_size, whole.intermediate_size, 16
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    router = jax.random.normal(k[0], (H, NE), jnp.float32) * H ** -0.5
    wgu = jax.random.normal(k[1], (NE, H, 2 * F), jnp.float32) * H ** -0.5
    wdn = jax.random.normal(k[2], (NE, F, H), jnp.float32) * F ** -0.5
    x = jax.random.normal(k[3], (2, 24, H), jnp.float32)
    full, st = pangu._routed_local(
        x, {"router": router, "wgu_e": wgu, "w_down": wdn}, whole, None,
        None)
    assert int(st[0]) == int(st[2]) == 2 * 24 * 4     # every pair is local
    parts, local = 0.0, 0
    for s in range(4):
        mine = np.arange(4 * s, 4 * s + 4)
        order = np.concatenate([mine, np.delete(np.arange(NE), mine)])
        part, st = pangu._routed_local(
            x, {"router": router[:, order], "wgu_e": wgu[mine],
                "w_down": wdn[mine]}, share, None, None)
        parts = parts + part
        local += int(st[0])
    assert local == 2 * 24 * 4
    np.testing.assert_allclose(np.asarray(parts), np.asarray(full),
                               atol=2e-5)


def test_counts_are_the_hand_arithmetics():
    """The issue's table: parameters a layer, the bytes of the cut, a
    decode step's bytes, a prompt token's FLOPs, and each kernel's
    operations and bytes."""
    cfg = manifest.load_cell(CELL, ROOT).config
    a = arch()
    p = a.layer_params(cfg)
    assert p["mla"] == (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576
                        + 512 * 128 * 256 + 16384 * 7680) == 196_575_232
    assert p["dense_mlp"] == 3 * 7680 * 18432 == 424_673_280
    assert p["expert"] == p["shared"] == 3 * 7680 * 2048 == 47_185_920
    assert p["router"] == 7680 * 256
    held = (p["mla"] + p["dense_mlp"]
            + 8 * (p["mla"] + p["shared"] + 16 * p["expert"])
            + 7680 * 19200)                       # int8, a byte each
    assert 8.75e9 < held < 8.77e9                 # + bf16 embed, routers
    assert a.latent_token_bytes(cfg) == 512 + 64 + 8
    # A step of 14 rows at a 3,000-token context: every dense weight
    # once, 16 x (1 - (31/32)^14) = 5.74 experts a routed layer, and the
    # rows' latents.
    step = a.decode_step_bytes(cfg, 14, 3000)
    touched = 16 * (1 - (31 / 32) ** 14)
    assert abs(touched - 5.74) < 0.01
    by_hand = (9 * 196.6e6 + 424.7e6 + 8 * (47.2e6 + 3.9e6)
               + 8 * touched * 47.2e6 + 147.5e6
               + 14 * 3000 * 9 * 584)
    assert abs(step - by_hand) / by_hand < 0.005
    assert 5.0e9 < step < 5.3e9       # 4.92 GB of weights, 0.22 of latents
    # A prompt token: MLA 9 x, the dense MLP once, 8 x (shared + router
    # + half an expert); a pair: 128 heads x (192 + 128) x 2.
    assert a.attention_pair_flops(cfg) == 81_920
    per_token = a.prefill_flops(cfg, 1, 0)
    assert abs(per_token - 2 * (9 * 196.6e6 + 424.7e6 + 8 * (
        47.2e6 + 2.0e6 + 0.5 * 47.2e6))) / per_token < 0.005
    assert a.prefill_flops(cfg, 0, 10) == 10 * 9 * 81_920
    flops, nbytes = a.mla_decode_cost(cfg, 32, 4096)
    assert flops == 2 * 32 * 128 * 4096 * (576 + 512)
    assert nbytes == (32 * 4096 * 584 + 32 * 128 * 576 * 2
                      + 32 * 128 * 512 * 4)
    assert 400 < flops / nbytes < 420       # 1.7 times the v5e's ridge (240)
    flops, nbytes = a.mla_prefill_cost(cfg, 256, 3328)
    assert flops == (256 * 3072 + 256 * 257 / 2) * 81_920
    assert nbytes == 2 * (256 * 128 * 192 + 3328 * 128 * 256 + 3328 * 64
                          + 256 * 128 * 128)


def test_cell_and_manifest_entries():
    man = manifest.load_manifest(ROOT)
    cell = manifest.load_cell(CELL, ROOT)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["loop"] == "closed"
    assert cell.traffic["clients"] == 16
    assert cell.traffic["warmup_buckets"] == [4096]
    body = cell.traffic["prompt"]["body_tokens"]
    assert (body["median"], body["min"], body["max"]) == (2800, 2048, 3584)
    assert len(cell.traffic["prompt"]["head"]) == 88
    entry = next(c for c in man["configs"] if c["name"] == cfg["name"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert not {"hidden_size", "q_lora_rank", "kv_lora_rank",
                "num_experts_per_tok"} & set(entry["reduced"])
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p50_ms",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"attn_ctx_mean", "moe_local_share", "decode_bw_util_family",
            "prefill_flops_util", "moe_drop_share", "kv_pages_peak",
            "hbm_peak_gb", "prefill_device_share"} <= names
    for m in cell.per_layer:
        manifest.load_reader(cell.root, m["name"])
    from benchmark import serve_cell
    config = serve_cell.model_config(cfg)
    from p2p_llm_chat_tpu.models.configs import get_config
    import dataclasses
    assert dataclasses.asdict(config) == dataclasses.asdict(
        get_config(cfg["name"]).with_(
            eos_token_ids=(), max_seq_len=cfg["max_position_embeddings"]))


def test_new_readers_read_nothing_from_a_program_without_the_counters():
    """Laid over the parent's program (no such counters) each new reader
    returns None and does not raise."""
    cell = manifest.load_cell(CELL, ROOT)
    obs = metrics.Observations(
        records=[], ramp_s=0.0, window_s=10.0, cell=cell,
        counters_start={"serve_decode_row_steps_total": 0.0,
                        "serve_prefill_tokens_total": 0.0},
        counters_end={"serve_decode_row_steps_total": 50.0,
                      "serve_prefill_tokens_total": 900.0},
        peaks=roofline.peaks_for("TPU v5 lite"))
    for name in ("attn_ctx_mean", "moe_local_share", "prefill_flops_util",
                 "decode_bw_util_family"):
        assert manifest.load_reader(cell.root, name)(obs) is None, name

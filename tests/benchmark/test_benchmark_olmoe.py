"""The OLMoE family in the benchmark: its architecture file, its
configuration and cell, and the three readers that came with it.

A rehearsal cell of ``tiny-olmoe`` (the published key names at a toy
size) runs whole on the CPU through benchmark/architectures/olmoe.py and
is ``correct``; the same program under a copy of that file whose
reference leaves ``q_norm`` out is refused. ``engine_weights`` hands the
engine's tree back element for element. The readers are driven with
made-up observations.
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

CELL = "olmoe-1b-7b-0125.chat-backlog"
OLMOE_PY = os.path.join(ROOT, "benchmark", "architectures", "olmoe.py")


def tiny_olmoe(name: str, architecture: str = "olmoe") -> dict:
    cfg = tiny(name, architecture=architecture, model_type="olmoe",
               norm_topk_prob=False, num_key_value_heads=4,
               intermediate_size=64)
    cfg.update(num_experts=8, num_experts_per_tok=4, moe_capacity_factor=2.0)
    return cfg


@pytest.fixture(scope="module")
def olmoe_root(tmp_path_factory):
    with open(OLMOE_PY) as f:
        text = f.read()
    with_q = 'rms_norm(x @ w["wq"], w["q_norm"], eps)'
    assert with_q in text
    return write_benchmark(
        tmp_path_factory.mktemp("olmoe"),
        [tiny_olmoe("tiny-olmoe-cell"),
         tiny_olmoe("tiny-olmoe-no-q-norm", "olmoe-no-q-norm")],
        architectures={"olmoe-no-q-norm": text.replace(with_q,
                                                       '(x @ w["wq"])')})


def _whole_run(cell, root, out, capsys, seconds=4.0):
    last = run.run_cell(run_args(cell, 0, seconds), time.monotonic(),
                        data_root=root, out_root=str(out))
    earlier = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    return last, next(x["reference"] for x in earlier if "reference" in x)


def test_rehearsal_cell_runs_whole_and_is_correct(olmoe_root, on_cpu,
                                                  tmp_path, capsys):
    cell = manifest.load_cell("tiny-olmoe-cell.tiny-open", olmoe_root)
    assert cell.config["architecture"] == "olmoe"
    assert "num_local_experts" not in cell.config
    last, ref = _whole_run(cell.name, olmoe_root, tmp_path, capsys)
    assert ref["ok"] and 0 < ref["median"] <= ref["tolerance"]["median"]
    # 2 sequences x 128 prefill tokens x top-4 / 8 experts, factor 2.
    assert ref["capacity"] == 256 and ref["overflow_pairs"] == 0
    assert ref["tolerance"] == {"median": 0.02, "max": None,
                                "overflow_pairs": 0}
    assert last["correct"] and last["failed"] == 0
    assert last["attempted"] >= 10


@pytest.mark.parametrize("factor,capacity,overflow", [
    (None, 16, 0), (4.0, 16, 0), (2.0, 8, 32)],
    ids=["dropless", "roomy", "overflowing"])
def test_compare_is_not_ok_when_the_prefill_dropped_pairs(
        factor, capacity, overflow, on_cpu):
    """A system that agrees with the reference to the last digit is
    still not ``ok`` if, by the reference's own routing, its capacity
    buckets lost pairs: the published model drops nothing."""
    import jax.numpy as jnp
    arch = manifest.load_architecture(os.path.join(ROOT, "benchmark"),
                                      "olmoe")
    seqs, n_prefill, total, experts, top_k = 2, 8, 10, 16, 4
    logits = jnp.asarray(np.random.default_rng(0).normal(
        size=(seqs, total, 32)), jnp.float32)
    # Every token keeps experts 0..3, so each is sent all 16 prefill
    # tokens (the decode positions' routing is not the prefill's and is
    # left out): at capacity 8, (16 - 8) x 4 experts in the first layer.
    routing = jnp.zeros((seqs * total, experts)).at[:, :top_k].set(0.1)
    spread = jnp.eye(experts)[jnp.arange(seqs * total) % experts] * 0.1
    facts = {"routing": [routing if overflow else spread, spread],
             "min_margin": jnp.full((seqs * total,), 0.5),
             "n_prefill": n_prefill}
    cfg = {"num_experts": experts, "num_experts_per_tok": top_k,
           "moe_capacity_factor": factor}
    out = arch.compare(logits, logits, facts, cfg)
    assert out["capacity"] == capacity and out["median"] == 0.0
    assert out["overflow_pairs"] == overflow
    assert out["ok"] == (not overflow)
    assert out["tolerance"] == {"median": arch.TOL_MEDIAN, "max": None,
                                "overflow_pairs": 0}


def test_a_reference_without_q_norm_refuses_the_same_program(
        olmoe_root, on_cpu, tmp_path, capsys):
    last, ref = _whole_run("tiny-olmoe-no-q-norm.tiny-open", olmoe_root,
                           tmp_path, capsys, 2.0)
    assert not ref["ok"] and ref["median"] > 2 * ref["tolerance"]["median"]
    assert not last["correct"]


@pytest.mark.parametrize("tp", [1, 2])
def test_engine_weights_hand_the_tree_back(tp):
    """``wq, wk, wv, wo``, the two norm vectors, the router and an
    expert's three matrices, read from the fused int8 tree (interleaved
    by device under ``tp`` 2), equal the unfused originals."""
    import jax
    import jax.numpy as jnp
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.models import mixtral
    from p2p_llm_chat_tpu.models.llama import fuse_tp_for
    from p2p_llm_chat_tpu.models.quant import quantize_params
    from p2p_llm_chat_tpu.parallel.mesh import MeshConfig, make_mesh
    config = serve_cell.model_config(tiny_olmoe("t"))
    assert config.qk_norm_whole and not config.moe_renormalize
    assert config.num_experts == 8
    mesh = (make_mesh(MeshConfig(tp=tp), devices=jax.devices()[:tp])
            if tp > 1 else None)
    plain = quantize_params(
        mixtral.init_params(config, jax.random.PRNGKey(3)), mode="int8")
    assert fuse_tp_for(config, mesh) == tp
    fused = mixtral.fuse_params(plain, tp=tp, mesh=mesh)
    arch = manifest.load_architecture(os.path.join(ROOT, "benchmark"),
                                      "olmoe")
    weights = arch.engine_weights(types.SimpleNamespace(
        _params=fused, config=config, mesh=mesh))
    deq = lambda w, *at: np.asarray(w.q[at], np.float32) * np.asarray(
        w.s[at], np.float32)
    L = plain["layers"]
    for layer in range(config.num_layers):
        got = weights.layer(layer)
        for name in ("wq", "wk", "wv", "wo"):
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          deq(L[name], layer), name)
        for name in ("q_norm", "k_norm", "router", "attn_norm"):
            np.testing.assert_array_equal(
                np.asarray(got[name]),
                np.asarray(L[name][layer], np.float32), name)
        for name, w in zip(("w_gate", "w_up", "w_down"),
                           weights.expert(layer, 5)):
            np.testing.assert_array_equal(np.asarray(w),
                                          deq(L[name], layer, 5), name)
    assert weights.lm_head.dtype == jnp.float32


# -- the configuration and the cell -------------------------------------------

PUBLISHED = {       # allenai/OLMoE-1B-7B-0125-Instruct config.json
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def test_the_cell_is_the_whole_published_model_on_the_shared_traffic():
    from benchmark import serve_cell
    man = manifest.load_manifest(ROOT)
    cell = manifest.load_cell(CELL, ROOT, man)
    assert cell.chips == 1 and cell.traffic_name == "chat-backlog"
    assert cell.extra == {}                 # the mix as it is, no overrides
    assert {k: cell.config[k] for k in PUBLISHED} == PUBLISHED
    assert cell.config["reduced"] == {}
    entry = next(c for c in man["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == [] and entry["source"] == cell.config["source"]
    config = serve_cell.model_config(cell.config)
    assert (config.num_layers, config.num_experts, config.num_heads,
            config.num_kv_heads, config.head_dim) == (16, 64, 16, 16, 128)
    assert config.qk_norm_whole and not config.moe_renormalize
    assert config.moe_capacity_factor == cell.config["moe_capacity_factor"]
    reported = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert {"tpot_p50_ms", "setup_s", "moe_drop_share",
            "decode_bw_util_arch", "pallas_share",
            "device_idle"} <= reported
    assert "decode_bw_util" not in reported and "itl_p50_ms" not in reported
    # Whole on one chip: int8 weights and the pool the stack asks for.
    weights = roofline.model_weight_bytes(
        manifest.load_architecture(cell.root, "olmoe").roofline_config(
            cell.config))
    assert 6.9e9 < weights < 7.2e9


# -- the readers --------------------------------------------------------------

def _obs(cell_name=CELL, **kw):
    cell = manifest.load_cell(cell_name, ROOT)
    rec = types.SimpleNamespace(ok=True, due_t=6.0, prompt_bytes=400,
                                tokens=100, chunk_t=[6.5, 7.0],
                                chunk_tokens=[1, 99])
    base = dict(records=[rec], ramp_s=5.0, window_s=51.0, cell=cell,
                peaks=roofline.peaks_for("TPU v5 lite"))
    base.update(kw)
    return metrics.Observations(**base)


def _read(name, obs):
    return manifest.load_reader(obs.cell.root, name)(obs)


def test_moe_drop_share_is_a_window_difference_and_absent_on_the_parent():
    obs = _obs(counters_start={"serve_moe_assignments_total": 1000,
                               "serve_moe_dropped_total": 10},
               counters_end={"serve_moe_assignments_total": 201000,
                             "serve_moe_dropped_total": 510})
    assert _read("moe_drop_share", obs) == pytest.approx(0.25)
    # A program without the counters (the parent of this PR), or a
    # window that routed nothing: nothing to read, and no fault.
    assert _read("moe_drop_share", _obs(counters_start={"x": 1},
                                        counters_end={"x": 2})) is None
    same = {"serve_moe_assignments_total": 5, "serve_moe_dropped_total": 0}
    assert _read("moe_drop_share", _obs(counters_start=same,
                                        counters_end=same)) is None


def test_decode_bw_util_arch_counts_olmoe_as_routed():
    steps = {"serve_decode_ticks_total": 700, "decode_fused_ticks_total": 600,
             "decode_fused_steps_total": 2400}
    zero = dict.fromkeys(steps, 0)
    obs = _obs(counters_start=zero, counters_end=steps)
    got = _read("decode_bw_util_arch", obs)
    cfg = dict(obs.cell.config, num_local_experts=64)
    want = 100.0 * roofline.decode_step_bytes(
        cfg, rows=1.0, context=400 + 1 + 50) * 2500 / (51.0 * 819e9)
    assert got == pytest.approx(want)
    # roofline.py alone would read it as a dense model of width 1024.
    # (At a full batch, where every expert is touched.)
    dense = roofline.decode_step_bytes(obs.cell.config, rows=30, context=451)
    assert dense < 0.3 * roofline.decode_step_bytes(cfg, rows=30,
                                                    context=451)
    # For a family roofline.py reads as it is, it is decode_bw_util.
    mix = _obs("mixtral-8x7b-v0.1-l6.chat-backlog", counters_start=zero,
               counters_end=steps)
    assert _read("decode_bw_util_arch", mix) == _read("decode_bw_util", mix)

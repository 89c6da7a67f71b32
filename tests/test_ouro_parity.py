"""tiny-ouro (three layers walked four times with one set of weights, four
norms a layer, the final norm and the exit gate after every pass) against
the plain reference of benchmark/architectures/ouro.py, on the CPU, on
seeded random weights in float32: every entry point of models/llama.py
that walks the stack, through ``KVCache`` and ``PagedKVCache``, logits
and exit distribution; each wrong model of the reference fails the
comparison; the name map loads the four norms and the gate.
"""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import family_for, llama
from p2p_llm_chat_tpu.models.configs import ModelConfig, get_config
from p2p_llm_chat_tpu.models.layers import causal_mask
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.models.weights import (_reverse_name_map,
                                             convert_hf_state_dict)
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache, write_prefill_batch

from solo import jit_model

from benchmark import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = manifest.load_architecture(os.path.join(ROOT, "benchmark"), "ouro")
CFG = get_config("tiny-ouro")
# The published key names at the test size (what the architecture file's
# reference reads).
FILE = {"name": "tiny-ouro", "hidden_size": 128, "intermediate_size": 256,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 32, "vocab_size": 512,
        "max_position_embeddings": 256, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        # The reference check's long sample is laid out for this chunk:
        # 2 x 32 + 22 positions through three chunks, then 8 decode steps.
        "stack": {"SERVE_PREFILL_CHUNK": "32"}}
B, P, D = 2, 24, 6
T = P + D
PAGE, PAGES = 8, 4


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(5).integers(
        0, CFG.vocab_size, size=(B, T)), jnp.int32)


def plain_weights(params):
    """The float32 tree as the reference's ``Weights``."""
    lay = params["layers"]
    return ARCH.Weights(
        embed=params["embed"],
        layer=lambda l: {k: v[l] for k, v in lay.items()},
        final_norm=params["final_norm"],
        gate_w=params["exit_gate_w"][:, 0], gate_b=params["exit_gate_b"][0],
        lm_head=params["lm_head"])


@pytest.fixture(scope="module")
def reference(params, tokens):
    """(logits [B, T, V], exit pdf [B, T, 4]) of the plain reference's
    full forward."""
    logits, facts = ARCH.forward(FILE, tokens, plain_weights(params))
    return np.asarray(logits), np.asarray(facts["exit_pdf"])


def close(got, want, tol=2e-4):
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=tol)


def test_the_dense_family_serves_it_and_the_cache_holds_a_layer_a_pass():
    assert family_for(CFG) is llama
    assert (CFG.num_layers, CFG.ut_steps, CFG.cache_layers) == (3, 4, 12)
    assert CFG.sandwich_norm and CFG.num_kv_heads == CFG.num_heads
    assert get_config("tiny").cache_layers == 2
    assert ModelConfig(**ARCH.model_config(FILE)) == CFG.with_(
        eos_token_ids=())
    assert KVCache.create(CFG, 2, 16).k.shape == (12, 2, 16, 4, 32)
    pool = PagedKVCache.create(CFG, 2, 5, 8, quantized=True)
    assert pool.k.shape == (12, 5, 8, 4, 32)
    assert pool.k_scale.shape == (12, 5, 4, 128)


def test_init_draws_the_new_leaves_away_from_the_identity(params):
    lay = params["layers"]
    for name in ("attn_out_norm", "mlp_out_norm"):
        assert lay[name].shape == (3, 128)
        assert 0.5 <= float(lay[name].min()) < float(lay[name].max()) < 1.5
    assert params["exit_gate_w"].shape == (128, 1)
    assert params["exit_gate_b"].shape == (1,)
    assert float(jnp.abs(params["exit_gate_w"]).max()) > 0.05
    # No other leaf's draw moved: the plain tree of the same key is this
    # one without the new leaves.
    plain = llama.init_params(
        CFG.with_(sandwich_norm=False, ut_steps=1), jax.random.PRNGKey(7),
        dtype=jnp.float32)
    assert set(params) - set(plain) == {"exit_gate_w", "exit_gate_b"}
    assert set(lay) - set(plain["layers"]) == {"attn_out_norm",
                                               "mlp_out_norm"}
    for k, v in plain["layers"].items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(lay[k]))
    axes = llama.param_axes(CFG)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, params)) == \
        jax.tree.structure(jax.tree.map(lambda _: 0, axes,
                                        is_leaf=lambda a: isinstance(a, tuple)))
    q = llama.init_params_quantized(CFG, jax.random.PRNGKey(7),
                                    dtype=jnp.float32)
    assert set(q["layers"]) == {"attn_norm", "mlp_norm", "attn_out_norm",
                                "mlp_out_norm", "wqkv", "wo", "wgu",
                                "w_down"}
    assert {"exit_gate_w", "exit_gate_b"} <= set(q)
    fused = llama.fuse_params(params)
    assert {"attn_out_norm", "mlp_out_norm", "wqkv", "wgu"} <= set(
        fused["layers"]) and "exit_gate_w" in fused


def test_exit_pdf_is_the_gates_stick_breaking():
    g = jnp.asarray([[0.5, 0.1], [0.5, 0.2], [0.5, 0.3], [0.9, 0.9]])
    pdf = np.asarray(llama.exit_pdf(g))
    np.testing.assert_allclose(pdf[0], [0.5, 0.25, 0.125, 0.125])
    np.testing.assert_allclose(
        pdf[1], [0.1, 0.9 * 0.2, 0.9 * 0.8 * 0.3, 0.9 * 0.8 * 0.7],
        rtol=1e-6)
    np.testing.assert_allclose(pdf.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ARCH.exit_pdf(g)), pdf, rtol=1e-6)


def test_full_forward_is_the_references(params, tokens, reference):
    """(a) One causal forward over all positions: logits and exit pdf."""
    ref_logits, ref_pdf = reference
    cache = KVCache.create(CFG, B, T, dtype=jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    logits, cache, pdf = jit_model(llama.forward_exit, CFG)(
        params, tokens, pos, cache, causal_mask(T, T, 0))
    close(logits, ref_logits)
    close(pdf, ref_pdf, 2e-5)
    # Every (pass, layer) pair wrote a cache layer of its own.
    k = np.asarray(cache.k)
    assert all(np.abs(k[i]).max() > 0 for i in range(12))
    assert not np.allclose(k[0], k[3])       # pass 1 is not pass 0
    # ``forward`` is the same walk without the pdf.
    plain, _ = jit_model(llama.forward, CFG)(
        params, tokens, pos, KVCache.create(CFG, B, T, dtype=jnp.float32),
        causal_mask(T, T, 0))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(logits))


def _prefilled(params, tokens, chunks: tuple):
    """The dense cache after the first P positions, one shot or in
    ``chunks`` (offsets), with the logits of those positions."""
    cache = KVCache.create(CFG, B, T, dtype=jnp.float32)
    if not chunks:
        logits, cache = jit_model(llama.prefill, CFG)(
            params, tokens[:, :P], jnp.full((B,), P, jnp.int32), cache)
        return logits, cache
    out = []
    edges = list(chunks) + [P]
    for lo, hi in zip(edges, edges[1:]):
        logits, cache = jit_model(llama.prefill_chunk, CFG, offset=lo)(
            params, tokens[:, lo:hi], cache)
        out.append(logits)
    return jnp.concatenate(out, axis=1), cache._replace(
        lengths=jnp.full((B,), P, jnp.int32))


@pytest.mark.parametrize("chunks", [(), (0, 8), (0, 16)],
                         ids=["one_shot", "chunks_at_0_8", "chunks_at_0_16"])
def test_prefill_then_dense_decode_is_the_references(chunks, params, tokens,
                                                     reference):
    """(b) ``prefill`` / ``prefill_chunk`` at two offsets, then
    ``decode_step`` over the dense cache, teacher-forced: every position's
    logits are the full forward's, and each step's exit mass is the
    reference's pdf summed over the rows."""
    ref_logits, ref_pdf = reference
    logits, cache = _prefilled(params, tokens, chunks)
    close(logits, ref_logits[:, :P])
    step = jit_model(llama.decode_step_exit, CFG)
    plain = jit_model(llama.decode_step, CFG)
    for t in range(P, T):
        twin, _ = plain(params, tokens[:, t: t + 1], cache)
        got, cache, mass = step(params, tokens[:, t: t + 1], cache)
        close(got[:, 0], ref_logits[:, t])
        close(mass, ref_pdf[:, t].sum(0), 5e-5)
        np.testing.assert_array_equal(np.asarray(twin), np.asarray(got))
    assert list(np.asarray(cache.lengths)) == [T, T]


def _paged(params, tokens, quantized=False):
    _, dense = _prefilled(params, tokens, ())
    pool = PagedKVCache.create(CFG, B, 1 + B * PAGES, PAGE,
                               max_pages_per_row=PAGES, dtype=jnp.float32,
                               quantized=quantized)
    tables = 1 + jnp.arange(B * PAGES, dtype=jnp.int32).reshape(B, PAGES)
    return write_prefill_batch(pool, dense.k[:, :, :P], dense.v[:, :, :P],
                               jnp.arange(B), jnp.full((B,), P, jnp.int32),
                               tables)


def test_paged_decode_steps_are_the_references(params, tokens, reference):
    """(b) ``decode_step_paged`` over a float pool of 12 page layers."""
    ref_logits, ref_pdf = reference
    cache = _paged(params, tokens)
    assert cache.k.shape[0] == 12
    step = jit_model(llama.decode_step_paged_exit, CFG, pages=PAGES)
    plain = jit_model(llama.decode_step_paged, CFG, pages=PAGES)
    for t in range(P, T):
        twin, _ = plain(params, tokens[:, t: t + 1], cache)
        got, cache, mass = step(params, tokens[:, t: t + 1], cache)
        close(got[:, 0], ref_logits[:, t])
        close(mass, ref_pdf[:, t].sum(0), 5e-5)
        np.testing.assert_array_equal(np.asarray(twin), np.asarray(got))


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_fused_decode_is_the_references(paged, params, tokens, reference):
    """(b) ``decode_fused_exit``: D steps in one dispatch, the sampler
    handing back the sample's next token; a row parked from the start
    adds nothing to the exit mass."""
    ref_logits, ref_pdf = reference
    cache = (_paged(params, tokens) if paged
             else _prefilled(params, tokens, ())[1])
    script = tokens[:, P + 1:].T                        # [D - 1, B]
    script = jnp.concatenate([script, jnp.zeros((1, B), jnp.int32)])
    seen = []

    def sample(logits, i, emit_pos, act):
        seen.append(None)
        return script[i], i + 1

    def run(active):
        return jax.jit(lambda p, t, c: llama.decode_fused_exit(
            p, CFG, t, c, active=active, num_steps=D, sample_fn=sample,
            sample_state=jnp.zeros((), jnp.int32), stop_ids=(),
            **({"pages": PAGES} if paged else {})))(
                params, tokens[:, P: P + 1], cache)

    *_, cache2, _, _, mass = run(jnp.ones((B,), bool))
    close(mass, ref_pdf[:, P:].sum((0, 1)), 2e-4)
    assert float(mass.sum()) == pytest.approx(B * D, abs=1e-3)
    assert list(np.asarray(cache2.lengths)) == [T, T]
    *_, mass1 = run(jnp.asarray([True, False]))
    close(mass1, ref_pdf[0, P:].sum(0), 2e-4)
    # The fused scan's last step equals a plain step at that position.
    step = jit_model(llama.decode_step_paged if paged else llama.decode_step,
                     CFG, **({"pages": PAGES} if paged else {}))
    c = cache
    for t in range(P, T):
        got, c = step(params, tokens[:, t: t + 1], c)
    close(got[:, 0], ref_logits[:, T - 1])


def test_verify_programs_walk_the_passes_too(params, tokens, reference):
    """The session-wake admission's forward (``verify_step_paged``) and
    the dense ``verify_step`` over a looped stack: S positions behind a
    cached prefix read this pass's keys in every pass."""
    ref_logits, _ = reference
    S = D
    logits, _ = jit_model(llama.verify_step_paged, CFG, pages=PAGES)(
        params, tokens[:, P:], _paged(params, tokens))
    close(logits, ref_logits[:, P:])
    _, dense = _prefilled(params, tokens, ())
    logits, _ = jit_model(llama.verify_step, CFG)(params, tokens[:, P:],
                                                 dense)
    close(logits, ref_logits[:, P:])
    anc = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)), (B, S, S))
    depths = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    logits, _ = jit_model(llama.verify_tree_paged, CFG, pages=PAGES)(
        params, tokens[:, P:], depths, anc, _paged(params, tokens))
    close(logits, ref_logits[:, P:])


# -- the reference's wrong models ---------------------------------------------

@pytest.fixture(scope="module")
def checked():
    """The reference check's two sides at the test size: the system
    through the architecture file's ``system_logits`` (int8 weights, an
    int8 pool of 4 slots, the long sample through the chunk ladder, fused
    decode of the three rows) and the engine's weights."""
    q = llama.init_params_quantized(CFG, jax.random.PRNGKey(3),
                                    dtype=jnp.float32)
    sched = types.SimpleNamespace(
        _model=llama, _params=q, config=CFG, mesh=None, page_size=16,
        num_slots=4, decode_fuse_max=4, _dtype=jnp.float32, kv_quant=True,
        prefill_chunk=32)
    toks = jnp.asarray(np.random.default_rng(53).integers(
        0, CFG.vocab_size, size=(2, 40 + 8)), jnp.int32)
    return (toks, ARCH.system_logits(sched, toks, 40),
            ARCH.engine_weights(sched))


@pytest.mark.parametrize("name", ["", *ARCH.WRONG])
def test_compare_passes_the_sound_model_and_fails_each_wrong_one(name,
                                                                checked):
    """(c) Under the limits read on the chip at the published widths."""
    toks, system, weights = checked
    cfg = {**FILE, "_n_prefill": 40}
    cfg, w = ARCH.wrong_models(cfg, weights)[name] if name else (cfg,
                                                                 weights)
    ref, facts = ARCH.forward(cfg, toks, w)
    out = ARCH.compare(system, ref, {**facts, "n_prefill": 40}, FILE)
    assert out["tolerance"] == {"median": ARCH.TOL_MEDIAN,
                                "long_median": ARCH.TOL_MEDIAN,
                                "decode_max": ARCH.TOL_DECODE,
                                "long_decode_max": ARCH.TOL_DECODE,
                                "exit_max": ARCH.TOL_EXIT}
    assert out["ok"] is (not name), out
    if name == "shared_cache":
        # The prefill positions are the sound model's, the long row's
        # too: only the decode steps tell, of either sample.
        assert out["median"] <= ARCH.TOL_MEDIAN
        assert out["long_median"] <= ARCH.TOL_MEDIAN
        assert out["decode_max"] > ARCH.TOL_DECODE
        assert out["long_decode_max"] > ARCH.TOL_DECODE
    if not name:
        assert system.exit_pdf.shape == (2, 40, 4)
        assert system.exit_mass.shape == (8, 4)
        assert abs(float(system.exit_mass.sum()) - 24) < 1e-3
        # 2 x 32 + 22 prefill positions, every eighth and the chunks'
        # firsts and the last, then the 8 decode steps.
        assert ARCH.long_shape(32) == (86, 8)
        assert system.long_logits.shape == (1, 12 + 8, 512)
        assert 0 < out["long_median"] and 0 < out["long_decode_max"]


# -- the name map -------------------------------------------------------------

def test_name_map_loads_the_four_norms_and_the_gate():
    """(g) A synthetic HF state dict, nothing downloaded: every leaf of
    the tree, the two output norms and the gate among them, lands where
    the walk reads it."""
    rng = np.random.default_rng(11)
    H, E, V = 128, 256, 512
    state = {"model.embed_tokens.weight": rng.normal(size=(V, H)),
             "model.norm.weight": rng.normal(size=(H,)),
             "lm_head.weight": rng.normal(size=(V, H)),
             "model.early_exit_gate.weight": rng.normal(size=(1, H)),
             "model.early_exit_gate.bias": rng.normal(size=(1,))}
    for i in range(3):
        p = f"model.layers.{i}"
        for n in ("input_layernorm", "input_layernorm_2",
                  "post_attention_layernorm", "post_attention_layernorm_2"):
            state[f"{p}.{n}.weight"] = rng.normal(size=(H,))
        for n in "qkvo":
            state[f"{p}.self_attn.{n}_proj.weight"] = rng.normal(size=(H, H))
        for n, shape in (("gate", (E, H)), ("up", (E, H)), ("down", (H, E))):
            state[f"{p}.mlp.{n}_proj.weight"] = rng.normal(size=shape)
    state = {k: v.astype(np.float32) for k, v in state.items()}
    assert set(_reverse_name_map(CFG)) == set(state)
    tree = convert_hf_state_dict(state, CFG, dtype=jnp.float32)
    ref = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert jax.tree.map(jnp.shape, tree) == jax.tree.map(jnp.shape, ref)
    lay = tree["layers"]
    np.testing.assert_array_equal(
        lay["attn_out_norm"][1],
        state["model.layers.1.input_layernorm_2.weight"])
    np.testing.assert_array_equal(
        lay["mlp_out_norm"][2],
        state["model.layers.2.post_attention_layernorm_2.weight"])
    np.testing.assert_array_equal(
        lay["mlp_norm"][0],
        state["model.layers.0.post_attention_layernorm.weight"])
    np.testing.assert_array_equal(
        tree["exit_gate_w"][:, 0], state["model.early_exit_gate.weight"][0])
    np.testing.assert_array_equal(tree["exit_gate_b"],
                                  state["model.early_exit_gate.bias"])
    # A plain dense model's map is what it was.
    assert not any("layernorm_2" in k or "early_exit" in k
                   for k in _reverse_name_map(get_config("tiny")))


def test_config_from_hf_json_reads_the_loop(tmp_path):
    import json
    from p2p_llm_chat_tpu.models.weights import config_from_hf_json
    hf = {**FILE, "model_type": "ouro"}
    path = tmp_path / "config.json"
    path.write_text(json.dumps(hf))
    got = config_from_hf_json(str(path))
    assert (got.ut_steps, got.sandwich_norm, got.cache_layers) == (4, True,
                                                                   12)
    path.write_text(json.dumps({**hf, "early_exit_threshold": 0.5}))
    with pytest.raises(ValueError, match="early_exit_threshold 0.5"):
        config_from_hf_json(str(path))
    path.write_text(json.dumps({k: v for k, v in FILE.items()
                                if k not in ("total_ut_steps",
                                             "early_exit_threshold")}))
    plain = config_from_hf_json(str(path))
    assert (plain.ut_steps, plain.sandwich_norm) == (1, False)

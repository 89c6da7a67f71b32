"""models/nemotron_h.py against the plain reference of
benchmark/architectures/nemotron_h.py (float32, the Mamba layer as the
sequential recurrence one position at a time, no cache, no kernels), at
test size on seeded random weights: int8 weights dequantise exactly, so
under float32 activations what is left is arithmetic order, and with an
int8 pool the cache's rounding.

The full forward; prefill then decode through both pools, plain and
fused; chunked prefill with carried state = one piece; one padded
admission program = each row's unpadded run; the four shares of the
routed layer + the shared expert once = the uncut layer; the router's
rule; and every wrong model fails ``compare``."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest, reference  # noqa: E402
from p2p_llm_chat_tpu.models import nemotron_h, pangu  # noqa: E402
from p2p_llm_chat_tpu.models.configs import get_config  # noqa: E402
from p2p_llm_chat_tpu.models.llama import KVCache, _layer_view  # noqa: E402
from p2p_llm_chat_tpu.ops import state_pool  # noqa: E402
from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,  # noqa: E402
                                           write_prefill_batch)

from solo import jit_model  # noqa: E402

CFG = get_config("tiny-nemotron-h")
# The published key names of the same model, as the reference reads them.
KEYS = {"name": "tiny-nemotron-h", "hidden_size": 128, "vocab_size": 512,
        "num_hidden_layers": 11, "hybrid_override_pattern": "MEMEM*EMEME",
        "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
        "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "rope_theta": 10000.0, "norm_eps": 1e-5,
        "moe_intermediate_size": 96, "moe_latent_size": 64,
        "moe_shared_expert_intermediate_size": 192, "n_shared_experts": 1,
        "n_routed_experts": 16, "n_held_experts": 4,
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "routed_scaling_factor": 5.0, "mlp_hidden_act": "relu2",
        "max_position_embeddings": 256, "tie_word_embeddings": False}
B, P, D = 2, 40, 8
PS, PER_ROW = 16, 4
# The program's entry points, each lowered whole (tests/solo.py): called
# eagerly a forward is a program an operation.
prefill = jit_model(nemotron_h.prefill, CFG)
prefill_last = jit_model(nemotron_h.prefill, CFG, last_only=True)
decode_step = jit_model(nemotron_h.decode_step_paged, CFG, pages=PER_ROW)


@pytest.fixture(scope="module")
def arch():
    return manifest.load_architecture(os.path.join(ROOT, "benchmark"),
                                      "nemotron_h")


def fake_sched(params, **kw):
    return types.SimpleNamespace(_params=params, config=CFG, mesh=None,
                                 _model=nemotron_h, _dtype=jnp.float32,
                                 page_size=PS, kv_quant=True, **kw)


@pytest.fixture(scope="module")
def setup(arch):
    params = nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(7),
                                              dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, CFG.vocab_size, (B, P + D)), jnp.int32)
    weights = arch.engine_weights(fake_sched(params))
    ref, facts = arch.forward(KEYS, tokens, weights)
    return params, tokens, ref, facts, weights


def pools_from(carry, quantized, lens=None):
    pool = PagedKVCache.create(CFG, B, 1 + B * PER_ROW, PS,
                               max_pages_per_row=PER_ROW, dtype=jnp.float32,
                               quantized=quantized)
    tables = 1 + jnp.arange(B * PER_ROW, dtype=jnp.int32).reshape(B, PER_ROW)
    lens = jnp.full((B,), P, jnp.int32) if lens is None else lens
    pool = write_prefill_batch(pool, carry.k, carry.v, jnp.arange(B), lens,
                               tables)
    return pool._replace(state=state_pool.write_rows(
        pool.state, carry.state, jnp.arange(B)))


def one_shot(params, tokens, n=P):
    cache = KVCache.create(CFG, B, n, dtype=jnp.float32)
    return prefill(params, tokens[:, :n], jnp.full((B,), n, jnp.int32),
                   cache)


def close(a, b, tol=2e-3):
    err = reference.position_errors(a, b)
    assert float(jnp.max(err)) < tol, float(jnp.max(err))


def test_model_config_from_the_published_keys(arch):
    kw = arch.model_config({**KEYS, "max_position_embeddings": 256})
    built = CFG.with_(**{k: v for k, v in kw.items()
                         if k not in ("eos_token_ids", "bos_token_id")})
    assert built == CFG


def test_full_forward_is_the_reference(setup):
    params, tokens, ref, facts, _ = setup
    logits, carry = one_shot(params, tokens, P + D)
    close(logits, ref)
    # The chunked scan's final state is the recurrence's, layer by layer.
    for l, S in enumerate(facts["states"]):
        np.testing.assert_allclose(np.asarray(carry.state.ssm[l]),
                                   np.asarray(S), rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("edges", [(0, 40), (0, 13, 40), (0, 24, 31, 40),
                                   (0, 16, 32, 40)])
def test_chunked_prefill_with_carry_is_one_piece(setup, edges):
    """Prompt length 40 and chunk edges that are no multiple of the
    block (16): logits, K/V, state and window."""
    params, tokens, ref, _, _ = setup
    _, whole = one_shot(params, tokens)
    carry = KVCache.create(CFG, B, P, dtype=jnp.float32)
    out = []
    for lo, hi in zip(edges, edges[1:]):
        logits, carry = jit_model(nemotron_h.prefill_chunk, CFG, offset=lo)(
            params, tokens[:, lo:hi], carry)
        out.append(logits)
    close(jnp.concatenate(out, axis=1), ref[:, :P])
    for got, want in ((carry.k, whole.k), (carry.state.ssm, whole.state.ssm),
                      (carry.state.conv, whole.state.conv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)


def test_padded_admission_is_each_rows_unpadded_run(setup):
    """One program over rows of different lengths, padded to a bucket of
    64 and to three rows (the third a dummy entry): each row's state and
    window equal its own unpadded run's, and the dummy's stay zero."""
    params, tokens, _, _, _ = setup
    lens = jnp.asarray([23, 40, 1], jnp.int32)
    padded = jnp.zeros((3, 64), jnp.int32).at[:2, :P].set(tokens[:, :P])
    padded = padded.at[0, 23:].set(7)       # junk behind row 0's prompt
    valid = (jnp.arange(64)[None, :] < lens[:, None]) & jnp.asarray(
        [True, True, False])[:, None]
    cache = KVCache.create(CFG, 3, 64, dtype=jnp.float32)
    logits, cache, _ = jit_model(nemotron_h.prefill_counted, CFG,
                                 last_only=True)(
        params, padded, lens, cache, valid)
    for row, n in ((0, 23), (1, 40)):
        solo = KVCache.create(CFG, 1, n, dtype=jnp.float32)
        want, solo = prefill_last(params, tokens[row: row + 1, :n],
                                  jnp.asarray([n]), solo)
        close(logits[row: row + 1], want)
        np.testing.assert_allclose(np.asarray(cache.state.ssm[:, row]),
                                   np.asarray(solo.state.ssm[:, 0]),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(cache.state.conv[:, row]),
                                   np.asarray(solo.state.conv[:, 0]),
                                   rtol=1e-3, atol=1e-5)
    assert not np.asarray(cache.state.ssm[:, 2]).any()
    assert not np.asarray(cache.state.conv[:, 2]).any()


@pytest.mark.parametrize("quantized,tol", [(False, 2e-3), (True, 0.05)])
def test_decode_through_both_pools_is_the_reference(setup, quantized, tol):
    params, tokens, ref, facts, _ = setup
    _, carry = one_shot(params, tokens)
    pool = pools_from(carry, quantized)
    out = []
    for t in range(P, P + D):
        logits, pool = decode_step(params, tokens[:, t: t + 1], pool)
        out.append(logits)
    close(jnp.concatenate(out, axis=1), ref[:, P:], tol)
    assert list(np.asarray(pool.lengths)) == [P + D] * B
    np.testing.assert_allclose(np.asarray(pool.state.ssm[0, :B]),
                               np.asarray(facts["states"][0]),
                               rtol=5e-3, atol=5e-4)


def test_fused_decode_is_the_plain_steps(setup):
    params, tokens, _, _, _ = setup
    _, carry = one_shot(params, tokens)

    def greedy(logits, state, emit_pos, act):
        return jnp.argmax(logits, -1).astype(jnp.int32), state

    plain, toks, tok = pools_from(carry, True), [], tokens[:, P: P + 1]
    for _ in range(4):
        logits, plain = decode_step(params, tok, plain)
        tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)[:, None]
        toks.append(tok[:, 0])
    fused = jit_model(
        nemotron_h.decode_fused, CFG, num_steps=4, sample_fn=greedy,
        sample_state=(), stop_ids=(), pages=PER_ROW)(
            params, tokens[:, P: P + 1], pools_from(carry, True))
    assert np.array_equal(np.asarray(fused[0]), np.asarray(jnp.stack(toks)))
    np.testing.assert_allclose(np.asarray(fused[3].state.ssm),
                               np.asarray(plain.state.ssm), rtol=1e-5,
                               atol=1e-6)


def test_system_logits_and_compare_pass_the_sound_program(setup, arch):
    params, tokens, ref, facts, _ = setup
    system = arch.system_logits(fake_sched(params), tokens, P)
    assert system.logits.shape == ref.shape
    got = arch.compare(system, ref, {**facts, "n_prefill": P}, KEYS)
    assert got["ok"], got
    assert got["state_error"] < 1e-4 and got["median"] < 0.02
    assert 0 < got["local_share"] < 1


@pytest.mark.parametrize("name", ["bf16_state", "no_d_skip", "no_conv_bias",
                                  "norm_before_gate", "no_selection_bias",
                                  "gated_experts", "rotary_applied",
                                  "int4_weights"])
def test_every_wrong_model_fails_compare(setup, arch, name):
    params, tokens, ref, _, weights = setup
    system = arch.SystemOut(logits=ref, state=setup[3]["states"][0])
    wcfg, w = arch.wrong_models(KEYS, weights)[name]
    wrong_ref, wfacts = arch.forward(wcfg, tokens, w)
    got = arch.compare(system, wrong_ref, {**wfacts, "n_prefill": P}, KEYS)
    if name == "bf16_state":
        # The limit on the state is the one that sees it: set on the
        # chip over 136 positions and 32 slow heads (the architecture
        # file has the readings); over this test's 48 positions and 2
        # heads the drift is smaller, and far above the sound program's.
        sound = arch.compare(system, ref, {**setup[3], "n_prefill": P},
                             KEYS)
        assert sound["ok"] and sound["state_error"] < 1e-5
        assert got["state_error"] > 5e-4, got["state_error"]
        assert got["median"] < arch.TOL_MEDIAN      # the logits cannot
        return
    assert not got["ok"], got


def test_router_chooses_by_biased_score_and_weighs_by_unbiased(setup):
    params = setup[0]
    lp = jax.tree.map(lambda a: a[0], {
        k: params["moe"][k] for k in ("router", "router_bias")})
    x = jax.random.normal(jax.random.PRNGKey(3), (9, CFG.hidden_size))
    top_w, top_i = pangu.route(x, lp["router"], CFG, lp["router_bias"])
    scores = jax.nn.sigmoid(x @ lp["router"])
    want_i = jnp.argsort(-(scores + lp["router_bias"]), axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(top_i), -1),
                          np.sort(np.asarray(want_i), -1))
    picked = jnp.take_along_axis(scores, top_i, -1)
    np.testing.assert_allclose(
        np.asarray(top_w),
        np.asarray(5.0 * picked / picked.sum(-1, keepdims=True)), rtol=1e-6)
    # The bias changes the choice somewhere, or the test tests nothing.
    plain_i = jnp.argsort(-scores, axis=-1)[:, :3]
    assert not np.array_equal(np.sort(np.asarray(plain_i), -1),
                              np.sort(np.asarray(want_i), -1))


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """A deployment's four chips hold experts 0-3, 4-7, 8-11, 12-15 of
    one routed layer; the sum of their routed parts (latent-wide, before
    the up-projection) through ``W_fc2`` plus the shared expert once is
    the layer that holds all sixteen."""
    whole_cfg = CFG.with_(name="tiny-nemotron-h-e16", num_experts=16)
    params = nemotron_h.init_params(whole_cfg, jax.random.PRNGKey(5),
                                    dtype=jnp.float32)
    lp = _layer_view(params["moe"], jnp.asarray(1, jnp.int32))
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 12, CFG.hidden_size))
    whole, stats = nemotron_h._moe(h, lp, whole_cfg, None, None)
    assert int(stats[0]) == int(stats[2]) == 2 * 12 * 3   # all local
    x = nemotron_h.rms_norm(h, lp["norm"], CFG.rms_norm_eps)
    latent = nemotron_h.mm(x, lp["w_fc1"])
    routed, local = 0.0, 0
    for share in range(4):
        ids = jnp.arange(4 * share, 4 * share + 4)
        # This chip's router columns FIRST, as its ids 0-3.
        order = jnp.concatenate([ids, jnp.delete(jnp.arange(16), ids)])
        part = {**lp, "router": lp["router"][:, order],
                "router_bias": lp["router_bias"][order],
                "w_up_e": lp["w_up_e"][ids], "w_down": lp["w_down"][ids]}
        out, st = pangu._routed_local(x, part, CFG, None, None, latent)
        routed = routed + out
        local += int(st[0])
    assert local == 2 * 12 * 3
    got = nemotron_h.mm(routed, lp["w_fc2"]) + nemotron_h._relu2_mlp(
        x, lp["w_up_s"], lp["w_down_s"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-4, atol=1e-4)

"""models/pangu.py against the plain reference of
benchmark/architectures/pangu_ultra_moe.py (float32, the expanded form,
no cache, no kernels), at test size on seeded random weights: int8
weights dequantise exactly, so under float32 activations what is left is
arithmetic order, and with an int8 pool the cache's rounding.

(a) prefill then decode through the latent pool, float and int8 pool,
chunked and one-shot prefill, fused and unfused decode; (b) the absorbed
form equals the expanded form on the same weights."""

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
from p2p_llm_chat_tpu.models import pangu  # noqa: E402
from p2p_llm_chat_tpu.models.configs import get_config  # noqa: E402
from p2p_llm_chat_tpu.models.llama import KVCache  # noqa: E402
from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,  # noqa: E402
                                           write_prefill_batch)

from solo import jit_model  # noqa: E402

CFG = get_config("tiny-pangu")
# The published key names of the same model, as the reference reads them.
KEYS = {"name": "tiny-pangu", "hidden_size": 128, "intermediate_size": 256,
        "moe_intermediate_size": 64, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "num_attention_heads": 4,
        "q_lora_rank": 48, "kv_lora_rank": 64, "qk_nope_head_dim": 32,
        "qk_rope_head_dim": 16, "v_head_dim": 32, "n_routed_experts": 16,
        "n_held_experts": 4, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "vocab_size": 512}
B, P, D = 2, 32, 8
PS, PER_ROW = 16, 4
# The program's entry points, each lowered whole (tests/solo.py).
prefill = jit_model(pangu.prefill, CFG)
decode_step = jit_model(pangu.decode_step_paged, CFG, pages=PER_ROW)
decode_step_touched = jit_model(pangu.decode_step_paged_touched, CFG,
                                pages=PER_ROW)


@pytest.fixture(scope="module")
def arch():
    return manifest.load_architecture(os.path.join(ROOT, "benchmark"),
                                      "pangu_ultra_moe")


@pytest.fixture(scope="module")
def setup(arch):
    params = pangu.init_params_quantized(CFG, jax.random.PRNGKey(7),
                                         dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, CFG.vocab_size, (B, P + D)), jnp.int32)
    weights = arch.engine_weights(types.SimpleNamespace(
        _params=params, config=CFG, mesh=None))
    ref, _ = arch.forward(KEYS, tokens, weights)
    return params, tokens, ref


def pool_from(carry, quantized):
    pool = PagedKVCache.create(CFG, B, 1 + B * PER_ROW, PS,
                               max_pages_per_row=PER_ROW, dtype=jnp.float32,
                               quantized=quantized)
    tables = 1 + jnp.arange(B * PER_ROW, dtype=jnp.int32).reshape(B, PER_ROW)
    return write_prefill_batch(pool, carry.k, carry.v, jnp.arange(B),
                               jnp.full((B,), P, jnp.int32), tables)


def one_shot(params, tokens):
    cache = KVCache.create(CFG, B, P, dtype=jnp.float32)
    return prefill(params, tokens[:, :P], jnp.full((B,), P, jnp.int32),
                   cache)


def test_one_shot_prefill_is_the_reference(setup):
    params, tokens, ref = setup
    logits, _ = one_shot(params, tokens)
    err = reference.position_errors(logits, ref[:, :P])
    assert float(jnp.max(err)) < 2e-4


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_is_the_one_shot_prefill(setup, chunk):
    """Chunks attend the carried latents at an offset (the expanded
    form over what earlier chunks wrote) and leave the same latents."""
    params, tokens, _ = setup
    want, want_cache = one_shot(params, tokens)
    carry = KVCache.create(CFG, B, P, dtype=jnp.float32)
    got = []
    for off in range(0, P, chunk):
        logits, carry = jit_model(pangu.prefill_chunk, CFG, offset=off)(
            params, tokens[:, off:off + chunk], carry)
        got.append(logits)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               np.asarray(want), atol=2e-4)
    np.testing.assert_allclose(np.asarray(carry.k),
                               np.asarray(want_cache.k), atol=2e-5)
    np.testing.assert_allclose(np.asarray(carry.v),
                               np.asarray(want_cache.v), atol=2e-5)


@pytest.mark.parametrize("quantized,limit", [(False, 2e-4), (True, 0.05)],
                         ids=["float-pool", "int8-pool"])
def test_decode_through_the_latent_pool_is_the_reference(setup, quantized,
                                                         limit):
    """(b) as well: the decode steps run the absorbed form over the
    pool, the reference the expanded form over the whole sequence. With
    a float pool they agree to arithmetic order; the int8 pool's
    rounding (one scale a token for the latent, one for the rotated
    key) stays under 5% of a position's spread at this size, where a
    latent is 64 numbers (at the published 512 the chip reads about 1%:
    PERF.md section 6, PR 30)."""
    params, tokens, ref = setup
    _, carry = one_shot(params, tokens)
    pool = pool_from(carry, quantized)
    out = []
    for t in range(P, P + D):
        logits, pool, stats = decode_step_touched(
            params, tokens[:, t:t + 1], pool)
        out.append(logits)
    err = reference.position_errors(jnp.concatenate(out, 1), ref[:, P:])
    assert float(jnp.max(err)) < limit
    assert int(pool.lengths[0]) == P + D
    # 2 routed layers x 4 held experts; 2 rows x top-4 x 2 layers pairs.
    assert int(stats[1]) == 8 and int(stats[2]) == 16
    assert 0 <= int(stats[3]) <= int(stats[2])


def test_fused_decode_is_the_plain_steps(setup):
    """K steps in one dispatch leave the tokens and the pool that K
    plain steps leave, a parked row untouched."""
    params, tokens, _ = setup
    _, carry = one_shot(params, tokens)
    active = jnp.asarray([True, False])

    def greedy(logits, state, emit_pos, act):
        return jnp.argmax(logits, -1).astype(jnp.int32), state

    first = tokens[:, P:P + 1]
    toks, _, _, fused_pool, _, _, stats = jit_model(
        pangu.decode_fused_touched, CFG, active=active, num_steps=4,
        sample_fn=greedy, sample_state=(), stop_ids=(), pages=PER_ROW)(
            params, first, pool_from(carry, True))
    pool, tok, want = pool_from(carry, True), first, []
    step = jit_model(pangu.decode_step_paged, CFG, active=active,
                     pages=PER_ROW)
    for _ in range(4):
        logits, pool = step(params, tok, pool)
        nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        want.append(nxt)
        tok = jnp.where(active[:, None], nxt[:, None], tok)
    np.testing.assert_array_equal(np.asarray(toks)[:, 0],
                                  np.asarray(jnp.stack(want))[:, 0])
    np.testing.assert_array_equal(np.asarray(fused_pool.lengths),
                                  [P + 4, P])
    np.testing.assert_array_equal(np.asarray(fused_pool.k),
                                  np.asarray(pool.k))
    assert int(stats[1]) == 4 * 8


def test_a_session_wakes_suffix_is_the_decode_steps(setup):
    """``verify_step_paged`` (a parked session's wake: several positions
    behind the pool's context at its dynamic length) gives the logits
    the same tokens give one decode step at a time."""
    params, tokens, _ = setup
    _, carry = one_shot(params, tokens)
    pool = pool_from(carry, False)
    blk, after = jit_model(pangu.verify_step_paged, CFG, pages=PER_ROW)(
        params, tokens[:, P:P + 4], pool)
    out = []
    for t in range(P, P + 4):
        logits, pool = decode_step(params, tokens[:, t:t + 1], pool)
        out.append(logits)
    np.testing.assert_allclose(np.asarray(blk),
                               np.asarray(jnp.concatenate(out, 1)),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(after.k), np.asarray(pool.k),
                               atol=2e-5)

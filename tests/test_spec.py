"""Speculative decoding tests: verify_step, acceptance sampling, n-gram
drafting, and end-to-end greedy equivalence through the serving engine.

The load-bearing property: with greedy sampling, speculative mode must be
BIT-EXACT with the sequential loop (acceptance is argmax-match and the
correction is the argmax); with sampling, the emitted stream must be
distributed exactly as sequential sampling (pinned distributionally).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama, sampling
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer
from p2p_llm_chat_tpu.utils.draft import NGramDrafter

from solo import Solo, jit_model

pytestmark = pytest.mark.model

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)


# The sequential greedy loop on the model layer's dense cache
# (tests/solo.py), by the cache's rows.
SOLO = {n: Solo(llama, CFG, TOK, max_seq=n) for n in (64, 128, 256)}


def greedy_oracle(prompt: str, max_new: int) -> str:
    return SOLO[128](PARAMS, prompt, max_new)


# -- drafting -----------------------------------------------------------------

def test_ngram_drafter_proposes_recent_continuation():
    d = NGramDrafter([1, 2, 3, 4, 1, 2], k=3)
    assert d.draft() == [3, 4, 1]          # continuation after last (1,2)
    d2 = NGramDrafter([5, 6, 7], k=3)
    assert d2.draft() == []                # trailing (6,7) never seen before


def test_ngram_drafter_incremental_matches_batch():
    ids = [1, 2, 3, 1, 2, 4, 1, 2]
    inc = NGramDrafter(ids[:3], k=2)
    for t in ids[3:]:
        inc.append(t)
    batch = NGramDrafter(ids, k=2)
    assert inc.draft() == batch.draft() == [4, 1]   # last (1,2) cont.


# -- verify_step --------------------------------------------------------------

def test_verify_step_logits_match_sequential_decode():
    """Feeding the true greedy continuation as drafts: position j's logits
    must equal the j-th sequential decode_step's logits, and both caches
    must agree on every trusted slot."""
    rng = np.random.default_rng(0)
    B, P, K = 2, 10, 3
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (B, P)), jnp.int32)
    lens = jnp.full((B,), P, jnp.int32)

    cache_a = KVCache.create(CFG, B, 32, jnp.float32)
    logits, cache_a = jit_model(llama.prefill, CFG)(PARAMS, tokens, lens,
                                                   cache_a)
    cache_b = jax.tree.map(lambda x: x, cache_a)     # deep copy
    decode_step = jit_model(llama.decode_step, CFG)

    # Sequential: current token + K greedy steps.
    cur = jnp.argmax(logits[:, P - 1], -1).astype(jnp.int32)[:, None]
    seq_logits = []
    toks = [cur]
    c = cache_a
    t = cur
    for _ in range(K + 1):
        lg, c = decode_step(PARAMS, t, c)
        seq_logits.append(np.asarray(lg[:, 0]))
        t = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)[:, None]
        toks.append(t)
    stream = jnp.concatenate(toks[: K + 1], axis=1)   # [B, K+1]

    ver_logits, cache_v = jit_model(llama.verify_step, CFG)(PARAMS, stream,
                                                            cache_b)
    for j in range(K + 1):
        np.testing.assert_allclose(np.asarray(ver_logits[:, j]),
                                   seq_logits[j], atol=2e-4, rtol=2e-4)
    # Caches agree over the K+1 written slots.
    for j in range(K + 1):
        np.testing.assert_allclose(np.asarray(cache_v.k[:, :, P + j]),
                                   np.asarray(c.k[:, :, P + j]),
                                   atol=1e-5, rtol=1e-5)


# -- acceptance rule ----------------------------------------------------------

def _onehotish(B, S, V, peaks, sharp=50.0):
    """Logits [B,S,V] strongly peaked at ``peaks`` [B,S]."""
    lg = np.zeros((B, S, V), np.float32)
    for b in range(B):
        for s in range(S):
            lg[b, s, peaks[b, s]] = sharp
    return jnp.asarray(lg)


def test_spec_verify_greedy_accepts_matching_prefix():
    B, K, V = 3, 3, 16
    peaks = np.array([[1, 2, 3, 4],     # row 0: all drafts match
                      [1, 9, 9, 9],     # row 1: first draft mismatches
                      [1, 2, 9, 9]], np.int32)     # row 2: 2 accepted...
    drafts = jnp.asarray([[1, 2, 3], [2, 3, 4], [1, 9, 7]], jnp.int32)
    logits = _onehotish(B, K + 1, V, peaks)
    keys = jnp.zeros((B, 2), jnp.uint32)
    zeros = jnp.zeros((B,), jnp.float32)
    acc, corr, _ = sampling.spec_verify_batched(
        logits, drafts, keys, zeros, jnp.zeros((B,), jnp.int32),
        jnp.ones((B,), jnp.float32), jnp.full((B,), K, jnp.int32))
    acc, corr = np.asarray(acc), np.asarray(corr)
    # Row 0: drafts [1,2,3] == argmax prefix -> all 3 accepted, bonus = 4.
    assert acc[0] == 3 and corr[0] == 4
    # Row 1: draft 2 != argmax 1 -> 0 accepted, correction = argmax 1.
    assert acc[1] == 0 and corr[1] == 1
    # Row 2: drafts [1,9,...]: pos0 ok (1==1), pos1 9 != 2 -> 1 accepted,
    # correction = argmax at pos1 = 2.
    assert acc[2] == 1 and corr[2] == 2


def test_spec_verify_respects_max_accept():
    B, K, V = 1, 3, 8
    peaks = np.array([[1, 2, 3, 4]], np.int32)
    drafts = jnp.asarray([[1, 2, 3]], jnp.int32)
    logits = _onehotish(B, K + 1, V, peaks)
    acc, corr, _ = sampling.spec_verify_batched(
        logits, drafts, jnp.zeros((B, 2), jnp.uint32),
        jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
        jnp.ones((B,), jnp.float32), jnp.asarray([1], jnp.int32))
    assert int(acc[0]) == 1 and int(corr[0]) == 2   # cut at the cap


def test_spec_verify_sampled_stream_distribution():
    """Exactness of speculative sampling for a point-mass draft: the
    emitted first token's distribution must equal the model's warped
    distribution, no matter the draft. B parallel rows = B trials."""
    B, V = 4000, 8
    probs = np.array([0.5, 0.25, 0.125, 0.0625, 0.0625, 0, 0, 0])
    logits1 = np.log(np.maximum(probs, 1e-9))[None, :]
    # Position 0 scores draft token 1 (p=0.25); position 1 is the
    # correction/bonus position with the same distribution.
    lg = jnp.asarray(np.repeat(logits1[None], B, 0).repeat(2, 1), jnp.float32)
    drafts = jnp.ones((B, 1), jnp.int32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(B, dtype=jnp.uint32))
    acc, corr, _ = sampling.spec_verify_batched(
        lg, drafts, keys, jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32),
        jnp.ones((B,), jnp.int32))
    acc, corr = np.asarray(acc), np.asarray(corr)
    first = np.where(acc > 0, 1, corr)          # emitted first token
    freq = np.bincount(first, minlength=V) / B
    # 4-sigma binomial tolerance per bucket.
    for v in range(V):
        sigma = np.sqrt(max(probs[v] * (1 - probs[v]), 1e-9) / B)
        assert abs(freq[v] - probs[v]) < 4 * sigma + 1e-3, (v, freq[v])
    # And acceptance happened at the expected ~p(draft) rate.
    assert abs(acc.mean() - 0.25) < 0.03


def test_spec_verify_forced_rejection_samples_unmodified_distribution():
    """An undrafted row in a mixed spec tick carries zero-filled drafts
    and max_accept=0 — a FORCED stop, not a probabilistic rejection. Its
    token must come from the unmodified distribution: the residual rule
    (remove the draft token) would make such a row unable to ever emit
    token id 0."""
    B, V = 4000, 8
    probs = np.array([0.5, 0.25, 0.125, 0.0625, 0.0625, 0, 0, 0])
    lg = jnp.asarray(
        np.repeat(np.log(np.maximum(probs, 1e-9))[None, None, :], B, 0)
        .repeat(2, 1), jnp.float32)
    drafts = jnp.zeros((B, 1), jnp.int32)           # "draft" = token 0
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(B, dtype=jnp.uint32))
    acc, corr, _ = sampling.spec_verify_batched(
        lg, drafts, keys, jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32))                 # max_accept = 0
    acc, corr = np.asarray(acc), np.asarray(corr)
    assert (acc == 0).all()
    freq = np.bincount(corr, minlength=V) / B
    for v in range(V):
        sigma = np.sqrt(max(probs[v] * (1 - probs[v]), 1e-9) / B)
        assert abs(freq[v] - probs[v]) < 4 * sigma + 1e-3, (v, freq[v])


# -- end-to-end ---------------------------------------------------------------

def test_spec_engine_greedy_matches_oracle():
    """Greedy speculative serving is bit-exact with the sequential greedy
    oracle — accepted drafts and corrections interleave invisibly — on
    the paged pool (Pallas verify path)."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128, spec_k=4,
                    page_size=16)
    try:
        # Prompts with internal repetition so the n-gram drafter fires.
        for prompt in ["abab abab abab", "hello hello hello world",
                       "no repeats here at all"]:
            req = GenerateRequest(prompt=prompt,
                                  options=GenerateOptions(max_tokens=16))
            got = "".join(eng.generate_stream(req, RequestStats()))
            assert got == greedy_oracle(prompt, 16), prompt
    finally:
        eng.stop()


def test_spec_engine_moe_greedy_matches_oracle():
    """The MoE leg of the same bit-exactness bar (round-4 verdict #3):
    speculative serving under a mixtral engine — the n-gram drafter
    feeding mixtral.verify_step(_paged) — must match the sequential
    greedy oracle on the same tree."""
    from p2p_llm_chat_tpu.models import mixtral

    mcfg = get_config("tiny-moe")
    mparams = mixtral.init_params(mcfg, jax.random.PRNGKey(2),
                                  dtype=jnp.float32)
    solo = Solo(mixtral, mcfg, TOK)

    eng = TPUEngine(mparams, mcfg, TOK, num_slots=2, max_seq=128,
                    spec_k=4, page_size=16)
    try:
        for prompt in ["moe moe moe moe", "expert expert expert routing"]:
            req = GenerateRequest(prompt=prompt,
                                  options=GenerateOptions(max_tokens=16))
            got = "".join(eng.generate_stream(req, RequestStats()))
            assert got == solo(mparams, prompt, 16), prompt
    finally:
        eng.stop()


def test_verify_step_paged_matches_dense():
    """The paged verify forward (attend-before-write + one batched
    scatter) must produce the dense verify_step's logits for the same
    state."""
    from p2p_llm_chat_tpu.ops.paged_kv import (PageAllocator, PagedKVCache,
                                               set_row_table, write_prefill)
    rng = np.random.default_rng(3)
    B, P, S, PS = 2, 9, 4, 8
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (B, P)), jnp.int32)
    lens = jnp.full((B,), P, jnp.int32)

    dense = KVCache.create(CFG, B, 32, jnp.float32)
    logits, dense = jit_model(llama.prefill, CFG)(PARAMS, tokens, lens, dense)

    alloc = PageAllocator(16, PS)
    paged = PagedKVCache.create(CFG, B, 16, PS, max_pages_per_row=4,
                                dtype=jnp.float32)
    for b in range(B):
        pgs = alloc.alloc(alloc.pages_for(P + S + 1))
        padded = np.zeros((4,), np.int32)
        padded[: len(pgs)] = pgs
        paged = set_row_table(paged, b, jnp.asarray(padded))
    paged = write_prefill(paged, dense.k[:, :, :P],
                          dense.v[:, :, :P], jnp.arange(B), lens)

    stream = jnp.asarray(rng.integers(0, CFG.vocab_size, (B, S)), jnp.int32)
    ref, _ = jit_model(llama.verify_step, CFG)(PARAMS, stream, dense)
    got, _ = jit_model(llama.verify_step_paged, CFG, pages=2)(PARAMS, stream,
                                                              paged)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_spec_engine_near_budget_matches_plain_engine():
    """max_acc capping near the context budget: speculative output equals
    the plain engine's (identical truncation), and trusted slots never
    pass max_seq (OOB draft writes drop instead of clamping)."""
    prompt = "xyxy xyxy xyxy"
    opts = GenerateOptions(max_tokens=64)

    def run(spec_k):
        eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=32,
                        spec_k=spec_k)
        try:
            req = GenerateRequest(prompt=prompt, options=opts)
            return "".join(eng.generate_stream(req, RequestStats()))
        finally:
            eng.stop()

    assert run(spec_k=4) == run(spec_k=0)


def test_all_serving_features_compose():
    """int8 weights + paged KV + speculative decoding together, through
    the batching engine: greedy output must equal the solo oracle run on
    the SAME quantized weights (the full feature stack composes without
    interference)."""
    from p2p_llm_chat_tpu.models.quant import quantize_params

    qparams = quantize_params(PARAMS)

    eng = TPUEngine(qparams, CFG, TOK, num_slots=2, max_seq=128,
                    page_size=16, spec_k=4)
    try:
        prompt = "compose compose compose everything"
        req = GenerateRequest(prompt=prompt,
                              options=GenerateOptions(max_tokens=12))
        got = "".join(eng.generate_stream(req, RequestStats()))
        assert got == SOLO[128](qparams, prompt, 12)
    finally:
        eng.stop()


def _penalty_oracle(prompt: str, max_new: int, rp: float,
                    max_seq: int = 128) -> str:
    """Sequential greedy loop with the Ollama repeat penalty over the
    last-64-token window (prompt + generated), mirroring the engine."""
    rng = np.random.default_rng(0)

    def pick(last, seen):
        return sampling.sample_np(last, rng, temperature=0.0,
                                  recent=seen[-64:], repeat_penalty=rp)

    return SOLO[max_seq](PARAMS, prompt, max_new, pick)


@pytest.mark.parametrize("spec_k", [
    0, pytest.param(4, marks=pytest.mark.slow)])     # tier-1 budget
def test_repeat_penalty_greedy_matches_oracle(spec_k):
    """Engine greedy with repeat_penalty equals the sequential penalised
    oracle — with and without speculation (the per-position draft-prefix
    penalty window must reproduce sequential behavior exactly)."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128,
                    spec_k=spec_k)
    try:
        for prompt in ["repeat repeat repeat", "penalty test here"]:
            req = GenerateRequest(
                prompt=prompt,
                options=GenerateOptions(max_tokens=16, repeat_penalty=1.3))
            got = "".join(eng.generate_stream(req, RequestStats()))
            assert got == _penalty_oracle(prompt, 16, 1.3), (spec_k, prompt)
    finally:
        eng.stop()


def test_repeat_penalty_changes_output():
    """Sanity: the penalty actually alters a repetitive greedy stream."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128)
    try:
        def run(rp):
            req = GenerateRequest(
                prompt="aaaa aaaa aaaa",
                options=GenerateOptions(max_tokens=20, repeat_penalty=rp))
            return "".join(eng.generate_stream(req, RequestStats()))
        assert run(1.0) != run(2.0)
    finally:
        eng.stop()


@pytest.mark.parametrize("spec_k", [0, 4])
def test_repeat_penalty_across_full_window(spec_k):
    """Context crosses the 64-token penalty window mid-generation: the
    sliding eviction (drafts push the oldest window tokens out) must
    keep speculative greedy output bit-exact with the sequential
    oracle."""
    prompt = "the quick brown fox jumps over the lazy dog " * 3   # ~130 toks
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=256,
                    spec_k=spec_k)
    try:
        req = GenerateRequest(
            prompt=prompt,
            options=GenerateOptions(max_tokens=24, repeat_penalty=1.3))
        got = "".join(eng.generate_stream(req, RequestStats()))
        assert got == _penalty_oracle(prompt, 24, 1.3, max_seq=256), spec_k
    finally:
        eng.stop()


def test_quote_params_greedy_follows_printable_cycle():
    """models/synth.quote_params: greedy decode follows the printable
    successor cycles (the property that makes prompt-lookup drafts land
    and suggestion streams decode as text)."""
    from p2p_llm_chat_tpu.models.synth import quote_params, successor_map

    params = quote_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    succ = successor_map(CFG.vocab_size)
    ids = [1, ord("H"), ord("i")]          # BOS + printable prompt
    out = SOLO[64].tokens(params, ids, 24)
    assert len(out) == 24
    cur = ids[-1]
    for t in out:
        assert t == int(succ[cur]), (cur, t, int(succ[cur]))
        assert 32 <= t < 127          # printable: streams as UTF-8 text
        cur = t

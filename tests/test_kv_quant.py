"""int8 KV pool end-to-end (SERVE_KV_QUANT / BatchScheduler kv_quant).

The int8 pool trades <= s/2 elementwise KV rounding for half the
attention read traffic (ops/paged_kv.py). These tests pin (a) model-level
logit closeness of the quantized paged decode against the dense bf16
oracle, and (b) the full serving stack (admission, decode, spec, prefix,
release) running on a quantized pool without contract violations.
"""

import numpy as np

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.ops.paged_kv import PageAllocator, PagedKVCache
from p2p_llm_chat_tpu.ops import paged_kv
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)


def test_quantized_paged_decode_close_to_dense_oracle():
    """Prefill + a few decode steps through the int8 pool: logits stay
    close to the dense f32 path (rounding-level error only)."""
    B, S, mppr, ps = 2, 12, 3, 16
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (B, S)), jnp.int32)
    lens = jnp.full((B,), S, jnp.int32)

    dense = KVCache.create(CFG, B, mppr * ps, jnp.float32)
    ref_logits, dense = llama.prefill(PARAMS, CFG, tokens, lens, dense)

    pool = PagedKVCache.create(CFG, B, 2 * B * mppr + 1, ps,
                               max_pages_per_row=mppr, quantized=True)
    alloc = PageAllocator(2 * B * mppr + 1, ps)
    small = KVCache.create(CFG, B, S, jnp.float32)
    pre_logits, small = llama.prefill(PARAMS, CFG, tokens, lens, small)
    tables = jnp.asarray(
        np.array([alloc.alloc(mppr) for _ in range(B)], np.int32))
    pool = paged_kv.write_prefill_batch(pool, small.k, small.v,
                                        jnp.arange(B), lens, tables)
    np.testing.assert_allclose(np.asarray(pre_logits), np.asarray(ref_logits),
                               atol=1e-4, rtol=1e-4)

    nxt = jnp.argmax(ref_logits[:, -1:], -1).astype(jnp.int32)
    for _ in range(4):
        ref_l, dense = llama.decode_step(PARAMS, CFG, nxt, dense)
        got_l, pool = llama.decode_step_paged(PARAMS, CFG, nxt, pool,
                                              pages=mppr)
        ref_n, got_n = np.asarray(ref_l[:, 0]), np.asarray(got_l[:, 0])
        # Rounding-level drift only: logits track the oracle closely and
        # the greedy choice is preserved on this workload.
        assert np.max(np.abs(ref_n - got_n)) < 0.2, np.max(
            np.abs(ref_n - got_n))
        assert (ref_n.argmax(-1) == got_n.argmax(-1)).all()
        nxt = jnp.argmax(ref_l[:, 0:1, :], -1).astype(jnp.int32)


def test_full_stack_serves_on_quantized_pool():
    """Admission + decode + spec + prefix + release all compose on the
    int8 pool; pages return after drain."""
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=3, max_seq=128,
                    page_size=16, spec_k=2,
                    kv_quant=True)
    try:
        outs = []
        for i in range(4):
            req = GenerateRequest(
                prompt=f"hello quantized world {i}",
                options=GenerateOptions(max_tokens=12, seed=i))
            text = "".join(eng.generate_stream(req, RequestStats()))
            outs.append(text)
        assert all(isinstance(t, str) for t in outs)
        m = eng.scheduler.metrics_snapshot()
        assert m["serve_admitted_total"] >= 4
        # Row release runs on the scheduler thread after the stream ends.
        import time
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            m = eng.scheduler.metrics_snapshot()
            if m["serve_kv_free_pages"] == m["serve_kv_total_pages"]:
                break
            time.sleep(0.05)
        assert m["serve_kv_free_pages"] == m["serve_kv_total_pages"]
    finally:
        eng.stop()


def test_spec_composes_with_quantized_pool():
    """Speculation + int8 pool: in-flight positions are attended at full
    precision in both tick kinds (paged_attention_append /
    _verify_append), so greedy spec output matches the non-spec engine
    on the same quantized pool for this workload. (The match is
    rounding-exact, not guaranteed bit-exact at logit ties — positions
    j >= 1 see earlier drafts pre-quantization; deterministic here
    because the suite runs f32 on CPU with fixed weights.)"""
    def serve(spec_k):
        eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=128,
                        page_size=16, spec_k=spec_k,
                        kv_quant=True)
        try:
            req = GenerateRequest(
                prompt="repeat repeat repeat repeat repeat",
                options=GenerateOptions(max_tokens=16, temperature=0.0))
            return "".join(eng.generate_stream(req, RequestStats()))
        finally:
            eng.stop()

    assert serve(3) == serve(0)

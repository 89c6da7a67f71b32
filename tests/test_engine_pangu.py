"""tiny-pangu (latent attention, sandwich norms, one dense layer, then
routed layers holding 4 of 16 sigmoid-scored experts beside a shared
one) through the scheduler, end to end on the CPU, on the stack the
benchmark serves with: int8 weights, the paged int8 LATENT pool, the
prefix store, fused decode and a chunk ladder. A module of its own, so
that its programs are freed before the next module's
(tests/conftest.py)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import family_for, pangu
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache, write_prefill_batch
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

CFG = get_config("tiny-pangu")
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)


def run(engine, prompt, max_tokens=12, **opts):
    stats = RequestStats()
    req = GenerateRequest(prompt=prompt, options=GenerateOptions(
        max_tokens=max_tokens, **opts))
    text = "".join(engine.generate_stream(req, stats))
    return text, stats


@pytest.fixture(scope="module")
def qparams():
    """int8 weights under float32 activations: in bfloat16 this model's
    top logits tie at bf16's resolution (they are flat: every sublayer's
    output is normed), and the last bits, which move with the attention
    window's width, then pick the token."""
    return pangu.init_params_quantized(CFG, jax.random.PRNGKey(4),
                                       dtype=jnp.float32)


def oracle(qparams, prompt: str, max_new: int, kv_quant: bool = True) -> str:
    """A solo loop on the same tree: one-shot prefill, the latents
    spliced into a one-row paged pool, then plain decode steps."""
    stop_ids = set(CFG.eos_token_ids) | {TOK.eos_id}
    ids = TOK.encode(prompt, add_bos=True)
    n = len(ids)
    small = KVCache.create(CFG, 1, n, dtype=jnp.float32)
    logits, small = pangu.prefill(qparams, CFG, jnp.asarray([ids]),
                                  jnp.asarray([n]), small, last_only=True)
    pool = PagedKVCache.create(CFG, 1, 17, 16, max_pages_per_row=16,
                               dtype=jnp.float32, quantized=kv_quant)
    pool = write_prefill_batch(pool, small.k, small.v, jnp.asarray([0]),
                               jnp.asarray([n]),
                               1 + jnp.arange(16, dtype=jnp.int32)[None])
    last = np.asarray(logits[0, 0], np.float32)
    out = []
    for _ in range(max_new):
        t = int(last.argmax())
        if t in stop_ids:
            break
        out.append(t)
        lg, pool = pangu.decode_step_paged(qparams, CFG, jnp.asarray([[t]]),
                                           pool, pages=16)
        last = np.asarray(lg[0, 0], np.float32)
    return TOK.decode(out)


def test_family_and_cache_geometry():
    assert family_for(CFG) is pangu
    assert (CFG.cache_kv_heads, CFG.cache_k_dim, CFG.cache_v_dim) == (
        1, CFG.kv_lora_rank, 128)
    big = get_config("openpangu-ultra-moe-718b-l9e16")
    assert (big.cache_kv_heads, big.cache_k_dim, big.cache_v_dim) == (
        1, 512, 128)
    assert big.router_width == 256 and big.num_experts == 16
    pool = PagedKVCache.create(CFG, 2, 5, 16, quantized=True)
    assert pool.k.shape == (3, 5, 16, 1, 64)
    assert pool.v.shape == (3, 5, 16, 1, 128)
    assert pool.k_scale.shape == pool.v_scale.shape == (3, 5, 1, 128)


def test_pangu_admission_chunks_prefix_fused_decode_and_counters(qparams):
    """A lone request, a prompt longer than a chunk (first / mid / final
    chunk programs), a burst that shares the registered head (prefix
    admission) beside prompts that do not: greedy output equals the solo
    loop's, and the counters count what they say: every routed pair of
    the real prompt positions (top-k x routed layers a token), of which
    the pairs to held experts are ``serve_moe_assignments_total``, none
    dropped; decode row-steps and the cache rows they read."""
    head = "pangu shared head, "
    eng = TPUEngine(qparams, CFG, TOK, num_slots=8, max_seq=256,
                    page_size=16, kv_quant=True, prefix_cache=True,
                    prefix_texts=(head,), decode_fuse_max=4,
                    prefill_chunk=32)
    try:
        built = eng.scheduler.register_prefix(head)
        assert built == len(TOK.encode(head, add_bos=True)) - 1
        lone = "a request that arrives alone"
        long = head + "x" * 90          # suffix bucket 128: four chunks
        burst = [head + f"burst {i}" for i in range(6)] + [
            f"no head {i}" for i in range(2)]
        assert run(eng, lone, max_tokens=6)[0] == oracle(qparams, lone, 6)
        assert run(eng, long, max_tokens=6)[0] == oracle(qparams, long, 6)
        got, errs = {}, []

        def worker(p):
            try:
                got[p] = run(eng, p, max_tokens=9)[0]
            except Exception as e:   # noqa: BLE001
                errs.append((p, e))

        threads = [threading.Thread(target=worker, args=(p,))
                   for p in burst]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errs, errs
        assert got == {p: oracle(qparams, p, 9) for p in burst}
        m = eng.metrics_snapshot()
        assert m["serve_admitted_total"] == 10
        assert m["prefill_chunks_total"] >= 3
        assert m["serve_prefix_admits_total"] >= 7
        assert m["decode_fused_ticks_total"] > 0
        routed_layers = CFG.num_layers - CFG.first_k_dense
        per_token = CFG.num_experts_per_tok * routed_layers
        prefill_pairs = per_token * (m["serve_prefill_tokens_total"] + built)
        decode_pairs = per_token * m["serve_decode_row_steps_total"]
        assert m["serve_moe_routed_pairs_total"] == (prefill_pairs
                                                     + decode_pairs)
        assert 0 < m["serve_moe_local_pairs_total"] < (
            m["serve_moe_routed_pairs_total"])
        assert 0 < m["serve_moe_assignments_total"] <= (
            m["serve_moe_local_pairs_total"])
        assert m["serve_moe_dropped_total"] == 0
        assert m["serve_moe_decode_expert_slots_total"] > 0
        # Every decode row-step read at least its prompt's rows.
        assert m["serve_attn_context_tokens_total"] >= (
            8 * m["serve_decode_row_steps_total"])
    finally:
        eng.stop()


@pytest.mark.parametrize("kw,what", [
    (dict(spec_k=2), "speculative"),
])
def test_paths_that_assume_per_head_pages_refuse_by_name(qparams, kw, what):
    with pytest.raises(ValueError, match=f"tiny-pangu.*{what}"):
        TPUEngine(qparams, CFG, TOK, num_slots=2, max_seq=64, page_size=16,
                  **kw)

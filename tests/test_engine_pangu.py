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
import pytest

from p2p_llm_chat_tpu.models import family_for, pangu
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

from solo import Solo, generate as run

CFG = get_config("tiny-pangu")
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
# One-shot prefill, the latents spliced into a one-row int8 pool, plain
# decode steps (tests/solo.py).
SOLO = Solo(pangu, CFG, TOK, pool="int8", max_seq=256, last_only=True)


@pytest.fixture(scope="module")
def qparams():
    """int8 weights under float32 activations: in bfloat16 this model's
    top logits tie at bf16's resolution (they are flat: every sublayer's
    output is normed), and the last bits, which move with the attention
    window's width, then pick the token."""
    return pangu.init_params_quantized(CFG, jax.random.PRNGKey(4),
                                       dtype=jnp.float32)


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
        assert run(eng, lone, max_tokens=6)[0] == SOLO(qparams, lone, 6)
        assert run(eng, long, max_tokens=6)[0] == SOLO(qparams, long, 6)
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
        assert got == {p: SOLO(qparams, p, 9) for p in burst}
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

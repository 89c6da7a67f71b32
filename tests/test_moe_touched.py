"""Decode streams only the experts a live row reached.

Three layers hold that up and each is pinned here: the expert-stripe
kernels leave an empty expert's weights unread and write zeros for it
(``count``, ops/quant_mm.py); ``moe_mlp`` keeps parked rows out of the
buckets without moving a live row's result (``live``); the decode
programs hand their ``active`` mask down and count, over layers and
fused steps, the experts touched (``serve_moe_decode_experts_touched_total``
/ ``serve_moe_decode_expert_slots_total`` on /metrics).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import llama, mixtral
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.models.quant import (QTensor4, dequantize4, quantize,
                                           quantize4)
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache
from p2p_llm_chat_tpu.ops.quant_mm import (quant_matmul_experts_stacked,
                                           quant_matmul_experts_stacked4)

C, H, O, L = 8, 256, 256, 2
TOUCHED = {
    "none": lambda ne: [],
    "first": lambda ne: [0],
    "last": lambda ne: [ne - 1],
    "alternating": lambda ne: list(range(1, ne, 2)),
    "all": lambda ne: list(range(ne)),
}


@pytest.mark.parametrize("quant", ["int8", "int4"])
@pytest.mark.parametrize("NE", [8, 64])
@pytest.mark.parametrize("which", list(TOUCHED))
def test_kernel_skips_empty_experts_and_matches_the_einsum(which, NE, quant):
    """Buckets emptied for a set of experts, the count handed over: the
    touched experts agree with the dequantising einsum (and, bit for
    bit, with the kernel that is handed no count), the others are 0."""
    rng = np.random.default_rng(NE + len(which))
    held = np.zeros((NE,), np.int32)
    held[TOUCHED[which](NE)] = rng.integers(1, C + 1, len(TOUCHED[which](NE)))
    x = rng.standard_normal((NE, C, H)).astype(np.float32)
    x *= (np.arange(C)[None, :] < held[:, None])[:, :, None]
    x = jnp.asarray(x, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((L, NE, H, O)), jnp.float32)
    if quant == "int8":
        qt, kernel = quantize(w), quant_matmul_experts_stacked
        deq = qt.q[1].astype(jnp.float32) * qt.s[1]
    else:
        qt, kernel = quantize4(w, group=128), quant_matmul_experts_stacked4
        deq = dequantize4(QTensor4(q=qt.q[1], s=qt.s[1]), jnp.float32)
    got = np.asarray(kernel(x, qt.q, qt.s, 1, jnp.asarray(held),
                            interpret=True), np.float32)
    plain = np.asarray(kernel(x, qt.q, qt.s, 1, interpret=True), np.float32)
    ref = np.asarray(jnp.einsum("ech,ehf->ecf", x.astype(jnp.float32), deq))
    np.testing.assert_array_equal(got, plain)
    assert not got[held == 0].any()
    np.testing.assert_allclose(got, ref, atol=2e-2 * (np.abs(ref).max() or 1))


def test_kernel_takes_the_count_at_its_word():
    """An expert whose count is 0 is not computed, whatever its bucket
    holds: the contract is the caller's count, not a scan of the rows."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, C, H)), jnp.bfloat16)
    qt = quantize(jnp.asarray(rng.standard_normal((1, 4, H, O)), jnp.float32))
    got = np.asarray(quant_matmul_experts_stacked(
        x, qt.q, qt.s, 0, jnp.asarray([C, 0, C, 0]), interpret=True),
        np.float32)
    assert got[0].any() and got[2].any()
    assert not got[1].any() and not got[3].any()


@pytest.mark.parametrize("renormalize", [True, False])
def test_moe_mlp_live_mask_leaves_live_rows_bit_identical(renormalize):
    rng = np.random.default_rng(7)
    B, Hm, F, NE, k = 6, 32, 16, 8, 2
    x = jnp.asarray(rng.standard_normal((B, 1, Hm)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((Hm, NE)), jnp.float32)
    ws = [jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
          for s in ((NE, Hm, F), (NE, Hm, F), (NE, F, Hm))]
    live = np.array([True, False, True, False, False, True])
    every = np.asarray(mixtral.moe_mlp(x, router, *ws, k,
                                       renormalize=renormalize))
    some = np.asarray(mixtral.moe_mlp(x, router, *ws, k,
                                      renormalize=renormalize,
                                      live=jnp.asarray(live)))
    np.testing.assert_array_equal(some[live], every[live])
    assert not some[~live].any() and every[~live].any()
    # The count the kernels are handed: the live rows' selections only.
    _, _, count = mixtral._moe_mlp(x, router, *ws, k, None, None, None, None,
                                   renormalize, jnp.asarray(live))
    top = np.asarray(jax.lax.top_k(jax.nn.softmax(
        np.asarray(x)[:, 0] @ np.asarray(router), axis=-1), k)[1])
    np.testing.assert_array_equal(
        np.asarray(count), np.bincount(top[live].ravel(), minlength=NE))


def _tiny_moe(B=4):
    cfg = get_config("tiny-moe")
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = PagedKVCache.create(cfg, B, 16, 16, max_pages_per_row=2,
                                dtype=jnp.float32)
    table = 1 + np.arange(B * 2, dtype=np.int32).reshape(B, 2)
    cache = cache._replace(page_table=jnp.asarray(table))
    tokens = jnp.asarray(np.arange(3, 3 + B)[:, None], jnp.int32)
    return cfg, params, cache, tokens


def _unmasked_step(params, cfg, tokens, cache, mesh, rules, aux, *, active,
                   pages):
    """The paged step as it was before the mask went down: every slot
    row is routed."""
    return (*llama.decode_step_paged(params, cfg, tokens, cache, mesh, rules,
                                     active, pages=pages,
                                     mlp_fn=mixtral._mlp_fn(cfg, None)), aux)


def test_decode_step_paged_matches_the_unmasked_program_on_live_rows():
    cfg, params, cache, tokens = _tiny_moe()
    active = jnp.asarray([True, False, True, False])
    logits, new, touched = mixtral.decode_step_paged_touched(
        params, cfg, tokens, cache, active=active, pages=2)
    ref, ref_cache, _ = _unmasked_step(params, cfg, tokens, cache, None,
                                       llama.DEFAULT_RULES, (),
                                       active=active, pages=2)
    live = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(logits)[live],
                                  np.asarray(ref)[live])
    np.testing.assert_array_equal(np.asarray(new.lengths),
                                  np.asarray(ref_cache.lengths))
    touched = np.asarray(touched)
    assert touched[1] == cfg.num_layers * cfg.num_experts
    assert 2 * cfg.num_layers <= touched[0] <= min(
        touched[1], 2 * cfg.num_experts_per_tok * cfg.num_layers)
    # The plain form is the same step without the count, and no mask
    # means every row is live.
    np.testing.assert_array_equal(
        np.asarray(mixtral.decode_step_paged(params, cfg, tokens, cache,
                                             active=active, pages=2)[0]),
        np.asarray(logits))
    every = mixtral.decode_step_paged(params, cfg, tokens, cache, pages=2)[0]
    np.testing.assert_array_equal(
        np.asarray(every),
        np.asarray(_unmasked_step(params, cfg, tokens, cache, None,
                                  llama.DEFAULT_RULES, (), active=None,
                                  pages=2)[0]))


def test_decode_fused_with_a_row_parking_mid_scan_matches_unmasked():
    """K = 4, greedy; row 1 stops at the token it emits at the second
    step, so steps three and four run without it: tokens while emitted,
    lengths and the rows still live agree with the unmasked scan, and
    the count falls with the live rows."""
    cfg, params, cache, tokens = _tiny_moe()
    active = jnp.asarray([True, True, False, True])

    def sample_fn(logits, state, emit_pos, act):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), state

    def run(stop_ids, masked):
        kw = dict(num_steps=4, sample_fn=sample_fn, sample_state=(),
                  stop_ids=stop_ids, pages=2)
        if masked:
            return mixtral.decode_fused_touched(params, cfg, tokens, cache,
                                                active=active, **kw)
        return llama.decode_fused_aux(params, cfg, tokens, cache,
                                      _unmasked_step, (), None,
                                      llama.DEFAULT_RULES, active, **kw)

    free = run(np.zeros((0,), np.int32), True)
    stop = np.asarray([int(free[0][1, 1])], np.int32)
    assert not (np.asarray(free[0])[:, [0, 3]] == stop[0]).any()
    got, ref = run(stop, True), run(stop, False)
    emitted = np.asarray(got[1])
    np.testing.assert_array_equal(emitted, np.asarray(ref[1]))
    assert emitted[:, 1].tolist()[:2] == [True, True] and not emitted[2:, 1].any()
    np.testing.assert_array_equal(np.asarray(got[0])[emitted],
                                  np.asarray(ref[0])[emitted])
    np.testing.assert_array_equal(np.asarray(got[3].lengths),
                                  np.asarray(ref[3].lengths))
    np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(ref[4]))
    k, layers = cfg.num_experts_per_tok, cfg.num_layers
    touched, slots = np.asarray(got[-1])
    assert slots == 4 * layers * cfg.num_experts
    assert touched <= (2 * 3 + 2 * 2) * k * layers
    assert touched <= np.asarray(free[-1])[0]    # fewer live row-steps
    # The plain form returns the same without the count.
    plain = mixtral.decode_fused(params, cfg, tokens, cache, active=active,
                                 num_steps=4, sample_fn=sample_fn,
                                 sample_state=(), stop_ids=stop, pages=2)
    assert len(plain) == len(got) - 1
    np.testing.assert_array_equal(np.asarray(plain[0]), np.asarray(got[0]))


def test_dense_cache_step_counts_and_masks_too():
    cfg = get_config("tiny-moe")
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = KVCache.create(cfg, 3, 32, dtype=jnp.float32)
    tokens = jnp.asarray([[5], [6], [7]], jnp.int32)
    lone = jnp.asarray([False, True, False])
    logits, new, touched = mixtral.decode_step_touched(
        params, cfg, tokens, cache, active=lone, kv_window=16)
    every = mixtral.decode_step(params, cfg, tokens, cache, kv_window=16)[0]
    np.testing.assert_array_equal(np.asarray(logits)[1], np.asarray(every)[1])
    assert np.asarray(new.lengths).tolist() == [0, 1, 0]
    # One live row reaches exactly top-k experts a layer.
    assert np.asarray(touched).tolist() == [
        cfg.num_layers * cfg.num_experts_per_tok,
        cfg.num_layers * cfg.num_experts]


def test_counters_rise_by_layers_x_touched_and_are_absent_on_a_dense_model():
    """A lone request through the scheduler (paged int8 stack, fused
    decode): one live row reaches exactly top-k experts a layer-step, so
    the two counters are the dispatched row-steps and steps times the
    model's constants; warm-up's parked dispatches add nothing. A dense
    model exports neither."""
    from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                                GenerateRequest, RequestStats)
    from p2p_llm_chat_tpu.serve.engine import TPUEngine
    from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

    cfg = get_config("tiny-moe")
    tok = ByteTokenizer(vocab_size=cfg.vocab_size)
    params = mixtral.init_params_quantized(cfg, jax.random.PRNGKey(4))
    eng = TPUEngine(params, cfg, tok, num_slots=4, max_seq=128,
                    page_size=16, kv_quant=True,
                    decode_fuse_max=4)
    try:
        touched, slots = ("serve_moe_decode_experts_touched_total",
                          "serve_moe_decode_expert_slots_total")
        assert eng.metrics_snapshot()[touched] == 0
        req = GenerateRequest(prompt="a lone request",
                              options=GenerateOptions(max_tokens=11))
        stats = RequestStats()
        "".join(eng.generate_stream(req, stats))
        assert stats.completion_tokens == 11   # no stop token parked the row
        def settled():
            m = eng.metrics_snapshot()
            steps = (m["serve_decode_ticks_total"]
                     - m["decode_fused_ticks_total"]
                     + m["decode_fused_steps_total"])
            return m, steps

        # The last dispatch is read one loop iteration after the request's
        # last token went out (the pipeline is one tick deep).
        deadline = time.monotonic() + 30
        m, steps = settled()
        while (m[slots] != steps * cfg.num_layers * cfg.num_experts
               and time.monotonic() < deadline):
            time.sleep(0.05)
            m, steps = settled()
        assert m["decode_fused_ticks_total"] > 0 and steps >= 10
        assert m[slots] == steps * cfg.num_layers * cfg.num_experts
        assert m[touched] == (m["serve_decode_row_steps_total"]
                              * cfg.num_layers * cfg.num_experts_per_tok)
        assert 0 < m[touched] < m[slots]
    finally:
        eng.stop()
    dense = get_config("tiny")
    eng = TPUEngine(llama.init_params(dense, jax.random.PRNGKey(0)), dense,
                    ByteTokenizer(vocab_size=dense.vocab_size), num_slots=2,
                    max_seq=64)
    try:
        m = eng.metrics_snapshot()
        assert touched not in m and slots not in m
        assert "serve_moe_dropped_total" in m
    finally:
        eng.stop()

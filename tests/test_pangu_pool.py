"""The latent page pool under the entry points every pool has
(ops/paged_kv.py): one head, the normed latent in ``k`` and the rotated
key in ``v`` (two widths), int8 with a scale a token for each.
Round trips through ``write_prefill_chunk`` at unaligned offsets,
``write_decode_all_layers`` (whole pages with one slot replaced),
``gather_pages`` / ``scatter_pages``, and a session parked to the host
and woken through the scheduler."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import pangu
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache, gather_pages,
                                           scatter_pages, set_row_table,
                                           write_decode_all_layers,
                                           write_prefill_chunk)
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

CFG = get_config("tiny-pangu")
L, KD, VD = CFG.num_layers, CFG.cache_k_dim, CFG.cache_v_dim
PS = 8


def latents(seed, rows, n):
    k = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k[0], (L, rows, n, 1, KD), jnp.float32),
            jax.random.normal(k[1], (L, rows, n, 1, VD), jnp.float32))


def dequant(pool, table, n):
    """The first ``n`` tokens of a row, back in float."""
    pages = jnp.asarray(table[: -(-n // PS)])
    k = pool.k[:, pages].reshape(L, -1, 1, KD)[:, :n]
    v = pool.v[:, pages].reshape(L, -1, 1, VD)[:, :n]
    if pool.quantized:
        sk = pool.k_scale_view[:, pages].reshape(L, -1, 1)[:, :n]
        sv = pool.v_scale_view[:, pages].reshape(L, -1, 1)[:, :n]
        k = k.astype(jnp.float32) * sk[..., None]
        v = v.astype(jnp.float32) * sv[..., None]
    return k, v


@pytest.mark.parametrize("quantized", [False, True])
def test_chunks_at_unaligned_offsets_then_decode_writes(quantized):
    pool = PagedKVCache.create(CFG, 2, 9, PS, max_pages_per_row=4,
                               dtype=jnp.float32, quantized=quantized)
    table = [5, 2, 7, 3]
    tables = jnp.asarray([table, [0] * 4], jnp.int32)
    k, v = latents(0, 2, 21)
    # 0..5, 5..16, 16..21: page boundaries fall inside every chunk.
    for lo, hi in ((0, 5), (5, 16), (16, 21)):
        pool = write_prefill_chunk(pool, k[:, :, lo:hi], v[:, :, lo:hi],
                                   tables, lo)
    pool = set_row_table(pool, 0, jnp.asarray(table, jnp.int32))
    pool = pool._replace(lengths=jnp.asarray([21, 0], jnp.int32))
    k1, v1 = latents(1, 2, 1)
    pool = write_decode_all_layers(pool, k1[:, :, 0], v1[:, :, 0])
    got_k, got_v = dequant(pool, table, 22)
    want_k = jnp.concatenate([k[:, 0], k1[:, 0]], 1)
    want_v = jnp.concatenate([v[:, 0], v1[:, 0]], 1)
    tol = 0.03 if quantized else 0
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want_k),
                               atol=tol)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               atol=tol)
    # The parked row wrote to the garbage page alone.
    assert not np.asarray(pool.k[:, [1, 4, 6, 8]]).any()


@pytest.mark.parametrize("quantized", [False, True])
def test_park_and_wake_move_the_pools_words(quantized):
    pool = PagedKVCache.create(CFG, 1, 9, PS, max_pages_per_row=4,
                               dtype=jnp.float32, quantized=quantized)
    k, v = latents(2, 1, 20)
    pool = write_prefill_chunk(pool, k, v, jnp.asarray([[4, 1, 6, 0]]), 0)
    parked = jax.jit(gather_pages)(pool, jnp.asarray([4, 1, 6, 0]))
    assert parked[0].shape == (L, 4, PS, 1, KD)
    assert parked[1].shape == (L, 4, PS, 1, VD)
    host = tuple(None if a is None else np.asarray(a) for a in parked)
    fresh = PagedKVCache.create(CFG, 1, 9, PS, max_pages_per_row=4,
                                dtype=jnp.float32, quantized=quantized)
    woken = jax.jit(scatter_pages, donate_argnums=(0,))(
        fresh, jnp.asarray([2, 8, 3, 0]),
        *(None if a is None else jnp.asarray(a) for a in host))
    a = dequant(pool, [4, 1, 6], 20)
    b = dequant(woken, [2, 8, 3], 20)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_a_session_parked_to_the_host_wakes_with_the_same_answer():
    """Two turns of one session through the scheduler, resident and
    parked between the turns: the second turn's greedy output is the
    same (the wake runs ``pangu.verify_step_paged`` over the pages
    ``scatter_pages`` brought back)."""
    tok = ByteTokenizer(vocab_size=CFG.vocab_size)
    params = pangu.init_params_quantized(CFG, jax.random.PRNGKey(4),
                                         dtype=jnp.float32)

    def run(eng, prompt, ctx=()):
        stats = RequestStats()
        req = GenerateRequest(
            prompt=prompt, session="s", context=tuple(ctx),
            options=GenerateOptions(max_tokens=8, temperature=0.0, seed=1))
        return "".join(eng.generate_stream(req, stats)), stats

    def turns(park):
        eng = TPUEngine(params, CFG, tok, num_slots=2, max_seq=256,
                        page_size=16, kv_quant=True, kv_host_gb=1.0,
                        kv_idle_s=1e9)
        try:
            _, s1 = run(eng, "hello there, how are you doing my friend?")
            tier = eng.scheduler._tier
            if park:
                tier.idle_s = 0.0
                deadline = time.monotonic() + 20
                while tier.counts()[1] < 1:
                    assert time.monotonic() < deadline, tier.counts()
                    time.sleep(0.02)
                tier.idle_s = 1e9
            t2, _ = run(eng, " tell me one more thing?", ctx=s1.context)
            snap = eng.scheduler.metrics_snapshot()
            assert snap["kv_waked_total"] == 1
            assert snap["kv_parked_total"] == (1 if park else 0)
            return t2
        finally:
            eng.stop()

    assert turns(park=True) == turns(park=False)

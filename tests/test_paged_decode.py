"""Paged decode path vs the dense oracle (CPU, kernel in interpret mode).

The serving contract: decode through the paged pool (llama/mixtral
``decode_step_paged`` + Pallas kernel + page-table writes) must produce
exactly the logits of the dense KV-cache path for the same context,
including parked rows and page-boundary crossings.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama, mixtral
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.ops.paged_kv import (PageAllocator, PagedKVCache,
                                           write_prefill_row)

pytestmark = pytest.mark.model

PS = 8


def setup_caches(model, cfg, params, prompts_lens, max_seq=64, num_pages=32):
    """Prefill both a dense cache and a paged pool with the same random
    prompts; return (dense_cache, paged_cache, last_logits)."""
    B = len(prompts_lens)
    S = int(max(prompts_lens))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(B, S)).astype(np.int32)
    lens = jnp.asarray(prompts_lens, jnp.int32)

    dense = KVCache.create(cfg, B, max_seq, jnp.float32)
    logits, dense = model.prefill(params, cfg, jnp.asarray(tokens), lens,
                                  dense)

    alloc = PageAllocator(num_pages, PS)
    paged = PagedKVCache.create(cfg, B, num_pages, PS,
                                max_pages_per_row=max_seq // PS,
                                dtype=jnp.float32)
    for b in range(B):
        # Budget: prompt + decode room (mirrors scheduler admission).
        pages = alloc.alloc(alloc.pages_for(int(prompts_lens[b]) + 16))
        table = np.zeros((paged.max_pages_per_row,), np.int32)
        table[: len(pages)] = pages
        paged = write_prefill_row(
            paged, dense.k[:, b, :S], dense.v[:, b, :S],
            jnp.asarray(b), jnp.asarray(prompts_lens[b]),
            jnp.asarray(table))
    return dense, paged, logits


@pytest.mark.parametrize("model,cfg_name", [
    (llama, "tiny"), (mixtral, "tiny-moe"),
    (mixtral, "tiny-olmoe")])       # rep 1: 4 query heads on 4 kv heads
def test_paged_decode_matches_dense(model, cfg_name):
    cfg = get_config(cfg_name)
    params = model.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompts_lens = [5, 8, 13]          # row 1 starts exactly at a page boundary
    dense, paged, logits = setup_caches(model, cfg, params, prompts_lens)
    B = len(prompts_lens)

    last = jnp.stack([logits[b, n - 1] for b, n in enumerate(prompts_lens)])
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]

    # 6 steps crosses a page boundary for every row.
    for step in range(6):
        pages = int(np.ceil((max(prompts_lens) + step + 1) / PS))
        d_logits, dense = model.decode_step(params, cfg, tok, dense)
        p_logits, paged = model.decode_step_paged(params, cfg, tok, paged,
                                                  pages=pages)
        np.testing.assert_allclose(np.asarray(p_logits),
                                   np.asarray(d_logits),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"step {step}")
        tok = jnp.argmax(d_logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
    assert list(np.asarray(paged.lengths)) == [n + 6 for n in prompts_lens]


def test_paged_decode_parked_rows_do_not_advance_or_corrupt():
    cfg = get_config("tiny")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompts_lens = [6, 9]
    dense, paged, logits = setup_caches(llama, cfg, params, prompts_lens)

    last = jnp.stack([logits[b, n - 1] for b, n in enumerate(prompts_lens)])
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
    active = jnp.asarray([True, False])

    for step in range(3):
        pages = int(np.ceil((max(prompts_lens) + step + 1) / PS))
        d_logits, dense = llama.decode_step(params, cfg, tok, dense,
                                            active=active)
        p_logits, paged = llama.decode_step_paged(params, cfg, tok, paged,
                                                  pages=pages, active=active)
        # Active row parity; parked row's logits are garbage by contract.
        np.testing.assert_allclose(np.asarray(p_logits[:1]),
                                   np.asarray(d_logits[:1]),
                                   atol=1e-4, rtol=1e-4)
        tok = jnp.argmax(d_logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
    assert list(np.asarray(paged.lengths)) == [9, 9]

"""The flash-append kernel at the windows it serves since PR 56.

Until PR 56 a decode step under 1,024 tokens of window (512 at OLMoE's
width) took the gather path, so the kernel
(ops/paged_attention._paged_attention_flash_append) never met what a
short window brings: ONE chunk a row, of fewer pages than the chunk
budget allows (the REAL budget here, nothing shrunk), a row that holds
nothing or one position beside live ones, and rows that end on a page's
edge. Here in interpret mode at W 128 and 256 of 64-token pages, for
GQA, 16 MHA heads and the paired pool (a head of 64, two to a 128-lane
row, the queries zero-extended), int8 and bf16 pools, against
:func:`paged_attention_reference` over the pool as float32 with the
current token written in (the kernel attends it at full precision).
"""

import dataclasses
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops import paged_attention_reference, paged_kv
from test_flash_append_geometry import _filled_cache
from test_flash_append_tiles import _dequantised

pa = importlib.import_module("p2p_llm_chat_tpu.ops.paged_attention")

pytestmark = pytest.mark.model

PS = 64

reference = jax.jit(paged_attention_reference, static_argnames="pages")

# name -> (query heads, KV heads, head) as the model has them, and
# whether the pool keeps its KV heads in pairs.
_GEOMETRY = {
    "gqa": (8, 2, 32, False),
    "mha16": (16, 16, 16, False),
    "paired": (8, 4, 64, True),
}


def _lengths(W: int) -> list:
    """Free rows (0) between live ones, one position, a page's edge
    (PS, W / 2, W - PS), one past an edge, and the window's last slot."""
    return [0, 1, PS, W - 1, 0, W // 2, PS + 1, W - PS]


@pytest.mark.parametrize("W", [128, 256])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(_GEOMETRY))
def test_one_short_chunk_a_row_against_the_reference(geometry, quantized, W):
    Hq, Hkv, D, paired = _GEOMETRY[geometry]
    rep = Hq // Hkv
    head = dataclasses.replace(get_config("tiny"), num_layers=2,
                               num_heads=Hq, num_kv_heads=Hkv, head_dim=D)
    # The pool's own geometry: a pair of heads is one row of 2 D lanes.
    pool_cfg = (dataclasses.replace(head, num_heads=Hq // 2,
                                    num_kv_heads=Hkv // 2, head_dim=2 * D)
                if paired else head)
    pages, lengths = W // PS, _lengths(W)
    rng = np.random.default_rng(W + Hq)
    cache = _filled_cache(pool_cfg, pages, PS, lengths, quantized, rng,
                          dtype=jnp.bfloat16)
    rows, lanes = pool_cfg.num_kv_heads, pool_cfg.head_dim
    # One chunk a row, cut by the window and not by the budget.
    budget = pa.flash_append_chunk_pages(rows * lanes,
                                         cache.k.dtype.itemsize, PS, 1 << 20)
    assert pages < budget
    assert pa.flash_append_chunk_pages(
        rows * lanes, cache.k.dtype.itemsize, PS, pages) == pages
    B = len(lengths)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=kc.shape), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    # What the reference attends: the pool's values as float32, head by
    # head (a pair's row is its two heads side by side).
    wide = _dequantised(cache)
    per_head = wide.k.shape[:3] + (Hkv, D)
    wide = wide._replace(k=wide.k.astype(jnp.float32).reshape(per_head),
                         v=wide.v.astype(jnp.float32).reshape(per_head))
    for layer in range(head.num_layers):
        if paired:
            got = pa.unpair_outputs(pa._paged_attention_flash_append(
                pa.pair_queries(q, rep), kc.reshape(B, rows, lanes),
                vc.reshape(B, rows, lanes), cache.k, cache.v, cache.k_scale,
                cache.v_scale, cache.page_table, lens, jnp.asarray(layer),
                pages=pages, quantized=quantized, interpret=True,
                scale=D ** -0.5), rep)
        else:
            got = pa._paged_attention_flash_append(
                q, kc, vc, cache.k, cache.v, cache.k_scale, cache.v_scale,
                cache.page_table, lens, jnp.asarray(layer), pages=pages,
                quantized=quantized, interpret=True)
        got = np.asarray(got)
        assert np.isfinite(got).all()
        written = paged_kv.write_decode(wide, jnp.asarray(layer), kc, vc)
        want = reference(q, written.k, written.v, written.page_table,
                         lens + 1, layer, pages=pages)
        np.testing.assert_allclose(got, np.asarray(want), atol=3e-5,
                                   rtol=3e-5, err_msg=f"layer {layer}")
        # A free row's answer is its own token's value, exactly.
        for b in (b for b, n in enumerate(lengths) if n == 0):
            np.testing.assert_allclose(
                got[b], np.repeat(np.asarray(vc[b]), rep, axis=0), atol=1e-6)

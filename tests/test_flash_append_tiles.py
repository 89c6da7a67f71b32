"""The flash-append kernel's fold, a tile at a time (PR 54).

The kernel (ops/paged_attention._paged_attention_flash_append) folds a
fetched chunk in tiles and stops a row at its last live tile: neither
the fetch nor the fold touches a tile that starts at or past the row's
length. Here in interpret mode, at 64-token pages with the budgets
shrunk to 128-token tiles, two tiles a chunk and four chunks a row, so
that rows of 0, 1, 127, 128, 129, 450, 512 and 513 tokens end before a
tile, on one, behind one, inside a chunk and either side of a chunk's
end — for 1 and 4 query heads a kv head, float and int8 pools, and with
an indexed layer's selection (``masked``), against two oracles that
index the pool a token at a time: a dense softmax written out here, and
:func:`paged_attention_reference` over the pool with the current token
written in (an int8 pool dequantised first: the kernel attends the
current token at full precision).
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops import paged_attention_reference, paged_kv
from test_flash_append_geometry import _filled_cache

pa = importlib.import_module("p2p_llm_chat_tpu.ops.paged_attention")

pytestmark = pytest.mark.model

PS, PAGES = 64, 16                      # a window of 1,024 tokens
TILE, CHUNK = 128, 256                  # tokens
LENGTHS = [0, 1, 127, 128, 129, 450, 512, 513]

reference = jax.jit(paged_attention_reference, static_argnames="pages")


def _budgets(monkeypatch, cfg, itemsize):
    """Chunks of 256 tokens in tiles of 128 at the test's width."""
    hd = cfg.num_kv_heads * cfg.head_dim
    monkeypatch.setattr(pa, "_FLASH_HD_REF", hd)
    monkeypatch.setattr(pa, "_FLASH_CHUNK_TOK_BYTES", CHUNK * itemsize)
    monkeypatch.setattr(pa, "_FLASH_TILE_TOK_BYTES", TILE * itemsize)
    chunk_pages = pa.flash_append_chunk_pages(hd, itemsize, PS, PAGES)
    tile_pages = pa.flash_append_tile_pages(hd, itemsize, PS, chunk_pages)
    assert (chunk_pages * PS, tile_pages * PS) == (CHUNK, TILE)


def _dequantised(cache):
    """The int8 pool as the float pool the kernel's arithmetic sees."""
    if cache.k_scale is None:
        return cache

    def wide(pages, scales):            # [L,N,ps,H,D] x [L,N,H,ps_pad]
        s = jnp.swapaxes(scales[..., :PS], -1, -2)[..., None]
        return pages.astype(jnp.float32) * s

    return cache._replace(k=wide(cache.k, cache.k_scale),
                          v=wide(cache.v, cache.v_scale),
                          k_scale=None, v_scale=None)


def _dense_oracle(q, kc, vc, pool, layer, keep, keep_cur):
    """Softmax over each row's kept positions and its current token,
    a token at a time through the page table."""
    q, kc, vc = (np.asarray(a, np.float64) for a in (q, kc, vc))
    k_pages = np.asarray(pool.k[layer], np.float64)
    v_pages = np.asarray(pool.v[layer], np.float64)
    table = np.asarray(pool.page_table)
    B, Hq, D = q.shape
    rep = Hq // kc.shape[1]
    out = np.zeros((B, Hq, D))
    for b, n in enumerate(LENGTHS):
        pos = [t for t in range(n) if keep[b, t]]
        k = np.stack([k_pages[table[b, t // PS], t % PS] for t in pos]
                     + ([kc[b]] if keep_cur[b] else []))    # [T, Hkv, D]
        v = np.stack([v_pages[table[b, t // PS], t % PS] for t in pos]
                     + ([vc[b]] if keep_cur[b] else []))
        for h in range(Hq):
            s = k[:, h // rep] @ q[b, h] / np.sqrt(D)
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v[:, h // rep]
    return out


def _poison_dead_tiles(cache):
    """NaN (int8: +-127 under NaN scales) in every page of every tile
    that starts at or past its row's length, inside live chunks too."""
    dead = jnp.asarray(
        [1 + b * PAGES + p for b, n in enumerate(LENGTHS)
         for p in range(-(-n // TILE) * TILE // PS, PAGES)], jnp.int32)
    if cache.k_scale is not None:
        return cache._replace(
            k=cache.k.at[:, dead].set(127), v=cache.v.at[:, dead].set(-127),
            k_scale=cache.k_scale.at[:, dead].set(jnp.nan),
            v_scale=cache.v_scale.at[:, dead].set(jnp.nan))
    return cache._replace(k=cache.k.at[:, dead].set(jnp.nan),
                          v=cache.v.at[:, dead].set(jnp.nan))


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("quantized", [False, True],
                         ids=["fppool", "int8pool"])
@pytest.mark.parametrize("rep", [1, 4])
def test_tiled_fold_at_ragged_lengths(rep, quantized, masked, monkeypatch):
    cfg = get_config("tiny").with_(num_heads=4, num_kv_heads=4 // rep)
    _budgets(monkeypatch, cfg, 1 if quantized else 4)
    rng = np.random.default_rng(54)
    cache = _filled_cache(cfg, PAGES, PS, LENGTHS, quantized, rng)
    B, W = len(LENGTHS), PAGES * PS
    q = jnp.asarray(rng.normal(size=(B, cfg.num_heads, cfg.head_dim)),
                    jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, cfg.num_kv_heads, cfg.head_dim)),
                     jnp.float32)
    vc = jnp.asarray(rng.normal(size=kc.shape), jnp.float32)
    lens = jnp.asarray(LENGTHS, jnp.int32)
    keep = np.ones((B, W), bool)
    keep_cur = np.ones((B,), bool)
    kw = {}
    if masked:
        keep = rng.random((B, W)) < 0.4
        keep[5, 128:384] = False        # a tile and a half with nothing
        keep[7, :512] = False           # two whole chunks with nothing
        keep_cur = np.asarray([True, False, True, True, False, True, False,
                               False])
        keep[1, 0] = keep[7, 512] = True    # a row keeps a position
        kw = dict(keep=jnp.asarray(keep), keep_cur=jnp.asarray(keep_cur))
    poisoned = _poison_dead_tiles(cache)
    wide = _dequantised(cache)
    for layer in range(cfg.num_layers):
        got = np.asarray(pa._paged_attention_flash_append(
            q, kc, vc, poisoned.k, poisoned.v, poisoned.k_scale,
            poisoned.v_scale, poisoned.page_table, lens, jnp.asarray(layer),
            pages=PAGES, quantized=quantized, interpret=True, **kw))
        assert np.isfinite(got).all(), f"a dead tile was read: layer {layer}"
        want = _dense_oracle(q, kc, vc, wide, layer, keep, keep_cur)
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5,
                                   err_msg=f"dense oracle, layer {layer}")
        if not masked:
            c2 = paged_kv.write_decode(wide, jnp.asarray(layer), kc, vc)
            ref = reference(q, c2.k, c2.v, c2.page_table, lens + 1, layer,
                            pages=PAGES)
            np.testing.assert_allclose(got, np.asarray(ref), atol=3e-5,
                                       rtol=3e-5,
                                       err_msg=f"reference, layer {layer}")
        # A row of length 0 returns its current token's value, exactly.
        if not masked:
            np.testing.assert_array_equal(
                got[0], np.repeat(np.asarray(vc[0]), rep, axis=0))


# hd, itemsize, pages a row -> (chunk pages, tile pages) at 64-token
# pages: half a chunk wherever a chunk is more than a page.
@pytest.mark.parametrize("hd, itemsize, pages, want", [
    (2048, 1, 8, (8, 4)),       # OLMoE, Ouro at W 512: 256-token tiles
    (2048, 1, 16, (8, 4)),
    (1024, 1, 16, (16, 8)),     # llama's GQA: 512-token tiles
    (1024, 1, 8, (8, 8)),       # a window under the chunk: one tile
    (512, 1, 256, (32, 16)),    # LFM2's pairs, Keye, Mellum: 1,024
    (1024, 2, 32, (8, 4)),      # a bf16 pool: half the tokens
    (2048, 2, 32, (4, 2)),
    (1024, 1, 3, (3, 3)),       # a tile divides its chunk
    (1024, 1, 6, (6, 6)),
    (8192, 1, 32, (2, 1)),
    (16384, 1, 32, (1, 1)),
])
def test_tile_size_rule(hd, itemsize, pages, want):
    chunk = pa.flash_append_chunk_pages(hd, itemsize, 64, pages)
    tile = pa.flash_append_tile_pages(hd, itemsize, 64, chunk)
    assert (chunk, tile) == want
    assert chunk % tile == 0

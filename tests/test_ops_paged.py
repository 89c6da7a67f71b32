"""Paged KV cache + paged decode attention tests (CPU).

The decode-attention entry points (the gather append, the flash-append
kernel in ``interpret=True`` mode, the block verify at one position) run
against two oracles (SURVEY.md §4 "TPU without a TPU"): the jnp reference
over gathered-dense pages, and models/layers.attend_gqa over an
equivalent dense cache. Write ops are checked for slot/page math,
garbage-page routing, and allocator hygiene.
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops import (PageAllocator, PagedKVCache,
                                  paged_attention_reference)
from p2p_llm_chat_tpu.ops import paged_kv

pa = importlib.import_module("p2p_llm_chat_tpu.ops.paged_attention")
# The XLA entry points and the oracle, each lowered whole (the kernel's
# entry point is jitted where it is defined).
append_gather = jax.jit(pa._append_gather, static_argnames="pages")
verify_append = jax.jit(pa.paged_attention_verify_append,
                        static_argnames="pages")
paged_attention_reference = jax.jit(paged_attention_reference,
                                    static_argnames="pages")

pytestmark = pytest.mark.model

CFG = get_config("tiny")          # Hkv=2, Hq=4, D=32, L=2
PS = 8                            # page size (slots)


# Rep 1 (OLMoE's MHA): 4 query heads on 4 kv heads, one-row slices of
# the kernels' scratch.
MHA = get_config("tiny-olmoe")


def make_cache(batch=3, num_pages=16, max_rows_pages=4, CFG=CFG):
    return PagedKVCache.create(CFG, batch, num_pages, PS,
                               max_pages_per_row=max_rows_pages,
                               dtype=jnp.float32)


def random_filled_cache(rng, lengths, num_pages=16, CFG=CFG):
    """Cache where each row's first ``lengths[b]`` slots hold random kv,
    installed through the real write ops (prefill splice)."""
    B = len(lengths)
    alloc = PageAllocator(num_pages, PS)
    cache = make_cache(batch=B, num_pages=num_pages, CFG=CFG)
    S = int(max(lengths))
    L = CFG.num_layers
    dense_k = rng.normal(size=(L, B, S, CFG.num_kv_heads,
                               CFG.head_dim)).astype(np.float32)
    dense_v = rng.normal(size=(L, B, S, CFG.num_kv_heads,
                               CFG.head_dim)).astype(np.float32)
    rows_pages = []
    for b in range(B):
        pages = alloc.alloc(alloc.pages_for(int(lengths[b]) + 1))
        assert pages is not None
        rows_pages.append(pages)
        padded = np.zeros((cache.max_pages_per_row,), np.int32)
        padded[: len(pages)] = pages
        cache = paged_kv.set_row_table(cache, b, jnp.asarray(padded))
    cache = paged_kv.write_prefill(
        cache, jnp.asarray(dense_k), jnp.asarray(dense_v),
        jnp.arange(B), jnp.asarray(lengths, jnp.int32))
    return cache, dense_k, dense_v, alloc, rows_pages


def test_allocator_basics():
    a = PageAllocator(8, PS)
    assert a.free_pages == 7                  # page 0 reserved
    got = a.alloc(3)
    assert got is not None and len(got) == 3 and 0 not in got
    assert a.alloc(5) is None                 # only 4 left — all-or-nothing
    assert a.free_pages == 4
    a.free(got)
    assert a.free_pages == 7
    with pytest.raises(ValueError):
        a.free([0])
    assert a.pages_for(1) == 1
    assert a.pages_for(PS) == 1
    assert a.pages_for(PS + 1) == 2


def test_write_prefill_then_gather_roundtrip():
    rng = np.random.default_rng(0)
    lengths = [5, 13, 1]
    cache, dense_k, dense_v, _, _ = random_filled_cache(rng, lengths)
    for layer in range(CFG.num_layers):
        k, v = paged_kv.gather_dense(cache, layer, max_seq=16)
        for b, n in enumerate(lengths):
            np.testing.assert_array_equal(np.asarray(k[b, :n]),
                                          dense_k[layer, b, :n])
            np.testing.assert_array_equal(np.asarray(v[b, :n]),
                                          dense_v[layer, b, :n])
    assert list(np.asarray(cache.lengths)) == lengths


def test_write_prefill_pads_go_to_garbage_page():
    rng = np.random.default_rng(1)
    cache, dense_k, _, _, rows_pages = random_filled_cache(rng, [3, 9])
    # Row 0's only real page holds its 3 slots; slots 3.. of that page are
    # untouched (zero), not clobbered by row padding.
    p0 = rows_pages[0][0]
    page = np.asarray(cache.k[0, p0])                 # [PS, Hkv, D]
    np.testing.assert_array_equal(page[3:], np.zeros_like(page[3:]))


@pytest.mark.parametrize("S", [4, 8, 16, 12])   # <page, =page, multi, ragged
def test_write_prefill_batch_matches_row_path(S):
    """The one-scatter admission splice (the production path in
    serve/scheduler.py) must agree with write_prefill_row for every S
    shape class, drop sentinel-row installs, and route past-allocation
    pages to garbage page 0."""
    rng = np.random.default_rng(9)
    B, R, L = 3, 4, CFG.num_layers
    lens = [max(1, S - 2), S, 1]                 # 3 real rows + 1 pad entry
    alloc = PageAllocator(32, PS)
    tables = np.zeros((R, 4), np.int32)
    for i, n in enumerate(lens):
        pages = alloc.alloc(alloc.pages_for(n + 1))
        tables[i, : len(pages)] = pages
    chunk_k = rng.normal(size=(L, R, S, CFG.num_kv_heads,
                               CFG.head_dim)).astype(np.float32)
    chunk_v = rng.normal(size=(L, R, S, CFG.num_kv_heads,
                               CFG.head_dim)).astype(np.float32)
    rows = jnp.asarray([0, 1, 2, B], jnp.int32)  # last entry: pad sentinel
    lens_j = jnp.asarray(lens + [1], jnp.int32)

    base = PagedKVCache.create(CFG, B, 32, PS, max_pages_per_row=4,
                               dtype=jnp.float32)
    got = paged_kv.write_prefill_batch(base, jnp.asarray(chunk_k),
                                       jnp.asarray(chunk_v), rows, lens_j,
                                       jnp.asarray(tables))
    ref = base
    for i in range(B):                            # oracle: per-row splice
        ref = paged_kv.write_prefill_row(ref, jnp.asarray(chunk_k[:, i]),
                                         jnp.asarray(chunk_v[:, i]),
                                         jnp.asarray(i),
                                         jnp.asarray(lens[i]),
                                         jnp.asarray(tables[i]))
    np.testing.assert_array_equal(np.asarray(got.page_table),
                                  np.asarray(ref.page_table))
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  np.asarray(ref.lengths))
    for layer in range(L):
        gk, gv = paged_kv.gather_dense(got, layer, max_seq=2 * S)
        rk, rv = paged_kv.gather_dense(ref, layer, max_seq=2 * S)
        for b, n in enumerate(lens[:B]):          # compare live slots only
            np.testing.assert_array_equal(np.asarray(gk[b, :n]),
                                          np.asarray(rk[b, :n]))
            np.testing.assert_array_equal(np.asarray(gv[b, :n]),
                                          np.asarray(rv[b, :n]))


def test_write_decode_appends_at_length():
    rng = np.random.default_rng(2)
    lengths = [5, 8]                                   # row1 exactly at a page boundary
    cache, dense_k, dense_v, alloc, rows_pages = random_filled_cache(rng, lengths)
    L = CFG.num_layers
    k_new = rng.normal(size=(L, 2, CFG.num_kv_heads,
                             CFG.head_dim)).astype(np.float32)
    v_new = rng.normal(size=(L, 2, CFG.num_kv_heads,
                             CFG.head_dim)).astype(np.float32)
    for layer in range(L):
        cache = paged_kv.write_decode(cache, jnp.asarray(layer),
                                      jnp.asarray(k_new[layer]),
                                      jnp.asarray(v_new[layer]))
    cache = cache._replace(lengths=cache.lengths + 1)
    for layer in range(L):
        k, v = paged_kv.gather_dense(cache, layer, max_seq=16)
        for b, n in enumerate(lengths):
            np.testing.assert_array_equal(np.asarray(k[b, n]), k_new[layer, b])
            np.testing.assert_array_equal(np.asarray(v[b, n]), v_new[layer, b])
            np.testing.assert_array_equal(np.asarray(k[b, :n]),
                                          dense_k[layer, b, :n])


def test_parked_row_with_zero_table_writes_garbage_only():
    """A released row (table zeroed) keeps scattering its per-step kv —
    it must land in garbage page 0 and corrupt nothing."""
    rng = np.random.default_rng(3)
    cache, dense_k, _, _, _ = random_filled_cache(rng, [5, 7])
    zeros = jnp.zeros((cache.max_pages_per_row,), jnp.int32)
    cache = paged_kv.set_row_table(cache, 0, zeros)    # release row 0
    junk = jnp.full((CFG.num_kv_heads, CFG.head_dim), 99.0, jnp.float32)
    snap_k = np.asarray(cache.k[0, 1:])                # all real pages, layer 0
    cache2 = paged_kv.write_decode(
        cache, jnp.asarray(0),
        jnp.stack([junk, jnp.zeros_like(junk)]),
        jnp.stack([junk, jnp.zeros_like(junk)]))
    # Row 1's write went to its own slot; row 0's junk went to page 0.
    np.testing.assert_array_equal(np.asarray(cache2.k[0, 1:])
                                  [np.asarray(cache.page_table[1, :1])[0] - 1],
                                  snap_k[np.asarray(cache.page_table[1, :1])[0] - 1])
    assert np.any(np.asarray(cache2.k[0, 0]) == 99.0)


def _attend_last(entry, q, cache, k_cur, v_cur, pool_lens, layer, pages):
    """One decode-attention entry point by name: the pool's first
    ``pool_lens`` positions plus the current token's k/v."""
    pool = (cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.page_table, pool_lens, jnp.asarray(layer))
    if entry == "gather":
        return append_gather(q, k_cur, v_cur, *pool, pages=pages)
    if entry == "flash":
        return pa._paged_attention_flash_append(
            q, k_cur, v_cur, *pool, pages=pages,
            quantized=cache.k_scale is not None, interpret=True)
    assert entry == "verify"
    return verify_append(
        q[:, None], k_cur[:, None], v_cur[:, None], cache, pool_lens,
        jnp.asarray(layer), pages=pages)[:, 0]


def _last_token(dense, layer, lengths):
    """[B, Hkv, D]: each row's token at ``lengths - 1``."""
    return jnp.asarray(np.stack(
        [dense[layer, b, n - 1] for b, n in enumerate(lengths)]))


ENTRIES = ["gather", "flash", "verify"]


@pytest.mark.parametrize("CFG", [CFG, MHA], ids=["gqa", "mha"])
@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("lengths", [[1, 9, 16], [8, 8, 8], [3, 27, 1]])
def test_kernel_matches_reference_and_dense(lengths, entry, CFG):
    """Every decode-attention entry point (the gather append, the
    flash-append kernel in interpret mode, the block verify at one
    position), attending a pool of ``lengths - 1`` plus the last token
    as the current one, against the index-naive reference over
    ``lengths`` AND an independent dense oracle, at rep 2 and at rep 1."""
    rng = np.random.default_rng(7)
    cache, dense_k, dense_v, _, _ = random_filled_cache(
        rng, lengths, num_pages=32, CFG=CFG)
    B = len(lengths)
    q = jnp.asarray(rng.normal(size=(B, CFG.num_heads, CFG.head_dim)),
                    jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    pages = -(-max(lengths) // PS)

    for layer in range(CFG.num_layers):
        got = _attend_last(entry, q, cache,
                           _last_token(dense_k, layer, lengths),
                           _last_token(dense_v, layer, lengths),
                           lens - 1, layer, pages)
        ref = paged_attention_reference(q, cache.k, cache.v,
                                        cache.page_table, lens, layer,
                                        pages=pages)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        # Independent dense oracle straight from the original kv.
        from p2p_llm_chat_tpu.models.layers import attend_gqa
        S = int(max(lengths))
        mask = (np.arange(S)[None, :] < np.asarray(lengths)[:, None]
                )[:, None, None, :]
        dense = attend_gqa(q[:, None], jnp.asarray(dense_k[layer]),
                           jnp.asarray(dense_v[layer]),
                           jnp.asarray(mask))[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("entry", ENTRIES)
def test_kernel_ignores_garbage_table_entries_past_length(entry):
    """Dead page-table entries (0) beyond a row's live pages must not
    affect the result even when the page walk covers them."""
    rng = np.random.default_rng(8)
    lengths = [3, 20]
    cache, dense_k, dense_v, _, _ = random_filled_cache(rng, lengths,
                                                        num_pages=32)
    # Poison the garbage page with huge values.
    cache = cache._replace(k=cache.k.at[:, 0].set(1e4),
                           v=cache.v.at[:, 0].set(1e4))
    B = 2
    q = jnp.asarray(rng.normal(size=(B, CFG.num_heads, CFG.head_dim)),
                    jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    got = _attend_last(entry, q, cache, _last_token(dense_k, 0, lengths),
                       _last_token(dense_v, 0, lengths), lens - 1, 0, 3)
    ref = paged_attention_reference(q, cache.k, cache.v, cache.page_table,
                                    lens, 0, pages=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.abs(np.asarray(got)) < 1e3)


def test_write_decode_multi_out_of_table_goes_to_garbage():
    """Speculative positions past a fully-allocated row's table must land
    in garbage page 0 — clamping onto the last real page would wrap the
    slot index into TRUSTED kv (regression: confirmed corruption at
    lengths near budget with S >= 2)."""
    B, mppr = 1, 2
    cache = PagedKVCache.create(CFG, B, 8, PS, max_pages_per_row=mppr,
                                dtype=jnp.float32)
    table = np.zeros((mppr,), np.int32)
    table[:] = [3, 5]                           # fully allocated row
    cache = paged_kv.set_row_table(cache, 0, jnp.asarray(table))
    cache = cache._replace(lengths=jnp.asarray([2 * PS - 2], jnp.int32))
    snap_k = np.asarray(cache.k[0, 5])          # last real page, layer 0

    S = 4                                       # 2 in-range + 2 past-table
    k = jnp.full((B, S, CFG.num_kv_heads, CFG.head_dim), 7.0, jnp.float32)
    k_all = jnp.broadcast_to(k, (CFG.num_layers,) + k.shape)
    out = paged_kv.write_decode_multi_all_layers(cache, k_all, k_all)
    got = np.asarray(out.k[0, 5])               # [PS, Hkv, D]
    # Slots 0..PS-3 of the last real page are untouched; only the two
    # in-range positions (slots PS-2, PS-1) changed.
    np.testing.assert_array_equal(got[: PS - 2], snap_k[: PS - 2])
    assert np.all(got[PS - 2:] == 7.0)
    # The overflow went to the garbage page.
    assert np.any(np.asarray(out.k[0, 0]) == 7.0)


# -- int8 KV pool (quantized=True) --------------------------------------------

def test_quant_kv_roundtrip_bound():
    """Per-(slot, head) symmetric int8: |dequant - x| <= s/2 elementwise
    (the same bound models/quant.py pins for weights)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, PS, CFG.num_kv_heads,
                                     CFG.head_dim)) * 3, jnp.float32)
    q, s = paged_kv.quant_kv(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    err = np.abs(np.asarray(q, np.float32) * np.asarray(s)[..., None]
                 - np.asarray(x))
    assert np.all(err <= np.asarray(s)[..., None] / 2 + 1e-7)


def test_quantized_pool_write_paths_and_attention():
    """All write paths quantize transparently; gather_dense dequantizes;
    int8 decode attention (every entry point) matches the reference run
    on the dequantized pool exactly (scale folding is algebra, not
    approximation), the current token at full precision."""
    rng = np.random.default_rng(1)
    B, mppr = 3, 4
    cache = PagedKVCache.create(CFG, B, 16, PS, max_pages_per_row=mppr,
                                quantized=True)
    assert cache.quantized and cache.k.dtype == jnp.int8
    lengths = [5, PS + 3, 2 * PS]
    # prefill splice per row (write_prefill_row path)
    for b, n in enumerate(lengths):
        pages = paged_kv.PageAllocator(16, PS).alloc(mppr)
        table = jnp.asarray(np.array([3 + b * 4, 4 + b * 4, 0, 0],
                                     np.int32))
        rk = jnp.asarray(rng.normal(size=(CFG.num_layers, 2 * PS,
                                          CFG.num_kv_heads, CFG.head_dim)),
                         jnp.float32)
        cache = paged_kv.write_prefill_row(cache, rk, rk * 0.5,
                                           jnp.asarray(b),
                                           jnp.asarray(n), table)
    # decode append (write_decode path)
    k1 = jnp.asarray(rng.normal(size=(B, CFG.num_kv_heads, CFG.head_dim)),
                     jnp.float32)
    cache2 = paged_kv.write_decode(cache, jnp.asarray(0), k1, k1 * 2)
    lens = jnp.asarray(lengths, jnp.int32)

    # int8 attention == reference over the dequantized pool (exact): the
    # pool before the append plus k1 as the current token, against the
    # dequantized pool with k1 written in at full precision.
    q = jnp.asarray(rng.normal(size=(B, CFG.num_heads, CFG.head_dim)),
                    jnp.float32)

    def dequantized(c):
        return c._replace(
            k=c.k.astype(jnp.float32) * c.k_scale_view[..., None],
            v=c.v.astype(jnp.float32) * c.v_scale_view[..., None],
            k_scale=None, v_scale=None)

    full = paged_kv.write_decode(dequantized(cache), jnp.asarray(0), k1,
                                 k1 * 2)
    ref = paged_attention_reference(q, full.k, full.v, full.page_table,
                                    lens + 1, 0, pages=mppr)
    for entry in ENTRIES:
        got = _attend_last(entry, q, cache, k1, k1 * 2, lens, 0, mppr)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4, err_msg=entry)
    deq_k = dequantized(cache2).k

    # gather_dense dequantizes to the same values the attend saw
    kd, vd = paged_kv.gather_dense(cache2, 0, mppr * PS)
    np.testing.assert_allclose(
        np.asarray(kd[0, :5]),
        np.asarray(deq_k[0][cache2.page_table[0, 0], :5]), rtol=1e-6)


def test_flash_append_kernel_interpret_matches_gather(monkeypatch):
    """The long-window flash-append kernel (multi-chunk
    ``(B, chunks)`` grid: manual page + scale DMAs, online softmax
    carried in VMEM scratch across the chunk axis, seeded with the
    current token) agrees with the gather append path in interpret
    mode — bf16 and int8 pools, ragged lengths. The chunk byte budget
    is shrunk so pages=3 runs as a THREE-chunk grid: the cross-chunk
    online-softmax rescale, DMA slot parity, and partial-final-chunk
    clamping (the riskiest logic) all execute hardware-free. The
    deeper edge-geometry matrix lives in
    tests/test_flash_append_geometry.py."""
    monkeypatch.setattr(pa, "_FLASH_CHUNK_TOK_BYTES", 64)  # 16 f32 tokens
    cfg = get_config("tiny-tp")     # 4 kv heads, head_dim 32
    # Identity hd scaling at the test geometry (see
    # test_flash_append_geometry._check_case).
    monkeypatch.setattr(pa, "_FLASH_HD_REF",
                        cfg.num_kv_heads * cfg.head_dim)
    rng = np.random.default_rng(7)
    B, pages, ps = 4, 3, 16
    mppr = pages
    for quantized in (False, True):
        cache = paged_kv.PagedKVCache.create(
            cfg, B, B * mppr + 1, ps, max_pages_per_row=mppr,
            dtype=jnp.float32, quantized=quantized)
        lens = []
        for b in range(B):
            n = int(rng.integers(1, pages * ps - 1))
            lens.append(n)
            table = jnp.asarray(1 + b * mppr + np.arange(mppr), jnp.int32)
            rk = jnp.asarray(rng.normal(size=(cfg.num_layers, pages * ps,
                                              cfg.num_kv_heads,
                                              cfg.head_dim)), jnp.float32)
            rv = jnp.asarray(rng.normal(size=rk.shape), jnp.float32)
            cache = paged_kv.write_prefill_row(cache, rk, rv,
                                               jnp.asarray(b),
                                               jnp.asarray(n), table)
        lens = jnp.asarray(lens, jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, cfg.num_heads, cfg.head_dim)),
                        jnp.float32)
        kc = jnp.asarray(rng.normal(size=(B, cfg.num_kv_heads,
                                          cfg.head_dim)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=kc.shape), jnp.float32)
        kern = pa._paged_attention_flash_append(
            q, kc, vc, cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.page_table, lens, jnp.asarray(0), pages=pages,
            quantized=quantized, interpret=True)
        ref = append_gather(
            q, kc, vc, cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.page_table, lens, jnp.asarray(0), pages=pages)
        # Tight: interpret mode computes in f32 (the dispatch swaps the
        # bf16 MXU dtype out).
        np.testing.assert_allclose(np.asarray(kern), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=str(quantized))


# -- PR 29: the int8 pool's scales land a page tile at a time ---------------
#
# write_decode_all_layers and write_prefill_chunk's unaligned path store
# their scales as whole [Hkv, ps_pad] page tiles indexed on the page
# dimension (paged_kv._scatter_scale_tiles). The index expressions they
# replaced stay here as the oracle (over _scatter_kv, which quantises as
# before): the pool must hold the same bits.

def _oracle_decode_burst(cache, k_all, v_all, inc):
    ps = cache.page_size
    logical = cache.lengths // ps
    phys = jnp.take_along_axis(cache.page_table, logical[:, None],
                               axis=1)[:, 0]
    slot = cache.lengths % ps
    cache = paged_kv._scatter_kv(
        cache, k_all, v_all,
        lambda arr, upd: arr.at[:, phys, slot].set(upd, mode="drop"),
        lambda arr, upd: arr.at[:, phys, :, slot].set(
            upd.transpose(1, 0, 2), mode="drop"))
    return cache._replace(lengths=cache.lengths + inc)


def _oracle_chunk_unaligned(cache, chunk_k, chunk_v, tables, start):
    R, C = chunk_k.shape[1:3]
    ps = cache.page_size
    pos = start + jnp.arange(C)
    logical = pos // ps
    safe = jnp.minimum(logical, tables.shape[1] - 1)
    phys = jnp.take_along_axis(tables.astype(jnp.int32),
                               jnp.broadcast_to(safe[None, :], (R, C)),
                               axis=1)
    phys = jnp.where((logical < tables.shape[1])[None, :], phys, 0)
    slot = jnp.broadcast_to((pos % ps)[None, :], (R, C))
    return paged_kv._scatter_kv(
        cache, chunk_k, chunk_v,
        lambda arr, upd: arr.at[:, phys, slot].set(upd, mode="drop"),
        lambda arr, upd: arr.at[:, phys, :, slot].set(
            upd.transpose(1, 2, 0, 3), mode="drop"))


def _noisy_pool(rng, quantized, batch, num_pages, ps, width):
    """A pool whose every word is random, the scales' lane padding
    included: a write that touches a lane it should have kept shows."""
    cache = PagedKVCache.create(CFG, batch, num_pages, ps,
                                max_pages_per_row=width,
                                dtype=jnp.float32, quantized=quantized)

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, size=a.shape), a.dtype)
        return jnp.asarray(rng.uniform(0.01, 1.0, size=a.shape), a.dtype)

    if quantized:
        cache = cache._replace(k_scale=fill(cache.k_scale),
                               v_scale=fill(cache.v_scale))
    return cache._replace(k=fill(cache.k), v=fill(cache.v))


def _assert_same_pool(got, want, garbage_tiles):
    """Every pool array bit for bit. Where several writes of one call
    land in garbage page 0 its scale tile holds one writer's (the
    lane-indexed scatter kept every writer's lane): garbage by contract,
    so that one tile is left out; every other page must match."""
    names = ["k", "v", "lengths", "page_table"]
    if want.quantized:
        names += ["k_scale", "v_scale"]
    for name in names:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        if name.endswith("_scale") and garbage_tiles > 1:
            a, b = a[:, 1:], b[:, 1:]
        np.testing.assert_array_equal(a, b, err_msg=name)


# (lengths, table rows, active) for a batch of 4 at page size 8, width 3.
_DECODE_CASES = {
    # slot 0 of a fresh page, mid-page, slot ps - 1, slot ps - 1 of the
    # row's last page
    "live-slots": ([PS, 3, PS - 1, 3 * PS - 1],
                   [[1, 2, 0], [3, 0, 0], [4, 0, 0], [5, 6, 7]],
                   [1, 1, 1, 1]),
    # one parked row with a zeroed table: its write is page 0's only one
    "one-parked": ([PS + 2, 5, 0, 2 * PS],
                   [[1, 2, 0], [0, 0, 0], [4, 0, 0], [5, 6, 7]],
                   [1, 0, 1, 1]),
    # parked rows share garbage page 0, at different slots and at one
    "parked-rows": ([4, 5, 5, 2 * PS + 1],
                    [[0, 0, 0], [0, 0, 0], [0, 0, 0], [5, 6, 7]],
                    [0, 0, 0, 1]),
    # a finished row kept resident writes its own page; an out-of-range
    # physical page (pool of 16) is dropped
    "resident-and-dropped": ([PS - 1, 2, PS + 4, 6],
                             [[9, 0, 0], [21, 0, 0], [10, 11, 0], [12, 0, 0]],
                             [0, 1, 1, 1]),
}


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("case", sorted(_DECODE_CASES))
def test_decode_write_is_bit_identical_to_lane_scatter(case, quantized):
    lengths, table, active = _DECODE_CASES[case]
    rng = np.random.default_rng(29)
    B, width = len(lengths), len(table[0])
    cache = _noisy_pool(rng, quantized, B, 16, PS, width)._replace(
        page_table=jnp.asarray(table, jnp.int32),
        lengths=jnp.asarray(lengths, jnp.int32))
    shape = (CFG.num_layers, B, CFG.num_kv_heads, CFG.head_dim)
    k_all = jnp.asarray(rng.normal(size=shape) * 3, jnp.float32)
    v_all = jnp.asarray(rng.normal(size=shape), jnp.float32)
    inc = jnp.asarray(active, jnp.int32)
    got = jax.jit(paged_kv.write_decode_burst)(cache, k_all, v_all, inc)
    want = jax.jit(_oracle_decode_burst)(cache, k_all, v_all, inc)
    page0 = sum(1 for b in range(B) if table[b][lengths[b] // PS] == 0)
    _assert_same_pool(got, want, page0)
    if quantized:       # the oracle moved something: the test can fail
        assert not np.array_equal(np.asarray(want.k_scale),
                                  np.asarray(cache.k_scale))


# Page size 64 (the servers'), so the registered template head's 88
# tokens end mid-page. Two rows: row 0 holds pages for 5 logical pages
# (the table's width), row 1's table ends after two.
_CHUNK_PS = 64
_CHUNK_TABLES = [[1, 2, 3, 4, 5], [6, 7, 0, 0, 0]]


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("start", [88, _CHUNK_PS - 1], ids=["s88", "s63"])
@pytest.mark.parametrize("C", [16, 64, 256], ids=["sub", "page", "pages"])
def test_unaligned_chunk_splice_is_bit_identical_to_lane_scatter(
        C, start, quantized):
    """C below, at and several times a page from a mid-page start: row 1's
    table ends inside the longer chunks (garbage page 0), and from 88 the
    256-token chunk also runs past the table's width."""
    rng = np.random.default_rng(88)
    ps, R = _CHUNK_PS, len(_CHUNK_TABLES)
    tables = jnp.asarray(_CHUNK_TABLES, jnp.int32)
    cache = _noisy_pool(rng, quantized, 3, 9, ps, tables.shape[1])
    shape = (CFG.num_layers, R, C, CFG.num_kv_heads, CFG.head_dim)
    k = jnp.asarray(rng.normal(size=shape) * 2, jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    got = jax.jit(paged_kv.write_prefill_chunk, static_argnums=(4,))(
        cache, k, v, tables, start)
    want = jax.jit(_oracle_chunk_unaligned, static_argnums=(4,))(
        cache, k, v, tables, start)
    span = range(start // ps, (start + C - 1) // ps + 1)
    page0 = sum(1 for row in _CHUNK_TABLES for p in span
                if p >= len(row) or row[p] == 0)
    _assert_same_pool(got, want, page0)
    if quantized:
        assert not np.array_equal(np.asarray(want.v_scale),
                                  np.asarray(cache.v_scale))

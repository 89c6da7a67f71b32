"""Edge-geometry parity for the multi-chunk flash-append kernel.

The long-window kernel (ops/paged_attention.
_paged_attention_flash_append: grid ``(B, chunks)``, cross-chunk
online-softmax merge in VMEM scratch, clamped partial-chunk DMAs) runs
here in ``interpret=True`` mode — SURVEY.md §4 "TPU without a TPU" —
against two oracles:

- the gather append path (``_append_gather``, called by name), which
  shares the kernel's exact append semantics (current token attended at
  full precision, pool writes batched after the scan);
- for bf16/f32 pools, the index-naive :func:`paged_attention_reference`
  over a pool with the current token written in (``write_decode`` +
  ``lengths + 1``) — the independent oracle the acceptance criteria
  name. (int8 pools pin against the gather path only: the reference
  ordering quantizes the current token before attending, the documented
  sub-quantization-noise divergence.)

In interpret mode the kernel computes in f32 (the dispatch swaps the
bf16 MXU operand dtype for f32 — same dataflow), so parity is tight,
not bf16-loose. ``_FLASH_CHUNK_TOK_BYTES`` is shrunk to 64 bytes (16
f32 tokens = 2 pages at ps=8) for the geometry cases so every
multi-chunk code path — cross-chunk rescale, DMA slot parity through
row boundaries, the clamped partial last chunk — executes hardware-free
with small arrays; the slow matrix at the bottom runs the REAL chunk
budget at serving windows (W ∈ {2048, 4096} × int8/bf16 × both page
sizes — ci.sh full mode).
"""

import importlib
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops import paged_attention_reference, paged_kv

pa = importlib.import_module("p2p_llm_chat_tpu.ops.paged_attention")

pytestmark = pytest.mark.model

# The two oracles, each lowered whole: called eagerly they are a program
# an operation, for every shape of the matrix below.
append_gather = jax.jit(pa._append_gather, static_argnames="pages")
reference = jax.jit(paged_attention_reference, static_argnames="pages")

# 64 bytes / f32 = 16 tokens = 2 pages at PS=8: pages=5 walks as 3
# chunks (2 + 2 + 1-clamped) — the geometry the fast cases pin.
PS = 8
CHUNK_BYTES = 64


def _filled_cache(cfg, pages, ps, lengths, quantized, rng,
                  dtype=jnp.float32):
    """Pool with each row's first ``lengths[b]`` slots holding random kv
    through the real splice op; rows own disjoint page ranges."""
    B = len(lengths)
    cache = paged_kv.PagedKVCache.create(
        cfg, B, B * pages + 1, ps, max_pages_per_row=pages,
        dtype=dtype, quantized=quantized)
    for b, n in enumerate(lengths):
        table = jnp.asarray(1 + b * pages + np.arange(pages), jnp.int32)
        rk = jnp.asarray(rng.normal(size=(cfg.num_layers, pages * ps,
                                          cfg.num_kv_heads, cfg.head_dim)),
                         dtype)
        rv = jnp.asarray(rng.normal(size=rk.shape), dtype)
        cache = paged_kv.write_prefill_row(cache, rk, rv, jnp.asarray(b),
                                           jnp.asarray(n), table)
    return cache


def _check_case(cfg_name, pages, ps, lengths, quantized, monkeypatch,
                chunk_bytes=CHUNK_BYTES, seed=0):
    """Run the kernel across every layer against both oracles."""
    cfg = get_config(cfg_name)
    rng = np.random.default_rng(seed)
    if chunk_bytes is not None:
        monkeypatch.setattr(pa, "_FLASH_CHUNK_TOK_BYTES", chunk_bytes)
    # Pin the calibration geometry AT the test config's hd so the
    # chunk budget's hd scaling is identity here and the chunk layouts
    # documented per case (pages/chunk, boundary positions) hold
    # exactly; the scaling itself is pinned by
    # test_chunk_size_function_is_the_kernels.
    monkeypatch.setattr(pa, "_FLASH_HD_REF",
                        cfg.num_kv_heads * cfg.head_dim)
    cache = _filled_cache(cfg, pages, ps, lengths, quantized, rng)
    B = len(lengths)
    q = jnp.asarray(rng.normal(size=(B, cfg.num_heads, cfg.head_dim)),
                    jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, cfg.num_kv_heads, cfg.head_dim)),
                     jnp.float32)
    vc = jnp.asarray(rng.normal(size=kc.shape), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    for layer in range(cfg.num_layers):
        kern = pa._paged_attention_flash_append(
            q, kc, vc, cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.page_table, lens, jnp.asarray(layer), pages=pages,
            quantized=quantized, interpret=True)
        ref = append_gather(
            q, kc, vc, cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.page_table, lens, jnp.asarray(layer), pages=pages)
        np.testing.assert_allclose(
            np.asarray(kern), np.asarray(ref), atol=2e-5, rtol=2e-5,
            err_msg=f"vs gather append: layer {layer} q={quantized}")
        if not quantized:
            # Independent oracle: the index-naive reference over the
            # pool WITH the current token written (write-then-attend
            # ordering — identical on full-precision pools).
            c2 = paged_kv.write_decode(cache, jnp.asarray(layer), kc, vc)
            ref2 = reference(
                q, c2.k, c2.v, c2.page_table, lens + 1, layer, pages=pages)
            np.testing.assert_allclose(
                np.asarray(kern), np.asarray(ref2), atol=2e-5, rtol=2e-5,
                err_msg=f"vs reference: layer {layer}")


@pytest.mark.parametrize("quantized", [False, True])
def test_non_chunk_multiple_window(quantized, monkeypatch):
    """pages=5 at 2 pages/chunk: 3 chunks, the last one PARTIAL — its
    second DMA clamps to the last real page and masks out. Lengths span
    every chunk, including the partial one's real half."""
    _check_case("tiny", 5, PS, [1, 7, 16, 33, 39], quantized, monkeypatch)


@pytest.mark.parametrize("quantized", [False, True])
def test_single_page_row(quantized, monkeypatch):
    """pages=1: the degenerate single-chunk grid (seed, one merge,
    finalise in the same program)."""
    _check_case("tiny", 1, PS, [1, PS - 1, 3], quantized, monkeypatch)


@pytest.mark.parametrize("quantized", [False, True])
def test_rows_shorter_than_one_chunk(quantized, monkeypatch):
    """Rows whose whole context fits inside chunk 0 (even inside ONE
    page) while the grid still walks 2 chunks: later chunks must be
    fully masked no-ops for them (their table entries past the live
    pages are the garbage page)."""
    _check_case("tiny", 4, PS, [3, 5, 1], quantized, monkeypatch)


def test_int8_scale_folding_at_chunk_boundaries(monkeypatch):
    """int8 pools: per-(slot, head) scale folding where lengths sit
    exactly ON a chunk boundary (16 = 2 pages/chunk at ps=8), one off
    either side, on a page boundary inside a chunk (8, 24), and at the
    full window — the geometry where a boundary off-by-one in the
    scale concat or position mask shows. rep=1 config (tiny-tp): a
    scale row is a query head's own, the other boundary worth
    covering (every other case runs rep=2)."""
    _check_case("tiny-tp", 4, PS, [16, 17, 15, 8, 24, 32], True,
                monkeypatch)


@pytest.mark.parametrize("quantized", [False, True])
def test_mixed_length_batch_rows_finish_in_different_chunks(
        quantized, monkeypatch):
    """Every row retires its page walk in a different chunk (lengths
    2..39 over a 3-chunk walk): the cross-chunk scratch state must
    re-seed per row and never leak a neighbour's merge (slot parity
    runs THROUGH row boundaries — num_chunks=3 is odd on purpose)."""
    _check_case("tiny", 5, PS, [2, 9, 17, 25, 31, 39], quantized,
                monkeypatch, seed=1)


def _poison_dead_chunks(cache, lengths, pages, chunk_pages, quantized):
    """Overwrite every page of every chunk that starts at or past its
    row's length: NaN in a float pool; in an int8 pool +-127 with NaN
    scale rows. A kernel that fetched and folded such a chunk after all
    would carry NaN through ``0 * NaN`` in the p.v dot or the v-scale
    fold, instead of masking it out."""
    ps = cache.page_size
    dead = [1 + b * pages + p
            for b, n in enumerate(lengths)
            for p in range(-(-n // (chunk_pages * ps)) * chunk_pages, pages)]
    if not dead:
        return cache
    dead = jnp.asarray(dead, jnp.int32)
    if quantized:
        k = cache.k.at[:, dead].set(127)
        v = cache.v.at[:, dead].set(-127)
        return cache._replace(
            k=k, v=v, k_scale=cache.k_scale.at[:, dead].set(jnp.nan),
            v_scale=cache.v_scale.at[:, dead].set(jnp.nan))
    return cache._replace(k=cache.k.at[:, dead].set(jnp.nan),
                          v=cache.v.at[:, dead].set(jnp.nan))


# Ct = 16 tokens = 2 pages at PS = 8 in every case below (64 bytes of
# f32, 16 of int8). Lengths are positions already in the pool.
CT = 16
_RAGGED = {
    # 4 chunks a row (even): 0, 1, Ct - 1, Ct, Ct + 1 and the whole
    # window in one batch. Behind the row boundary 0 -> 1 the next
    # row's chunk 0 is live, behind 5 -> 6 it is dead (a free row after
    # a full one), behind 6 -> 7 live again, after two dead rows.
    "even-4-chunks": (8, [0, 1, CT - 1, CT, CT + 1, 64, 0, 0, 33]),
    # 3 chunks a row (odd, the last one partial and clamped): slot
    # parity runs through rows that skip everything; the batch opens on
    # a full row (the warm-up fetch) and closes on a free one.
    "odd-3-chunks": (5, [40, 0, 0, CT + 1, 1, 39, CT, 0]),
    # The batch opens on a free row: no warm-up fetch at step 0, and
    # the first live chunk is fetched by an empty program.
    "free-row-first": (4, [0, 0, 2 * CT, 5, 0, 32, 31]),
}


@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("quantized", [False, True], ids=["fppool", "int8pool"])
@pytest.mark.parametrize("case", sorted(_RAGGED))
def test_chunks_past_a_rows_length_are_not_read(case, quantized, rep,
                                                monkeypatch):
    """The kernel's work follows the rows' lengths: against the gather
    path (and, on a float pool, the index-naive reference) on the pool
    as filled, while the kernel reads a copy whose dead chunks are
    poisoned. Every row, lengths 0 and the whole window included,
    agrees; nothing is NaN."""
    pages, lengths = _RAGGED[case]
    cfg = get_config("tiny").with_(num_heads=4, num_kv_heads=4 // rep)
    monkeypatch.setattr(pa, "_FLASH_CHUNK_TOK_BYTES", 16 if quantized else 64)
    monkeypatch.setattr(pa, "_FLASH_HD_REF",
                        cfg.num_kv_heads * cfg.head_dim)
    rng = np.random.default_rng(7)
    cache = _filled_cache(cfg, pages, PS, lengths, quantized, rng)
    chunk_pages = pa.flash_append_chunk_pages(
        cfg.num_kv_heads * cfg.head_dim, cache.k.dtype.itemsize, PS, pages)
    assert chunk_pages * PS == CT
    poisoned = _poison_dead_chunks(cache, lengths, pages, chunk_pages,
                                   quantized)
    B = len(lengths)
    q = jnp.asarray(rng.normal(size=(B, cfg.num_heads, cfg.head_dim)),
                    jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, cfg.num_kv_heads, cfg.head_dim)),
                     jnp.float32)
    vc = jnp.asarray(rng.normal(size=kc.shape), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    for layer in range(cfg.num_layers):
        kern = np.asarray(pa._paged_attention_flash_append(
            q, kc, vc, poisoned.k, poisoned.v, poisoned.k_scale,
            poisoned.v_scale, poisoned.page_table, lens, jnp.asarray(layer),
            pages=pages, quantized=quantized, interpret=True))
        assert np.isfinite(kern).all(), f"a dead chunk was read: layer {layer}"
        ref = append_gather(
            q, kc, vc, cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.page_table, lens, jnp.asarray(layer), pages=pages)
        np.testing.assert_allclose(kern, np.asarray(ref), atol=2e-5,
                                   rtol=2e-5, err_msg=f"layer {layer}")
        # A row of length 0 returns its current token's value, exactly.
        for b in (b for b, n in enumerate(lengths) if n == 0):
            np.testing.assert_allclose(
                kern[b], np.repeat(np.asarray(vc[b]), rep, axis=0),
                atol=1e-6)
        if not quantized:
            # The independent oracle writes the current token first, so
            # it has no slot for a row that already fills the window.
            fits = np.asarray(lengths) < pages * PS
            c2 = paged_kv.write_decode(cache, jnp.asarray(layer), kc, vc)
            ref2 = reference(
                q, c2.k, c2.v, c2.page_table, lens + 1, layer, pages=pages)
            np.testing.assert_allclose(kern[fits], np.asarray(ref2)[fits],
                                       atol=2e-5, rtol=2e-5,
                                       err_msg=f"vs reference: layer {layer}")


def _chunking_used(monkeypatch, hd, dtype, ps, pages):
    """(chunk_pages, tile_pages, num_chunks) that
    _paged_attention_flash_append hands its kernel body for a pool of
    this geometry, read while it traces."""
    seen = []
    real = pa._flash_append_kernel_body

    def spy(quantized, page_size, n_pages, chunk_pages, tile_pages,
            num_chunks, *rest):
        seen.append((chunk_pages, tile_pages, num_chunks))
        return real(quantized, page_size, n_pages, chunk_pages, tile_pages,
                    num_chunks, *rest)

    monkeypatch.setattr(pa, "_flash_append_kernel_body", spy)
    Hkv, D, B = hd // 128, 128, 2
    quantized = dtype == jnp.int8
    pool = jax.ShapeDtypeStruct((1, B * pages + 1, ps, Hkv, D), dtype)
    scales = (jax.ShapeDtypeStruct((1, B * pages + 1, Hkv, 128), jnp.float32)
              if quantized else None)
    row = jax.ShapeDtypeStruct((B, Hkv, D), jnp.bfloat16)
    jax.eval_shape(
        lambda *a: pa._paged_attention_flash_append.__wrapped__(
            *a, pages=pages, quantized=quantized, interpret=True),
        row, row, row, pool, pool, scales, scales,
        jax.ShapeDtypeStruct((B, pages), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    return seen[-1]


@pytest.mark.parametrize("dtype,itemsize", [(jnp.int8, 1), (jnp.bfloat16, 2)],
                         ids=["int8", "bf16"])
@pytest.mark.parametrize("hd,int8_tokens", [(512, 2048), (1024, 1024),
                                            (2048, 512)])
def test_chunk_size_function_is_the_kernels(hd, int8_tokens, dtype, itemsize,
                                            monkeypatch):
    """flash_append_chunk_pages is the one home of the chunk arithmetic:
    what the dispatch hands its kernel, at the served geometries
    (bench-moe 512, Mistral / Mixtral 1,024, OLMoE 2,048), both pool
    dtypes, below and above one chunk of window."""
    ps = 64
    for W in (256, 2048, 4096):
        pages = W // ps
        want = min(pages, int8_tokens // itemsize // ps)
        assert pa.flash_append_chunk_pages(hd, itemsize, ps, pages) == want
        assert _chunking_used(monkeypatch, hd, dtype, ps, pages) == (
            want, pa.flash_append_tile_pages(hd, itemsize, ps, want),
            -(-pages // want))


def _pool(Hkv=8, D=128, quantized=True, ps=64, pages=16, B=2):
    """What the chooser reads of a cache: shapes, and whether it has
    scales."""
    N = B * pages + 1
    pool = jax.ShapeDtypeStruct((1, N, ps, Hkv, D),
                                jnp.int8 if quantized else jnp.bfloat16)
    scale = (jax.ShapeDtypeStruct((1, N, Hkv, 128), jnp.float32)
             if quantized else None)
    return types.SimpleNamespace(
        k=pool, v=pool, k_scale=scale, v_scale=scale,
        page_table=jax.ShapeDtypeStruct((B, pages), jnp.int32))


def _stub_both_sides(monkeypatch, on_tpu=True):
    """The chooser's two implementations answer with their names."""
    monkeypatch.setattr(pa, "on_tpu", lambda: on_tpu)
    monkeypatch.setattr(pa, "_append_gather",
                        lambda *a, pages: ("gather", pages))
    monkeypatch.setattr(
        pa, "_paged_attention_flash_append",
        lambda *a, pages, quantized: ("flash", pages, quantized))


# The kernel's first window (PR 56: one for every geometry; PERF.md
# section 6 has the table it came from).
MIN_W = 256

# Pool widths hd = Hkv * head_dim as (kv heads, int8 pool): one and two
# heads (float pools: an int8 pool of fewer than four is refused), the
# paired pools' and the 4-KV-head models' 512, Mistral's and Mixtral's
# 1,024, OLMoE's and Ouro's 2,048, and wider than anything served.
# Until PR 56 the window scaled with the width (1,024 up to hd 1,024,
# 512 at 2,048, 256 from 4,096).
_WIDTHS = {128: (1, False), 256: (2, False), 512: (4, True),
           1024: (8, True), 2048: (16, True), 8192: (64, True)}


@pytest.mark.parametrize("hd", sorted(_WIDTHS))
def test_dispatch_policy_table(hd, monkeypatch):
    """The dispatch rule is a function of the window and nothing else:
    the same table at every pool width through the chooser itself, and
    no environment variable that moves it."""
    Hkv, quantized = _WIDTHS[hd]
    cache = _pool(Hkv=Hkv, quantized=quantized, ps=64, pages=16)
    _stub_both_sides(monkeypatch)
    for env in ({}, {"PAGED_APPEND_FLASH_MIN_W": "4096",
                     "PAGED_APPEND_IMPL": "gather",
                     "PAGED_ATTN_IMPL": "kernel"}):
        for name, value in env.items():
            monkeypatch.setenv(name, value)
        for W in (128, 192, 256, 512, 1024):
            assert pa._flash_append_policy(W) == (W >= MIN_W)
            got = pa.paged_attention_append(None, None, None, cache, None,
                                            0, pages=W // 64)
            assert got == (("flash", W // 64, quantized) if W >= MIN_W
                           else ("gather", W // 64))
        assert not pa._flash_append_policy(MIN_W - 1)
        assert pa._flash_append_policy(4096)
        assert pa.effective_flash_min_w(False, 128,
                                        Hkv if quantized else 0) == MIN_W


# Each reason flash_append_blocked can give, as (what the reason says, the
# platform probe's answer, the pool, ``sharded``), then a pool nothing
# blocks. Every pool's window (1,024) is past the kernel's first.
_GUARD = {
    "not-a-tpu": ("not on a TPU", False, _pool(), False),
    "sharded": ("sharded over a mesh", True, _pool(), True),
    "lanes": ("128 lanes", True, _pool(Hkv=16, D=32, quantized=False), False),
    "sublanes": ("2 kv heads", True, _pool(Hkv=2), False),
    "bf16-2-heads": (None, True, _pool(Hkv=2, quantized=False), False),
    "open": (None, True, _pool(), False),
}


@pytest.mark.parametrize("case", sorted(_GUARD))
def test_chooser_takes_gather_wherever_the_kernel_is_blocked(case,
                                                             monkeypatch):
    """paged_attention_append picks by what it observes alone: the gather
    for each reason the guard names, the kernel where it names none, and
    the gauge (``effective_flash_min_w``) says the same."""
    reason, on_tpu, cache, sharded = _GUARD[case]
    _stub_both_sides(monkeypatch, on_tpu)
    Hkv, D = cache.k.shape[3:]
    quantized = cache.k_scale is not None
    guard = (sharded, D, Hkv if quantized else 0)
    pages = cache.page_table.shape[1]
    for short in (False, True):
        n = 2 if short else pages       # 128 tokens / the whole 1,024
        got = pa.paged_attention_append(None, None, None, cache, None, 0,
                                        pages=n, sharded=sharded)
        if reason or short:
            assert got == ("gather", n)
        else:
            assert got == ("flash", n, quantized)
    if reason:
        assert reason in pa.flash_append_blocked(*guard)
        assert pa.effective_flash_min_w(*guard) == 0
    else:
        assert pa.flash_append_blocked(*guard) is None
        assert pa.effective_flash_min_w(*guard) == MIN_W


# -- long-window matrix (ci.sh full mode) -------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("ps", [64, 128])
@pytest.mark.parametrize("quantized", [False, True],
                         ids=["fppool", "int8pool"])
@pytest.mark.parametrize("W", [2048, 4096])
def test_long_window_matrix(W, quantized, ps, monkeypatch):
    """The serving-shape matrix at the REAL chunk budget (no shrink):
    W ∈ {2048, 4096} × int8 / full-precision pools (f32 here — the
    hermetic CPU stand-in for the bf16 serving pool, same code path) ×
    both page sizes, B=2 with one near-full and one mid-window row. At
    the default chunk budget the walk is 8..16 chunks of 2..4 pages —
    the exact grid shapes the TPU default dispatch compiles at these
    windows."""
    pages = W // ps
    lengths = [W - 1, W // 2 + ps // 2]
    _check_case("tiny", pages, ps, lengths, quantized, monkeypatch,
                chunk_bytes=None, seed=2)

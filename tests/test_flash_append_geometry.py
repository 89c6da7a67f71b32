"""Edge-geometry parity for the multi-chunk flash-append kernel.

The round-8 long-window kernel (ops/paged_attention.
_paged_attention_flash_append: grid ``(B, chunks)``, cross-chunk
online-softmax merge in VMEM scratch, clamped partial-chunk DMAs) runs
here in ``interpret=True`` mode — SURVEY.md §4 "TPU without a TPU" —
against two oracles:

- the gather append path (``paged_attention_append`` with
  ``_APPEND_IMPL`` pinned to "gather"), which shares the kernel's exact
  append semantics (current token attended at full precision, pool
  writes batched after the scan);
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

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops import paged_attention_reference, paged_kv

pa = importlib.import_module("p2p_llm_chat_tpu.ops.paged_attention")

pytestmark = pytest.mark.model

# 64 bytes / f32 = 16 tokens = 2 pages at PS=8: pages=5 walks as 3
# chunks (2 + 2 + 1-clamped) — the geometry the fast cases pin.
PS = 8
CHUNK_BYTES = 64


def _filled_cache(cfg, pages, ps, lengths, quantized, rng):
    """Pool with each row's first ``lengths[b]`` slots holding random kv
    through the real splice op; rows own disjoint page ranges."""
    B = len(lengths)
    cache = paged_kv.PagedKVCache.create(
        cfg, B, B * pages + 1, ps, max_pages_per_row=pages,
        dtype=jnp.float32, quantized=quantized)
    for b, n in enumerate(lengths):
        table = jnp.asarray(1 + b * pages + np.arange(pages), jnp.int32)
        rk = jnp.asarray(rng.normal(size=(cfg.num_layers, pages * ps,
                                          cfg.num_kv_heads, cfg.head_dim)),
                         jnp.float32)
        rv = jnp.asarray(rng.normal(size=rk.shape), jnp.float32)
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
    # round-18 hd-aware scaling is identity here and the chunk layouts
    # documented per case (pages/chunk, boundary positions) hold
    # exactly; the scaling itself is pinned by the policy-table test.
    monkeypatch.setattr(pa, "_FLASH_HD_REF",
                        cfg.num_kv_heads * cfg.head_dim)
    monkeypatch.setattr(pa, "_APPEND_IMPL", "gather")  # pin the oracle path
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
        ref = pa.paged_attention_append(q, kc, vc, cache, lens,
                                        jnp.asarray(layer), pages=pages,
                                        interpret=True)
        np.testing.assert_allclose(
            np.asarray(kern), np.asarray(ref), atol=2e-5, rtol=2e-5,
            err_msg=f"vs gather append: layer {layer} q={quantized}")
        if not quantized:
            # Independent oracle: the index-naive reference over the
            # pool WITH the current token written (write-then-attend
            # ordering — identical on full-precision pools).
            c2 = paged_kv.write_decode(cache, jnp.asarray(layer), kc, vc)
            ref2 = paged_attention_reference(
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
    scale concat or position mask shows. rep=1 config (tiny-tp): the
    expander dot degenerates to identity, the other boundary worth
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


def test_dispatch_policy_table(monkeypatch):
    """The pure dispatch rule (decision table) plus the two runtime
    properties the satellites pin: the threshold is read per decision —
    flipping PAGED_APPEND_FLASH_MIN_W needs NO re-import — and the
    platform guard keeps gather everywhere on CPU."""
    # Default boundary: kernel at W >= 2048, gather below.
    monkeypatch.delenv("PAGED_APPEND_FLASH_MIN_W", raising=False)
    assert pa._flash_append_min_w() == 2048
    assert pa._flash_append_policy(2048, "gather", 2048)
    assert pa._flash_append_policy(4096, "gather", 2048)
    assert not pa._flash_append_policy(1024, "gather", 2048)
    assert not pa._flash_append_policy(192, "gather", 2048)
    # 0 disables the flash default outright.
    assert not pa._flash_append_policy(1 << 20, "gather", 0)
    # Explicit impl overrides win in both directions.
    assert pa._flash_append_policy(64, "flash", 2048)
    assert not pa._flash_append_policy(1 << 20, "kernel", 2048)
    # Geometry scaling (round-18): the boundary is min_w * hd / 1024.
    # At the calibration geometry (hd=1024) nothing changes; at
    # bench-moe's narrow KV (4 kv heads x 128 = 512) it halves to 1024
    # — the window regime where the recorded ~1.3 ms MoE paged-walk gap
    # lived; at 70B-class hd=1024 it is identity again.
    assert pa._flash_append_policy(2048, "gather", 2048, hd=1024)
    assert not pa._flash_append_policy(1024, "gather", 2048, hd=1024)
    assert pa._flash_append_policy(1024, "gather", 2048, hd=512)
    assert not pa._flash_append_policy(1023, "gather", 2048, hd=512)
    assert pa._flash_append_policy(512, "gather", 2048, hd=256)
    # The floor: no geometry engages below 256 tokens on the default
    # rule (sub-2-chunk grids cannot pipeline).
    assert not pa._flash_append_policy(255, "gather", 2048, hd=32)
    assert pa._flash_append_policy(256, "gather", 2048, hd=32)
    # Wider-than-calibration KV (OLMoE's MHA, hd=2048): the ratio to the
    # knob is measured, not extrapolated: the kernel won 3.5-6.6x at W = 512..2048 on a v5e (PERF.md
    # section 6, PR 26), where the scaled rule said gather below 4096.
    assert pa._flash_append_policy(512, "gather", 2048, hd=2048)
    assert pa._flash_append_policy(2048, "gather", 2048, hd=2048)
    assert not pa._flash_append_policy(256, "gather", 2048, hd=2048)
    assert not pa._flash_append_policy(2048, "gather", 0, hd=2048)
    assert pa.effective_flash_min_w(hd=2048) in (0, 512)   # 0: not a TPU
    # The knob scales that geometry too: a quarter of it.
    assert pa._flash_append_policy(1024, "gather", 4096, hd=2048)
    assert not pa._flash_append_policy(512, "gather", 4096, hd=2048)
    # Overrides ignore geometry.
    assert pa._flash_append_policy(64, "flash", 2048, hd=2048)
    assert not pa._flash_append_policy(1 << 20, "kernel", 2048, hd=256)
    # Runtime toggle: read through utils/env at dispatch time.
    monkeypatch.setenv("PAGED_APPEND_FLASH_MIN_W", "4096")
    assert pa._flash_append_min_w() == 4096
    monkeypatch.setenv("PAGED_APPEND_FLASH_MIN_W", "")
    assert pa._flash_append_min_w() == 2048      # empty = unset
    monkeypatch.delenv("PAGED_APPEND_FLASH_MIN_W", raising=False)
    if not pa.on_tpu():
        # CPU CI: the platform guard must hold regardless of the policy,
        # and the gauge helper (serve/scheduler.py `paged_flash_min_w`)
        # must report "cannot engage" = 0.
        assert not pa._flash_append_wanted(1 << 20)
        assert pa.effective_flash_min_w() == 0
        monkeypatch.setattr(pa, "on_tpu", lambda: True)
    # On the TPU — the probe patched open above, or for real on the chip
    # (`python -m pytest --noconftest tests/test_flash_append_geometry.py
    # -k dispatch_policy`; conftest would pin the CPU) — the guard opens
    # at the geometry-scaled boundary, and shuts again for a pool
    # sharded over a mesh (pallas_call cannot consume one).
    assert pa._flash_append_wanted(2048) and not pa._flash_append_wanted(1024)
    assert pa._flash_append_wanted(1024, 512)
    assert pa.effective_flash_min_w() == 2048
    assert pa.effective_flash_min_w(512) == 1024
    assert not pa._flash_append_wanted(1 << 20, sharded=True)
    assert pa.effective_flash_min_w(sharded=True) == 0
    # ... and for a head_dim that does not fill 128-lane rows: Mosaic
    # refuses the kernel there (seen on the chip at tiny's D=32, where
    # the scaled boundary would engage it from W=256).
    assert not pa._flash_append_wanted(1 << 20, 64, head_dim=32)
    assert pa.effective_flash_min_w(64, head_dim=32) == 0
    assert "128 lanes" in pa.flash_append_blocked(head_dim=32)
    assert pa.flash_append_blocked(head_dim=128) is None


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

"""Decode attention over the paged KV pool.

One query token per batch row attends that row's live context through
its page table, BEFORE the step's own k/v is in the pool: the current
token folds in as one extra softmax term and the caller lands every
layer's k/v with one batched scatter after its layer scan
(ops/paged_kv.write_decode_burst). Three entry points:

- :func:`paged_attention_append` — the decode tick (models/llama.py,
  models/mixtral.py through it, models/nemotron_h.py's page layers).
- :func:`paged_attention_verify_append` — a block of S positions a row
  (speculative verify, a session wake's suffix): the pool window plus
  the in-register block under a causal or tree mask.
- :func:`gather_window` — one layer's window gathered once, for a layer
  whose pages other layers read too (models/nemotron_h.py's cross
  layers).
- :func:`paged_attention_append_paired` — the decode tick at a head of
  64 over a pool that keeps its KV heads in pairs (128 numbers a row):
  the same two implementations under the same rule, the queries
  zero-extended onto their own half of a pair's row.

``paged_attention_append`` has two implementations, the same f32 softmax
over the same scores:

- :func:`_append_gather` — XLA: one joint (layer, page) gather of each
  row's whole ``[page_size, Hkv, D]`` pages (the token-major pool makes
  the window a pure reshape), scores, the merge. Runs everywhere; its
  cost follows the WINDOW (the gathered, dequantised copy is
  ``B x W x hd``).
- :func:`_paged_attention_flash_append` — a Pallas kernel, grid ``(row,
  chunk)``: each program DMAs one bounded chunk of pages (and scale
  rows) and folds it into online-softmax state held in VMEM scratch
  across the chunk axis; a chunk that starts past its row's length is
  neither fetched nor folded, so its cost follows the rows' LENGTHS.
  HBM sees each live page once. TPU only.

The rule that chooses (:func:`_flash_append_policy`, guarded by
:func:`flash_append_blocked`): the kernel from ``W >= max(256, 1024 *
1024 / max(hd, 1024))`` with ``hd = Hkv * head_dim``, the gather below
it and wherever the kernel cannot run (no TPU, a pool sharded over a
mesh, a head_dim that does not fill 128 lanes, an int8 pool of fewer
than 4 kv heads). A function of window and pool geometry alone, decided
once per trace (a paired pool's ``Hkv`` is its pairs and its
``head_dim`` 128: four pairs are 512 numbers a token, the boundary
1,024, and neither refusal meets them; PERF.md section 6, PR 45, has
that geometry's table); its measurement is PERF.md section 6, PR 31, and
``python tools/check_append_kernel.py time`` measures it again by calling
the two implementations by name. The block verify stays on the gather
at every window (the kernel's state is seeded with ONE current token).

:func:`paged_attention_reference` is the index-naive jnp oracle the tests
hold both implementations to (tests/test_ops_paged.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import on_tpu

NEG_INF = -1e30


def _gqa_selection_matrices(Hq: int, Hkv: int, D: int, rep: int):
    """Constant 0/1 selection matrices built from in-register iotas
    for the flash-append kernel, which turns every GQA shuffle into an
    MXU dot: SEL tiles / collapses per-head D-blocks, BLOCKM masks q
    columns to their own kv block (built both ways — Mosaic cannot
    transpose i1), EXPT expands kv-head rows to query-head columns.
    Returns
    (sel bf16 [HD, D], blockm bool [HD, Hq], blockm_t bool [Hq, HD],
    expt f32 [Hq, Hkv])."""
    HD = Hkv * D
    cmod = jax.lax.broadcasted_iota(jnp.int32, (HD, D), 0) % D
    drng = jax.lax.broadcasted_iota(jnp.int32, (HD, D), 1)
    sel = (cmod == drng).astype(jnp.bfloat16)
    cdiv = jax.lax.broadcasted_iota(jnp.int32, (HD, Hq), 0) // D
    hdiv = jax.lax.broadcasted_iota(jnp.int32, (HD, Hq), 1) // rep
    blockm = cdiv == hdiv
    cdiv2 = jax.lax.broadcasted_iota(jnp.int32, (Hq, HD), 1) // D
    hdiv2 = jax.lax.broadcasted_iota(jnp.int32, (Hq, HD), 0) // rep
    blockm_t = cdiv2 == hdiv2
    return sel, blockm, blockm_t, _gqa_expander(Hq, Hkv, rep)


def _gqa_expander(Hq: int, Hkv: int, rep: int):
    """EXPT alone (f32 [Hq, Hkv]): kv-head rows to query-head rows."""
    hh = jax.lax.broadcasted_iota(jnp.int32, (Hq, Hkv), 0) // rep
    gg = jax.lax.broadcasted_iota(jnp.int32, (Hq, Hkv), 1)
    return (hh == gg).astype(jnp.float32)


def _scaled(scores, D: int, scale):
    """Scores over the softmax's scale: ``1 / sqrt(D)``, or ``scale``
    where the caller's head is not the pool's row (a pair of heads)."""
    if scale is None:
        return scores / jnp.sqrt(D).astype(jnp.float32)
    return scores * jnp.float32(scale)


def paged_attention_append(q, k_cur, v_cur, cache, lengths, layer,
                           *, pages: int, sharded: bool = False,
                           scale=None):
    """Decode attention where this step's k/v is NOT yet in the pool:
    attend over the pool window (positions < ``lengths``) and merge the
    current token's own (k_cur, v_cur) contribution with one exact
    online-softmax step.

    Why: writing each layer's k/v into the pool BEFORE attending forces
    one [B]-indexed pool scatter per layer inside the decode scan — 22+
    small scatters per step whose fixed cost is measurable against the
    bandwidth bound. With the merge, the scan collects per-layer k/v as
    stacked outputs and ONE batched scatter (ops/paged_kv.
    write_decode_burst) lands the whole step after the trunk. On int8
    pools the CURRENT token is attended at FULL precision, where a later
    step reads it back quantized — a sub-quantisation-noise difference
    that can flip logit ties (the same caveat verify_append documents
    for drafts; see the scheduler's kv_quant notes).

    q/k_cur/v_cur: [B, Hq|Hkv, D] (one token per row); cache: the
    PagedKVCache (bf16 or int8 pools); lengths: positions already in
    the pool per row (NOT including the current token). Returns
    [B, Hq, D] in q.dtype. ``sharded``: the pool is sharded over a mesh
    (TP serving), which the Pallas kernel cannot consume. ``scale``: the
    softmax's, where it is not ``1 / sqrt(D)`` of the pool's row.

    Chooses between :func:`_append_gather` and
    :func:`_paged_attention_flash_append` from what it can observe — the
    window, the pool's geometry, the platform — and nothing else (module
    docstring). A caller that wants one side calls it by name.
    """
    Hkv, D = cache.k.shape[3], cache.k.shape[4]
    quantized = cache.k_scale is not None
    args = (q, k_cur, v_cur, cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.page_table, lengths, layer)
    blocked = flash_append_blocked(sharded, D, Hkv if quantized else 0)
    scaled = {} if scale is None else {"scale": scale}
    if not blocked and _flash_append_policy(pages * cache.k.shape[2],
                                            Hkv * D):
        return _paged_attention_flash_append(*args, pages=pages,
                                             quantized=quantized, **scaled)
    return _append_gather(*args, pages=pages, **scaled)


def pair_queries(q: jax.Array, rep: int) -> jax.Array:
    """[B, Hq, D] -> [B, Hq, 2D]: each query zero-extended onto its KV
    head's half of a PAIR's row (``ModelConfig.kv_paired``: KV heads 2g
    and 2g + 1 side by side), so that its dot with the row is its dot
    with its own head. ``rep`` query heads a KV head."""
    B, Hq, D = q.shape
    half = jnp.arange(Hq)[:, None] // rep % 2 == jnp.arange(2)[None, :]
    return jnp.where(half[None, :, :, None], q[:, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(B, Hq, 2 * D)


def unpair_outputs(o: jax.Array, rep: int) -> jax.Array:
    """[B, Hq, 2D] -> [B, Hq, D]: of ``p . V`` over a pair's row, the
    half that is the query's own KV head."""
    B, Hq, D2 = o.shape
    odd = (jnp.arange(Hq) // rep % 2 == 1)[None, :, None]
    return jnp.where(odd, o[..., D2 // 2:], o[..., : D2 // 2])


def paged_attention_append_paired(q, k_cur, v_cur, cache, lengths, layer,
                                  *, pages: int):
    """:func:`paged_attention_append` for a head of 64 over a pool that
    keeps its KV heads in pairs (``[.., Hkv / 2, 128]``). q [B, Hq, 64];
    k_cur, v_cur [B, Hkv, 64]. The pool's row is what both
    implementations already take (whole 128-lane rows, and four of them
    fill an int8 tile's sublanes): the queries go in zero-extended
    (:func:`pair_queries`), the scores are each query's with its own
    head, and of each output the own head's half is kept. Twice the MXU
    work of a kernel written for the head, on a path bound by the pool's
    bytes; measured against the gather path on a per-head pool in
    PERF.md section 6, PR 45."""
    B, Hq, D = q.shape
    Hkv = k_cur.shape[1]
    rep = Hq // Hkv
    out = paged_attention_append(
        pair_queries(q, rep), k_cur.reshape(B, Hkv // 2, 2 * D),
        v_cur.reshape(B, Hkv // 2, 2 * D), cache, lengths, layer,
        pages=pages, scale=D ** -0.5)
    return unpair_outputs(out, rep)


def _append_gather(q, k_cur, v_cur, k_pages, v_pages, k_scale, v_scale,
                   page_table, lengths, layer, *, pages: int, scale=None):
    """:func:`paged_attention_append` in XLA: gather the window, score
    it, merge the current token's term. ``k_scale`` None = a bf16 pool."""
    B, Hq, D = q.shape
    Hkv = k_cur.shape[1]
    rep = Hq // Hkv
    scores, v, sv = _gather_window_scores(
        q[:, None], k_pages, v_pages, k_scale, v_scale, page_table,
        lengths, layer, pages=pages, scale=scale)

    # Current token's own score: q . k_cur per kv head.
    qg = q.reshape(B, 1, Hkv, rep, D)
    s_cur = _scaled(jnp.einsum("bgrd,bgd->bgr", qg[:, 0].astype(jnp.float32),
                               k_cur.astype(jnp.float32)), D, scale)
    s_cur = s_cur[..., None, None]                           # [B,G,rep,1,1]

    m_w = jnp.max(scores, axis=-1, keepdims=True)            # [B,G,rep,1,1]
    m = jnp.maximum(m_w, s_cur)
    p = jnp.exp(scores - m)                                  # masked -> ~0
    p_cur = jnp.exp(s_cur - m)                               # > 0 always
    if sv is not None:
        pv = jnp.einsum("bgrst,btgd->bgrsd",
                        (p * sv[:, :, None, None, :]).astype(q.dtype),
                        v.astype(q.dtype)).astype(jnp.float32)
    else:
        pv = jnp.einsum("bgrst,btgd->bgrsd", p.astype(v.dtype),
                        v).astype(jnp.float32)
    num = pv + p_cur * v_cur.astype(jnp.float32)[:, :, None, None, :]
    den = jnp.sum(p, axis=-1, keepdims=True) + p_cur         # [B,G,rep,1,1]
    out = num / den
    return out[:, :, :, 0].reshape(B, Hq, D).astype(q.dtype)


def _gather_window_scores(q4, k_pages, v_pages, k_scale, v_scale,
                          page_table, lengths, layer, *, pages: int,
                          scale=None):
    """Shared preamble of the gather append and the block verify: gather
    one layer's window, compute masked pre-softmax scores (per-position
    k scales folded in when the pool is int8), and return
    (scores [B,G,rep,S,W] f32, v [B,W,Hkv,D], sv [B,G,W] | None).
    q4: [B, S, Hq, D] (S query positions per row; every position sees the
    same window mask ``pos < lengths`` — block-internal causality is the
    caller's concern, see paged_attention_verify_append)."""
    B, S, Hq, D = q4.shape
    L, N, ps, Hkv, _ = k_pages.shape
    rep = Hq // Hkv
    W = pages * ps
    # Joint (layer, page) index into the flat [L*N] page axis: slicing the
    # layer first (k_pages[layer][pt]) materialises the layer's ENTIRE
    # pool slice before the gather (~0.4 ms a step of pure copy at bench
    # serving shapes); one gather from the flat pool reads only the
    # window's pages.
    pt = layer * N + page_table[:, :pages].astype(jnp.int32)
    k = k_pages.reshape(L * N, ps, Hkv, D)[pt].reshape(B, W, Hkv, D)
    v = v_pages.reshape(L * N, ps, Hkv, D)[pt].reshape(B, W, Hkv, D)
    qg = q4.reshape(B, S, Hkv, rep, D)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qg, k.astype(q4.dtype),
                        preferred_element_type=jnp.float32)
    scores = _scaled(scores, D, scale)
    sv = None
    if k_scale is not None:
        # Scales are stored head-major, lane-padded [L, N, Hkv, ps_pad]
        # (paged_kv.py: the layout the flash-append kernel DMAs); the
        # gathered [B, P, Hkv, ps] window transposes to [B, G, W] with one
        # cheap swap of small middle axes (no full-array relayout).
        ps_pad = k_scale.shape[-1]
        sk = k_scale.reshape(L * N, Hkv, ps_pad)[pt][..., :ps].transpose(
            0, 2, 1, 3).reshape(B, Hkv, W)                     # [B,G,W]
        sv = v_scale.reshape(L * N, Hkv, ps_pad)[pt][..., :ps].transpose(
            0, 2, 1, 3).reshape(B, Hkv, W)
        scores = scores * sk[:, :, None, None, :]
    mask = (jnp.arange(W)[None, :] < lengths[:, None])[:, None, None, None, :]
    return jnp.where(mask, scores, NEG_INF), v, sv


def gather_window(cache, layer, *, pages: int) -> tuple:
    """One layer's window of every row, gathered once for whoever reads
    it (a layer whose pages other layers read too): (k [B,W,Hkv,D], v, k
    scales [B,Hkv,W] | None, v scales), W = ``pages`` x page_size. The
    gather of :func:`_gather_window_scores`, without the scores."""
    L, N, ps, Hkv, D = cache.k.shape
    B = cache.page_table.shape[0]
    W = pages * ps
    pt = layer * N + cache.page_table[:, :pages].astype(jnp.int32)
    k = cache.k.reshape(L * N, ps, Hkv, D)[pt].reshape(B, W, Hkv, D)
    v = cache.v.reshape(L * N, ps, Hkv, cache.v.shape[-1])[pt].reshape(
        B, W, Hkv, cache.v.shape[-1])
    if cache.k_scale is None:
        return k, v, None, None
    ps_pad = cache.k_scale.shape[-1]

    def scales(a):
        return a.reshape(L * N, Hkv, ps_pad)[pt][..., :ps].transpose(
            0, 2, 1, 3).reshape(B, Hkv, W)

    return k, v, scales(cache.k_scale), scales(cache.v_scale)


def paged_attention_verify_append(q_blk, k_blk, v_blk, cache, lengths,
                                  layer, *, pages: int, block_mask=None):
    """Speculative-verify attention where the candidate block's k/v is
    NOT yet in the pool: position j attends the pool window (positions
    < ``lengths``, identical mask for every j) plus block positions
    i <= j from the in-register k/v — one softmax over the concatenated
    score axis. (On int8 pools the block is attended at FULL
    precision. Position 0 then sees exactly what the plain tick's
    paged_attention_append sees; positions j >= 1 view EARLIER drafts
    at full precision where the plain path, once those drafts commit,
    reads them quantized — so spec output under int8 KV tracks the
    plain engine to rounding error, not bit-exactly, at logit ties.)
    The caller lands the whole block (and all
    layers) with ONE batched scatter afterwards
    (ops/paged_kv.write_decode_multi_all_layers) — the multi-position
    generalisation of :func:`paged_attention_append`.

    q_blk: [B, S, Hq, D]; k_blk/v_blk: [B, S, Hkv, D]; lengths: pool
    positions per row (excluding the block). ``block_mask`` ([B,S,S]
    bool, True = attend, self-diagonal included) replaces the chain-
    causal triangle over the in-register block — tree speculation
    (models/llama.verify_tree_paged) passes its ancestor matrix so each
    node sees only its own root path; the pool-window mask is branch-
    agnostic either way. Returns [B, S, Hq, D].
    """
    B, S, Hq, D = q_blk.shape
    Hkv = k_blk.shape[2]
    rep = Hq // Hkv
    scores_w, v_w, sv = _gather_window_scores(
        q_blk, cache.k, cache.v, cache.k_scale, cache.v_scale,
        cache.page_table, lengths, layer, pages=pages)   # [B,G,rep,S,W]

    qg = q_blk.reshape(B, S, Hkv, rep, D)
    scores_b = jnp.einsum("bsgrd,btgd->bgrst", qg.astype(jnp.float32),
                          k_blk.astype(jnp.float32))     # [B,G,rep,S,S]
    scores_b = scores_b / jnp.sqrt(D).astype(jnp.float32)
    if block_mask is None:
        causal = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])
        scores_b = jnp.where(causal[None, None, None], scores_b, NEG_INF)
    else:
        scores_b = jnp.where(block_mask[:, None, None], scores_b, NEG_INF)

    scores = jnp.concatenate([scores_w, scores_b], axis=-1)  # [.., W+S]
    probs = jax.nn.softmax(scores, axis=-1)
    p_w, p_b = probs[..., : scores_w.shape[-1]], probs[..., scores_w.shape[-1]:]
    if sv is not None:
        p_w = p_w * sv[:, :, None, None, :]
    out = (jnp.einsum("bgrst,btgd->bgrsd", p_w.astype(q_blk.dtype),
                      v_w.astype(q_blk.dtype)).astype(jnp.float32)
           + jnp.einsum("bgrst,btgd->bgrsd", p_b,
                        v_blk.astype(jnp.float32)))
    # [B,G,rep,S,D] -> [B,S,Hq,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D).astype(
        q_blk.dtype)


# Per-dtype chunk sizing for the flash-append DMA pipeline: bytes of
# one (k or v) buffer side per token AT THE CALIBRATION GEOMETRY
# (_FLASH_HD_REF) — the chunk token budget is
# _FLASH_CHUNK_TOK_BYTES * _FLASH_HD_REF / (hd * pool_itemsize), i.e.
# 1024 int8 tokens / 512 bf16 tokens / 256 f32 tokens per grid step at
# hd=1024, and proportionally MORE tokens per chunk at narrower KV
# geometries (same VMEM bytes, fewer grid programs). The VMEM ceiling is
# geometry-invariant by construction: double-buffered int8 k+v DMA
# slots 4 MB + the chunk-local bf16 dequant view 4 MB + f32 softmax
# state ~0.2 MB = 8.2 MB, under the 16 MB stack. Module-level so tests
# can shrink it to exercise many-chunk grids in interpret mode at tiny
# geometries.
_FLASH_CHUNK_TOK_BYTES = 1024

# The Hkv * head_dim the chunk budget and the boundary were calibrated
# at (llama-8B class: 8 kv heads x 128), and the boundary there.
_FLASH_HD_REF = 1024
_FLASH_MIN_W = 1024

# Floor for the engagement boundary: no geometry measured engages below
# it (one chunk a row, nothing to skip at a full batch).
_FLASH_MIN_W_FLOOR = 256


def _flash_boundary(hd: int) -> int:
    """The window from which the flash kernel serves a pool of ``hd =
    Hkv * head_dim`` numbers a token: 1,024 up to the calibration
    geometry, scaled down by ``1024 / hd`` for wider ones (OLMoE's MHA,
    hd = 2048: 512), never below 256."""
    return max(_FLASH_MIN_W_FLOOR,
               _FLASH_MIN_W * _FLASH_HD_REF // max(hd, _FLASH_HD_REF))


def _flash_append_policy(window: int, hd: int = _FLASH_HD_REF) -> bool:
    """The dispatch rule for the append path where the kernel can run
    (:func:`flash_append_blocked` is the guard), pure so CPU tests pin
    its table (tests/test_flash_append_geometry.py).

    Why a function of window and width alone (PR 31; PERF.md section 6
    has the table): the kernel's work follows the rows' lengths, the
    gather path's the window, so what decides is the FULL batch, where
    the kernel has least to skip. There a grid program costs 3-5 us
    whatever the width while the gather path's materialised, dequantised
    window grows with ``W * hd`` (and past hd = 1024 stops fitting what
    XLA fuses): measured on a v5e at 32 live rows of chat-mix lengths,
    int8 pool, the kernel is level with gather or ahead from W = 1024 at
    hd 512 (2% behind) and 1024 (10% ahead) and from W = 256 at hd 2048,
    and at 2 live rows of 32 it is 1.2x-56x faster at every window
    measured (so the boundary is where the full batch stops losing,
    never a function of live rows, which a trace cannot see)."""
    return window >= _flash_boundary(hd)


def flash_append_blocked(sharded: bool = False, head_dim: int = 128,
                         int8_kv_heads: int = 0) -> str | None:
    """Why the compiled flash-append kernel cannot run in this process
    for this pool, or None when it can — the guard around
    :func:`_flash_append_policy`, worded for the boot log:

    - the compiled kernel needs the TPU (utils/device.py, the one
      platform probe);
    - ``pallas_call`` cannot consume a pool whose kv-head axis is
      sharded over a mesh (``sharded`` — same policy as the prefill and
      matmul kernels; the XLA gather path shards fine);
    - Mosaic (libtpu 0.0.34) refuses the kernel's ``[Hkv, D] -> [Hkv*D]``
      tile collapse unless ``head_dim`` fills whole 128-lane rows
      ("infer-vector-layout: unsupported shape cast", seen on a v5e at
      the ``tiny`` config's D=32);
    - an int8 pool's tiles hold 4 kv heads on their sublanes, and Mosaic
      refuses the kernel's page slice of fewer (``int8_kv_heads``: the
      kv heads of an int8 pool, 0 for a bf16 one; "Slice shape along
      dimension 3 must be aligned to tiling (4), but is 2", seen on a
      v5e at 2 kv heads, PR 32). Such a pool is small: the gather path
      serves every window."""
    if not on_tpu():
        return "not on a TPU"
    if sharded:
        return "the pool is sharded over a mesh"
    if head_dim % 128:
        return f"head_dim {head_dim} is not a multiple of 128 lanes"
    if int8_kv_heads % 4:
        return (f"an int8 pool of {int8_kv_heads} kv heads does not fill "
                "its tiles' 4 sublanes")
    return None


def effective_flash_min_w(hd: int = _FLASH_HD_REF, sharded: bool = False,
                          head_dim: int = 128, int8_kv_heads: int = 0) -> int:
    """The flash-append engagement boundary as ONE number, for gauges
    and logs (serve/scheduler.py's ``paged_flash_min_w``): 0 = the
    kernel cannot engage in this process (:func:`flash_append_blocked`),
    else the boundary for ``hd = Hkv * head_dim`` (the scheduler passes
    its model's)."""
    if flash_append_blocked(sharded, head_dim, int8_kv_heads):
        return 0
    return _flash_boundary(hd)


def flash_append_chunk_pages(hd: int, itemsize: int, page_size: int,
                             pages: int) -> int:
    """Pages a flash-append grid program fetches and folds — its chunk
    — for a pool of ``hd = Hkv * head_dim`` numbers a token, ``itemsize``
    bytes each, walked ``pages`` pages a row. Pure, so the scheduler can
    count the chunks a dispatch walks (``serve_attn_chunks_*``) with the
    kernel's own arithmetic.

    The budget is in TOKENS, bounded by the VMEM stack, NOT by the
    window: _FLASH_CHUNK_TOK_BYTES derives the per-dtype chunk (1024
    int8 / 512 bf16 / 256 f32 tokens at the hd=1024 calibration
    geometry), scaled by _FLASH_HD_REF / hd so the chunk's VMEM BYTES
    stay constant across KV geometries — narrow-KV models (hd=512)
    carry 2x the tokens per chunk for the same VMEM, halving the
    per-chunk fixed cost per window token. The grid — not a bigger
    chunk — is what amortises per-chunk overhead, so chunks never grow
    with W."""
    tok_budget = max(page_size,
                     _FLASH_CHUNK_TOK_BYTES * _FLASH_HD_REF
                     // (hd * itemsize))
    return max(1, min(pages, tok_budget // page_size))


def _flash_append_kernel_body(quantized: bool, page_size: int, pages: int,
                              chunk_pages: int, num_chunks: int, rep: int,
                              scale: float, compute_dtype):
    """Build the multi-chunk flash-append kernel body: ONE program per
    (row, chunk) of a ``(B, num_chunks)`` grid — the split-K /
    flash-decoding shape (Dao et al.; the paged pool walk is vLLM
    PagedAttention's). The chunk axis is the grid's minor dimension, so
    for a fixed row the chunk programs run back to back and the
    online-softmax state (m, l, acc) lives in VMEM **scratch
    accumulators** that persist across them — VMEM holds one bounded
    chunk's tiles, never a whole window (a whole-window scratch
    overflowed the VMEM stack at 2,048-token chunks). Structure:

    - **append semantics**: chunk 0 INITIALISES the scratch state with
      the current token's term (m = s_cur, l = 1, acc = v_cur) — exactly
      the extra softmax term paged_attention_append's gather path
      merges, so pool writes still batch after the layer scan. The last
      chunk normalises and writes the output block.
    - **cross-program double buffering**: each program issues the NEXT
      chunk's page DMAs (rolling over to the next row's chunk 0 at row
      boundaries) before waiting on its own, into 2-slot DMA scratch
      indexed by global step parity — the grid replaces a
      kernel-internal chunk loop, so launch overhead amortises across
      programs and no program serialises a whole window's DMA waits.
    - **work follows the rows' lengths** (``holds_rows``): a chunk that
      starts at or past its row's length is skipped WHOLE — the
      program before it does not fetch it, its own program neither
      waits nor folds, and only the seed (chunk 0) and the finalise
      (last chunk) still run. The window is the power of two over the
      LONGEST live row, so among rows of ragged lengths (and free rows,
      whose length is 0) most of the grid is such chunks; an empty
      program costs a fraction of a microsecond, which is what makes
      the window's size stop mattering.
    - **inside a chunk that is folded** nothing is skipped: a page past
      the row's last is fetched through its table entry (0, the garbage
      page, by the pool contract) and masks to NEG_INF by position, and
      in a non-chunk-multiple window the page walk index clamps to
      ``pages - 1`` (a redundant re-fetch of the last real page).
      Skipping single page DMAs would leave uninitialised VMEM, which
      can be NaN, and a NaN row poisons the p.v dot even at zero
      probability.
    - **int8 pools** (``quantized``): the per-page scale rows
      ([Hkv, ps_pad] f32, the head-major layout paged_kv.py stores for
      kernel DMAs) ride the same DMA slots; k scales fold into the
      scores, v scales into the probabilities — the same
      fold-outside-the-dots contract as the gather path, so HBM sees
      int8 KV only.
    - **selection-matmul GQA math** (_gqa_selection_matrices): scores
      run as ONE [Ct, HD] x [HD, Hq] dot per chunk and the softmax chain
      on full-width [Ct, Hq] arrays; the scale folds are one
      [Ct, Hkv] x [Hkv, Hq] expander dot each.
    - ``compute_dtype``: bf16 on hardware (the MXU's preferred operand
      dtype; int8 -> bf16 is the cheap unpack), f32 in interpret mode so
      the CPU parity tests pin the kernel against the oracle at f32
      precision instead of bf16 rounding.
    """
    def body(*refs):
        if quantized:
            (pt_ref, len_ref, layer_ref, q_ref, kc_ref, vc_ref, k_hbm,
             v_hbm, ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf,
             m_ref, l_ref, acc_ref, sems) = refs
        else:
            (pt_ref, len_ref, layer_ref, q_ref, kc_ref, vc_ref, k_hbm,
             v_hbm, o_ref, kbuf, vbuf, m_ref, l_ref, acc_ref, sems) = refs
            ksbuf = vsbuf = ks_hbm = vs_hbm = None
        b = pl.program_id(0)
        c = pl.program_id(1)
        ly = layer_ref[0]
        length = len_ref[b]

        def dma(slot, bb, cc, i: int):
            # Clamped page-walk index: see the docstring's partial-chunk
            # note. pt entries past a row's allocation are 0 (garbage
            # page) by the pool contract, so every fetch is in bounds.
            j = jnp.minimum(cc * chunk_pages + i, pages - 1)
            page = pt_ref[bb, j]
            copies = [
                pltpu.make_async_copy(k_hbm.at[ly, page], kbuf.at[slot, i],
                                      sems.at[0, slot, i]),
                pltpu.make_async_copy(v_hbm.at[ly, page], vbuf.at[slot, i],
                                      sems.at[1, slot, i]),
            ]
            if quantized:
                copies += [
                    pltpu.make_async_copy(ks_hbm.at[ly, page],
                                          ksbuf.at[slot, i],
                                          sems.at[2, slot, i]),
                    pltpu.make_async_copy(vs_hbm.at[ly, page],
                                          vsbuf.at[slot, i],
                                          sems.at[3, slot, i]),
                ]
            return copies

        def start_chunk(slot, bb, cc) -> None:
            for i in range(chunk_pages):
                for d in dma(slot, bb, cc, i):
                    d.start()

        def wait_chunk(slot, bb, cc) -> None:
            for i in range(chunk_pages):
                for d in dma(slot, bb, cc, i):
                    d.wait()

        # Global step index orders the whole grid's chunk walk; its
        # parity picks the DMA slot (num_chunks may be odd, so parity
        # must run THROUGH row boundaries, not reset per row — and
        # through skipped programs, which neither start nor wait on
        # their slot).
        step = b * num_chunks + c
        slot = jax.lax.rem(step, 2)
        rows = pl.num_programs(0)
        Ct = chunk_pages * page_size

        def holds_rows(bb, cc):
            # A chunk that starts at or past its row's length (a free
            # row's every chunk, a short row's tail under a window some
            # other row set) is not fetched by the program before it,
            # not waited for and not folded: every position in it would
            # mask to NEG_INF and weigh exactly zero. The issuer and the
            # waiter read the same length — the FETCHED row's, which at
            # a row boundary is the next row's.
            return cc * Ct < len_ref[jnp.minimum(bb, rows - 1)]

        @pl.when((step == 0) & holds_rows(b, c))
        def _warmup():
            start_chunk(0, b, c)

        # Prefetch the next chunk — the next row's chunk 0 at a row
        # boundary — before waiting on our own.
        nb = jnp.where(c + 1 == num_chunks, b + 1, b)
        nc = jnp.where(c + 1 == num_chunks, 0, c + 1)

        @pl.when((step + 1 < rows * num_chunks) & holds_rows(nb, nc))
        def _prefetch():
            start_chunk(jax.lax.rem(step + 1, 2), nb, nc)

        q = q_ref[0].astype(jnp.float32)                 # [Hq, D]
        Hq, D = q.shape
        Hkv = Hq // rep
        HD = Hkv * D

        @pl.when(c == 0)
        def _seed():
            # Append init: state = the current token's softmax term at
            # FULL precision (p_cur = exp(s_cur - m) = 1 at m = s_cur).
            # State layout matches the chunk math: m/l [1, Hq],
            # acc [Hq, D]. Unconditional: a row of length 0 returns
            # this term alone.
            expt = _gqa_expander(Hq, Hkv, rep)
            kcur = jax.lax.dot(expt, kc_ref[0].astype(jnp.float32),
                               preferred_element_type=jnp.float32)
            vcur = jax.lax.dot(expt, vc_ref[0].astype(jnp.float32),
                               preferred_element_type=jnp.float32)
            m_ref[:] = jnp.sum(q * kcur, axis=-1,
                               keepdims=True).T * scale          # [1, Hq]
            l_ref[:] = jnp.ones((1, Hq), jnp.float32)
            acc_ref[:] = vcur                                    # [Hq, D]

        @pl.when(holds_rows(b, c))
        def _fold():
            # Constant selection matrices (_gqa_selection_matrices).
            sel, blockm, blockm_t, expt = _gqa_selection_matrices(
                Hq, Hkv, D, rep)
            sel_c = sel.astype(compute_dtype)

            # Q stacked into its kv block: [HD, Hq]. Hkv copies of q's
            # columns, the bits ``sel @ q.T`` gives (sel is 0/1), without
            # the MXU round trip: inside this region that dot cost a
            # live program 1.1 us of its 5 (v5e, PERF.md section 6,
            # PR 31), and outside it every empty program 0.6 us.
            q_cols = jnp.concatenate([q.T.astype(compute_dtype)] * Hkv,
                                     axis=0)
            q_blk = jnp.where(blockm, q_cols,
                              jnp.zeros((), compute_dtype))      # [HD, Hq]

            wait_chunk(slot, b, c)
            kflat = kbuf[slot].reshape(Ct, HD).astype(compute_dtype)
            vflat = vbuf[slot].reshape(Ct, HD).astype(compute_dtype)
            s = jax.lax.dot(kflat, q_blk,
                            preferred_element_type=jnp.float32) * scale
            if quantized:
                # [Ct, Hkv] scale columns -> [Ct, Hq] via the expander
                # dot (one MXU op; per-page segment concats measured
                # overhead-bound on the VPU).
                sk = jnp.concatenate(
                    [ksbuf[slot][i, :, :page_size].T
                     for i in range(chunk_pages)], axis=0)       # [Ct, Hkv]
                s = s * jax.lax.dot(sk, expt.T,
                                    preferred_element_type=jnp.float32)
            pos = c * Ct + jax.lax.broadcasted_iota(
                jnp.int32, (Ct, 1), dimension=0)
            s = jnp.where(pos < length, s, NEG_INF)              # [Ct, Hq]

            m_prev = m_ref[:]                                    # [1, Hq]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)                      # [1, Hq]
            probs = jnp.exp(s - m_cur)                           # [Ct, Hq]
            # Denominator sums the UNSCALED probabilities (v scales fold
            # into the p.v dot only — the gather path's contract).
            l_ref[:] = l_ref[:] * alpha + jnp.sum(probs, axis=0,
                                                  keepdims=True)
            if quantized:
                sv = jnp.concatenate(
                    [vsbuf[slot][i, :, :page_size].T
                     for i in range(chunk_pages)], axis=0)       # [Ct, Hkv]
                probs = probs * jax.lax.dot(
                    sv, expt.T, preferred_element_type=jnp.float32)
            out_full = jax.lax.dot(probs.T.astype(compute_dtype), vflat,
                                   preferred_element_type=jnp.float32)
            out_full = jnp.where(blockm_t, out_full, 0.0)        # [Hq, HD]
            acc_ref[:] = acc_ref[:] * alpha.T + jax.lax.dot(
                out_full.astype(compute_dtype), sel_c,
                preferred_element_type=jnp.float32)              # [Hq, D]
            m_ref[:] = m_cur

        @pl.when(c == num_chunks - 1)
        def _finalise():
            # l >= 1 always: the current token's own term seeds it.
            o_ref[0] = (acc_ref[:] / l_ref[:].T).astype(o_ref.dtype)

    return body


@functools.partial(jax.jit, static_argnames=("pages", "quantized",
                                             "interpret", "scale"))
def _paged_attention_flash_append(q, k_cur, v_cur, k_pages, v_pages,
                                  k_scale, v_scale, page_table, lengths,
                                  layer, *, pages: int, quantized: bool,
                                  interpret: bool = False, scale=None):
    """Multi-chunk flash-append dispatch: grid ``(B, num_chunks)``, one
    bounded chunk of manually-DMA'd pages (and scale rows) per program,
    online softmax carried in VMEM scratch across the chunk axis and
    seeded with the current token (_flash_append_kernel_body). HBM reads
    each page exactly once per (layer, step) — no gathered-window
    materialisation — and only the pages of chunks that start inside
    their row's context (PR 31). ``interpret`` runs it on the CPU, with
    f32 dot operands, for hardware-free parity tests."""
    B, Hq, D = q.shape
    L, N, page_size, Hkv, _ = k_pages.shape
    rep = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    pt = page_table[:, :pages].astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    chunk_pages = flash_append_chunk_pages(
        Hkv * D, k_pages.dtype.itemsize, page_size, pages)
    num_chunks = -(-pages // chunk_pages)
    # bf16 math on hardware; f32 in interpret mode so CPU parity tests
    # pin against the oracle at full precision (the body's dataflow is
    # identical — only the dot operand dtype changes).
    compute_dtype = jnp.float32 if interpret else jnp.bfloat16

    in_specs = [
        pl.BlockSpec((1, Hq, D), lambda b, c, pt, ln, ly: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda b, c, pt, ln, ly: (b, 0, 0)),
        pl.BlockSpec((1, Hkv, D), lambda b, c, pt, ln, ly: (b, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),      # k pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),      # v pool stays in HBM
    ]
    operands = [q, k_cur, v_cur, k_pages, v_pages]
    scratch = [
        pltpu.VMEM((2, chunk_pages, page_size, Hkv, D), k_pages.dtype),
        pltpu.VMEM((2, chunk_pages, page_size, Hkv, D), v_pages.dtype),
    ]
    n_sem = 2
    if quantized:
        ps_pad = k_scale.shape[-1]
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),  # k scales stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),  # v scales stay in HBM
        ]
        operands += [k_scale, v_scale]
        scratch += [
            pltpu.VMEM((2, chunk_pages, Hkv, ps_pad), jnp.float32),
            pltpu.VMEM((2, chunk_pages, Hkv, ps_pad), jnp.float32),
        ]
        n_sem = 4
    # Cross-chunk online-softmax state (persists across the grid's
    # chunk axis; re-seeded at every row's chunk 0).
    scratch += [
        pltpu.VMEM((1, Hq), jnp.float32),       # running max m
        pltpu.VMEM((1, Hq), jnp.float32),       # running sum l
        pltpu.VMEM((Hq, D), jnp.float32),       # unnormalised acc
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # page_table, lengths, layer
        grid=(B, num_chunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, D),
                               lambda b, c, pt, ln, ly: (b, 0, 0)),
        scratch_shapes=scratch + [
            pltpu.SemaphoreType.DMA((n_sem, 2, chunk_pages))],
    )
    return pl.pallas_call(
        _flash_append_kernel_body(quantized, page_size, pages, chunk_pages,
                                  num_chunks, rep, scale, compute_dtype),
        name="paged_attention_flash_append",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(pt, lengths.astype(jnp.int32), layer, *operands)


def paged_attention_reference(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, page_table: jax.Array,
                              lengths: jax.Array, layer,
                              *, pages: int) -> jax.Array:
    """jnp oracle: gather the pages dense slot-by-slot, run masked GQA
    attention (models/layers.attend_gqa). Same signature/semantics as
    :func:`paged_attention`; kept deliberately index-naive (per-token
    fetches, no whole-page reshape tricks) so it stays an independent
    check on both production implementations."""
    from ..models.layers import attend_gqa

    B = q.shape[0]
    page_size = k_pages.shape[2]
    window = pages * page_size
    pos = jnp.arange(window)
    phys = page_table[:, :pages][:, pos // page_size]      # [B, window]
    slot = jnp.broadcast_to(pos % page_size, (B, window))
    k = k_pages[layer][phys, slot]                         # [B, window, Hkv, D]
    v = v_pages[layer][phys, slot]
    mask = (pos[None, :] < lengths[:, None])[:, None, None, :]  # [B,1,1,W]
    return attend_gqa(q[:, None], k, v, mask)[:, 0]        # [B, Hq, D]

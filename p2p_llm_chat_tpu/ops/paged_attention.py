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

- :func:`paged_attention_select_append` — the decode tick of an INDEXED
  layer (models/nemotron_h.py's ``s``): index scores over the row's
  context from the layer's index keys (``PagedKVCache.idx``), each row's
  ``topk``-th score by bisection (no sort), and the selection handed as a
  mask to the two implementations below, whose softmax then runs over the
  selected rows alone. Beside it the selection's own pieces, each an XLA
  form everywhere and a Pallas kernel on the chip: :func:`index_scores`
  (a chunk's: ``index_scores``; a decode step's where the keys lie:
  ``index_scores_decode``), :func:`kth_largest`
  (``index_select_threshold``) and a chunk's attention under its
  selection (``select_attention_prefill``).

``paged_attention_append`` has two implementations, the same f32 softmax
over the same scores:

- :func:`_append_gather` — XLA: one joint (layer, page) gather of each
  row's whole ``[page_size, Hkv, D]`` pages (the token-major pool makes
  the window a pure reshape), scores, the merge. Runs everywhere; its
  cost follows the WINDOW (the gathered, dequantised copy is
  ``B x W x hd``).
- :func:`_paged_attention_flash_append` — a Pallas kernel, grid ``(row,
  chunk)``: each program DMAs one bounded chunk of pages (and scale
  rows) and folds it, a tile of half a chunk at a time, into
  online-softmax state held in VMEM scratch across the chunk axis; a
  tile that starts past its row's length is neither fetched nor folded,
  so its cost follows the rows' LENGTHS. HBM sees each live page once.
  TPU only.

The rule that chooses (:func:`_flash_append_policy`, guarded by
:func:`flash_append_blocked`): the kernel from a window of 256 tokens
(``_FLASH_MIN_W``) at every pool geometry, the gather below it and
wherever the kernel cannot run (no TPU, a pool sharded over a mesh, a
head_dim that does not fill 128 lanes, an int8 pool of fewer than 4 kv
heads). A function of the window alone, decided once per trace; a paired
pool's ``Hkv`` is its pairs and its ``head_dim`` 128, so neither refusal
meets it. Its measurement is PERF.md section 6, PR 56 (the table by
window, width, pool and occupancy; PR 31's rule, ``W >= max(256, 1024 *
1024 / max(hd, 1024))``, was set with a kernel that folded tokens x
heads), and ``python tools/check_append_kernel.py time`` measures it
again by calling the two implementations by name. The block verify
stays on the gather at every window (the kernel's state is seeded with
ONE current token).

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


def _gqa_block_mask(Hq: int, Hkv: int, D: int, rep: int):
    """bool [Hq, Hkv * D], built from in-register iotas for the
    flash-append kernel: a query head's row is true over its own kv
    head's D columns of a token's flattened ``[Hkv * D]`` row. Queries
    masked by it score all heads in ONE dot against a flattened K tile,
    and of ``p . V`` over a flattened V tile it keeps each head's own
    block."""
    HD = Hkv * D
    cdiv = jax.lax.broadcasted_iota(jnp.int32, (Hq, HD), 1) // D
    hdiv = jax.lax.broadcasted_iota(jnp.int32, (Hq, HD), 0) // rep
    return cdiv == hdiv


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
    if not blocked and _flash_append_policy(pages * cache.k.shape[2]):
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
                   page_table, lengths, layer, *, pages: int, scale=None,
                   keep=None, keep_cur=None):
    """:func:`paged_attention_append` in XLA: gather the window, score
    it, merge the current token's term. ``k_scale`` None = a bf16 pool.
    ``keep`` ([B, W] bool) and ``keep_cur`` ([B] bool): an indexed
    layer's selection, the window's positions and the current token a
    row's query reads; the others weigh nothing."""
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
    if keep is not None:
        scores = jnp.where(keep[:, None, None, None, :], scores, NEG_INF)
        s_cur = jnp.where(keep_cur[:, None, None, None, None], s_cur,
                          NEG_INF)

    m_w = jnp.max(scores, axis=-1, keepdims=True)            # [B,G,rep,1,1]
    m = jnp.maximum(m_w, s_cur)
    p = jnp.exp(scores - m)                                  # masked -> ~0
    p_cur = jnp.exp(s_cur - m)                               # > 0 always
    if keep is not None:        # ... but for a token that is not kept
        p_cur = jnp.where(keep_cur[:, None, None, None, None], p_cur, 0.0)
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


# -- an indexed layer: scores, the selection, attention under it ---------------

_SCORE_TQ, _SCORE_TK = 256, 512     # queries x keys a program scores


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_scores_kernel(qi, wi, ki, *, interpret: bool = False):
    """:func:`index_scores` of a chunk as a Pallas kernel, grid (row,
    query block, key block): a program multiplies its 256 queries of each
    head with its 512 keys on the MXU and folds ``w relu(.)`` into the
    [256, 512] float32 tile it writes, so the products of a head never
    reach HBM (head at a time in XLA they are written and read back: 3 GB
    a layer for 1,024 queries against 16 K keys). S a multiple of 256, T
    of 512."""
    B, S, Hi, Dp = qi.shape
    T = ki.shape[1]
    f32 = jnp.float32

    def body(q_ref, w_ref, k_ref, o_ref):
        keys = k_ref[0]                                   # [tk, Dp]
        acc = jnp.zeros((_SCORE_TQ, _SCORE_TK), f32)
        for h in range(Hi):
            dots = jax.lax.dot_general(
                q_ref[0, h], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=f32)               # [tq, tk]
            acc = acc + w_ref[0, h] * jnp.maximum(dots, 0.0)
        o_ref[0] = acc

    return pl.pallas_call(
        body, name="index_scores",
        grid=(B, S // _SCORE_TQ, T // _SCORE_TK),
        in_specs=[
            # Heads outermost: a head's queries are whole [tq, Dp] tiles,
            # and its weights a [tq, 1] column that spreads over lanes.
            pl.BlockSpec((1, Hi, _SCORE_TQ, Dp), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, Hi, _SCORE_TQ, 1), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, _SCORE_TK, Dp), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, _SCORE_TQ, _SCORE_TK),
                               lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, S, T), f32),
        interpret=interpret,
    )(jnp.swapaxes(qi, 1, 2),
      jnp.swapaxes(wi, 1, 2).astype(f32)[..., None], ki)


def index_scores(qi, wi, ki):
    """The indexer's scores ``sum_h w[.., h] relu(qi[.., h, :] . ki[s])``
    in float32. qi [B, S, Hi, Di], wi [B, S, Hi], ki [B, T, Di] ->
    [B, S, T]. The products of all heads in one batched matmul where they
    are small (a decode step: with one query a row the head is the
    matmul's row dimension; a dot a head is a multiply-reduce on the
    vector unit there, 3.4 ms a layer at 32 rows x 16 K, PERF.md section
    6, PR 49); for a chunk on the TPU the kernel
    (:func:`_index_scores_kernel`); else a head at a time, because the
    [Hi, S, T] products of all heads together are Hi times what is kept
    (a 1,024-query chunk against 16 K keys: 1 GB at 16 heads)."""
    B, S, Hi, _ = qi.shape
    T = ki.shape[1]
    f32 = jnp.float32
    if B * S * Hi * T <= 2 ** 26:
        dots = jnp.einsum("bshd,btd->bsht", qi, ki,
                          preferred_element_type=f32)
        return jnp.einsum("bsh,bsht->bst", wi.astype(f32),
                          jax.nn.relu(dots))
    if on_tpu() and S % _SCORE_TQ == 0 and T % _SCORE_TK == 0:
        return _index_scores_kernel(qi, wi, ki)

    def head(h, acc):
        q = jax.lax.dynamic_index_in_dim(qi, h, 2, keepdims=False)
        w = jax.lax.dynamic_index_in_dim(wi, h, 2, keepdims=False)
        dots = jnp.einsum("bsd,btd->bst", q, ki, preferred_element_type=f32)
        return acc + w.astype(f32)[..., None] * jax.nn.relu(dots)

    return jax.lax.fori_loop(0, Hi, head, jnp.zeros((B, S, T), f32))


_SCORE_CHUNK_PAGES = 16     # pages a program of the decode scores fetches


@functools.partial(jax.jit, static_argnames=("pages", "interpret"))
def _index_scores_decode_kernel(qi, wi, idx, page_table, lengths, layer, *,
                                pages: int, interpret: bool = False):
    """A decode step's index scores over the pool, where the keys lie:
    qi [B, Hi, Dp], wi [B, Hi], ``idx`` the index keys' pool [L, N, ps, 1,
    Dp] -> [B, pages x ps] float32, position ``s`` of a row its
    ``sum_h w[h] relu(qi[h] . kI[s])``; positions at or past a row's
    ``lengths`` are not computed (they read 0 where their chunk is, and
    what the buffer held where it is not: the caller masks them). The
    flash-append kernel's walk (:func:`_flash_append_kernel_body`): grid
    (row, chunk), a chunk of pages fetched by manual DMA into one of two
    VMEM slots while the chunk before it is scored, and a chunk that
    starts at or past its row's length neither fetched nor scored, so the
    work follows the rows' LENGTHS where XLA's gather of the window reads
    ``slots x window`` keys (6.1 ms of a 19.6 ms step at 14 rows of 9.3 K
    in 32 slots x 16 K: PERF.md section 6, PR 49)."""
    B, Hi, Dp = qi.shape
    L, N, ps = idx.shape[:3]
    f32 = jnp.float32
    cp = min(_SCORE_CHUNK_PAGES, pages)
    nc = -(-pages // cp)
    Ct = cp * ps
    keys = idx.reshape(L, N, ps, Dp)

    def body(pt_ref, len_ref, layer_ref, q_ref, w_ref, k_hbm, o_ref, kbuf,
             sems):
        b, c = pl.program_id(0), pl.program_id(1)
        ly = layer_ref[0]
        rows = pl.num_programs(0)

        def dma(slot, bb, cc, i: int):
            j = jnp.minimum(cc * cp + i, pages - 1)
            return pltpu.make_async_copy(k_hbm.at[ly, pt_ref[bb, j]],
                                         kbuf.at[slot, i], sems.at[slot, i])

        def holds_rows(bb, cc):
            return cc * Ct < len_ref[jnp.minimum(bb, rows - 1)]

        step = b * nc + c
        slot = jax.lax.rem(step, 2)

        @pl.when((step == 0) & holds_rows(b, c))
        def _warmup():
            for i in range(cp):
                dma(0, b, c, i).start()

        nb = jnp.where(c + 1 == nc, b + 1, b)
        ncn = jnp.where(c + 1 == nc, 0, c + 1)

        @pl.when((step + 1 < rows * nc) & holds_rows(nb, ncn))
        def _prefetch():
            for i in range(cp):
                dma(jax.lax.rem(step + 1, 2), nb, ncn, i).start()

        @pl.when(holds_rows(b, c))
        def _score():
            for i in range(cp):
                dma(slot, b, c, i).wait()
            dots = jax.lax.dot_general(
                q_ref[0], kbuf[slot].reshape(Ct, Dp),
                (((1,), (1,)), ((), ())), preferred_element_type=f32)
            o_ref[0] = jnp.sum(w_ref[0] * jnp.maximum(dots, 0.0), axis=0,
                               keepdims=True)                 # [1, Ct]

        @pl.when(jnp.logical_not(holds_rows(b, c)))
        def _none():
            o_ref[0] = jnp.zeros((1, Ct), f32)

    out = pl.pallas_call(
        body, name="index_scores_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # page_table, lengths, layer
            grid=(B, nc),
            in_specs=[
                pl.BlockSpec((1, Hi, Dp), lambda b, c, *_: (b, 0, 0)),
                # A head's weight as a column: it spreads over the lanes.
                pl.BlockSpec((1, Hi, 1), lambda b, c, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # the keys stay in HBM
            ],
            out_specs=pl.BlockSpec((1, 1, Ct), lambda b, c, *_: (b, 0, c)),
            scratch_shapes=[pltpu.VMEM((2, cp, ps, Dp), idx.dtype),
                            pltpu.SemaphoreType.DMA((2, cp))]),
        out_shape=jax.ShapeDtypeStruct((B, 1, nc * Ct), f32),
        interpret=interpret,
    )(page_table[:, :pages].astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qi.astype(idx.dtype),
      wi.astype(f32)[..., None], keys)
    return out[:, 0, : pages * ps]


_INT_MIN = -2 ** 31


def _ordered(bits: jax.Array) -> jax.Array:
    """int32 keys that order as the float32 values whose bits they are
    (a negative value's magnitude bits flipped; -0.0 just under +0.0);
    its own inverse."""
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _bisect(keys: jax.Array, k: int) -> jax.Array:
    """The ``k``-th largest of each row of int32 ``keys`` [R, T] as [R,
    1]: from the top bit down, 32 passes of compare and count. In the
    unsigned order a row's answer is built a bit at a time from 0; the
    keys are that order shifted by 2^31, so the search starts at INT_MIN
    and adds each bit (the first wraps INT_MIN to 0)."""
    def bit(i, found):
        cand = found + jnp.left_shift(jnp.int32(1), 31 - i)
        held = jnp.sum((keys >= cand).astype(jnp.float32), axis=-1,
                       keepdims=True)
        return jnp.where(held >= k, cand, found)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.full(keys.shape[:-1] + (1,), _INT_MIN, jnp.int32))


_SELECT_ROWS = 8        # rows a program of the threshold kernel holds


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _kth_largest_kernel(x, *, k: int, interpret: bool = False):
    """:func:`kth_largest` of [R, T] float32 as a Pallas kernel: a program
    holds ``_SELECT_ROWS`` rows whole in VMEM (512 KB at 16 K positions)
    and runs the 32 passes there, so HBM sees the scores once where XLA's
    loop reads them 32 times (2 GB a layer for a 1,024-query chunk
    against 16 K keys). R a multiple of 8, T of 128."""
    R, T = x.shape

    def body(x_ref, o_ref):
        keys = _ordered(jax.lax.bitcast_convert_type(x_ref[...], jnp.int32))
        found = _ordered(_bisect(keys, k))
        o_ref[...] = jnp.broadcast_to(
            jax.lax.bitcast_convert_type(found, jnp.float32),
            (_SELECT_ROWS, 128))

    return pl.pallas_call(
        body, name="index_select_threshold",
        grid=(R // _SELECT_ROWS,),
        in_specs=[pl.BlockSpec((_SELECT_ROWS, T), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((_SELECT_ROWS, 128), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 128), jnp.float32),
        interpret=interpret)(x)[:, 0]


def kth_largest(x: jax.Array, k: int) -> jax.Array:
    """The ``k``-th largest value of each row of float32 ``x`` [..., T]
    (k <= T), exactly, by bisection over the bits: 32 passes of compare
    and count, no sort, ending on a value that occurs in the row
    (:func:`_bisect`). No NaNs. On the TPU, at whole tiles, the passes
    run in VMEM (:func:`_kth_largest_kernel`)."""
    T = x.shape[-1]
    rows = x.reshape(-1, T)
    if on_tpu() and rows.shape[0] % _SELECT_ROWS == 0 and T % 128 == 0:
        return _kth_largest_kernel(rows, k=k).reshape(x.shape[:-1])
    keys = _ordered(jax.lax.bitcast_convert_type(rows, jnp.int32))
    return jax.lax.bitcast_convert_type(
        _ordered(_bisect(keys, k)), jnp.float32).reshape(x.shape[:-1])


def select_mask(scores: jax.Array, allowed: jax.Array, k: int) -> jax.Array:
    """[..., T] bool: of each row's ``allowed`` positions the ``k`` of
    largest ``scores`` (all of them where there are no more than ``k``),
    ties to the lower position: the set ``lax.top_k`` names, as a mask
    and without its sort (:func:`kth_largest`)."""
    T = scores.shape[-1]
    s = jnp.where(allowed, scores, -jnp.inf)
    if T <= k:
        return allowed
    thr = kth_largest(s, k)[..., None]
    above = s > thr
    tie = (s == thr) & allowed
    want = k - jnp.sum(above, axis=-1, keepdims=True)
    # Ties at the threshold, by position, as many as are still wanted:
    # nearly always the one position that IS the threshold, and then
    # every tie is kept and no running count is needed.
    return jax.lax.cond(
        jnp.all(jnp.sum(tie, axis=-1, keepdims=True) <= want),
        lambda: above | tie,
        lambda: above | (tie & (jnp.cumsum(tie, axis=-1) <= want)))


_ATTN_TQ, _ATTN_TK = 256, 512       # queries x keys a program attends


@functools.partial(jax.jit, static_argnames=("offset", "interpret"))
def select_attention_chunk(q, k, v, keep, *, offset: int,
                             interpret: bool = False):
    """A chunk's attention under its selection as a Pallas kernel: q [B,
    S, Hq, D] at positions ``offset..``, k and v [B, T, Hkv, D], ``keep``
    [B, S, T] bool (the selection, causal already) -> [B, S, Hq, D]. Grid
    (row, KV head, query block, key block), the key blocks innermost: a
    program multiplies the 256 queries of each of its KV head's query
    heads with 512 keys, sends what is not kept to NEG_INF by ONE [256,
    512] mask tile that all those heads share, and folds the tile into
    online-softmax state in VMEM scratch; probabilities never reach HBM.
    XLA's chunk scan under the same mask round-trips every [Hq, S, 1024]
    score tensor through HBM (84 ms of a 149 ms chunk at 9 K keys, 157 of
    255 at 16 K: PERF.md section 6, PR 49). A key block wholly past its
    query block's last position is not computed. S a multiple of 256, T of
    512, D of 128."""
    B, S, Hq, D = q.shape
    T, G = k.shape[1], k.shape[2]
    rep = Hq // G
    tq, tk = _ATTN_TQ, _ATTN_TK
    nk = T // tk
    f32 = jnp.float32
    qg = (q * jnp.asarray(D ** -0.5, q.dtype)).reshape(
        B, S, G, rep, D).transpose(0, 2, 3, 1, 4)        # [B,G,rep,S,D]
    kg, vg = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # [B,G,T,D]

    def body(q_ref, k_ref, v_ref, m_ref, o_ref, mx, lse, acc):
        i, j = pl.program_id(2), pl.program_id(3)

        @pl.when(j == 0)
        def _start():
            mx[...] = jnp.full(mx.shape, NEG_INF, f32)
            lse[...] = jnp.zeros(lse.shape, f32)
            acc[...] = jnp.zeros(acc.shape, f32)

        @pl.when(j * tk <= offset + (i + 1) * tq - 1)
        def _fold():
            kept = m_ref[0] != 0                              # [tq, tk]
            keys, vals = k_ref[0, 0], v_ref[0, 0]             # [tk, D]
            for r in range(rep):
                s = jax.lax.dot_general(
                    q_ref[0, 0, r], keys, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32)               # [tq, tk]
                s = jnp.where(kept, s, NEG_INF)
                m_prev = mx[r][:, :1]                         # [tq, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # exp(NEG_INF - NEG_INF) is 1, not 0.
                p = jnp.where(kept, jnp.exp(s - m_new), 0.0)
                lse[r] = jnp.broadcast_to(
                    lse[r][:, :1] * alpha
                    + jnp.sum(p, axis=1, keepdims=True), (tq, 128))
                acc[r] = acc[r] * alpha + jax.lax.dot(
                    p.astype(vals.dtype), vals, preferred_element_type=f32)
                mx[r] = jnp.broadcast_to(m_new, (tq, 128))

        @pl.when(j == nk - 1)
        def _finish():
            for r in range(rep):
                o_ref[0, 0, r] = (acc[r] / jnp.maximum(
                    lse[r][:, :1], 1e-30)).astype(o_ref.dtype)

    out = pl.pallas_call(
        body, name="select_attention_prefill",
        grid=(B, G, S // tq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, rep, tq, D), lambda b, g, i, j: (b, g, 0, i, 0)),
            pl.BlockSpec((1, 1, tk, D), lambda b, g, i, j: (b, g, j, 0)),
            pl.BlockSpec((1, 1, tk, D), lambda b, g, i, j: (b, g, j, 0)),
            pl.BlockSpec((1, tq, tk), lambda b, g, i, j: (b, i, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, tq, D),
                               lambda b, g, i, j: (b, g, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, rep, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((rep, tq, 128), f32),
                        pltpu.VMEM((rep, tq, 128), f32),
                        pltpu.VMEM((rep, tq, D), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qg, kg, vg, keep.astype(jnp.int8))
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D)


def select_attention_fits(S: int, T: int, D: int) -> bool:
    """Whether :func:`select_attention_chunk` takes a chunk of ``S``
    queries against ``T`` keys at a head of ``D``: on the TPU, at whole
    tiles."""
    return (on_tpu() and S % _ATTN_TQ == 0 and T % _ATTN_TK == 0
            and D % 128 == 0)


def paged_attention_select_append(q, k_cur, v_cur, qi, wi, ki_cur, cache,
                                  lengths, layer, *, pages: int, topk: int,
                                  sharded: bool = False):
    """Decode attention of an indexed layer, the step's own K, V and
    index key not yet in the pool (:func:`paged_attention_append`'s
    contract). q [B, Hq, D], k_cur / v_cur [B, Hkv, D]; qi [B, Hi, Dp]
    index queries, wi [B, Hi] their weights, ki_cur [B, Dp] the token's
    own index key, in the pool's lanes (``ModelConfig.cache_idx_dim``).
    Position ``s <= lengths`` of a row scores
    ``I[s] = sum_h wi[h] relu(qi[h] . ki[s])`` in float32 (the token's
    own position by ``ki_cur``); the ``min(lengths + 1, topk)`` positions
    of largest ``I`` (ties to the lower position) are read, and the
    softmax runs over them alone. Returns (out [B, Hq, D], keep [B, W]
    bool: the positions read, the token's own at ``lengths``).

    A window of no more than ``topk`` positions selects all of them: that
    is :func:`paged_attention_append`, and is what runs (the same
    function, decided once per trace from the window). Else the window's
    index keys are gathered and scored, each row's ``topk``-th score found
    by bisection (:func:`select_mask`: no sort), and the selection goes
    as a MASK into the two implementations that are there under the rule
    that is there: the flash-append kernel walks the row's pages and
    weighs what is not kept nothing, the gather does the same in XLA.
    Reading the selected rows where an index list names them (XLA's
    ``top_k``, a row-granular gather of 2,048 K and V rows a row a layer)
    was built first and measured: the gather moved 26 GB/s and a step
    took 130 ms, where the masked walk of three to eight times the bytes
    takes a tenth of it (PERF.md section 6, PR 49)."""
    B, Hq, D = q.shape
    L, N, ps, Hkv, _ = cache.k.shape
    W = pages * ps
    at = jnp.arange(W, dtype=jnp.int32)[None, :]
    allowed = at <= lengths[:, None]
    if W <= topk:
        return paged_attention_append(q, k_cur, v_cur, cache, lengths, layer,
                                      pages=pages, sharded=sharded), allowed
    Dp = cache.idx.shape[-1]            # one head: [L, N, ps, 1, Dp]
    dt = cache.idx.dtype
    qi4, wi3 = qi[:, None].astype(dt), wi[:, None]
    if on_tpu() and not sharded:
        pooled = _index_scores_decode_kernel(
            qi, wi, cache.idx, cache.page_table, lengths, layer, pages=pages)
    else:
        pt = layer * N + cache.page_table[:, :pages].astype(jnp.int32)
        ki = cache.idx.reshape(L * N, ps, Dp)[pt].reshape(B, W, Dp)
        pooled = index_scores(qi4, wi3, ki)[:, 0]
    # The token's own score goes in at its own position (the window
    # holds its key from the next step on, in the same dtype): into the
    # [B, W] scores, not into the gathered keys, whose every row a select
    # there would read and write again (4.4 ms a step at 32 x 16 K).
    own = index_scores(qi4, wi3, ki_cur.astype(dt)[:, None])[:, 0]
    scores = jnp.where(at == lengths[:, None], own, pooled)
    keep = select_mask(scores, allowed, topk)                   # [B, W]
    keep_cur = jnp.take_along_axis(
        keep, jnp.minimum(lengths, W - 1)[:, None], axis=1)[:, 0]
    in_pool = keep & (at < lengths[:, None])
    quantized = cache.k_scale is not None
    args = (q, k_cur, v_cur, cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.page_table, lengths, layer)
    blocked = flash_append_blocked(sharded, D, Hkv if quantized else 0)
    if not blocked and _flash_append_policy(W):
        out = _paged_attention_flash_append(
            *args, pages=pages, quantized=quantized, keep=in_pool,
            keep_cur=keep_cur)
    else:
        out = _append_gather(*args, pages=pages, keep=in_pool,
                             keep_cur=keep_cur)
    return out, keep


def kept_positions(keep: jax.Array, topk: int) -> jax.Array:
    """[B, W] bool -> [B, topk] int32: the kept positions in order, -1
    behind a row's last (what a reference check replays)."""
    W = keep.shape[-1]
    weight = jnp.where(keep, W - jnp.arange(W, dtype=jnp.int32), 0)
    top, _ = jax.lax.top_k(weight, min(topk, W))
    out = jnp.where(top > 0, W - top, -1)
    return jnp.pad(out, ((0, 0), (0, topk - out.shape[-1])),
                   constant_values=-1)


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
# slots 4 MB + a tile's bf16 view of K and of V (half a chunk: 2 MB)
# + f32 softmax state ~0.2 MB = 6.2 MB, under the 16 MB stack (8.2 MB
# until PR 54, which widens a tile where it widened a chunk).
# Module-level so tests
# can shrink it to exercise many-chunk grids in interpret mode at tiny
# geometries.
_FLASH_CHUNK_TOK_BYTES = 1024

# The Hkv * head_dim the chunk and tile budgets were calibrated at
# (llama-8B class: 8 kv heads x 128).
_FLASH_HD_REF = 1024

# The smallest window the kernel serves, at every pool geometry.
_FLASH_MIN_W = 256


def _flash_append_policy(window: int) -> bool:
    """The dispatch rule for the append path where the kernel can run
    (:func:`flash_append_blocked` is the guard), pure so CPU tests pin
    its table (tests/test_flash_append_geometry.py).

    Why a function of the window alone: the kernel's work follows the
    rows' lengths, the gather path's the window and every slot, so what
    decides is the FULL batch, where the kernel has least to skip; live
    rows are never read (a trace cannot see them). Measured on a v5e
    (PR 56, ``tools/check_append_kernel.py time``; PERF.md section 6 and
    docs/serving.md have the 80 points: W 128-2,048, hd 512 / 1,024 /
    2,048, int8 and bf16 pools, 32 slots and 64 at hd 512, full and 2
    live rows): from W 256 the kernel is level with the gather or ahead
    at a full batch on the int8 pool at every width (1.00-1.27x at W 256
    below hd 2,048, 3.1x there), 3% and 7% behind at W 256 on a bf16
    pool at hd 512 (32 and 64 slots; no configuration serves one) and
    ahead of it from W 512, and 1.5-8x ahead at 2 live rows; at W 128 a
    full batch LOSES at hd 512 (0.029 ms a layer-step against 0.037,
    0.047 against 0.064 at 64 slots: a launch a row of one short tile
    costs more than that gather) and is level at hd 1,024, so one window
    serves every geometry. Until PR 56 the rule was PR 31's, ``W >=
    max(256, 1024 * 1024 / max(hd, 1024))``, set with a kernel that
    folded tokens x heads and lost a full batch to the gather at W <=
    512 below hd 2,048; PR 54 rewrote the fold."""
    return window >= _FLASH_MIN_W


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


def effective_flash_min_w(sharded: bool = False, head_dim: int = 128,
                          int8_kv_heads: int = 0) -> int:
    """The smallest window the flash-append kernel serves as ONE number,
    for gauges and logs (serve/scheduler.py's ``paged_flash_min_w``):
    0 = the kernel cannot engage in this process for this pool
    (:func:`flash_append_blocked`), else :func:`_flash_append_policy`'s
    window."""
    if flash_append_blocked(sharded, head_dim, int8_kv_heads):
        return 0
    return _FLASH_MIN_W


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


# Bytes of one (k or v) buffer side per token a fold step takes of a
# fetched chunk AT THE CALIBRATION GEOMETRY, scaled as the chunk's are:
# 512 int8 tokens at hd = 1024, 256 at hd = 2048, 1,024 at hd = 512 —
# half a chunk. Module-level so tests can shrink it to put many tiles in
# a chunk at tiny geometries.
_FLASH_TILE_TOK_BYTES = 512

# What the kernel's fold leaves out, for tools/check_append_kernel.py
# ``time-fold`` alone (read at trace time; empty wherever anything is
# served): "convert" folds zeros where it would widen the fetched K and
# V, "dots" leaves both MXU dots out as well, "fold" waits for a tile's
# pages and does nothing with them.
_FOLD_WITHOUT: frozenset = frozenset()


def flash_append_tile_pages(hd: int, itemsize: int, page_size: int,
                            chunk_pages: int) -> int:
    """Pages a fold step takes of a chunk — a tile — for a pool of ``hd
    = Hkv * head_dim`` numbers a token, ``itemsize`` bytes each: the
    whole pages of _FLASH_TILE_TOK_BYTES' budget, and a divisor of the
    chunk. A tile is what a row's walk stops at and what a fold step
    pays its fixed cost for (0.4 us on a v5e: the MXU's fill and drain
    and the softmax's chain stand in line once a step), so its size is a
    trade between the positions folded past a row's end (half a tile a
    row) and the steps a row takes: measured at 16 MHA heads (hd 2,048)
    256 tokens serve 450-token rows best, at hd 1,024 and 512 (GQA) 512
    tokens serve 450-token and 13 K-token rows alike (PERF.md section 6,
    PR 54)."""
    most = max(1, _FLASH_TILE_TOK_BYTES * _FLASH_HD_REF
               // (hd * itemsize * page_size))
    return max(n for n in range(1, min(most, chunk_pages) + 1)
               if chunk_pages % n == 0)


def _flash_append_kernel_body(quantized: bool, page_size: int, pages: int,
                              chunk_pages: int, tile_pages: int,
                              num_chunks: int, rep: int, scale: float,
                              compute_dtype, masked: bool = False):
    """Build the multi-chunk flash-append kernel body: ONE program per
    (row, chunk) of a ``(B, num_chunks)`` grid — the split-K /
    flash-decoding shape (Dao et al.; the paged pool walk is vLLM
    PagedAttention's). The chunk axis is the grid's minor dimension, so
    for a fixed row the chunk programs run back to back and the
    online-softmax state (m, l, acc) lives in VMEM **scratch
    accumulators** that persist across them — VMEM holds one bounded
    chunk's pages, never a whole window (a whole-window scratch
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
    - **work follows the rows' lengths, a tile at a time**
      (``live_tiles``): a chunk is fetched and folded in tiles of
      :func:`flash_append_tile_pages` pages (half a chunk), and a tile
      that starts at or past its row's length is skipped WHOLE — the
      program before it does not fetch it, its own program neither waits
      for it nor folds it. A chunk with no live tile (a free row's every
      chunk, a short row's tail under a window some other row set) costs
      the seed (chunk 0) and the finalise (last chunk) alone, a fraction
      of a microsecond, which is what makes the window's size stop
      mattering; a row of 450 tokens under llama's 1,024-token chunk
      folds 512 positions. The fold is a loop over the row's live tiles
      that waits for a tile's pages where it folds them, so tile 0 is
      folded while the last tile's pages land.
    - **inside a tile that is folded** nothing is skipped: a page past
      the row's last is fetched through its table entry (0, the garbage
      page, by the pool contract) and masks to NEG_INF by position, and
      in a non-chunk-multiple window the page walk index clamps to
      ``pages - 1`` (a redundant re-fetch of the last real page).
      Skipping single page DMAs would leave uninitialised VMEM, which
      can be NaN, and a NaN row poisons the p.v dot even at zero
      probability.
    - **the scores lie heads x tokens** (``[Hq, tile]``): one dot of the
      block-masked queries ``[Hq, Hkv * D]`` (:func:`_gqa_block_mask`)
      against the tile's flattened keys, contracted over the keys' own
      minor axis (``q . K^T``, the keys being the MXU's stationary
      operand), so the softmax chain runs on full 128-lane rows, its
      state is a column a head, and ``p . V`` takes the probabilities
      as they lie: ``[Hq, tile] x [tile, Hkv * D]`` accumulates every
      head against every kv block in ``acc [Hq, Hkv * D]``, of which the
      finalise keeps each head's own block. (Until PR 54 the scores lay
      tokens x heads, 16 or 32 lanes of 128, and a live 512-token chunk
      of 16 heads cost 5.3 us where waiting for its pages costs 3.3:
      the table is in PERF.md section 6, PR 54.)
    - **int8 pools** (``quantized``): the per-page scale rows
      ([Hkv, ps_pad] f32, the head-major layout paged_kv.py stores for
      kernel DMAs) ride the same DMA slots and are the scores' own
      layout: a tile's pages side by side are ``[Hkv, tile]``, one row a
      kv head (``rep`` query heads read a row by a broadcast and a
      select; at ``rep`` 1 it is the row itself). k scales fold into the
      scores, v scales into the probabilities — the same
      fold-outside-the-dots contract as the gather path, so HBM sees
      int8 KV only.
    - ``compute_dtype``: bf16 on hardware (the MXU's preferred operand
      dtype; int8 -> bf16 is the cheap unpack), f32 in interpret mode so
      the CPU parity tests pin the kernel against the oracle at f32
      precision instead of bf16 rounding.
    - ``masked`` (an indexed layer's selection,
      :func:`paged_attention_select_append`): a fourth prefetched scalar
      a row says whether the current token is kept (the seed is then its
      term, else nothing), and a mask a position, laid out as the scales
      are (``[B, pages, Hkv, ps_pad]`` float32, a page's tile a DMA on
      the same slots), sends what is not kept to NEG_INF with the
      positions past the row's length; a position at NEG_INF weighs
      exactly 0, also where nothing has been kept yet.
    """
    Ct = chunk_pages * page_size
    Tt = tile_pages * page_size
    without = _FOLD_WITHOUT
    # A window of ONE chunk (every window under the chunk budget: where a
    # part-full batch decodes) starts and waits for its page DMAs in
    # loops the trace holds once; a longer window unrolls them a page.
    # Unrolled, every program that holds the kernel costs a boot 0.2 s a
    # page of its chunk to trace and lower; rolled, a FULL batch of a
    # narrow pool folds 6-13% slower (PERF.md section 6, PR 56, has both
    # tables), which is what the long windows' cells would pay.
    rolled = num_chunks == 1

    def body(*refs):
        # Prefetched scalars, inputs, the output, scratch: in that order,
        # each group with what its variant adds.
        refs = list(refs)
        pt_ref, len_ref, layer_ref = refs[:3]
        del refs[:3]
        ckeep_ref = refs.pop(0) if masked else None
        q_ref, kc_ref, vc_ref, k_hbm, v_hbm = refs[:5]
        del refs[:5]
        ks_hbm, vs_hbm = ((refs.pop(0), refs.pop(0)) if quantized
                          else (None, None))
        keep_hbm = refs.pop(0) if masked else None
        o_ref, kbuf, vbuf = refs[:3]
        del refs[:3]
        ksbuf, vsbuf = ((refs.pop(0), refs.pop(0)) if quantized
                        else (None, None))
        mbuf = refs.pop(0) if masked else None
        m_ref, l_ref, acc_ref, sems = refs
        b = pl.program_id(0)
        c = pl.program_id(1)
        ly = layer_ref[0]
        length = len_ref[b]

        def dma(slot, bb, cc, i):
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
            if masked:      # by (row, logical page), not by pool page
                copies.append(pltpu.make_async_copy(
                    keep_hbm.at[bb, j], mbuf.at[slot, i],
                    sems.at[len(copies), slot, i]))
            return copies

        # Global step index orders the whole grid's chunk walk; its
        # parity picks the DMA slot (num_chunks may be odd, so parity
        # must run THROUGH row boundaries, not reset per row — and
        # through skipped programs, which neither start nor wait on
        # their slot).
        step = b * num_chunks + c
        slot = jax.lax.rem(step, 2)
        rows = pl.num_programs(0)

        def live_tiles(bb, cc):
            # The tiles of chunk ``cc`` that start inside row ``bb``'s
            # context: the others are not fetched by the program before
            # them, not waited for and not folded (every position in
            # them would mask to NEG_INF and weigh exactly zero). The
            # issuer and the waiter read the same length — the FETCHED
            # row's, which at a row boundary is the next row's.
            left = len_ref[jnp.minimum(bb, rows - 1)] - cc * Ct
            return jax.lax.div(jnp.clip(left, 0, Ct) + (Tt - 1), Tt)

        def start_chunk(slot, bb, cc) -> None:
            live = live_tiles(bb, cc)
            if rolled:      # the live tiles are the chunk's first
                def start_page(i, carry):
                    for d in dma(slot, bb, cc, i):
                        d.start()
                    return carry

                jax.lax.fori_loop(0, live * tile_pages, start_page, 0)
                return
            for t in range(chunk_pages // tile_pages):
                @pl.when(t < live)
                def _start_tile():
                    for i in range(t * tile_pages, (t + 1) * tile_pages):
                        for d in dma(slot, bb, cc, i):
                            d.start()

        @pl.when(step == 0)
        def _warmup():
            start_chunk(0, b, c)

        # Prefetch the next chunk — the next row's chunk 0 at a row
        # boundary — before waiting on our own.
        nb = jnp.where(c + 1 == num_chunks, b + 1, b)
        nc = jnp.where(c + 1 == num_chunks, 0, c + 1)

        @pl.when(step + 1 < rows * num_chunks)
        def _prefetch():
            start_chunk(jax.lax.rem(step + 1, 2), nb, nc)

        q = q_ref[b].astype(jnp.float32)                 # [Hq, D]
        Hq, D = q.shape
        Hkv = Hq // rep
        HD = Hkv * D
        group = jax.lax.broadcasted_iota(jnp.int32, (Hq, 1), 0) // rep

        def heads(x):
            # kv-head rows -> query-head rows ([Hkv, n] -> [Hq, n]): a
            # row broadcast and a select a kv head, exact and off the
            # MXU (as an expander dot, float32 x float32, it cost a
            # GQA tile 0.2 us twice over; PERF.md section 6, PR 54).
            if rep == 1:
                return x
            out = jnp.zeros((Hq, x.shape[1]), x.dtype)
            for g in range(Hkv):
                out = jnp.where(group == g, x[g:g + 1], out)
            return out

        @pl.when(c == 0)
        def _seed():
            # Append init: state = the current token's softmax term at
            # FULL precision (p_cur = exp(s_cur - m) = 1 at m = s_cur).
            # State layout matches the fold's: m/l [Hq, 1], acc
            # [Hq, HD] with v_cur in every kv block (the finalise reads
            # a head's own). Unconditional: a row of length 0 returns
            # this term alone.
            kcur = heads(kc_ref[b].astype(jnp.float32))
            vcur = heads(vc_ref[b].astype(jnp.float32))
            s_cur = jnp.sum(q * kcur, axis=-1, keepdims=True) * scale
            ones = jnp.ones((Hq, 1), jnp.float32)
            acc = jnp.concatenate([vcur] * Hkv, axis=1)          # [Hq, HD]
            if masked:      # a current token that is not kept: nothing
                cur = ckeep_ref[b] > 0
                m_ref[:] = jnp.where(cur, s_cur, NEG_INF)
                l_ref[:] = jnp.where(cur, ones, 0.0)
                acc_ref[:] = jnp.where(cur, acc, 0.0)
            else:
                m_ref[:] = s_cur
                l_ref[:] = ones
                acc_ref[:] = acc

        live = live_tiles(b, c)

        @pl.when(live > 0)
        def _fold():
            # The queries, each over its own kv block of a flattened
            # row and zero elsewhere: [Hq, HD].
            q_rows = jnp.where(_gqa_block_mask(Hq, Hkv, D, rep),
                               jnp.concatenate([q] * Hkv, axis=1),
                               0.0).astype(compute_dtype)

            def flat(buf, p0):
                # A tile's pages as [Tt, HD] MXU operands.
                if "convert" in without:
                    return jnp.zeros((Tt, HD), compute_dtype)
                return buf[slot, pl.ds(p0, tile_pages)].reshape(
                    Tt, HD).astype(compute_dtype)

            def lanes(buf, p0):
                # A tile's scale (or mask) rows side by side, a row a
                # query head: [Hq, Tt].
                tile = buf[slot, pl.ds(p0, tile_pages)]
                return heads(jnp.concatenate(
                    [tile[i, :, :page_size] for i in range(tile_pages)],
                    axis=1))

            def fold_tile(t, carry):
                p0 = t * tile_pages

                def wait_page(i, carry=0):
                    for d in dma(slot, b, c, p0 + i):
                        d.wait()
                    return carry

                if rolled:
                    jax.lax.fori_loop(0, tile_pages, wait_page, 0)
                else:
                    for i in range(tile_pages):
                        wait_page(i)
                if "fold" in without:
                    return carry
                if "dots" in without:
                    s = jnp.zeros((Hq, Tt), jnp.float32)
                else:
                    s = jax.lax.dot_general(
                        q_rows, flat(kbuf, p0), (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                if quantized:
                    s = s * lanes(ksbuf, p0)
                pos = c * Ct + t * Tt + jax.lax.broadcasted_iota(
                    jnp.int32, (1, Tt), dimension=1)
                seen = pos < length
                if masked:
                    seen = seen & (lanes(mbuf, p0) > 0.5)
                s = jnp.where(seen, s, NEG_INF)                  # [Hq, Tt]

                m_prev = m_ref[:]                                # [Hq, 1]
                m_cur = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_cur)                  # [Hq, 1]
                probs = jnp.exp(s - m_cur)                       # [Hq, Tt]
                if masked:      # exp(NEG_INF - NEG_INF) is 1, not 0
                    probs = jnp.where(seen, probs, 0.0)
                # Denominator sums the UNSCALED probabilities (v scales
                # fold into the p.v dot only — the gather path's
                # contract).
                l_ref[:] = l_ref[:] * alpha + jnp.sum(probs, axis=1,
                                                      keepdims=True)
                if quantized:
                    probs = probs * lanes(vsbuf, p0)
                if "dots" in without:
                    pv = jnp.zeros((Hq, HD), jnp.float32)
                else:
                    pv = jax.lax.dot(probs.astype(compute_dtype),
                                     flat(vbuf, p0),
                                     preferred_element_type=jnp.float32)
                acc_ref[:] = acc_ref[:] * alpha + pv             # [Hq, HD]
                m_ref[:] = m_cur
                return carry

            jax.lax.fori_loop(0, live, fold_tile, 0)

        @pl.when(c == num_chunks - 1)
        def _finalise():
            # Of acc's [Hq, HD] each head's own kv block. l >= 1 always:
            # the current token's own term seeds it (a selection keeps
            # at least one position of a row).
            out = jnp.zeros((Hq, D), jnp.float32)
            for g in range(Hkv):
                out = jnp.where(group == g, acc_ref[:, g * D:(g + 1) * D],
                                out)
            o_ref[b] = (out / l_ref[:]).astype(o_ref.dtype)

    return body


@functools.partial(jax.jit, static_argnames=("pages", "quantized",
                                             "interpret", "scale"))
def _paged_attention_flash_append(q, k_cur, v_cur, k_pages, v_pages,
                                  k_scale, v_scale, page_table, lengths,
                                  layer, *, pages: int, quantized: bool,
                                  interpret: bool = False, scale=None,
                                  keep=None, keep_cur=None):
    """Multi-chunk flash-append dispatch: grid ``(B, num_chunks)``, one
    bounded chunk of manually-DMA'd pages (and scale rows) per program,
    online softmax carried in VMEM scratch across the chunk axis and
    seeded with the current token (_flash_append_kernel_body). HBM reads
    each page exactly once per (layer, step) — no gathered-window
    materialisation — and only the pages of tiles that start inside
    their row's context (chunks since PR 31, tiles since PR 54).
    ``interpret`` runs it on the CPU, with f32 dot operands, for
    hardware-free parity tests. ``keep`` ([B, W] bool) and ``keep_cur``
    ([B] bool): an indexed layer's selection, the kernel's ``masked``
    variant."""
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

    masked = keep is not None

    def whole(b, c, *prefetched):
        return (0, 0, 0)

    in_specs = [
        # The step's queries and current tokens, every row's, fetched
        # once and held: a block a row is a DMA a program and a wait
        # on it, which was most of what an empty program cost.
        pl.BlockSpec((B, Hq, D), whole),
        pl.BlockSpec((B, Hkv, D), whole),
        pl.BlockSpec((B, Hkv, D), whole),
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
    prefetched = [pt, lengths.astype(jnp.int32), layer]
    if masked:
        # A page's mask as a tile of the scales' shape, so that it
        # reaches the scores by the path their scales take.
        ps_pad = -(-page_size // 128) * 128
        tiles = jnp.pad(keep.reshape(B, pages, 1, page_size),
                        ((0, 0), (0, 0), (0, 0), (0, ps_pad - page_size)))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(jnp.broadcast_to(
            tiles, (B, pages, Hkv, ps_pad)).astype(jnp.float32))
        scratch.append(pltpu.VMEM((2, chunk_pages, Hkv, ps_pad),
                                  jnp.float32))
        n_sem += 1
        prefetched.append(keep_cur.astype(jnp.int32))
    # Cross-chunk online-softmax state (persists across the grid's
    # chunk axis; re-seeded at every row's chunk 0).
    scratch += [
        pltpu.VMEM((Hq, 1), jnp.float32),       # running max m
        pltpu.VMEM((Hq, 1), jnp.float32),       # running sum l
        pltpu.VMEM((Hq, Hkv * D), jnp.float32),  # unnormalised acc
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # page_table, lengths, layer (and whether the token is kept)
        num_scalar_prefetch=len(prefetched),
        grid=(B, num_chunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, Hq, D), whole),
        scratch_shapes=scratch + [
            pltpu.SemaphoreType.DMA((n_sem, 2, chunk_pages))],
    )
    tile_pages = flash_append_tile_pages(
        Hkv * D, k_pages.dtype.itemsize, page_size, chunk_pages)
    kernel = _flash_append_kernel_body(
        quantized, page_size, pages, chunk_pages, tile_pages, num_chunks,
        rep, scale, compute_dtype, masked)
    common = dict(grid_spec=grid_spec, interpret=interpret,
                  out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype))
    # A kernel's name is a literal where it is called (the trace's
    # reduction finds it by that name; tests/test_loop_phases.py).
    if masked:
        call = pl.pallas_call(
            kernel, name="paged_attention_flash_append_masked", **common)
    else:
        call = pl.pallas_call(kernel, name="paged_attention_flash_append",
                              **common)
    return call(*prefetched, *operands)


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

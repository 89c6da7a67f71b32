"""Latent attention (MLA) over the caches models/pangu.py keeps: one
normed latent row ``c`` [r] and one rotated key ``k_rope`` a token,
shared by every query head.

Two ops, each a Pallas kernel on the TPU and an XLA path everywhere
else (and for shapes the kernel does not take), both held to the same
oracles in tests/test_mla_attention.py:

- :func:`mla_prefill_attention` (kernel ``mla_prefill_attention``): the
  EXPANDED form. Per head, q.k is ``dn + dr`` wide (the head's own
  ``k_nope`` and the shared ``k_rope``) and v is ``dv`` wide; a block of
  queries at positions ``offset + i`` attends the ``offset + S`` context
  rows causally. A flash kernel: grid (row, head group, query block, key
  block), online softmax in VMEM scratch, key blocks past a query
  block's diagonal neither fetched again nor computed.
- :func:`mla_decode_attention` (kernel ``mla_decode_attention``): the
  ABSORBED form. One query token a row; every head's ``q_lat`` [r] and
  ``q_rope`` score the SAME latent rows of the paged pool, and the value
  is the latent row itself (no V pages): scores over ``r + dr`` numbers,
  values over the first ``r`` of the same row. The walk is
  ops/paged_attention's flash-append: grid (row, chunk of pages), pages
  DMA'd by hand from the pool in HBM, double-buffered across programs,
  the current token (not yet in the pool) seeding the online softmax.
  A cached row (584 bytes the algorithm needs, 640 + 2 scales read) is
  read once and used by all 128 heads: 128 x 2 x (576 + 512) FLOP for
  it, about 400 a byte against the v5e's ridge of 240, so from a few
  hundred rows of context on the MXU bounds this kernel and not the
  pool's bytes.

The pool is ops/paged_kv.PagedKVCache with ``k`` = c pages [L, N, ps, 1,
r] and ``v`` = k_rope pages [L, N, ps, 1, dr padded to 128 lanes] (zero
lanes behind the real ones; the queries' pad lanes are zero too), int8
with one float32 scale a token each, or bf16.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import on_tpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))      # [M,K] x [N,K] -> [M,N]

# Heads a prefill program handles a grid step, query rows and key rows a
# block, and the tokens a decode program folds a grid step.
_PREFILL_HEADS = 4
_PREFILL_BQ = 256
_PREFILL_BK = 512
_DECODE_CHUNK_TOKENS = 512


# -- prefill (expanded form) --------------------------------------------------

def mla_prefill_reference(q_nope, q_rope, kv, k_rope, offset: int, *,
                          dn: int, dr: int, dv: int) -> jax.Array:
    """The XLA path and the oracle. q_nope [B,S,Hq,dn]; q_rope
    [B,S,Hq,dr]; kv [B,W,Hq*(dn+dv)] (head-major, each head's k_nope
    before its v); k_rope [B,W,>=dr]; W = offset + S. Returns
    [B,S,Hq*dv] in q_nope's dtype, softmax in float32."""
    B, S, Hq, _ = q_nope.shape
    W = kv.shape[1]
    kv = kv.reshape(B, W, Hq, dn + dv)
    s = jnp.einsum("bshd,bthd->bhst", q_nope, kv[..., :dn],
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bshd,btd->bhst", q_rope, k_rope[..., :dr],
                       preferred_element_type=jnp.float32)
    s = s * (dn + dr) ** -0.5
    qpos = offset + jnp.arange(S)[:, None]
    s = jnp.where(jnp.arange(W)[None, :] <= qpos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", p.astype(kv.dtype), kv[..., dn:],
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, Hq * dv).astype(q_nope.dtype)


def _prefill_kernel(qn_ref, qr_ref, kv_ref, kr_ref, o_ref, m_ref, l_ref,
                    acc_ref, *, offset: int, bq: int, bk: int, hb: int,
                    d: int, sm_scale: float):
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    # The last key block a row of this query block may see.
    last = (offset + (qi + 1) * bq - 1) // bk

    @pl.when(kj <= last)
    def _fold():
        kr = kr_ref[0]                                         # [bk, d]
        qpos = offset + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        seen = kpos <= qpos
        for i in range(hb):
            qn = qn_ref[0, :, i * d:(i + 1) * d]               # [bq, d]
            qr = qr_ref[0, :, i * d:(i + 1) * d]
            kn = kv_ref[0, :, 2 * i * d:(2 * i + 1) * d]       # [bk, d]
            v = kv_ref[0, :, (2 * i + 1) * d:(2 * i + 2) * d]
            s = jax.lax.dot_general(qn, kn, _NT,
                                    preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(qr, kr, _NT,
                                        preferred_element_type=jnp.float32)
            s = jnp.where(seen, s * sm_scale, NEG_INF)         # [bq, bk]
            m_prev = m_ref[i]                                  # [bq, d]
            m_cur = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur[:, :1])
            l_ref[i] = l_ref[i] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[i] = acc_ref[i] * alpha + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[i] = m_cur

    @pl.when(kj == pl.num_programs(3) - 1)
    def _done():
        for i in range(hb):
            o_ref[0, :, i * d:(i + 1) * d] = (
                acc_ref[i] / l_ref[i]).astype(o_ref.dtype)


def _prefill_blocks(S: int, W: int, Hq: int) -> Optional[tuple]:
    """(bq, bk, hb) the kernel runs these sizes with, or None."""
    bq = min(_PREFILL_BQ, S)
    bk = next((b for b in (_PREFILL_BK, 256, 128) if W % b == 0), None)
    if W < 128:
        bk = W if W % 8 == 0 else None
    hb = next((h for h in (_PREFILL_HEADS, 2, 1) if Hq % h == 0), None)
    if bk is None or S % bq or bq % 8:
        return None
    return bq, bk, hb


def _mla_prefill_kernel_call(q_nope, q_rope, kv, k_rope, offset: int, *,
                             dn: int, dr: int, blocks: tuple,
                             interpret: bool):
    B, S, Hq, _ = q_nope.shape
    W = kv.shape[1]
    d = dn
    bq, bk, hb = blocks
    qn = q_nope.reshape(B, S, Hq * d)
    qr = jnp.pad(q_rope, ((0, 0), (0, 0), (0, 0), (0, d - dr))
                 ).reshape(B, S, Hq * d)
    kr = k_rope
    if kr.shape[-1] != d:
        kr = jnp.pad(kr[..., :dr], ((0, 0), (0, 0), (0, d - dr)))

    def last_block(qi):
        return (offset + (qi + 1) * bq - 1) // bk

    return pl.pallas_call(
        functools.partial(_prefill_kernel, offset=offset, bq=bq, bk=bk,
                          hb=hb, d=d, sm_scale=(dn + dr) ** -0.5),
        name="mla_prefill_attention",
        grid=(B, Hq // hb, S // bq, W // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hb * d), lambda b, h, qi, kj: (b, qi, h)),
            pl.BlockSpec((1, bq, hb * d), lambda b, h, qi, kj: (b, qi, h)),
            pl.BlockSpec((1, bk, 2 * hb * d), lambda b, h, qi, kj: (
                b, jnp.minimum(kj, last_block(qi)), h)),
            pl.BlockSpec((1, bk, d), lambda b, h, qi, kj: (
                b, jnp.minimum(kj, last_block(qi)), 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hb * d),
                               lambda b, h, qi, kj: (b, qi, h)),
        out_shape=jax.ShapeDtypeStruct((B, S, Hq * d), q_nope.dtype),
        scratch_shapes=[pltpu.VMEM((hb, bq, d), jnp.float32),
                        pltpu.VMEM((hb, bq, d), jnp.float32),
                        pltpu.VMEM((hb, bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qn, qr, kv, kr)


def mla_prefill_attention(q_nope, q_rope, kv, k_rope, offset: int, *,
                          dn: int, dr: int, dv: int,
                          interpret: bool = False,
                          impl: Optional[str] = None) -> jax.Array:
    """Causal expanded-form attention of S queries at positions
    ``offset + i`` over ``W = offset + S`` context rows
    (:func:`mla_prefill_reference` has the shapes). ``impl``: ``kernel``
    | ``xla`` | None = the kernel on the TPU where it takes the shape
    (nope and v widths one 128-lane tile, rope no wider, block-divisible
    S and W), XLA otherwise."""
    B, S, Hq, _ = q_nope.shape
    W = kv.shape[1]
    blocks = (_prefill_blocks(S, W, Hq)
              if dn == dv and dn % 128 == 0 and dr <= dn else None)
    if impl is None:
        impl = "kernel" if (on_tpu() and not interpret
                            and blocks is not None) else "xla"
    if impl == "kernel":
        if blocks is None:
            raise ValueError(f"mla_prefill_attention's kernel does not "
                             f"take S={S} W={W} dn={dn} dr={dr} dv={dv}")
        return _mla_prefill_kernel_call(q_nope, q_rope, kv, k_rope, offset,
                                        dn=dn, dr=dr, blocks=blocks,
                                        interpret=interpret)
    return mla_prefill_reference(q_nope, q_rope, kv, k_rope, offset,
                                 dn=dn, dr=dr, dv=dv)


# -- decode (absorbed form) ---------------------------------------------------

def _window_scales(cache, pt, layer):
    """The window's per-token scales [B, pages*ps] of the c and k_rope
    pages (None, None for a bf16 pool): whole [1, ps_pad] tiles gathered
    on the page dimension, lanes past the page size cut."""
    if cache.k_scale is None:
        return None, None
    ps = cache.page_size
    B, pages = pt.shape

    def take(arr):
        return arr[layer, pt][:, :, 0, :ps].reshape(B, pages * ps)
    return take(cache.k_scale), take(cache.v_scale)


def mla_block_reference(q_lat, q_rope, c_blk, r_blk, cache, lengths, layer,
                        *, pages: int, sm_scale: float) -> jax.Array:
    """The XLA path and the oracle, for a block of S query positions a
    row (decode: S = 1; a session wake: a suffix). q_lat [B,S,Hq,r];
    q_rope [B,S,Hq,vd] (zero behind the real lanes); c_blk [B,S,r],
    r_blk [B,S,vd]: the block's own latents, not yet in the pool; cache:
    the PagedKVCache; lengths [B]: rows already in the pool. Position j
    attends the pool's rows below ``lengths`` (gathered by whole pages,
    dequantised) and the block's positions i <= j at full precision, in
    one float32 softmax. Returns [B,S,Hq,r] float32."""
    pt = cache.page_table[:, :pages]
    B, S = q_lat.shape[:2]
    W = pages * cache.page_size
    cw = cache.k[layer, pt].reshape(B, W, -1)
    rw = cache.v[layer, pt].reshape(B, W, -1)
    sc, sr = _window_scales(cache, pt, layer)
    f32 = jnp.float32
    s = jnp.einsum("bshr,btr->bhst", q_lat, cw.astype(q_lat.dtype),
                   preferred_element_type=f32)
    s_r = jnp.einsum("bshd,btd->bhst", q_rope, rw.astype(q_rope.dtype),
                     preferred_element_type=f32)
    if sc is not None:
        s = s * sc[:, None, None, :]
        s_r = s_r * sr[:, None, None, :]
    s = (s + s_r) * sm_scale
    s = jnp.where(jnp.arange(W)[None, None, None, :]
                  < lengths[:, None, None, None], s, NEG_INF)
    s_blk = (jnp.einsum("bshr,bir->bhsi", q_lat.astype(f32),
                        c_blk.astype(f32))
             + jnp.einsum("bshd,bid->bhsi", q_rope.astype(f32),
                          r_blk.astype(f32))) * sm_scale
    s_blk = jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None],
                      s_blk, NEG_INF)                      # i <= j
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True),
                    jnp.max(s_blk, axis=-1, keepdims=True))
    p = jnp.exp(s - m)
    p_blk = jnp.exp(s_blk - m)
    den = (jnp.sum(p, axis=-1, keepdims=True)
           + jnp.sum(p_blk, axis=-1, keepdims=True))
    if sc is not None:
        p = p * sc[:, None, None, :]
    num = jnp.einsum("bhst,btr->bhsr", p.astype(q_lat.dtype),
                     cw.astype(q_lat.dtype), preferred_element_type=f32)
    num = num + jnp.einsum("bhsi,bir->bhsr", p_blk, c_blk.astype(f32))
    return (num / den).transpose(0, 2, 1, 3)


def mla_decode_reference(q_lat, q_rope, c_cur, r_cur, cache, lengths, layer,
                         *, pages: int, sm_scale: float) -> jax.Array:
    """:func:`mla_block_reference` for one query token a row: q_lat
    [B,Hq,r], q_rope [B,Hq,vd], c_cur [B,r], r_cur [B,vd] -> [B,Hq,r]."""
    return mla_block_reference(
        q_lat[:, None], q_rope[:, None], c_cur[:, None], r_cur[:, None],
        cache, lengths, layer, pages=pages, sm_scale=sm_scale)[:, 0]


def _decode_kernel_body(quantized: bool, page_size: int, pages: int,
                        chunk_pages: int, num_chunks: int, sm_scale: float,
                        compute_dtype):
    """ops/paged_attention._flash_append_kernel_body for one shared
    latent head: one program a (row, chunk) of a (B, num_chunks) grid;
    the chunk's c and k_rope pages DMA'd by hand into 2-slot scratch,
    the next program's issued before this one's are waited on; (m, l,
    acc) in VMEM scratch across the chunk axis, seeded at chunk 0 with
    the current token's term; page indices past the window clamp to its
    last page (fetched again, masked); a chunk that starts at or past
    its row's length is skipped whole (no fetch by the program before
    it, no wait, no fold). Scores sit as [Hq, Ct] (tokens on
    lanes), so an int8 pool's per-token scales, gathered outside as rows
    [1, Ct], fold in by a broadcast: c's into its scores and into the
    probabilities that weigh the values, k_rope's into its scores."""
    Ct = chunk_pages * page_size

    def body(*refs):
        if quantized:
            (pt_ref, len_ref, layer_ref, ql_ref, qr_ref, cc_ref, rc_ref,
             sc_ref, sr_ref, c_hbm, r_hbm, o_ref, cbuf, rbuf, m_ref, l_ref,
             acc_ref, sems) = refs
        else:
            (pt_ref, len_ref, layer_ref, ql_ref, qr_ref, cc_ref, rc_ref,
             c_hbm, r_hbm, o_ref, cbuf, rbuf, m_ref, l_ref, acc_ref,
             sems) = refs
            sc_ref = sr_ref = None
        b, c = pl.program_id(0), pl.program_id(1)
        ly = layer_ref[0]
        length = len_ref[b]

        def dma(slot, bb, cc, i: int):
            j = jnp.minimum(cc * chunk_pages + i, pages - 1)
            page = pt_ref[bb, j]
            return [
                pltpu.make_async_copy(c_hbm.at[ly, page], cbuf.at[slot, i],
                                      sems.at[0, slot, i]),
                pltpu.make_async_copy(r_hbm.at[ly, page], rbuf.at[slot, i],
                                      sems.at[1, slot, i]),
            ]

        def start_chunk(slot, bb, cc) -> None:
            for i in range(chunk_pages):
                for d in dma(slot, bb, cc, i):
                    d.start()

        def wait_chunk(slot, bb, cc) -> None:
            for i in range(chunk_pages):
                for d in dma(slot, bb, cc, i):
                    d.wait()

        step = b * num_chunks + c
        slot = jax.lax.rem(step, 2)
        rows = pl.num_programs(0)

        def holds_rows(bb, cc):
            # A chunk past its row's length (a parked row's every chunk,
            # a short row's tail) is neither fetched nor folded: at the
            # benchmark's 2-3 live rows of 32 that is nine programs in
            # ten.
            return cc * Ct < len_ref[jnp.minimum(bb, rows - 1)]

        @pl.when((step == 0) & holds_rows(b, c))
        def _warmup():
            start_chunk(0, b, c)

        nb = jnp.where(c + 1 == num_chunks, b + 1, b)
        nc = jnp.where(c + 1 == num_chunks, 0, c + 1)

        @pl.when((step + 1 < rows * num_chunks) & holds_rows(nb, nc))
        def _prefetch():
            start_chunk(jax.lax.rem(step + 1, 2), nb, nc)

        ql = ql_ref[0]                                       # [Hq, r]
        qr = qr_ref[0]                                       # [Hq, vd]

        @pl.when(c == 0)
        def _seed():
            cc = cc_ref[0].astype(jnp.float32)               # [1, r]
            rc = rc_ref[0].astype(jnp.float32)               # [1, vd]
            s_cur = (jnp.sum(ql.astype(jnp.float32) * cc, axis=-1,
                             keepdims=True)
                     + jnp.sum(qr.astype(jnp.float32) * rc, axis=-1,
                               keepdims=True)) * sm_scale    # [Hq, 1]
            m_ref[:] = jnp.broadcast_to(s_cur, m_ref.shape)
            l_ref[:] = jnp.ones(l_ref.shape, jnp.float32)
            acc_ref[:] = jnp.broadcast_to(cc, acc_ref.shape)

        @pl.when(holds_rows(b, c))
        def _fold():
            wait_chunk(slot, b, c)
            cflat = cbuf[slot].reshape(Ct, cbuf.shape[-1]).astype(
                compute_dtype)
            rflat = rbuf[slot].reshape(Ct, rbuf.shape[-1]).astype(
                compute_dtype)
            s = jax.lax.dot_general(ql.astype(compute_dtype), cflat, _NT,
                                    preferred_element_type=jnp.float32)
            s_r = jax.lax.dot_general(qr.astype(compute_dtype), rflat, _NT,
                                      preferred_element_type=jnp.float32)
            if quantized:
                sc = sc_ref[0]                               # [1, Ct]
                s = s * sc + s_r * sr_ref[0]
            else:
                s = s + s_r
            pos = c * Ct + jax.lax.broadcasted_iota(jnp.int32, (1, Ct), 1)
            s = jnp.where(pos < length, s * sm_scale, NEG_INF)   # [Hq, Ct]

            m_prev = m_ref[:]                                # [Hq, 128]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur[:, :1])
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * sc
            acc_ref[:] = acc_ref[:] * alpha[:, :1] + jax.lax.dot(
                p.astype(compute_dtype), cflat,
                preferred_element_type=jnp.float32)          # [Hq, r]
            m_ref[:] = m_cur

        @pl.when(c == num_chunks - 1)
        def _finalise():
            o_ref[0] = (acc_ref[:] / l_ref[:][:, :1]).astype(o_ref.dtype)

    return body


@functools.partial(jax.jit, static_argnames=("pages", "sm_scale",
                                             "interpret"))
def _mla_decode_kernel_call(q_lat, q_rope, c_cur, r_cur, c_pages, r_pages,
                            c_scale, r_scale, page_table, lengths, layer, *,
                            pages: int, sm_scale: float, interpret: bool):
    B, Hq, r = q_lat.shape
    vd = q_rope.shape[-1]
    L, N, ps = c_pages.shape[:3]
    quantized = c_scale is not None
    pt = page_table[:, :pages].astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    chunk_pages = max(1, min(pages, _DECODE_CHUNK_TOKENS // ps))
    num_chunks = -(-pages // chunk_pages)
    Ct = chunk_pages * ps
    compute_dtype = jnp.float32 if interpret else jnp.bfloat16

    row = lambda b, c, pt, ln, ly: (b, 0, 0)     # noqa: E731
    in_specs = [pl.BlockSpec((1, Hq, r), row), pl.BlockSpec((1, Hq, vd), row),
                pl.BlockSpec((1, 1, r), row), pl.BlockSpec((1, 1, vd), row)]
    operands = [q_lat, q_rope, c_cur[:, None, :], r_cur[:, None, :]]
    if quantized:
        # The window's scales as rows, the clamped re-fetches of its
        # last page included (they are masked by position).
        walk = jnp.minimum(jnp.arange(num_chunks * chunk_pages), pages - 1)

        def rows(arr):
            tiles = arr[layer[0], pt[:, walk]]       # [B, P, 1, ps_pad]
            return tiles[:, :, 0, :ps].reshape(B, 1, num_chunks * Ct)
        chunk = lambda b, c, pt, ln, ly: (b, 0, c)   # noqa: E731
        in_specs += [pl.BlockSpec((1, 1, Ct), chunk),
                     pl.BlockSpec((1, 1, Ct), chunk)]
        operands += [rows(c_scale), rows(r_scale)]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
    # One head: the pool's [.., ps, 1, d] pages are [.., ps, d] tiles.
    operands += [c_pages.reshape(L, N, ps, r), r_pages.reshape(L, N, ps, vd)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, num_chunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, r), row),
        scratch_shapes=[
            pltpu.VMEM((2, chunk_pages, ps, r), c_pages.dtype),
            pltpu.VMEM((2, chunk_pages, ps, vd), r_pages.dtype),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, r), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2, chunk_pages)),
        ],
    )
    return pl.pallas_call(
        _decode_kernel_body(quantized, ps, pages, chunk_pages, num_chunks,
                            sm_scale, compute_dtype),
        name="mla_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, r), jnp.float32),
        interpret=interpret,
    )(pt, lengths.astype(jnp.int32), layer, *operands)


def mla_decode_attention(q_lat, q_rope, c_cur, r_cur, cache, lengths, layer,
                         *, pages: int, sm_scale: float,
                         interpret: bool = False,
                         impl: Optional[str] = None) -> jax.Array:
    """Absorbed-form decode attention over the paged latent pool, the
    current token merged in (:func:`mla_decode_reference` has the shapes
    and the semantics). ``impl``: ``kernel`` | ``xla`` | None = the
    kernel on the TPU where the pool's tiles are lane-aligned, XLA
    otherwise."""
    ps = cache.k.shape[2]
    aligned = (q_lat.shape[-1] % 128 == 0 and q_rope.shape[-1] % 128 == 0
               and _DECODE_CHUNK_TOKENS % ps == 0 and ps % 32 == 0)
    if impl is None:
        impl = "kernel" if (on_tpu() and not interpret and aligned) else "xla"
    if impl == "kernel":
        return _mla_decode_kernel_call(
            q_lat, q_rope, c_cur, r_cur, cache.k, cache.v, cache.k_scale,
            cache.v_scale, cache.page_table, lengths, layer, pages=pages,
            sm_scale=float(sm_scale), interpret=interpret)
    return mla_decode_reference(q_lat, q_rope, c_cur, r_cur, cache, lengths,
                                layer, pages=pages, sm_scale=sm_scale)

"""Shared transformer layer primitives (functional, TPU-first).

Conventions:
- activations flow in ``compute_dtype`` (bfloat16 by default — MXU-native);
  normalisation statistics and attention softmax run in float32.
- weights are stored as ``[in, out]`` so matmuls are ``x @ w`` (lands on the
  MXU with the contraction on the last axis, XLA's preferred layout).
- KV cache layout is ``[batch, max_seq, kv_heads, head_dim]`` — sequential
  writes at the position axis are contiguous and the decode attention
  contraction reads it without transposition.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.device import on_tpu
from .configs import ModelConfig, RopeScaling
from .quant import mm

DEFAULT_COMPUTE_DTYPE = jnp.bfloat16

# A large-negative constant for masking that is safe in bf16/f32 softmax.
NEG_INF = -1e9


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in float32, result cast back to x.dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * scale.astype(jnp.float32)).astype(x.dtype)


def yarn_ramp(s: RopeScaling, theta: float, d: int) -> tuple:
    """(low, high) of YaRN's ramp over the ``d / 2`` frequency indices:
    the indices whose frequency turns ``beta_fast`` / ``beta_slow`` times
    over the original context, floor / ceil (the published ``truncate``),
    clamped to [0, d - 1]."""
    def turns(n: float) -> float:
        return (d * math.log(s.original_max_position / (n * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(turns(s.beta_fast)), 0),
            min(math.ceil(turns(s.beta_slow)), d - 1))


def rope_table(config: ModelConfig, window: bool = False) -> tuple:
    """(inverse frequencies [head_dim/2], the factor on cos and sin) of
    one kind of layer: ``window`` layers rotate by the plain table,
    the others by ``config.rope_scaling``'s rule (ModelConfig says why
    a model has two)."""
    s = None if window else config.rope_scaling
    if s is None or s.kind != "yarn":
        return rope_frequencies(config.with_(rope_scaling=s)), 1.0
    d = config.head_dim
    plain = rope_frequencies(config.with_(rope_scaling=None))
    low, high = yarn_ramp(s, config.rope_theta, d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return ((1.0 - ramp) * plain + ramp * plain / s.factor,
            s.attention_factor or 0.1 * math.log(s.factor) + 1.0)


def rope_frequencies(config: ModelConfig) -> jax.Array:
    """Inverse frequencies [head_dim/2], with llama3.1 NTK-by-parts scaling
    applied when configured (a YaRN rule comes with a factor on cos and
    sin: :func:`rope_table`)."""
    d = config.head_dim
    inv_freq = 1.0 / (config.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    s = config.rope_scaling
    if s is None:
        return inv_freq
    if s.kind != "llama3":
        raise ValueError(f"{config.name}: the {s.kind!r} rule has a factor "
                         "on cos and sin beside its table: call rope_table")
    # llama3.1 scaling: low-frequency components are slowed by `factor`,
    # high-frequency kept, a smooth ramp in between.
    low_wavelen = s.original_max_position / s.low_freq_factor
    high_wavelen = s.original_max_position / s.high_freq_factor
    wavelen = 2.0 * jnp.pi / inv_freq
    scaled = inv_freq / s.factor
    smooth = (s.original_max_position / wavelen - s.low_freq_factor) / (
        s.high_freq_factor - s.low_freq_factor)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    blended = (1.0 - smooth) * scaled + smooth * inv_freq
    return jnp.where(wavelen > low_wavelen, scaled,
                     jnp.where(wavelen < high_wavelen, inv_freq, blended))


def apply_rope(x: jax.Array, positions: jax.Array,
               inv_freq: jax.Array, factor: float = 1.0) -> jax.Array:
    """Rotate pairs (x[..., :d/2], x[..., d/2:]) by position*freq.

    x: [..., seq, heads, head_dim]; positions: [..., seq] (broadcastable).
    Uses the half-split convention (HF llama's rotate_half), so HF
    checkpoints work without permutation. ``factor`` multiplies cos and
    sin (:func:`rope_table`'s second value).
    """
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # [..., S, d/2]
    cos = jnp.cos(angles)[..., :, None, :]   # [..., S, 1, d/2]
    sin = jnp.sin(angles)[..., :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """GQA: expand kv heads to query heads. [B,S,Hkv,D] -> [B,S,Hkv*n,D]."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def attend(q: jax.Array, k: jax.Array, v: jax.Array,
           mask: Optional[jax.Array]) -> jax.Array:
    """Scaled dot-product attention, softmax in f32.

    q: [B,Sq,H,D]; k,v: [B,Skv,H,D]; mask: broadcastable to [B,H,Sq,Skv]
    (True = attend). Returns [B,Sq,H,D].
    """
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def attend_gqa(q: jax.Array, k: jax.Array, v: jax.Array,
               mask: Optional[jax.Array]) -> jax.Array:
    """Grouped-query attention without materialising repeated kv heads.

    q: [B,Sq,Hq,D]; k,v: [B,Skv,Hkv,D] with Hq = Hkv * rep; mask:
    broadcastable to [B,H,Sq,Skv] (True = attend). Returns [B,Sq,Hq,D].

    The repeat_kv + attend formulation reads (and on TPU, writes) the kv
    cache ``rep``× per step — at serving shapes that is gigabytes of pure
    HBM waste. Here q is reshaped to [B,Sq,G,rep,D] and contracted against
    the unexpanded cache; scores accumulate in f32 on the MXU
    (``preferred_element_type``) without an f32 copy of the cache. Query
    head h maps to kv head h // rep, matching repeat_kv's expansion order.
    """
    B, Sq, Hq, D = q.shape
    G = k.shape[2]
    rep = Hq // G
    if rep == 1:
        return attend(q, k, v, mask)
    qg = q.reshape(B, Sq, G, rep, D)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(D).astype(jnp.float32)
    if mask is not None:
        if mask.ndim == 4:                     # [B|1, 1, Sq, Skv]
            mask = mask[:, :, None]            # -> [B|1, 1, 1, Sq, Skv]
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, Hq, D)


def flash_attend_gqa(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: Optional[jax.Array],
                     chunk: int = 512) -> jax.Array:
    """attend_gqa with online-softmax accumulation over KV chunks — the
    score tensor never materialises past ``[B,G,rep,Sq,chunk]``.

    Same contract/results as :func:`attend_gqa` (f32 statistics); used by
    the model when the full ``[...,Sq,Skv]`` scores would blow the HBM
    budget (long-context prefill at serving batch sizes). The
    chunk-update math is the same flash recurrence parallel/ring.py runs
    across devices; here it runs across KV chunks on one device via
    ``lax.scan`` (constant-size graph for any context length).

    Fully-masked chunks contribute zero weight (their statistics scale
    out), so ragged lengths and causal masks need no special-casing.
    """
    B, Sq, Hq, D = q.shape
    Skv, G = k.shape[1], k.shape[2]
    rep = Hq // G
    if Skv <= chunk:
        return attend_gqa(q, k, v, mask)
    assert Skv % chunk == 0, (Skv, chunk)   # power-of-two windows hold this
    N = Skv // chunk
    if mask is None:
        mask = jnp.ones((1, 1, Sq, Skv), bool)
    if mask.ndim == 4:
        mask = mask[:, :, None]             # [B|1, 1, 1, Sq, Skv]
    mask = jnp.broadcast_to(mask, (B, 1, 1, Sq, Skv))

    # Chunks carry kv EXPANDED to query heads (repeat_kv): prefill is
    # compute-bound, so the rep-fold read matters not at all, while the
    # unexpanded [B,G,rep,Sq,chunk] statistics put a size-2 dim next to
    # the minors and XLA answered with transposed layouts + VPU-shaped
    # chains — measured ~2/5 of the whole B=2 S=2048 prefill. Natural
    # [B,Hq,Sq,chunk] shapes + bf16 probs into the p.v dot (f32 MXU runs
    # at 1/8 rate; the dense attend casts probs too) took a 22-layer
    # prefill from 87 to >110 TFLOPs/chip. (The DECODE paths keep the
    # unexpanded contraction — there the rep-fold kv READ is the
    # bandwidth bound; see attend_gqa.)
    kc = repeat_kv(k, rep).reshape(B, N, chunk, Hq, D).transpose(
        1, 0, 2, 3, 4)
    vc = repeat_kv(v, rep).reshape(B, N, chunk, Hq, D).transpose(
        1, 0, 2, 3, 4)
    mc = mask.reshape(B, 1, 1, Sq, N, chunk).transpose(4, 0, 1, 2, 3, 5)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, mb = xs          # [B,chunk,Hq,D], mask [B,1,1,Sq,chunk]
        s = jnp.einsum("bshd,bthd->bhst", q, kb,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(D).astype(jnp.float32)
        s = jnp.where(mb[:, 0], s, NEG_INF)               # [B,Hq,Sq,chunk]
        m_new = jnp.maximum(m, s.max(axis=-1))
        # Fully-masked-so-far rows keep m at NEG_INF; exp(NEG_INF-NEG_INF)
        # would poison alpha, so clamp the shift.
        alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(m_new[..., None] <= NEG_INF / 2, 0.0, p)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhst,bthd->bhsd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hq, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hq, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, mc))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# Score tensors past this many f32 elements take the chunked flash path.
# Measured on v5e (bench-1b): at B=2 S=2048 the dense path's 268 MB
# score round-trips cap prefill at 66 TFLOPs/chip while the flash path
# runs 87; at the 2^25 boundary shapes the two are equal — so the
# threshold sits at 2^25 (128 MB of f32 scores) rather than the HBM-fit
# bound it started as.
_FLASH_SCORE_ELEMS = 2 ** 25
# The flash scan's KV chunk (1024 measured ~6% faster than 512 on v5e at
# long-prefill shapes: fewer scan steps, same VMEM fit), and so the
# granule a caller cuts its keys to where it wants the scan to take them
# (nemotron_h._attn_prefill).
FLASH_KV_CHUNK = 1024


def attend_gqa_causal0(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal-from-position-0 attention via the canonical Pallas TPU
    flash kernel (jax.experimental.pallas.ops) — probabilities never
    leave VMEM, where the XLA chunk-scan path round-trips the f32 score
    tensor through HBM three times per chunk (~2.2 ms/layer at B=2
    S=2048 vs 0.41 ms for the kernel at the tuned 512x512 blocks; the
    kernel also skips the causally-dead upper triangle). kv expands to
    query heads first — prefill is compute-bound, the rep-fold read is
    noise. q/k/v: [B, S, H*, D] with equal S; returns [B, S, Hq, D]."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention)

    B, S, Hq, D = q.shape
    rep = Hq // k.shape[2]
    kx = repeat_kv(k, rep).transpose(0, 2, 1, 3)       # [B, Hq, S, D]
    vx = repeat_kv(v, rep).transpose(0, 2, 1, 3)
    bq = bkv = min(512, S)
    bs = BlockSizes(block_q=bq, block_k_major=bkv, block_k=bkv, block_b=1,
                    block_q_major_dkv=bq, block_k_major_dkv=bkv,
                    block_k_dkv=bkv, block_q_dkv=bq,
                    block_k_major_dq=bkv, block_k_dq=bkv, block_q_dq=bq)
    out = flash_attention(q.transpose(0, 2, 1, 3), kx, vx, causal=True,
                          sm_scale=1.0 / (D ** 0.5), block_sizes=bs)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attend_gqa_auto(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: Optional[jax.Array],
                    causal0_len: Optional[int] = None) -> jax.Array:
    """attend_gqa, switching to a flash path when the score tensor would
    be HBM-hostile (long-context prefill at batch).

    ``causal0_len``: set by callers whose mask is EXACTLY causal from
    position 0 over the first ``causal0_len`` kv slots (llama.prefill's
    whole-prompt path) — on TPU those shapes take the canonical Pallas
    flash kernel (attend_gqa_causal0); everything else (ragged admission
    splices, prefix-spliced suffixes, CPU tests) keeps the XLA paths.
    The KV length must divide the chunk for the XLA flash scan —
    SERVE_MAX_SEQ is user-set and need not be a power of two; an
    indivisible length stays on the dense path."""
    B, Sq, Hq, D = q.shape
    Skv = k.shape[1]
    big = B * Hq * Sq * Skv > _FLASH_SCORE_ELEMS
    if (big and causal0_len is not None and causal0_len == Sq
            and on_tpu() and Sq % 512 == 0 and D % 128 == 0):
        return attend_gqa_causal0(q, k[:, :Sq], v[:, :Sq])
    if big and Sq >= 256 and Skv >= FLASH_KV_CHUNK and Skv % 512 == 0:
        # Sq >= 256 keeps DECODE-side shapes (speculative verify: a few
        # query positions against a long window) off the flash scan,
        # whose repeat_kv-expanded chunks would pay rep-fold KV traffic
        # on a bandwidth-bound path; the dense attend materialises the
        # modest [B,G,rep,Sq,W] scores once instead.
        # Falls back to half the chunk when the KV length doesn't divide.
        return flash_attend_gqa(
            q, k, v, mask, chunk=FLASH_KV_CHUNK if Skv % FLASH_KV_CHUNK == 0
            else FLASH_KV_CHUNK // 2)
    return attend_gqa(q, k, v, mask)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """SwiGLU MLP: down(silu(x@gate) * (x@up)). Weights may be int8
    QTensors (models/quant.py)."""
    g = jax.nn.silu(mm(x, w_gate))
    u = mm(x, w_up)
    return mm(g * u, w_down)


def causal_mask(q_len: int, kv_len: int, q_offset: jax.Array | int) -> jax.Array:
    """[1,1,Sq,Skv] boolean mask: query i (at absolute pos q_offset+i) may
    attend kv position j iff j <= q_offset+i."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return (kv_pos <= q_pos)[None, None, :, :]


def length_mask(kv_len: int, lengths: jax.Array) -> jax.Array:
    """[B,1,1,Skv] mask limiting attention to the first ``lengths[b]``
    cache slots (decode path with ragged per-request lengths)."""
    kv_pos = jnp.arange(kv_len)[None, :]
    return (kv_pos < lengths[:, None])[:, None, None, :]

"""Differential attention (arXiv:2410.05258 as the SambaY stack of
arXiv:2507.06607 uses it), in XLA, over whatever holds the keys: a dense
carry, a window ring, gathered pages.

Heads come in pairs. View q as [pairs, 2, D] and k, v as [kv pairs, 2,
D]; query pair ``p`` reads KV pair ``g = p // rep`` (``rep`` query pairs
a KV pair). With ``a_s = softmax(q_{p,s} k_{g,s}^T / sqrt(D))`` for s in
{0, 1} and ``V_g = [v_{g,0} | v_{g,1}]`` (2D wide):

    o_p = a_0 V_g - lam a_1 V_g
    o_p <- RMSNorm_2D(o_p) * w_sub * (1 - lam0)

In flat heads, query head ``2p + s`` scores against key head ``2g + s``
and weighs BOTH value heads of the pair, which is why no GQA routine of
ops/paged_attention.py or models/layers.py serves it. Here the query is
folded to [g, s, r] (r the pair's index inside its KV pair) so that ONE
pair of einsums does every head: scores contract D against key head (g,
s); values are read as [g, (h, D)] and shared by s and r.

A decode step reads its keys where they lie, in the caches' layout
``[B, T, C]`` with ``C = Hkv x D``: every KV head of a position side by
side in one row (ModelConfig.cache_k_dim). :func:`attend_decode` pads
each query head with zeros over the lanes of every KV head but its own,
so that the scores of all heads are ONE ``[Hq, C] x [C, T]`` product a
row that contracts the cache's lanes, and the values one ``[Hq, T] x [T,
C]`` of which each head keeps its pair's 2D lanes. The zeros cost the
MXU nothing (40 query rows fill a third of its 128 either way), and no
head is ever sliced out of a row: with the heads as a dimension of
their own, 20 x 64, the TPU compiler pads an int8 cache to 32 x 128 and
transposes the whole ring and the whole page pool every step (read off
the chip compiler's HLO, PERF.md section 6, PR 38).

Keys may come in several parts (a ring and the chunk's own keys; a
pool's window and this step's token): each part is scored on its own
and the softmax runs over their concatenated scores, so nothing of K or
V is copied to join them. A decode step's int8 parts carry a float32
scale a position, folded outside the dots as
ops/paged_attention does.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


class Keys(NamedTuple):
    """One part of what a chunk's queries read, from a prefill's carry
    (never int8). k, v [B,T,Hkv,D]; mask [B|1,S,T] bool."""

    k: jax.Array
    v: jax.Array
    mask: jax.Array


class FlatKeys(NamedTuple):
    """One part of what a decode query reads, in the caches' layout: k,
    v [B,T,C]; mask [B,T] bool; ks, vs [B,T] float32 scales of an int8
    part, else None."""

    k: jax.Array
    v: jax.Array
    mask: jax.Array
    ks: Optional[jax.Array] = None
    vs: Optional[jax.Array] = None


def lam0_of(layer: int) -> float:
    """The published schedule, by the layer's index in the stack."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def lam_of(lp: dict, lam0: jax.Array) -> jax.Array:
    f32 = jnp.float32
    return (jnp.exp(jnp.sum(lp["lq1"].astype(f32) * lp["lk1"].astype(f32)))
            - jnp.exp(jnp.sum(lp["lq2"].astype(f32) * lp["lk2"].astype(f32)))
            + lam0)


def _finish(o: jax.Array, lam0, sub_w, eps: float, dtype) -> jax.Array:
    """The sub-norm and the ``(1 - lam0)`` factor over the last axis of
    o [..., pairs, 2D], then the pairs side by side."""
    f32 = jnp.float32
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * sub_w.astype(f32) * (1.0 - lam0)
    return o.reshape(*o.shape[:-2], -1).astype(dtype)


def attend_decode(q: jax.Array, parts: list, lam: jax.Array,
                  lam0: jax.Array, sub_w: jax.Array, eps: float) -> jax.Array:
    """One query a row: q [B,Hq,D]; ``parts`` a list of
    :class:`FlatKeys`. Returns [B, Hq * D] in q's dtype."""
    import numpy as np
    f32 = jnp.float32
    B, Hq, D = q.shape
    Hkv = parts[0].k.shape[-1] // D
    rep = Hq // Hkv
    # Query head h = 2p + s reads key head 2 (p // rep) + s and the value
    # heads of KV pair p // rep.
    h = np.arange(Hq)
    key_of = np.eye(Hkv, dtype=np.float32)[2 * (h // 2 // rep) + h % 2]
    pair_of = np.eye(Hkv // 2, dtype=np.float32)[h // 2 // rep]
    qp = (q[:, :, None, :] * jnp.asarray(key_of, q.dtype)[None, :, :, None]
          ).reshape(B, Hq, Hkv * D)
    scores = []
    for p in parts:
        sc = jnp.einsum("bhc,btc->bht", qp, p.k.astype(q.dtype),
                        preferred_element_type=f32) / jnp.sqrt(D).astype(f32)
        if p.ks is not None:
            sc = sc * p.ks[:, None, :]
        scores.append(jnp.where(p.mask[:, None, :], sc, NEG_INF))
    probs = jax.nn.softmax(jnp.concatenate(scores, axis=-1), axis=-1)
    out, at = 0.0, 0
    for p in parts:
        T = p.k.shape[1]
        a = probs[..., at: at + T]
        at += T
        if p.vs is not None:
            a = a * p.vs[:, None, :]
        out = out + jnp.einsum("bht,btc->bhc", a.astype(q.dtype),
                               p.v.astype(q.dtype),
                               preferred_element_type=f32)
    out = jnp.einsum("bhge,hg->bhe", out.reshape(B, Hq, Hkv // 2, 2 * D),
                     jnp.asarray(pair_of))
    out = out.reshape(B, Hq // 2, 2, 2 * D)
    return _finish(out[:, :, 0] - lam * out[:, :, 1], lam0, sub_w, eps,
                   q.dtype)


def attend(q: jax.Array, parts: list, lam: jax.Array, lam0: jax.Array,
           sub_w: jax.Array, eps: float) -> jax.Array:
    """q [B,S,Hq,D]; ``parts`` a list of :class:`Keys`. Returns
    [B,S,Hq/2 * 2D] in q's dtype: the normed pair outputs, pair-major,
    ready for the output projection."""
    f32 = jnp.float32
    B, S, Hq, D = q.shape
    Hkv = parts[0].k.shape[2]
    G, rep = Hkv // 2, Hq // Hkv
    # [B,S,(g,r,s),D] -> [B,S,g,s,r,D]
    qf = q.reshape(B, S, G, rep, 2, D).transpose(0, 1, 2, 4, 3, 5)
    scores = []
    for p in parts:
        T = p.k.shape[1]
        sc = jnp.einsum("bqgsrd,btgsd->bgsrqt", qf,
                        p.k.reshape(B, T, G, 2, D).astype(q.dtype),
                        preferred_element_type=f32) / jnp.sqrt(D).astype(f32)
        scores.append(jnp.where(p.mask[:, None, None, None], sc, NEG_INF))
    probs = jax.nn.softmax(jnp.concatenate(scores, axis=-1), axis=-1)
    out, at = 0.0, 0
    for p in parts:
        T = p.k.shape[1]
        a = probs[..., at: at + T]
        at += T
        out = out + jnp.einsum(
            "bgsrqt,btghd->bqgsrhd", a.astype(q.dtype),
            p.v.reshape(B, T, G, 2, D).astype(q.dtype),
            preferred_element_type=f32)
    o = out[:, :, :, 0] - lam * out[:, :, :, 1]        # [B,S,g,r,h,D]
    return _finish(o.reshape(B, S, G * rep, 2 * D), lam0, sub_w, eps,
                   q.dtype)

"""Latent-attention sparse-MoE decoder (the openPangu-Ultra-MoE /
DeepSeek-V3 block): MLA, sandwich norms, leading dense layers, then
routed layers that hold a SHARE of the experts their router scores,
beside a shared expert. ``models.family_for`` picks this module for a
configuration with ``kv_lora_rank`` > 0; the functional surface is the
other families' (init_params, prefill, prefill_chunk, decode_step_paged,
decode_fused, their ``_counted`` / ``_touched`` forms), so the scheduler
serves it through the same programs.

**The layer** (``x`` [T, H]; ``n(.)`` an RMSNorm with learned weight):

- ``a = n_in(x)``; ``cq = n_qa(a Wqa)``; ``q = cq Wqb`` -> per head
  ``q_nope`` (``qk_nope_head_dim``) and ``q_rope`` (``qk_rope_head_dim``,
  rotated).
- ``kv = a Wkva``: ``c = n_kva(kv[:, :kv_lora_rank])`` and ``k_rope =
  rope(kv[:, kv_lora_rank:])``, ONE row a token, shared by every head.
  **The cache holds (c, k_rope) and nothing else**: ``c`` in the cache's
  ``k`` array ([.., 1, kv_lora_rank]), ``k_rope`` in its ``v`` array
  ([.., 1, rope dim padded to 128 lanes]): ``ModelConfig.cache_*``. Both
  are ordinary :class:`~.llama.KVCache` / ``PagedKVCache`` leaves, so
  every pool write, park, wake and prefix splice is the other families'.
- **Expanded form** (every prefill program): ``[k_nope_h, v_h] = c Wkvb``
  for the whole context, ``s_h = (q_nope_h . k_nope_h + q_rope_h .
  k_rope) / sqrt(nope + rope)``, causal softmax, ``o_h = p v_h``
  (ops/mla_attention.mla_prefill_attention: q.k 192 wide, v 128).
- **Absorbed form** (every decode program): ``q_lat_h = q_nope_h
  Wuk_h^T`` (Wuk_h the k_nope half of Wkvb's head h), scores and values
  over the latent rows themselves (ops/mla_attention.mla_decode_attention:
  every head reads the one shared row), ``o_h = o_lat_h Wuv_h``. The same
  mathematics; tests hold both to the expanded plain reference.
- Sandwich residuals: ``x1 = x + n_post_attn(attn Wo)``; ``m =
  n_pre_mlp(x1)``; ``x2 = x1 + n_post_mlp(mlp(m))``.
- ``mlp``: a dense layer's SwiGLU, or ``shared(m) + sum_{e in top-k}
  w_e expert_e(m)`` with ``g = sigmoid(m Wr)`` in float32 over ALL
  ``router_width`` experts, the k largest kept, ``w = g_top / (sum g_top
  + 1e-20) * routed_scaling_factor``. Of those pairs only the ones whose
  expert id is below ``num_experts`` are computed here (the experts this
  chip holds); the others are another chip's work, left out here as in
  the plain reference. Nothing stands in for the absent chips.
  :func:`_routed_local` is that sum, for this family and for
  models/nemotron_h.py's routed layers: a prefill's pairs go sorted by
  expert into tiles (models/moe_tiles.py, the one prefill dispatch of
  the tree: the pairs that were routed here are the rows multiplied), a
  decode step's into per-expert buckets that hold every row. Dropless
  both ways.

**The stack is not one scan**: ``params["dense_layers"]`` (the leading
dense layers, unrolled) then ``params["layers"]`` (the routed ones, one
``lax.scan``). Cache layer ``i`` is layer ``i`` of the stack.

**Weight layout** (fused at init, ``fuse_params`` is the identity):
``wqkva`` [H, q_lora + kv_lora + rope, padded to 128] (Wqa | Wkva);
``wqb`` [q_lora, heads*nope | heads*rope] (all nope columns first, so
both halves are lane-aligned blocks); ``wkvb`` [kv_lora, heads*(nope +
v)], head-major with each head's k_nope columns before its v columns;
``wo`` [heads*v, H]; ``wgu`` / ``w_down`` the dense MLP; ``wgu_s`` /
``w_down_s`` the shared expert; ``wgu_e`` / ``w_down`` the held experts;
``router`` [H, router_width] bf16. RoPE is rotate-half, the repo's
convention: a checkpoint in the interleaved layout is permuted on load
(models/weights.py's name maps are where that would go; random weights
cannot tell the two apart).

Single chip only: the latent pool has one head, which a mesh cannot
split by heads, so a mesh is refused at boot (serve/scheduler.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..parallel.sharding import LogicalRules, DEFAULT_RULES
from ..utils.device import on_tpu, pallas_interpret
from .configs import ModelConfig
from .layers import DEFAULT_COMPUTE_DTYPE, apply_rope, rms_norm
from .llama import KVCache, _default_mlp, _layer_view  # same cache contract
from .moe_tiles import relu2_experts, routed_tiles, swiglu_experts, tile_rows
from .quant import LayerSlice, QTensor, mm

# Width of the counts this family's programs hand the scheduler (the
# other routed family hands 2 or 3). Prefill: (pairs routed to held
# experts, of those dropped, pairs routed, rows the experts multiplied:
# filled tiles x rows a tile, as mixtral.prefill_stats' third), real
# prompt positions only. Decode: (held experts a live row reached, held
# experts there were, pairs routed, pairs routed to held experts), live
# rows only.
STATS_WIDTH = 4

def _pad128(n: int) -> int:
    return -(-n // 128) * 128


def _dims(config: ModelConfig) -> dict:
    """Per-layer matmul leaves (without the layer axis)."""
    H, Hq = config.hidden_size, config.num_heads
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    attn = {
        "wqkva": (H, _pad128(config.q_lora_rank + config.kv_lora_rank + dr)),
        "wqb": (config.q_lora_rank, Hq * (dn + dr)),
        "wkvb": (config.kv_lora_rank, Hq * (dn + dv)),
        "wo": (Hq * dv, H),
    }
    F = config.intermediate_size
    Fs = F * config.num_shared_experts
    NE = config.num_experts
    dense = {**attn, "wgu": (H, 2 * config.dense_intermediate_size),
             "w_down": (config.dense_intermediate_size, H)}
    moe = {**attn, "wgu_s": (H, 2 * Fs), "w_down_s": (Fs, H),
           "wgu_e": (NE, H, 2 * F), "w_down": (NE, F, H)}
    return {"dense": dense, "moe": moe}


def _norm_shapes(config: ModelConfig) -> dict:
    H = config.hidden_size
    return {"attn_norm": H, "q_a_norm": config.q_lora_rank,
            "kv_a_norm": config.kv_lora_rank, "post_attn_norm": H,
            "mlp_norm": H, "post_mlp_norm": H}


def _norm_leaves(config: ModelConfig, key: jax.Array, L: int, dtype) -> dict:
    """Every norm of ``L`` layers drawn from [0.5, 1.5), not ones (as
    llama.qk_norm_leaves): under the scaled-normal init a projection
    already has unit RMS, so a norm of ones is nearly the identity and a
    model that left one out would pass every comparison."""
    out = {}
    for i, (name, n) in enumerate(_norm_shapes(config).items()):
        out[name] = (0.5 + jax.random.uniform(jax.random.fold_in(key, i),
                                              (L, n), jnp.float32)
                     ).astype(dtype)
    return out


def _layer_counts(config: ModelConfig) -> tuple[int, int]:
    Ld = config.first_k_dense
    return Ld, config.num_layers - Ld


def _normal(k, shape, scale, dtype):
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def _build(config: ModelConfig, key: jax.Array, dtype, stack, head) -> dict:
    """The parameter tree both initialisers return. ``stack(k, L, dims)``
    makes the ``L`` layers' matmul leaves of one group, ``head(k,
    shape)`` the output projection; embeddings, routers and norms are
    plain ``dtype`` here."""
    Ld, Lm = _layer_counts(config)
    H = config.hidden_size
    key, k_embed, k_head, k_nd, k_nm = jax.random.split(key, 5)
    kd, km, kr = jax.random.split(key, 3)
    dims = _dims(config)
    return absorb_params({
        "embed": _normal(k_embed, (config.vocab_size, H), 1.0, dtype),
        "dense_layers": {**stack(kd, Ld, dims["dense"]),
                         **_norm_leaves(config, k_nd, Ld, dtype)},
        "layers": {**stack(km, Lm, dims["moe"]),
                   **_norm_leaves(config, k_nm, Lm, dtype),
                   "router": _normal(kr, (Lm, H, config.router_width),
                                     H ** -0.5, dtype)},
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": head(k_head, (H, config.vocab_size)),
    }, config)


def init_params(config: ModelConfig, key: jax.Array,
                dtype=DEFAULT_COMPUTE_DTYPE) -> dict:
    """Random init (scaled normal), in the fused layout."""
    def stack(k, L, dims):
        return {name: _normal(jax.random.fold_in(k, i), (L, *shape),
                              shape[-2] ** -0.5, dtype)
                for i, (name, shape) in enumerate(dims.items())}

    return _build(config, key, dtype, stack, lambda k, shape: _normal(
        k, shape, shape[0] ** -0.5, dtype))


def streamed_stack(leaf, quant: str):
    """``stack(k, L, dims)`` for :func:`_build` that streams random
    leaves straight into the quantized buffers, one leaf of one layer
    (one expert of it) at a time. ``leaf(k, shape, name) -> QTensor``
    draws and quantizes one of them. (models/nemotron_h.py streams its
    tree through this too.)"""
    from .quant import stream_bufs

    @functools.partial(jax.jit, donate_argnums=(0,),
                       static_argnames=("shape", "name"))
    def write(buf, k, at, *, shape, name):
        qt = leaf(k, shape, name)
        return QTensor(q=buf.q.at[tuple(at)].set(qt.q),
                       s=buf.s.at[tuple(at)].set(qt.s))

    def stack(k, L, dims):
        out = {}
        for i, (name, shape) in enumerate(dims.items()):
            buf = stream_bufs(L, shape, quant)
            for li in range(L):
                kl = jax.random.fold_in(jax.random.fold_in(k, i), li)
                if len(shape) == 3:         # an expert stack: one at a time
                    for e in range(shape[0]):
                        buf = write(buf, jax.random.fold_in(kl, e),
                                    jnp.asarray([li, e]), shape=shape[1:],
                                    name=name)
                else:
                    buf = write(buf, kl, jnp.asarray([li]), shape=shape,
                                name=name)
            out[name] = buf
        return out

    return stack


def init_params_quantized(config: ModelConfig, key: jax.Array,
                          dtype=DEFAULT_COMPUTE_DTYPE,
                          quant: str = "int8") -> dict:
    """Random init streamed straight into the int8 tree, one leaf of one
    layer (one expert of it) at a time: the bf16 tree of the benchmark's
    cut is 18 GB and cannot exist on the chip, and one layer's sixteen
    experts in float32 are 2 GB of transients beside 9 GB of weights."""
    from .quant import quantize

    if quant != "int8":
        raise ValueError(f"{config.name}: the latent-attention family "
                         f"serves int8 or plain weights (the absorbed "
                         f"form reads Wkvb's int8 numbers), not {quant!r}")

    def leaf(k, shape, name=""):
        return quantize(_normal(k, shape, shape[-2] ** -0.5, dtype))

    return _build(config, key, dtype, streamed_stack(leaf, quant), leaf)


def _absorbed_leaves(wkvb, config: ModelConfig) -> dict:
    """Wkvb's numbers as the absorbed form contracts them, for a whole
    layer stack: ``wuk_t`` [L, Hq, dn, r] (q_lat_h = q_nope_h @ wuk_t[h])
    and ``wuv_t`` [L, Hq, r, dv] (o_h = o_lat_h @ wuv_t[h]): the int8 (or
    plain) values of ``wkvb`` [L, r, Hq*(dn+dv)] transposed once, here.
    Contracting ``wkvb`` itself over its columns made every decode
    program relayout the whole stack (134 MB a dispatch at the
    benchmark's widths). The scales stay ``wkvb``'s: k_nope's fold into
    q_nope before the first product, v's into the output after the
    second."""
    q = wkvb.q if isinstance(wkvb, QTensor) else wkvb
    L, r, _ = q.shape
    Hq, dn, dv = (config.num_heads, config.qk_nope_head_dim,
                  config.v_head_dim)
    q = q.reshape(L, r, Hq, dn + dv)
    return {"wuk_t": q[..., :dn].transpose(0, 2, 3, 1),
            "wuv_t": q[..., dn:].transpose(0, 2, 1, 3)}


def absorb_params(params: dict, config: ModelConfig) -> dict:
    """``params`` with the absorbed form's leaves beside ``wkvb`` in both
    layer groups (both initialisers end with this; a checkpoint loader
    would too)."""
    out = dict(params)
    for group in ("dense_layers", "layers"):
        out[group] = {**params[group],
                      **_absorbed_leaves(params[group]["wkvb"], config)}
    return out


def fuse_params(params: dict, tp: int = 1, mesh: Optional[Mesh] = None,
                **_) -> dict:
    """The tree is born fused (module docstring)."""
    return params


def param_axes(config: ModelConfig) -> dict:
    """Everything replicated: the family serves on one chip."""
    def none_tree(d):
        return {k: none_tree(v) if isinstance(v, dict)
                else (None,) * (len(v) + 1) for k, v in d.items()}
    dims = _dims(config)
    norms = {n: (None, None) for n in _norm_shapes(config)}
    norms.update(wuk_t=(None,) * 4, wuv_t=(None,) * 4)
    return {
        "embed": ("vocab", "embed"),
        "dense_layers": {**none_tree(dims["dense"]), **norms},
        "layers": {**none_tree(dims["moe"]), **norms,
                   "router": (None, None, None)},
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# -- attention ----------------------------------------------------------------

def _rope_freq(config: ModelConfig) -> jax.Array:
    d = config.qk_rope_head_dim
    return 1.0 / (config.rope_theta
                  ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def _attn_inputs(h: jax.Array, lp: dict, config: ModelConfig,
                 positions: jax.Array):
    """h [B,S,H] -> q_nope [B,S,Hq,dn], q_rope [B,S,Hq,dr] (rotated),
    c [B,S,r] (normed latent), k_rope [B,S,dr] (rotated)."""
    B, S, _ = h.shape
    Hq, eps = config.num_heads, config.rms_norm_eps
    ql, r = config.q_lora_rank, config.kv_lora_rank
    dn, dr = config.qk_nope_head_dim, config.qk_rope_head_dim
    a = rms_norm(h, lp["attn_norm"], eps)
    qkva = mm(a, lp["wqkva"])
    cq = rms_norm(qkva[..., :ql], lp["q_a_norm"], eps)
    c = rms_norm(qkva[..., ql: ql + r], lp["kv_a_norm"], eps)
    k_rope = qkva[..., ql + r: ql + r + dr]
    q = mm(cq, lp["wqb"])
    q_nope = q[..., : Hq * dn].reshape(B, S, Hq, dn)
    q_rope = q[..., Hq * dn:].reshape(B, S, Hq, dr)
    inv_freq = _rope_freq(config)
    q_rope = apply_rope(q_rope, positions, inv_freq)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, inv_freq)[:, :, 0]
    return q_nope, q_rope, c, k_rope


def _pad_rope(k_rope: jax.Array, config: ModelConfig) -> jax.Array:
    """The shared rotated key at the cache's ``v`` width (zero lanes
    behind the real ones)."""
    pad = config.cache_v_dim - config.qk_rope_head_dim
    if not pad:
        return k_rope
    return jnp.pad(k_rope, [(0, 0)] * (k_rope.ndim - 1) + [(0, pad)])


def _sandwich(h, attn_out, lp, config, mlp_fn):
    eps = config.rms_norm_eps
    x1 = h + rms_norm(attn_out, lp["post_attn_norm"], eps)
    m = rms_norm(x1, lp["mlp_norm"], eps)
    return x1 + rms_norm(mlp_fn(m, lp), lp["post_mlp_norm"], eps)


def _block_expanded(h, lp, config, positions, ck, cv, layer, offset: int,
                    mlp_fn):
    """One block of a prefill program against the dense carry: the
    chunk's latents land at slots offset..offset+S of layer ``layer``,
    then the whole context so far (offset+S rows) is expanded through
    Wkvb and attended causally. h [B,S,H]; ck [L,B,W,1,r]; cv
    [L,B,W,1,cache_v_dim]."""
    from ..ops.mla_attention import mla_prefill_attention
    B, S, _ = h.shape
    Hq = config.num_heads
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    q_nope, q_rope, c, k_rope = _attn_inputs(h, lp, config, positions)
    zero = jnp.zeros((), jnp.int32)
    at = (layer, zero, jnp.asarray(offset, jnp.int32), zero, zero)
    ck = jax.lax.dynamic_update_slice(
        ck, c[None, :, :, None, :].astype(ck.dtype), at)
    cv = jax.lax.dynamic_update_slice(
        cv, _pad_rope(k_rope, config)[None, :, :, None, :].astype(cv.dtype),
        at)
    W = offset + S
    c_ctx = jax.lax.dynamic_index_in_dim(ck, layer, 0, False)[:, :W, 0]
    r_ctx = jax.lax.dynamic_index_in_dim(cv, layer, 0, False)[:, :W, 0]
    kv = mm(c_ctx.astype(h.dtype), lp["wkvb"])        # [B,W,Hq*(dn+dv)]
    attn = mla_prefill_attention(
        q_nope, q_rope, kv, r_ctx.astype(h.dtype), offset,
        dn=dn, dr=dr, dv=dv, interpret=pallas_interpret())   # [B,S,Hq*dv]
    out = mm(attn, lp["wo"])
    return _sandwich(h, out, lp, config, mlp_fn), ck, cv


def _einsum_f32(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """``einsum`` with a float32 result: accumulated so on the MXU; off
    the TPU on float32 operands (XLA's CPU dot takes no bf16 pair with a
    float32 result in the head-batched forms used here)."""
    if on_tpu():
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


def _wkvb_views(lp: dict, config: ModelConfig):
    """One layer's Wkvb as the absorbed form reads it: (wuk [Hq,dn,r],
    wuv [Hq,r,dv], s_uk [Hq,dn] | None, s_uv [Hq,dv] | None): the
    tree's ``wuk_t`` / ``wuv_t`` (:func:`_absorbed_leaves`) and
    ``wkvb``'s per-column scales (None for a plain tree), which fold
    into q_nope before the first product and into the output after the
    second."""
    dn = config.qk_nope_head_dim
    w = lp["wkvb"]
    if isinstance(w, LayerSlice):
        s = jax.lax.dynamic_index_in_dim(w.w.s, w.layer, 0, False)
    elif isinstance(w, QTensor):
        s = w.s
    else:
        return lp["wuk_t"], lp["wuv_t"], None, None
    s = s.reshape(config.num_heads, dn + config.v_head_dim)
    return lp["wuk_t"], lp["wuv_t"], s[:, :dn], s[:, dn:]


def _block_absorbed(h, lp, config, positions, cache, layer, pages: int,
                    mlp_fn):
    """One block over the paged latent pool: attend before the write,
    the block's own tokens folded in by one online-softmax merge (as
    llama.decode_step_paged_aux does), the latents handed back for the
    one batched pool write after the stack. h [B,S,H]: S = 1 is a decode
    step (the kernel); S > 1 a session wake's suffix (XLA)."""
    from ..ops.mla_attention import (mla_block_reference,
                                     mla_decode_attention)
    B, S, _ = h.shape
    Hq, dv = config.num_heads, config.v_head_dim
    sm_scale = (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
    q_nope, q_rope, c, k_rope = _attn_inputs(h, lp, config, positions)
    wuk, wuv, s_uk, s_uv = _wkvb_views(lp, config)
    if s_uk is not None:
        q_nope = (q_nope.astype(jnp.float32) * s_uk).astype(h.dtype)
    q_lat = _einsum_f32("bshd,hdr->bshr", q_nope, wuk.astype(h.dtype)
                        ).astype(h.dtype)
    q_rope = _pad_rope(q_rope, config)
    r_blk = _pad_rope(k_rope, config)
    if S == 1:
        o_lat = mla_decode_attention(
            q_lat[:, 0], q_rope[:, 0], c[:, 0], r_blk[:, 0], cache,
            cache.lengths, layer, pages=pages, sm_scale=sm_scale,
            interpret=pallas_interpret())[:, None]          # [B,1,Hq,r]
    else:
        o_lat = mla_block_reference(q_lat, q_rope, c, r_blk, cache,
                                    cache.lengths, layer, pages=pages,
                                    sm_scale=sm_scale)
    o = _einsum_f32("bshr,hrd->bshd", o_lat.astype(h.dtype),
                    wuv.astype(h.dtype))
    if s_uv is not None:
        o = o * s_uv
    out = mm(o.astype(h.dtype).reshape(B, S, Hq * dv), lp["wo"])
    return (_sandwich(h, out, lp, config, mlp_fn),
            c[:, :, None, :], r_blk[:, :, None, :])


# -- the MLPs -----------------------------------------------------------------

def _dense_mlp(m, lp):
    return _default_mlp(m, lp, None, DEFAULT_RULES)


def _shared_mlp(m, lp):
    gu = mm(m, lp["wgu_s"])
    F = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :F]) * gu[..., F:], lp["w_down_s"])


def route(xt: jax.Array, router: jax.Array, config: ModelConfig,
          bias: Optional[jax.Array] = None):
    """(top_w [T,k] float32, top_i [T,k]) over ALL ``router_width``
    experts, in float32: sigmoid (or softmax) scores, the k largest, the
    kept weights divided by their sum + ``moe_renorm_eps``
    (``moe_renormalize``) and multiplied by ``routed_scaling_factor``. ``bias`` ([router_width],
    ``moe_selection_bias``): added to the scores for the CHOICE alone;
    the kept weights are the unbiased scores of the chosen."""
    if bias is None:
        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    else:
        # A choice by biased score among hundreds of experts is decided
        # in the scores' third decimal: float32 products, not the TPU's
        # default single bfloat16 pass.
        logits = jnp.matmul(xt.astype(jnp.float32),
                            router.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if config.moe_scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    if bias is None:
        top_w, top_i = jax.lax.top_k(scores, config.num_experts_per_tok)
    else:
        _, top_i = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                 config.num_experts_per_tok)
        top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if config.moe_renormalize:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True)
                         + config.moe_renorm_eps)
    return top_w * config.routed_scaling_factor, top_i


def _experts_ffn(lp: dict, config: ModelConfig):
    """The held experts' feed-forward over buckets or tiles, ``ffn(xin
    [N,C,H], count, source) -> [N,C,H]`` as models/moe_tiles.py takes it
    (``count`` and ``source`` are quant.q_einsum's): gated SwiGLU from
    ``wgu_e``, or ungated ``relu(.)^2`` from ``w_up_e``
    (``mlp_activation``)."""
    if config.mlp_activation == "relu2":
        return functools.partial(relu2_experts, w_up=lp["w_up_e"],
                                 w_down=lp["w_down"])
    return functools.partial(swiglu_experts, w_gu=lp["wgu_e"],
                             w_down=lp["w_down"])


def _routed_local(x: jax.Array, lp: dict, config: ModelConfig,
                  counted: Optional[jax.Array], live: Optional[jax.Array],
                  latent: Optional[jax.Array] = None, chosen: bool = False):
    """The held experts' part of the routed sum, and the counts. x
    [B,S,H]. ``live`` ([B] bool) given: a decode step, its active rows
    alone take slots in per-expert buckets that hold every row.
    ``live`` None: a prefill (an admission, a chunk of a ladder, a
    prefix build, generate, a session wake's suffix), whose pairs go
    sorted by expert into tiles (models/moe_tiles.routed_tiles, the one
    prefill dispatch of the tree): the experts multiply the pairs that
    were routed here, padded to tiles, whatever the router's skew.
    ``counted`` ([B,S] bool): such a prefill's real prompt positions,
    which the counts run over and which alone are sent anywhere
    (padding and an admission's dummy entries get 0; None: every
    position is real).

    What a model names, this one dispatch reads from its configuration
    and its layer: a selection bias (``lp["router_bias"]``,
    ``moe_selection_bias``); the experts' MLP (:func:`_experts_ffn`);
    and ``latent`` ([B,S,latent width]): what the experts read and
    write when they live in a latent (``moe_latent_size``: the router
    still reads ``x``, and the sum comes back latent-wide, for the
    caller to project up). That family's decode buckets also return to
    their tokens through the placement matrix, as they came (its pairs
    a token are many and its rows narrow; the other keeps the row
    gather it was measured with).

    A pair routed to an expert this chip does not hold (id >=
    ``num_experts``) is sent nowhere and adds nothing. Dropless both
    ways.

    Returns (out [B,S,H], stats int32 [STATS_WIDTH]) and, where
    ``chosen``, third the experts the router kept (int32 [B*S,k]), for a
    caller that hands them on (models/nemotron_h.py's ``chosen``)."""
    B, S, H = x.shape
    NE, k = config.num_experts, config.num_experts_per_tok
    T = B * S
    xt = x.reshape(T, H)
    top_w, top_i = route(xt, lp["router"], config, lp.get("router_bias"))
    if latent is not None:
        H = latent.shape[-1]
        xt = latent.reshape(T, H)
    ffn = _experts_ffn(lp, config)
    if live is None:
        takes = jnp.ones((T, k), bool) if counted is None else \
            jnp.broadcast_to(counted.reshape(T, 1), (T, k))
        if config.router_width > NE:
            takes = takes & (top_i < NE)
        out, tiles = routed_tiles(xt, top_w, top_i, takes, NE, ffn,
                                  config.router_width)
        n_real = jnp.asarray(T) if counted is None else jnp.sum(counted)
        stats = jnp.stack([jnp.sum(takes), jnp.asarray(0), n_real * k,
                           jnp.sum(tiles) * tile_rows(
                               T * k, NE, config.router_width)])
        return (out.astype(x.dtype).reshape(B, S, H),
                stats.astype(jnp.int32)) + ((top_i,) if chosen else ())
    takes = (top_i < NE) & jnp.broadcast_to(live[:, None, None],
                                            (B, S, k)).reshape(T, k)
    # one_hot of an id past NE is all zeros: an absent expert's queue
    # does not exist here.
    flat = (jax.nn.one_hot(top_i, NE, dtype=jnp.int32)
            * takes[..., None].astype(jnp.int32)).reshape(T * k, NE)
    pos = jnp.cumsum(flat, axis=0) - flat
    slot = jnp.sum(flat * pos, axis=-1)                          # [T*k]
    sent = jnp.sum(flat, axis=0)                                 # [NE]
    # A bucket of T slots holds whatever its expert was sent. Rows go
    # into buckets as a 0/1 matrix times the tokens (exact: a slot has
    # one source): a row-indexed scatter costs the TPU about 1.4 us an
    # index, 0.35 ms a layer at decode's 256 pairs (PERF.md section 6,
    # PR 30); this is a [NE*T, T] x [T, H] product over a step's rows.
    # (``count`` and ``slot < T`` say nothing new, a bucket cannot
    # overflow; they keep the decode programs the text they were.)
    expert, placed = top_i.reshape(T * k), takes.reshape(T * k)
    count = jnp.minimum(sent, T)
    idx = jnp.where(placed & (slot < T), expert * T + slot, NE * T)
    if latent is not None:
        at = idx.reshape(T, k)[..., None] == jnp.arange(NE * T)
        place = jnp.sum(at, axis=1).astype(xt.dtype)             # [T,NE*T]
        xin = _einsum_f32("ts,th->sh", place, xt).astype(
            xt.dtype).reshape(NE, T, H)
        # A slot has one source pair: its weight, summed exactly.
        w_slot = jnp.sum(jnp.where(at, top_w[..., None], 0.0),
                         axis=(0, 1))                            # [NE*T]
        y = ffn(xin, count, None).reshape(NE * T, H)
        y = (y.astype(jnp.float32) * w_slot[:, None]).astype(xt.dtype)
        out = _einsum_f32("ts,sh->th", place, y)
    else:
        place = jnp.sum(jax.nn.one_hot(idx.reshape(T, k), NE * T,
                                       dtype=xt.dtype), axis=1)
        xin = _einsum_f32("ts,th->sh", place, xt).astype(
            xt.dtype).reshape(NE, T, H)
        got = jnp.take(ffn(xin, count, None).reshape(NE * T, H), idx, axis=0,
                       mode="fill", fill_value=0)
        out = jnp.sum(got.reshape(T, k, H).astype(jnp.float32)
                      * top_w[..., None], axis=1)
    stats = jnp.stack([jnp.sum(sent > 0), jnp.asarray(NE),
                       jnp.sum(live) * S * k, jnp.sum(takes)])
    return (out.astype(x.dtype).reshape(B, S, H),
            stats.astype(jnp.int32)) + ((top_i,) if chosen else ())


def no_stats() -> jax.Array:
    return jnp.zeros((STATS_WIDTH,), jnp.int32)


def prefill_stats(config: ModelConfig) -> tuple[str, ...]:
    """The prefill entries above by name, for the scheduler that reads
    them (BatchScheduler._count_moe; mixtral.prefill_stats)."""
    return ("assigned", "dropped", "routed", "rows")


no_touched = no_stats


# -- the stack ----------------------------------------------------------------

def _run_stack(params: dict, config: ModelConfig, h: jax.Array, block,
               counted: Optional[jax.Array], live: Optional[jax.Array],
               carry):
    """The leading dense layers unrolled, then the routed layers as one
    scan. ``block(h, lp, layer, mlp_fn, carry) -> (h, carry, ys)``;
    returns (h, carry, ys stacked over all layers or None, stats)."""
    Ld, Lm = _layer_counts(config)
    ys_dense = []
    for li in range(Ld):
        lp = _layer_view(params["dense_layers"], jnp.asarray(li, jnp.int32))
        h, carry, ys = block(h, lp, jnp.asarray(li, jnp.int32), _dense_mlp,
                             carry)
        ys_dense.append(ys)

    def body(state, i):
        h, carry, stats = state
        lp = _layer_view(params["layers"], i)
        more = []

        def mlp(m, lp):
            out, st = _routed_local(m, lp, config, counted, live)
            more.append(st)
            return _shared_mlp(m, lp) + out

        h, carry, ys = block(h, lp, i + Ld, mlp, carry)
        return (h, carry, stats + more[0]), ys

    (h, carry, stats), ys_moe = jax.lax.scan(
        body, (h, carry, no_stats()), jnp.arange(Lm, dtype=jnp.int32))
    ys = ys_moe
    if ys_dense and ys_dense[0] is not None:
        ys = jax.tree.map(
            lambda *a: jnp.concatenate([jnp.stack(a[:-1]), a[-1]]),
            *ys_dense, ys_moe)
    return h, carry, ys, stats


def _logits(params, config, h, last_idx):
    if last_idx is not None:
        h = jnp.take_along_axis(h, last_idx[:, None, None].astype(jnp.int32),
                                axis=1)
    h = rms_norm(h, params["final_norm"], config.rms_norm_eps)
    return mm(h, params["lm_head"]).astype(jnp.float32)


def _forward(params: dict, config: ModelConfig, tokens: jax.Array,
             cache: KVCache, offset: int, counted: Optional[jax.Array],
             last_idx: Optional[jax.Array], hidden: bool = False):
    """Tokens [B,S] at positions offset..offset+S against the dense
    carry ``cache`` (slots below ``offset`` hold the context's latents).
    Returns (logits | hidden states, cache, stats)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(offset + jnp.arange(S)[None, :], (B, S))
    h = params["embed"][tokens]

    def block(h, lp, layer, mlp_fn, carry):
        ck, cv = carry
        h, ck, cv = _block_expanded(h, lp, config, positions, ck, cv, layer,
                                    offset, mlp_fn)
        return h, (ck, cv), None

    h, (ck, cv), _, stats = _run_stack(params, config, h, block, counted,
                                       None, (cache.k, cache.v))
    cache = KVCache(ck, cv, cache.lengths)
    if hidden:
        return rms_norm(h, params["final_norm"], config.rms_norm_eps), \
            cache, stats
    return _logits(params, config, h, last_idx), cache, stats


def _refuse_mesh(mesh) -> None:
    if mesh is not None:
        raise ValueError("the latent-attention family serves on one chip: "
                         "its cache has one head, which a mesh cannot "
                         "split by heads")


def forward_counted(params: dict, config: ModelConfig, tokens: jax.Array,
                    positions: jax.Array, cache: KVCache, mask,
                    valid: jax.Array, mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    last_idx: Optional[jax.Array] = None, **_):
    """The other families' ``forward_counted`` for the one use the
    scheduler has: tokens at the LAST S slots of the carry, behind a
    context of ``W - S`` rows already in it (a cached prefix), causal.
    ``positions`` and ``mask`` say the same and are not read."""
    _refuse_mesh(mesh)
    offset = cache.k.shape[2] - tokens.shape[1]
    return _forward(params, config, tokens, cache, offset, valid, last_idx)


def forward(params: dict, config: ModelConfig, tokens: jax.Array,
            positions: jax.Array, cache: KVCache, mask,
            mesh: Optional[Mesh] = None,
            rules: LogicalRules = DEFAULT_RULES,
            last_idx: Optional[jax.Array] = None, **_):
    return forward_counted(params, config, tokens, positions, cache, mask,
                           None, mesh, rules, last_idx)[:2]


def prefill_counted(params: dict, config: ModelConfig, tokens: jax.Array,
                    prompt_lens: jax.Array, cache: KVCache,
                    valid: Optional[jax.Array],
                    mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    last_only: bool = False, **_):
    """llama.prefill's contract (right-padded prompts from position 0),
    and third the counts over ``valid``."""
    _refuse_mesh(mesh)
    logits, cache, stats = _forward(
        params, config, tokens, cache, 0, valid,
        prompt_lens - 1 if last_only else None)
    return (logits, cache._replace(lengths=prompt_lens.astype(jnp.int32)),
            stats)


def prefill(params: dict, config: ModelConfig, tokens: jax.Array,
            prompt_lens: jax.Array, cache: KVCache,
            mesh: Optional[Mesh] = None,
            rules: LogicalRules = DEFAULT_RULES,
            last_only: bool = False, **_):
    return prefill_counted(params, config, tokens, prompt_lens, cache, None,
                           mesh, rules, last_only)[:2]


def prefill_chunk_counted(params: dict, config: ModelConfig,
                          tokens: jax.Array, cache: KVCache, offset: int,
                          valid: Optional[jax.Array],
                          mesh: Optional[Mesh] = None,
                          rules: LogicalRules = DEFAULT_RULES,
                          last_idx: Optional[jax.Array] = None, **_):
    """llama.prefill_chunk's contract (C tokens a row at positions
    offset..offset+C, resuming from the latents in ``cache``; lengths
    untouched), except that the chunk attends the ``offset + C`` rows
    there are and not the carry's whole width: expanding a row costs a
    matmul here, and rows not yet written would be expanded for a
    probability of zero."""
    _refuse_mesh(mesh)
    return _forward(params, config, tokens, cache, int(offset), valid,
                    last_idx)


def prefill_chunk(params: dict, config: ModelConfig, tokens: jax.Array,
                  cache: KVCache, offset: int,
                  mesh: Optional[Mesh] = None,
                  rules: LogicalRules = DEFAULT_RULES,
                  last_idx: Optional[jax.Array] = None, **_):
    return prefill_chunk_counted(params, config, tokens, cache, offset,
                                 None, mesh, rules, last_idx)[:2]


def embed_pooled(params: dict, config: ModelConfig, tokens: jax.Array,
                 lens: jax.Array, mesh: Optional[Mesh] = None,
                 rules: LogicalRules = DEFAULT_RULES) -> jax.Array:
    """llama.embed_pooled over this family's trunk."""
    B, S = tokens.shape
    cache = KVCache.create(config, B, S, dtype=params["embed"].dtype)
    h, _, _ = _forward(params, config, tokens, cache, 0, None, None,
                       hidden=True)
    h = h.astype(jnp.float32)
    valid = (jnp.arange(S)[None, :] < lens[:, None]).astype(jnp.float32)
    pooled = (h * valid[:, :, None]).sum(axis=1) / jnp.maximum(
        lens[:, None].astype(jnp.float32), 1.0)
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-9)


# -- decode -------------------------------------------------------------------

def decode_step_paged_touched(params: dict, config: ModelConfig,
                              tokens: jax.Array, cache,
                              mesh: Optional[Mesh] = None,
                              rules: LogicalRules = DEFAULT_RULES,
                              active: Optional[jax.Array] = None,
                              *, pages: int):
    """One autoregressive step over the paged latent pool, in the
    absorbed form (llama.decode_step_paged's contract: tokens [B,1],
    parked rows hold position and write to the garbage page). Returns
    (logits [B,1,V], cache with lengths advanced where active, counts
    int32 [4] over the live rows)."""
    from ..ops.paged_kv import write_decode_burst
    _refuse_mesh(mesh)
    B = tokens.shape[0]
    positions = cache.lengths[:, None]
    h = params["embed"][tokens]
    live = jnp.ones((B,), bool) if active is None else active
    inc = live.astype(jnp.int32)

    def block(h, lp, layer, mlp_fn, carry):
        h, c_cur, r_cur = _block_absorbed(h, lp, config, positions, cache,
                                          layer, pages, mlp_fn)
        return h, carry, (c_cur[:, 0], r_cur[:, 0])

    h, _, (c_all, r_all), stats = _run_stack(params, config, h, block, None,
                                             live, ())
    return (_logits(params, config, h, None),
            write_decode_burst(cache, c_all, r_all, inc), stats)


def verify_step_paged(params: dict, config: ModelConfig, tokens: jax.Array,
                      cache, mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES, *, pages: int,
                      last_idx: Optional[jax.Array] = None, **_):
    """llama.verify_step_paged's contract (S positions a row behind the
    row's pool context at its dynamic length, their latents written at
    lengths..lengths+S, lengths unchanged), for the one use this family
    has: a parked session's wake (serve/scheduler.py kv_wake).
    Speculation is refused at boot."""
    from ..ops.paged_kv import write_decode_multi_all_layers
    _refuse_mesh(mesh)
    B, S = tokens.shape
    positions = cache.lengths[:, None] + jnp.arange(S)[None, :]
    h = params["embed"][tokens]

    def block(h, lp, layer, mlp_fn, carry):
        h, c_blk, r_blk = _block_absorbed(h, lp, config, positions, cache,
                                          layer, pages, mlp_fn)
        return h, carry, (c_blk, r_blk)

    h, _, (c_all, r_all), _ = _run_stack(params, config, h, block, None,
                                         None, ())
    return (_logits(params, config, h, last_idx),
            write_decode_multi_all_layers(cache, c_all, r_all))


def decode_step_paged(params: dict, config: ModelConfig, tokens: jax.Array,
                      cache, mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES,
                      active: Optional[jax.Array] = None, *, pages: int):
    return decode_step_paged_touched(params, config, tokens, cache, mesh,
                                     rules, active, pages=pages)[:2]


def decode_fused_touched(params: dict, config: ModelConfig,
                         tokens: jax.Array, cache,
                         mesh: Optional[Mesh] = None,
                         rules: LogicalRules = DEFAULT_RULES,
                         active: Optional[jax.Array] = None, *,
                         num_steps: int, sample_fn, sample_state, stop_ids,
                         kv_window: Optional[int] = None,
                         pages: Optional[int] = None):
    """llama.decode_fused_aux over this family's paged step, the counts
    summed over the steps."""
    from .llama import decode_fused_aux
    if pages is None:
        raise ValueError("the latent-attention family decodes from the "
                         "paged pool only")

    def step_fn(params, config, tokens, cache, mesh, rules, aux, *, active,
                pages):
        logits, cache, st = decode_step_paged_touched(
            params, config, tokens, cache, mesh, rules, active, pages=pages)
        return logits, cache, aux + st

    return decode_fused_aux(params, config, tokens, cache, step_fn,
                            no_stats(), mesh, rules, active,
                            num_steps=num_steps, sample_fn=sample_fn,
                            sample_state=sample_state, stop_ids=stop_ids,
                            pages=pages)


def decode_fused(params: dict, config: ModelConfig, tokens: jax.Array,
                 cache, mesh: Optional[Mesh] = None,
                 rules: LogicalRules = DEFAULT_RULES,
                 active: Optional[jax.Array] = None, **kw):
    return decode_fused_touched(params, config, tokens, cache, mesh, rules,
                                active, **kw)[:-1]

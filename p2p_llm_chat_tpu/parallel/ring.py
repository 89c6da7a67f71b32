"""Ring attention + sequence-parallel serving over the ``sp`` mesh axis.

Long-context support the reference delegates to Ollama wholesale (it never
even sends history — web/streamlit_app.py:93 wraps one message in a fixed
template). TPU-native design instead of a port:

- **Prefill** (:func:`ring_prefill`): the prompt's sequence dim is sharded
  over ``sp`` via ``shard_map``; every device runs the full layer stack on
  its chunk while k/v chunks rotate around the ring with
  ``jax.lax.ppermute`` — classic ring attention (flash/online-softmax
  accumulation in f32, one hop per step, comms overlapped with the chunk
  matmuls by XLA's async collectives). HBM per device holds 1/sp of the
  activations and KV, so max context scales linearly with the ring size.
- **Decode** (:func:`sp_decode_step`): the KV cache stays sequence-sharded
  after prefill. Each device computes partial flash statistics (m, l, acc)
  of the one query token against its local KV shard; the partials merge
  with one ``pmax`` + two ``psum``s (the distributed-softmax reduction —
  an "all-to-all" sequence-parallel decode, comms O(B·Hq·D) per step,
  independent of context length).

Both paths are numerically identical (f32 softmax statistics) to the dense
single-device oracle in models/llama.py — pinned by tests/test_ring.py on
the virtual CPU mesh and the driver's ``dryrun_multichip``.

**TP×SP composition**: a ``tp`` axis alongside ``sp`` shards heads and
the MLP intermediate Megatron-style INSIDE the shard_map body — q/k/v
projections are column-sharded (each tp device runs the ring over its
own kv-head group; ring hops move 1/tp of the kv bytes), and the output/
down projections are row-sharded with one ``psum`` over ``tp`` each.
This is the configuration a 70B-class long-context deployment needs:
the sequence dim scales context over sp while tp keeps the per-device
weight shard small. Params must be sharded with :func:`ring_param_specs`
(embed/lm_head replicated — the vocab-sharded embedding gather is not
worth the masked-gather+psum inside this path).

**SP×EP composition** (long-context Mixtral): an ``ep`` axis alongside
``sp`` shards the expert-stacked FFN weights; each device's sequence
chunk is replicated across its ep group, so routing is computed
identically everywhere, every device dispatches its chunk's tokens into
ONLY its local experts' capacity buckets (:func:`moe_ring_mlp_fn`), and
one ``psum`` over ep combines — tokens never move between devices, only
the O(B·Sl·H) combine does. MoE under ``tp`` inside the ring remains
future work (expert weights already shard over ("ep","tp") in the
non-ring path, parallel/sharding.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..models.configs import ModelConfig
from ..models.layers import NEG_INF, apply_rope, rms_norm, rope_frequencies
from ..models.llama import KVCache
from ..models.quant import mm
from .sharding import DEFAULT_RULES, tree_specs

# Logical rules for the ring path: attention/MLP tp-sharded as usual,
# embeddings and lm_head replicated (the device_fn gathers/projects the
# full vocab; h is tp-replicated after each block's psum).
RING_RULES = dict(DEFAULT_RULES, vocab=None, act_vocab=None)


def ring_param_specs(axes_tree) -> object:
    """PartitionSpec tree for ring-path params (models/*.param_axes ->
    specs under RING_RULES). Shard params with these before calling
    ring_prefill/sp_decode_step on a tp>1 mesh; the shard_map in_specs
    use the same tree, so layouts always agree."""
    return tree_specs(axes_tree, RING_RULES)


def _attn_qkv_local(h, lp, config: ModelConfig, inv_freq, positions):
    """Pre-norm + q/k/v projections + rope on LOCAL head shards: under
    tp the weight columns arriving here are this device's head group, so
    head counts come from the projection widths, not config (llama's
    _attn_qkv reshapes with the global config.num_heads)."""
    B, S, _ = h.shape
    D = config.head_dim
    x = rms_norm(h, lp["attn_norm"], config.rms_norm_eps)
    q = mm(x, lp["wq"]).reshape(B, S, -1, D)
    k = mm(x, lp["wk"]).reshape(B, S, -1, D)
    v = mm(x, lp["wv"]).reshape(B, S, -1, D)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    return q, k, v


def _post_attn_tp(h, attn, lp, config: ModelConfig, mlp_fn,
                  tp_axis: Optional[str]):
    """Output projection + residual + MLP + residual with row-sharded
    wo/w_down under tp: one psum after each row-sharded matmul (the
    Megatron pattern, written explicitly because shard_map bodies use
    collectives, not sharding constraints)."""
    B, S = attn.shape[:2]
    attn = attn.reshape(B, S, -1)
    o = mm(attn, lp["wo"])
    if tp_axis is not None:
        o = jax.lax.psum(o, tp_axis)
    h = h + o
    x = rms_norm(h, lp["mlp_norm"], config.rms_norm_eps)
    if mlp_fn is not None:
        mlp = mlp_fn(x, lp, None, {})
    else:
        g = jax.nn.silu(mm(x, lp["w_gate"])) * mm(x, lp["w_up"])
        mlp = mm(g, lp["w_down"])
        if tp_axis is not None:
            mlp = jax.lax.psum(mlp, tp_axis)
    return h + mlp


def moe_ring_mlp_fn(config: ModelConfig, ep_axis: Optional[str]):
    """Sparse-MoE MLP for the ring/sp shard_map body with experts sharded
    over ``ep_axis`` (None = experts replicated, sp-only).

    The device's sequence chunk is replicated across its ep group
    (ring_prefill's in_specs shard tokens over sp only), so every device
    computes identical routing, scatters its chunk's tokens into its
    LOCAL experts' buckets (the same scatter/gather dispatch as
    models/mixtral.moe_mlp, bucketed by local expert id), runs its
    expert shard's FFNs, and the per-token combine psums over ep —
    non-owners contribute exact zeros via the fill-gather. Math matches
    mixtral.moe_mlp exactly (dropless: C = T bounds every expert's
    assignment count).
    """
    from ..models.quant import q_einsum

    k = config.num_experts_per_tok
    ne_total = config.num_experts

    def fn(x, lp, _mesh, _rules):
        B, S, H = x.shape
        T = B * S
        w_gate = lp["w_gate"]                    # [NE_local, H, F] shard
        ne_local = (w_gate.q if hasattr(w_gate, "q") else w_gate).shape[0]
        xt = x.reshape(T, H)
        logits = xt.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                # [T, NE]
        top_w, top_i = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

        # Position-in-expert over GLOBAL expert ids — identical on every
        # ep device, so bucket slots agree without communication.
        sel = jax.nn.one_hot(top_i, ne_total, dtype=jnp.int32)
        flat = sel.reshape(T * k, ne_total)
        pos = jnp.cumsum(flat, axis=0) - flat
        slot = jnp.sum(flat * pos, axis=-1)                    # [T*k]
        expert = top_i.reshape(T * k)
        base = (jax.lax.axis_index(ep_axis) * ne_local
                if ep_axis is not None else 0)
        local_e = expert - base
        owned = (local_e >= 0) & (local_e < ne_local)
        C = T                                    # dropless: slot < T
        idx = jnp.where(owned, local_e * C + slot, ne_local * C)

        x_rep = jnp.repeat(xt, k, axis=0)                      # [T*k, H]
        xin = jnp.zeros((ne_local * C, H), xt.dtype).at[idx].set(
            x_rep, mode="drop").reshape(ne_local, C, H)
        g = jax.nn.silu(q_einsum("ech,ehf->ecf", xin, lp["w_gate"]))
        u = q_einsum("ech,ehf->ecf", xin, lp["w_up"])
        y = q_einsum("ecf,efh->ech", g * u, lp["w_down"])      # [NEl,C,H]
        gathered = jnp.take(y.reshape(ne_local * C, H), idx, axis=0,
                            mode="fill", fill_value=0)         # [T*k, H]
        out = jnp.sum(gathered.reshape(T, k, H).astype(jnp.float32)
                      * top_w[..., None], axis=1)
        if ep_axis is not None:
            out = jax.lax.psum(out, ep_axis)
        return out.astype(x.dtype).reshape(B, S, H)

    return fn


def _chunk_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """GQA scores of a q chunk against a kv chunk, f32 on the MXU.

    q: [B,Sq,Hq,D]; k: [B,Sk,Hkv,D]. Returns [B,G,rep,Sq,Sk]."""
    B, Sq, Hq, D = q.shape
    G = k.shape[2]
    rep = Hq // G
    qg = q.reshape(B, Sq, G, rep, D)
    s = jnp.einsum("bsgrd,btgd->bgrst", qg, k,
                   preferred_element_type=jnp.float32)
    return s / jnp.sqrt(D).astype(jnp.float32)


def _online_update(s: jax.Array, v: jax.Array, mask: jax.Array,
                   m: jax.Array, l: jax.Array, acc: jax.Array):
    """One flash-attention accumulation step.

    s: [B,G,rep,Sq,Sk] raw scores; v: [B,Sk,G,D]; mask broadcastable to s
    (True = attend); m,l: [B,G,rep,Sq]; acc: [B,G,rep,Sq,D] (all f32)."""
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])                       # [B,G,rep,Sq,Sk]
    l = l * alpha + p.sum(axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bgrst,btgd->bgrsd", p, v.astype(jnp.float32))
    return m_new, l, acc


def _ring_attend(q: jax.Array, k: jax.Array, v: jax.Array,
                 axis_name: str, sp: int) -> jax.Array:
    """Causal ring attention for one layer, inside shard_map.

    q/k/v: this device's sequence chunk [B,Sl,H*,D] (global positions
    ``my*Sl + i``). k/v make ``sp`` hops around the ring; each step masks
    by global causal order. Python loop — ``sp`` is static and small, and
    unrolling lets XLA overlap each hop's ppermute with the previous
    chunk's matmuls. Returns [B,Sl,Hq,D] in q.dtype."""
    B, Sl, Hq, D = q.shape
    G = k.shape[2]
    rep = Hq // G
    my = jax.lax.axis_index(axis_name)
    q_pos = my * Sl + jnp.arange(Sl)                        # [Sl] global

    m = jnp.full((B, G, rep, Sl), NEG_INF, jnp.float32)
    l = jnp.zeros((B, G, rep, Sl), jnp.float32)
    acc = jnp.zeros((B, G, rep, Sl, D), jnp.float32)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    for t in range(sp):
        src = (my - t) % sp                 # ring position of this kv chunk
        k_pos = src * Sl + jnp.arange(Sl)                   # [Sl] global
        s = _chunk_scores(q, k)
        mask = (k_pos[None, :] <= q_pos[:, None])[None, None, None]
        m, l, acc = _online_update(s, v, mask, m, l, acc)
        if t != sp - 1:
            k, v = jax.lax.ppermute((k, v), axis_name, perm)

    out = acc / l[..., None]                                # causal: l >= 1
    # [B,G,rep,Sl,D] -> [B,Sl,Hq,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sl, Hq, D).astype(q.dtype)


def _axes_for(config: ModelConfig):
    from ..models import family_for
    return family_for(config).param_axes(config)


def ring_prefill(params: dict, config: ModelConfig, tokens: jax.Array,
                 prompt_lens: jax.Array, mesh: Mesh,
                 mlp_fn=None) -> tuple[jax.Array, KVCache]:
    """Sequence-parallel prefill: the whole layer stack with the prompt
    sharded over ``sp`` and ring attention in place of dense attention.

    tokens: [B,S] right-padded, S divisible by sp; prompt_lens: [B].
    Returns (logits [B,S,vocab] f32 — sequence-sharded over sp — and a
    KVCache whose k/v [L,B,S,Hkv,D] are sharded on the sequence dim, ready
    for :func:`sp_decode_step`; its max_seq IS S, so budget S for prompt +
    generation). Numerics match models/llama.prefill (same f32 softmax).

    Cited contract: models/llama.py prefill — causal masking makes pad
    slots invisible to real queries; lengths gate decode.
    """
    sp = mesh.shape["sp"]
    tp = mesh.shape.get("tp", 1)
    ep = mesh.shape.get("ep", 1)
    assert mesh.size == sp * tp * ep, (
        f"ring path runs over sp (x tp | x ep) only "
        f"(mesh {dict(mesh.shape)}); set other axes to 1")
    assert tp == 1 or mlp_fn is None, \
        "MoE composes with the ring via ep (moe_ring_mlp_fn), not tp"
    assert ep == 1 or mlp_fn is not None, \
        "an ep axis shards experts; pass moe_ring_mlp_fn(config, 'ep')"
    assert config.num_kv_heads % tp == 0, (config.num_kv_heads, tp)
    B, S = tokens.shape
    assert S % sp == 0, f"seq {S} not divisible by sp={sp}"
    Sl = S // sp
    inv_freq = rope_frequencies(config)
    tp_axis = "tp" if tp > 1 else None

    def device_fn(params, tokens):
        # tokens: local chunk [B, Sl]; params: local tp head shards.
        my = jax.lax.axis_index("sp")
        positions = (my * Sl + jnp.arange(Sl))[None, :].astype(jnp.int32)
        positions = jnp.broadcast_to(positions, (B, Sl))
        h = params["embed"][tokens]

        def body(carry, xs):
            h, ck, cv = carry
            lp, layer = xs
            q, k, v = _attn_qkv_local(h, lp, config, inv_freq, positions)
            ck = jax.lax.dynamic_update_index_in_dim(ck, k, layer, 0)
            cv = jax.lax.dynamic_update_index_in_dim(cv, v, layer, 0)
            attn = _ring_attend(q, k, v, "sp", sp)
            h = _post_attn_tp(h, attn, lp, config, mlp_fn, tp_axis)
            return (h, ck, cv), None

        L = config.num_layers
        ck = jnp.zeros((L, B, Sl, config.num_kv_heads // tp,
                        config.head_dim), h.dtype)
        (h, ck, cv), _ = jax.lax.scan(
            body, (h, ck, jnp.zeros_like(ck)),
            (params["layers"], jnp.arange(L)))
        h = rms_norm(h, params["final_norm"], config.rms_norm_eps)
        lm_head = (params["embed"].T if config.tie_embeddings
                   else params["lm_head"])
        logits = mm(h, lm_head).astype(jnp.float32)
        return logits, ck, cv

    mapped = shard_map(
        device_fn, mesh=mesh,
        in_specs=(ring_param_specs(_axes_for(config)),
                  P(None, "sp")),
        out_specs=(P(None, "sp", None),
                   P(None, None, "sp", "tp" if tp > 1 else None, None),
                   P(None, None, "sp", "tp" if tp > 1 else None, None)),
        check_vma=False,
    )
    logits, ck, cv = mapped(params, tokens)
    return logits, KVCache(k=ck, v=cv,
                           lengths=prompt_lens.astype(jnp.int32))


def sp_decode_step(params: dict, config: ModelConfig, tokens: jax.Array,
                   cache: KVCache, mesh: Mesh,
                   active: Optional[jax.Array] = None,
                   mlp_fn=None) -> tuple[jax.Array, KVCache]:
    """One decode step against a sequence-sharded KV cache.

    Same contract as models/llama.decode_step (including the parked-row
    ``active`` semantics): each row writes cache slot ``lengths[b]`` —
    which lives on exactly one ring device; the others' out-of-range
    scatter indices are dropped — and attends to slots [0, lengths[b]]
    via per-device flash partials merged with pmax/psum. tokens: [B,1].
    Returns (logits [B,1,vocab] — replicated — and the advanced cache).
    """
    sp = mesh.shape["sp"]
    tp = mesh.shape.get("tp", 1)
    ep = mesh.shape.get("ep", 1)
    assert mesh.size == sp * tp * ep, "sp (x tp | x ep); see ring_prefill"
    assert tp == 1 or mlp_fn is None, \
        "MoE composes with the ring via ep (moe_ring_mlp_fn), not tp"
    assert ep == 1 or mlp_fn is not None, \
        "an ep axis shards experts; pass moe_ring_mlp_fn(config, 'ep')"
    B = tokens.shape[0]
    Sl = cache.k.shape[2] // sp
    inv_freq = rope_frequencies(config)
    tp_axis = "tp" if tp > 1 else None

    def device_fn(params, tokens, ck_all, cv_all, lengths):
        my = jax.lax.axis_index("sp")
        positions = lengths[:, None]                        # [B,1] global
        h = params["embed"][tokens]
        G, D = config.num_kv_heads // tp, config.head_dim
        rep = config.num_heads // config.num_kv_heads
        local_pos = jnp.arange(Sl) + my * Sl                # [Sl] global
        b_idx = jnp.arange(B)

        def body(carry, xs):
            h, ck, cv = carry
            lp, layer = xs
            q, k, v = _attn_qkv_local(h, lp, config, inv_freq, positions)
            # Scatter the new k/v at the owning device; everyone else's
            # local index is out of [0, Sl) and mode="drop" discards it.
            li = lengths - my * Sl                          # [B] local slot
            ck = ck.at[layer, b_idx, li].set(k[:, 0], mode="drop")
            cv = cv.at[layer, b_idx, li].set(v[:, 0], mode="drop")
            k_loc = jax.lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
            v_loc = jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)

            s = _chunk_scores(q, k_loc)                     # [B,G,rep,1,Sl]
            valid = (local_pos[None, :] < (lengths + 1)[:, None])  # [B,Sl]
            mask = valid[:, None, None, None, :]
            s = jnp.where(mask, s, NEG_INF)
            m_loc = s.max(axis=-1)                          # [B,G,rep,1]
            p = jnp.exp(s - m_loc[..., None])
            # Fully-masked shards contribute exp(NEG_INF - m_g) ~ 0.
            l_loc = jnp.where(m_loc > NEG_INF / 2,
                              p.sum(axis=-1), 0.0)
            acc_loc = jnp.einsum("bgrst,btgd->bgrsd", p,
                                 v_loc.astype(jnp.float32))
            m_g = jax.lax.pmax(m_loc, "sp")
            scale = jnp.where(m_loc > NEG_INF / 2,
                              jnp.exp(m_loc - m_g), 0.0)
            l_g = jax.lax.psum(l_loc * scale, "sp")
            acc_g = jax.lax.psum(acc_loc * scale[..., None], "sp")
            out = acc_g / l_g[..., None]                    # [B,G,rep,1,D]
            attn = out.transpose(0, 3, 1, 2, 4).reshape(
                B, 1, G * rep, D).astype(h.dtype)
            h = _post_attn_tp(h, attn, lp, config, mlp_fn, tp_axis)
            return (h, ck, cv), None

        (h, ck_all, cv_all), _ = jax.lax.scan(
            body, (h, ck_all, cv_all),
            (params["layers"], jnp.arange(config.num_layers)))
        h = rms_norm(h, params["final_norm"], config.rms_norm_eps)
        lm_head = (params["embed"].T if config.tie_embeddings
                   else params["lm_head"])
        logits = mm(h, lm_head).astype(jnp.float32)
        return logits, ck_all, cv_all

    kv_spec = P(None, None, "sp", "tp" if tp > 1 else None, None)
    mapped = shard_map(
        device_fn, mesh=mesh,
        in_specs=(ring_param_specs(_axes_for(config)), P(), kv_spec,
                  kv_spec, P()),
        out_specs=(P(), kv_spec, kv_spec),
        check_vma=False,
    )
    logits, ck, cv = mapped(params, tokens, cache.k, cache.v, cache.lengths)
    inc = (jnp.ones_like(cache.lengths) if active is None
           else active.astype(jnp.int32))
    return logits, KVCache(k=ck, v=cv, lengths=cache.lengths + inc)

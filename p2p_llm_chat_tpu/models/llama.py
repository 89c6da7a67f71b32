"""llama-family decoder (3.x dense models) — functional JAX, TPU-first.

Replaces the reference's out-of-tree Ollama llama3.1 backend
(web/streamlit_app.py:28, README.md:52) with an in-tree implementation.
Architecture: pre-norm transformer, RMSNorm, RoPE (llama3.1 NTK scaling),
grouped-query attention, SwiGLU MLP, optionally tied embeddings.

TPU-first choices:
- layers stacked on a leading axis, decoder body is one ``lax.scan`` —
  constant-size XLA graph regardless of depth (fast compiles for 80-layer
  70B), and scan keeps weights resident in HBM with no per-layer dispatch.
- dense KV cache ``[L, B, max_seq, Hkv, D]`` with ragged per-row lengths;
  decode writes one slot via a batched scatter and masks by length. (The
  serving engine swaps this for the paged Pallas cache; this dense path is
  the reference implementation and the test oracle.)
- bf16 activations/weights, f32 softmax/norms; one all-reduce per block
  under tensor parallelism (Megatron layout — see parallel/sharding.py).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..parallel.sharding import LogicalRules, DEFAULT_RULES, constrain
from .configs import ModelConfig
from .quant import LayerSlice, QTensor, QTensor4, mm
from .layers import (
    DEFAULT_COMPUTE_DTYPE,
    apply_rope,
    attend_gqa,
    attend_gqa_auto,
    causal_mask,
    length_mask,
    rms_norm,
    rope_frequencies,
    swiglu,
)


class KVCache(NamedTuple):
    """k/v: [L, B, max_seq, Hkv, D]; lengths: [B] valid slots per row.
    (A latent-attention model keeps one head: its normed latent in ``k``
    and its shared rotated key in ``v``, of different widths:
    ``ModelConfig.cache_*``. A hybrid model's ``L`` is its attention
    layers alone, and ``state`` the recurrent layers' state and the window
    layers' rings, a row an entry, zero at the start:
    ops/state_pool.StatePool. None otherwise. ``idx``: an indexed
    model's index keys, [L, B, max_seq, index_head_dim], a third kind of
    per-token past beside K and V (models/nemotron_h.py's ``s`` layers);
    None otherwise.)"""

    k: jax.Array
    v: jax.Array
    lengths: jax.Array
    state: Optional[Any] = None
    idx: Optional[jax.Array] = None

    @classmethod
    def create(cls, config: ModelConfig, batch: int, max_seq: int,
               dtype=DEFAULT_COMPUTE_DTYPE) -> "KVCache":
        lead = (config.cache_layers, batch, max_seq, config.cache_kv_heads)
        state = None
        if config.state_layers:
            from ..ops.state_pool import StatePool
            state = StatePool.create(config, batch, dtype)
        idx = None
        if config.is_indexed:
            idx = jnp.zeros(lead[:3] + (1, config.cache_idx_dim), dtype)
        return cls(k=jnp.zeros(lead + (config.cache_k_dim,), dtype),
                   v=jnp.zeros(lead + (config.cache_v_dim,), dtype),
                   lengths=jnp.zeros((batch,), jnp.int32), state=state,
                   idx=idx)


# -- parameters ---------------------------------------------------------------

def qk_norm_leaves(config: ModelConfig, key: jax.Array, dtype) -> dict:
    """The whole-projection QK-norm weights (``config.qk_norm_whole``:
    OLMoE): ``q_norm`` [L, q_dim], ``k_norm`` [L, kv_dim]. Empty for a
    configuration without it, so every initialiser of both families can
    ``update`` its layer tree with it. Drawn from [0.5, 1.5), not ones:
    under the scaled-normal init a projection already has unit RMS, so
    a norm of ones is nearly the identity and a model that left it out
    would pass every comparison with a reference."""
    if not config.qk_norm_whole:
        return {}
    kq, kk = jax.random.split(key)
    L = config.num_layers

    def draw(k, n):
        return (0.5 + jax.random.uniform(k, (L, n), jnp.float32)
                ).astype(dtype)
    return {"q_norm": draw(kq, config.q_dim),
            "k_norm": draw(kk, config.kv_dim)}


QK_NORM_AXES = {"q_norm": (None, "heads"), "k_norm": (None, "kv_heads")}


def loop_leaves(config: ModelConfig, key: jax.Array, dtype) -> tuple:
    """(layer leaves, top-level leaves) of a sandwich-normed, looped stack
    (Ouro): ``attn_out_norm`` / ``mlp_out_norm`` [L, H], the norms on each
    branch's OUTPUT (``config.sandwich_norm``), and ``exit_gate_w`` [H, 1]
    / ``exit_gate_b`` [1], the exit gate that reads every pass's normed
    output (``config.ut_steps`` > 1). Both empty otherwise, so the
    initialisers ``update`` their trees with them. The norms are drawn
    from [0.5, 1.5), as :func:`qk_norm_leaves`'s are and for its reason;
    the gate's logit has unit spread about 0.5 over unit-RMS inputs, so
    that a gate of zeros (every pass 0.5) reads as another model."""
    layer, top = {}, {}
    L, H = config.num_layers, config.hidden_size
    ka, km, kw = jax.random.split(key, 3)
    if config.sandwich_norm:
        def draw(k):
            return (0.5 + jax.random.uniform(k, (L, H), jnp.float32)
                    ).astype(dtype)
        layer = {"attn_out_norm": draw(ka), "mlp_out_norm": draw(km)}
    if config.ut_steps > 1:
        top = {"exit_gate_w": (jax.random.normal(kw, (H, 1), jnp.float32)
                               * H ** -0.5).astype(dtype),
               "exit_gate_b": jnp.full((1,), 0.5, dtype)}
    return layer, top


LOOP_LAYER_AXES = {"attn_out_norm": (None, "embed"),
                   "mlp_out_norm": (None, "embed")}
LOOP_TOP_AXES = {"exit_gate_w": ("embed", None), "exit_gate_b": (None,)}


def init_params(config: ModelConfig, key: jax.Array,
                dtype=DEFAULT_COMPUTE_DTYPE) -> dict:
    """Random init (scaled normal). Real weights come from
    models/weights.py; random init serves tests and synthetic benches."""
    ks = jax.random.split(key, 10)
    L, H, E = config.num_layers, config.hidden_size, config.intermediate_size
    std = H ** -0.5

    def normal(k, shape, scale=std):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    params = {
        "embed": normal(ks[0], (config.vocab_size, H), scale=1.0),
        "layers": {
            "attn_norm": jnp.ones((L, H), dtype),
            "wq": normal(ks[1], (L, H, config.q_dim)),
            "wk": normal(ks[2], (L, H, config.kv_dim)),
            "wv": normal(ks[3], (L, H, config.kv_dim)),
            "wo": normal(ks[4], (L, config.q_dim, H)),
            "mlp_norm": jnp.ones((L, H), dtype),
            "w_gate": normal(ks[5], (L, H, E)),
            "w_up": normal(ks[6], (L, H, E)),
            "w_down": normal(ks[7], (L, E, H)),
        },
        "final_norm": jnp.ones((H,), dtype),
    }
    params["layers"].update(qk_norm_leaves(config, ks[9], dtype))
    # A key of its own, so that no other leaf's draw moves.
    layer, top = loop_leaves(config, jax.random.fold_in(ks[9], 2), dtype)
    params["layers"].update(layer)
    params.update(top)
    if not config.tie_embeddings:
        params["lm_head"] = normal(ks[8], (H, config.vocab_size))
    return params


def init_params_quantized(config: ModelConfig, key: jax.Array,
                          dtype=DEFAULT_COMPUTE_DTYPE,
                          quant: str = "int8") -> dict:
    """Random init streamed straight into quantized tensors, one layer
    at a time — the bf16 tree is never materialised. ``quant``:
    ``int8`` (per-channel QTensor) or ``int4`` (group-wise QTensor4 —
    packed nibbles, HALF the int8 footprint again; leaves whose
    contraction dim cannot group fall back to int8 per
    quant._quantize_leaf).

    Why: ``init_params`` + ``quantize_params`` peaks at the full bf16
    model (~16 GB for llama3.1-8B), which cannot fit a single v5e chip's
    16 GB HBM even though the int8 model (~8.6 GB with bf16 embeddings)
    plus an int8 KV pool does. This builds the stacked int8 leaves with
    a donated per-layer write loop (one dispatch per layer), so peak
    extra memory is one layer's bf16 leaves (~0.3 GB at 8B).

    The projection pairs are generated ALREADY FUSED (wqkv / wgu —
    models/llama.fuse_params' layout), so ``fuse_params`` is a no-op on
    the result and no second copy of the weights ever exists; the same
    numerics path as fused+quantized serving. Distribution matches
    init_params' scaled normal (different RNG stream). Synthetic-bench /
    random-init serving only — real checkpoints stream through
    models/weights.py.
    """
    from .quant import _quantize_leaf, stream_bufs

    if quant not in ("int8", "int4"):
        raise ValueError(f"quant must be int8|int4, got {quant!r}")
    L, H, E = config.num_layers, config.hidden_size, config.intermediate_size
    std = H ** -0.5
    key, k_embed, k_head = jax.random.split(key, 3)

    def normal(k, shape, scale=std, dt=dtype):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    dims = {
        "wqkv": (H, config.q_dim + 2 * config.kv_dim),
        "wo": (config.q_dim, H),
        "wgu": (H, 2 * E),
        "w_down": (E, H),
    }
    layers: dict = {
        "attn_norm": jnp.ones((L, H), dtype),
        "mlp_norm": jnp.ones((L, H), dtype),
        # A key of its own, so that no other leaf's draw moves.
        **qk_norm_leaves(config, jax.random.fold_in(k_head, 1), dtype),
    }
    loop_layer, loop_top = loop_leaves(config, jax.random.fold_in(k_head, 2),
                                       dtype)
    layers.update(loop_layer)
    for name, (din, dout) in dims.items():
        layers[name] = stream_bufs(L, (din, dout), quant)

    import functools

    @functools.partial(jax.jit, donate_argnums=(0,))
    def write_layer(bufs: dict, k: jax.Array, layer: jax.Array) -> dict:
        ks = jax.random.split(k, len(dims))
        out = dict(bufs)
        for i, (name, (din, dout)) in enumerate(dims.items()):
            qt = _quantize_leaf(normal(ks[i], (din, dout)), quant)
            out[name] = type(qt)(q=bufs[name].q.at[layer].set(qt.q),
                                 s=bufs[name].s.at[layer].set(qt.s))
        return out

    bufs = {name: layers[name] for name in dims}
    layer_keys = jax.random.split(key, L)
    for li in range(L):
        bufs = write_layer(bufs, layer_keys[li], jnp.asarray(li))
    layers.update(bufs)

    params = {
        "embed": normal(k_embed, (config.vocab_size, H), scale=1.0),
        "layers": layers,
        "final_norm": jnp.ones((H,), dtype),
        **loop_top,
    }
    if not config.tie_embeddings:
        params["lm_head"] = _quantize_leaf(
            normal(k_head, (H, config.vocab_size)), quant)
    jax.block_until_ready(params)
    return params


def fuse_tp_for(config: ModelConfig, mesh: Optional[Mesh]) -> int:
    """Device-block count of the fused-projection column layout under a
    mesh — the single decision point shared by :func:`fuse_params` (which
    builds the layout) and the extraction sites in :func:`_attn_qkv` /
    ``_default_mlp`` (which must unpack the same layout). 1 = the plain
    ``[q | k | v]`` concatenation; ``tp`` = per-device interleaved blocks
    ``[q_0|k_0|v_0 | q_1|k_1|v_1 | ...]`` so sharding the fused column
    axis over tp keeps every device's block exactly its own head/ffn
    columns (a plain concat sharded over tp would split mid-tensor).
    Falls back to 1 when any fused dimension doesn't divide tp (tiny test
    configs; production dims always divide)."""
    if mesh is None or "tp" not in mesh.shape:
        return 1
    t = mesh.shape["tp"]
    if t <= 1:
        return 1
    if (config.num_heads % t or config.num_kv_heads % t
            or config.intermediate_size % t):
        return 1
    return t


def fuse_params(params: dict, tp: int = 1, mesh: Optional[Mesh] = None,
                rules: LogicalRules = DEFAULT_RULES) -> dict:
    """Concatenate per-layer ``wq|wk|wv -> wqkv`` and ``w_gate|w_up ->
    wgu`` so a decode step runs 4 weight matmuls per layer instead of 7.

    Why: decode is HBM-bandwidth-bound, and on a v5e chip the measured
    per-matmul-call fixed cost (kernel entry + tile pipeline fill) is what
    keeps the weight stream below the bandwidth bound — fusing the
    column-parallel pairs cut the matmul floor of a bench-1b step by
    ~20% (builder-reported, before PR 1). The math is identical:
    the fused weight's output columns are a permutation of the originals',
    and int8 per-output-channel scales permute with them
    (models/quant.QTensor stores s per output column).

    Under tensor parallelism pass ``tp = fuse_tp_for(config, mesh)`` and
    the mesh: columns interleave as per-device blocks (see fuse_tp_for)
    and the fused leaves are device_put with the fused column axis
    sharded over tp — each device's shard is exactly its own q/k/v (or
    gate/up) columns, so TP serving keeps the fused-matmul win instead
    of giving it up.

    Works on bf16 arrays and QTensors alike; no-op if already fused.

    CAVEAT: the layout is derived from (config, mesh) at every use site
    (fuse_tp_for), not recorded on the params — running tp-fused params
    through a forward with a DIFFERENT mesh (or none) unpacks the wrong
    interleave and silently scrambles head columns. The serving
    scheduler, the only production composition point, fuses and runs
    under the same mesh object by construction; keep it that way.
    """
    layers = params["layers"]
    if "wqkv" in layers:
        if tp > 1:
            raise ValueError(
                "params are already fused in the plain [q|k|v] layout; "
                "they cannot be re-laid-out for tp>1 (unpacking would "
                "scramble head columns). Fuse from unfused weights under "
                "the mesh instead.")
        return params

    def cat(ws):
        """Interleaved per-device concat: [L, H, C_i] -> per-device
        column blocks [L, H, tp, C_i/tp] concatenated on the block
        axis -> [L, H, sum(C_i)]. tp=1 degenerates to a plain concat."""
        def icat(arrs):
            if tp == 1:
                return jnp.concatenate(arrs, axis=-1)
            blk = [a.reshape(*a.shape[:-1], tp, a.shape[-1] // tp)
                   for a in arrs]
            out = jnp.concatenate(blk, axis=-1)
            return out.reshape(*out.shape[:-2], -1)

        if isinstance(ws[0], (QTensor, QTensor4)):
            # Both precisions concat on the OUT axis: int8 scales ride
            # their columns; int4's packed rows and group scales share
            # the contraction layout, so columns concat the same way.
            return type(ws[0])(q=icat([w.q for w in ws]),
                               s=icat([w.s for w in ws]))
        return icat(ws)

    fuse_mlp = layers["w_gate"].ndim == 3   # dense [L,H,E]
    # MoE 4-D per-expert ffn leaves fuse into "wgu_e" [L,NE,H,2F] on the
    # single-chip path only (models/mixtral.moe_mlp runs gate+up as one
    # batched einsum). Under a mesh they stay separate: the expert axis
    # shards over ("ep","tp") and the ring path (parallel/ring.py
    # moe_ring_mlp_fn) reads w_gate/w_up by name from its local shard.
    fuse_moe = (not fuse_mlp and layers["w_gate"].ndim == 4
                and tp == 1 and mesh is None)
    drop = ("wq", "wk", "wv") + (("w_gate", "w_up")
                                 if (fuse_mlp or fuse_moe) else ())
    fused = {k: v for k, v in layers.items() if k not in drop}
    fused["wqkv"] = cat([layers["wq"], layers["wk"], layers["wv"]])
    if fuse_mlp:
        fused["wgu"] = cat([layers["w_gate"], layers["w_up"]])
    if fuse_moe:
        fused["wgu_e"] = cat([layers["w_gate"], layers["w_up"]])
    if mesh is not None and tp > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        tp_ax = rules.get("heads", "tp")
        def put(leaf):
            def put_arr(a):
                spec = [None] * (a.ndim - 1) + [tp_ax]
                return jax.device_put(a, NamedSharding(mesh, P(*spec)))
            if isinstance(leaf, (QTensor, QTensor4)):
                return type(leaf)(q=put_arr(leaf.q), s=put_arr(leaf.s))
            return put_arr(leaf)

        fused["wqkv"] = put(fused["wqkv"])
        if fuse_mlp:
            fused["wgu"] = put(fused["wgu"])
    out = dict(params)
    out["layers"] = fused
    return out


def param_axes(config: ModelConfig) -> dict:
    """Logical-axis tree matching init_params (leading layer axis on stacked
    leaves is unsharded). Feed to parallel.sharding.shard_params."""
    axes = {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": (None, "embed"),
            "wq": (None, "embed", "heads"),
            "wk": (None, "embed", "kv_heads"),
            "wv": (None, "embed", "kv_heads"),
            "wo": (None, "heads", "embed"),
            "mlp_norm": (None, "embed"),
            "w_gate": (None, "embed", "mlp"),
            "w_up": (None, "embed", "mlp"),
            "w_down": (None, "mlp", "embed"),
        },
        "final_norm": ("embed",),
    }
    if config.qk_norm_whole:
        axes["layers"].update(QK_NORM_AXES)
    if config.sandwich_norm:
        axes["layers"].update(LOOP_LAYER_AXES)
    if config.ut_steps > 1:
        axes.update(LOOP_TOP_AXES)
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# -- forward ------------------------------------------------------------------

def _layer_view(layers: dict, layer: jax.Array) -> dict:
    """One layer's view of the stacked layer tree, for a scan body that
    iterates ``layer`` indices instead of scanning over the weights.

    Why not scan xs: scan's per-iteration slicing of the stacked weights
    materialises each layer's slice before the Pallas w8a16 matmul
    (custom-call operands cannot alias a slice view) — measured at ~1.9 ms
    of a 3.8 ms bench-1b decode step, half the step. Stacked quantized
    matmul weights therefore stay WHOLE here, wrapped as
    :class:`~.quant.LayerSlice` so ``mm`` / ``q_einsum`` feed them to the
    layer-indexed kernels (ops/quant_mm.quant_matmul_stacked and the
    4-D expert twin quant_matmul_experts_stacked — before round-18 the
    expert stacks were sliced eagerly here, which bypassed the Pallas
    path for every MoE expert matmul); everything else (norms, bf16
    weights) is sliced lazily — XLA fuses those slices into their
    consumers for free.
    """
    out = {}
    for k, v in layers.items():
        if isinstance(v, (QTensor, QTensor4)):
            if v.q.ndim >= 3:
                out[k] = LayerSlice(v, layer)
            else:
                out[k] = type(v)(
                    q=jax.lax.dynamic_index_in_dim(v.q, layer, 0, False),
                    s=jax.lax.dynamic_index_in_dim(v.s, layer, 0, False))
        else:
            out[k] = jax.lax.dynamic_index_in_dim(v, layer, 0, False)
    return out


def _default_mlp(x: jax.Array, lp: dict, mesh: Optional[Mesh],
                 rules: LogicalRules,
                 config: Optional[ModelConfig] = None) -> jax.Array:
    if "wgu" in lp:                      # fused gate|up (fuse_params)
        gu = mm(x, lp["wgu"])
        E = gu.shape[-1] // 2
        t = fuse_tp_for(config, mesh) if config is not None else 1
        if t > 1:
            # per-device interleaved fused layout (fuse_tp_for): unpack
            # within each device block; gate/up land in natural order
            # because gate columns are dealt to devices contiguously.
            lead = gu.shape[:-1]
            blk = gu.reshape(*lead, t, 2 * E // t)
            Ed = E // t
            g_, u_ = blk[..., :Ed], blk[..., Ed:]
            gu2 = jax.nn.silu(g_) * u_
            h = gu2.reshape(*lead, E)
            h = constrain(h, mesh, ("batch", None, "act_mlp"), rules)
            return mm(h, lp["w_down"])
        g = jax.nn.silu(gu[..., :E]) * gu[..., E:]
        return mm(g, lp["w_down"])
    return swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def _attn_qkv(h: jax.Array, lp: dict, config: ModelConfig,
              inv_freq: jax.Array, positions: jax.Array,
              mesh: Optional[Mesh], rules: LogicalRules):
    """Pre-norm + q/k/v projections + rope. h: [B,S,H] -> q [B,S,Hq,D],
    k/v [B,S,Hkv,D]. Shared between the dense and paged block variants."""
    B, S, _ = h.shape
    x = rms_norm(h, lp["attn_norm"], config.rms_norm_eps)
    if "wqkv" in lp:                     # fused q|k|v (fuse_params)
        qkv = mm(x, lp["wqkv"])
        Q, KV = config.q_dim, config.kv_dim
        t = fuse_tp_for(config, mesh)
        if t > 1:
            # per-device interleaved fused layout (fuse_tp_for): unpack
            # within each device block. Heads come out in natural order
            # (head columns are dealt to devices contiguously).
            blk = qkv.reshape(B, S, t, (Q + 2 * KV) // t)
            Qd, KVd = Q // t, KV // t
            q = blk[..., :Qd].reshape(B, S, config.num_heads,
                                      config.head_dim)
            k = blk[..., Qd: Qd + KVd].reshape(B, S, config.num_kv_heads,
                                               config.head_dim)
            v = blk[..., Qd + KVd:].reshape(B, S, config.num_kv_heads,
                                            config.head_dim)
        else:
            q = qkv[..., :Q].reshape(B, S, config.num_heads,
                                     config.head_dim)
            k = qkv[..., Q: Q + KV].reshape(B, S, config.num_kv_heads,
                                            config.head_dim)
            v = qkv[..., Q + KV:].reshape(B, S, config.num_kv_heads,
                                          config.head_dim)
    else:
        q = mm(x, lp["wq"]).reshape(B, S, config.num_heads, config.head_dim)
        k = mm(x, lp["wk"]).reshape(B, S, config.num_kv_heads,
                                    config.head_dim)
        v = mm(x, lp["wv"]).reshape(B, S, config.num_kv_heads,
                                    config.head_dim)
    if config.qk_norm_whole:
        # RMSNorm over the WHOLE projection, all heads together, before
        # RoPE. q and k are in natural head order here under either
        # fused layout, so the weights are too. Under tensor parallelism
        # the mean runs over a sharded axis: a reduction across devices,
        # which the partitioner inserts.
        q = rms_norm(q.reshape(B, S, config.q_dim), lp["q_norm"],
                     config.rms_norm_eps).reshape(q.shape)
        k = rms_norm(k.reshape(B, S, config.kv_dim), lp["k_norm"],
                     config.rms_norm_eps).reshape(k.shape)
    q = constrain(q, mesh, ("batch", None, "act_heads", None), rules)
    k = constrain(k, mesh, ("batch", None, "act_heads", None), rules)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    return q, k, v


def _post_attn(h: jax.Array, attn: jax.Array, lp: dict, config: ModelConfig,
               mesh: Optional[Mesh], rules: LogicalRules, mlp_fn) -> jax.Array:
    """Output projection + residual + MLP + residual. attn: [B,S,Hq,D].
    Under ``config.sandwich_norm`` each branch's output is normed before
    its residual add (``attn_out_norm`` / ``mlp_out_norm``)."""
    B, S = attn.shape[:2]
    attn = attn.reshape(B, S, config.q_dim)
    out = mm(attn, lp["wo"])
    if config.sandwich_norm:
        out = rms_norm(out, lp["attn_out_norm"], config.rms_norm_eps)
    h = h + constrain(out, mesh, ("batch", None, "act_embed"), rules)
    x = rms_norm(h, lp["mlp_norm"], config.rms_norm_eps)
    mlp = (mlp_fn(x, lp, mesh, rules) if mlp_fn is not None
           else _default_mlp(x, lp, mesh, rules, config))
    if config.sandwich_norm:
        mlp = rms_norm(mlp, lp["mlp_out_norm"], config.rms_norm_eps)
    return h + constrain(mlp, mesh, ("batch", None, "act_embed"), rules)


def exit_pdf(gates: jax.Array) -> jax.Array:
    """A looped stack's exit distribution from its passes' gates
    ``g`` [T, ...] -> [..., T]: ``p_t = g_t prod_{s<t} (1 - g_s)`` for t
    < T - 1 and ``p_{T-1} = prod_{s<T-1} (1 - g_s)`` (the last pass takes
    what is left, whatever its own gate says). Sums to 1."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    pdf = jnp.concatenate([gates[:-1] * before[:-1], before[-1:]], axis=0)
    return jnp.moveaxis(pdf, 0, -1)


def _walk(params: dict, config: ModelConfig, carry: tuple, body):
    """THE walk over the stack, which every entry point makes through
    here: ``body(carry, layer, cache_layer) -> (carry, ys)`` once a layer,
    ``carry[0]`` the hidden state ``h`` [B,S,H], ``layer`` the weights'
    index (:func:`_layer_view`) and ``cache_layer`` the index of the K
    and V it reads and writes. Returns (carry, ys stacked
    [``config.cache_layers``, ...], exit pdf).

    One pass (``config.ut_steps`` 1): one ``lax.scan`` over the layers,
    the two indices equal, the hidden state handed back BEFORE the final
    norm (the caller's, :func:`_final_norm`) and no pdf (None).

    A looped stack: an outer scan over the passes around that scan; pass
    ``t``, layer ``l`` reads weights ``l`` and cache layer ``t * L + l``;
    after EVERY pass the final norm, whose output enters the next pass and
    is what the exit gate reads (``sigmoid(h . w + b)``, float32). The
    hidden state handed back is the last pass's normed output
    (:func:`_final_norm` then adds nothing) and the pdf
    (:func:`exit_pdf`) is [B,S,``ut_steps``] float32: computed and
    counted, never acted on: every token runs every pass."""
    L, T = config.num_layers, config.ut_steps
    if T == 1:
        carry, ys = jax.lax.scan(lambda c, layer: body(c, layer, layer),
                                 carry, jnp.arange(L))
        return carry, ys, None

    def one_pass(carry, t):
        carry, ys = jax.lax.scan(
            lambda c, layer: body(c, layer, t * L + layer), carry,
            jnp.arange(L))
        h = rms_norm(carry[0], params["final_norm"], config.rms_norm_eps)
        logit = (h.astype(jnp.float32)
                 @ params["exit_gate_w"].astype(jnp.float32)
                 + params["exit_gate_b"].astype(jnp.float32))
        return (h, *carry[1:]), (ys, jax.nn.sigmoid(logit[..., 0]))

    carry, (ys, gates) = jax.lax.scan(one_pass, carry, jnp.arange(T))
    ys = jax.tree.map(lambda y: y.reshape((T * L,) + y.shape[2:]), ys)
    return carry, ys, exit_pdf(gates)


def _final_norm(params: dict, config: ModelConfig, h: jax.Array) -> jax.Array:
    """The final norm over what :func:`_walk` handed back: a looped
    stack's last pass has run it already."""
    if config.ut_steps > 1:
        return h
    return rms_norm(h, params["final_norm"], config.rms_norm_eps)


def _block(h: jax.Array, lp: dict, config: ModelConfig, inv_freq: jax.Array,
           positions: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
           layer: jax.Array, write_pos: jax.Array, mask: jax.Array,
           mesh: Optional[Mesh], rules: LogicalRules,
           kv_window: Optional[int] = None, mlp_fn=None,
           causal0: bool = False):
    """One decoder block against the full stacked cache.

    h: [B,S,H]; cache_k/v: [L,B,max_seq,Hkv,D] (the whole stacked cache —
    this layer's slice is selected by ``layer``, the CACHE's layer index,
    which in a looped stack is not the weights': :func:`_walk`); write_pos: [B,S] absolute
    slots to write this step's k/v into; mask: [B or 1, 1, S, max_seq].
    Returns (h, new_cache_k, new_cache_v).

    The cache flows through the layer scan as *carry* and is updated with a
    scatter at exactly the written slots: per step, HBM sees a tiny write
    plus one read of this layer's history — not a rewrite of the stacked
    cache (which scan ys would force), and not a ``rep``× expanded read
    (attend_gqa contracts the unexpanded cache).

    ``mlp_fn(x, lp, mesh, rules)`` swaps the dense SwiGLU for another MLP —
    models/mixtral.py passes its sparse-MoE block here, so the attention/
    cache mechanics exist in exactly one place.
    """
    B, S, _ = h.shape
    q, k, v = _attn_qkv(h, lp, config, inv_freq, positions, mesh, rules)

    # Scatter this step's k/v into the carried cache at (layer, row,
    # write_pos); rows write S consecutive slots, in place. mode="drop":
    # in-bounds for every normal path; the speculative verify_step aims
    # positions past a near-budget row's cache at max_seq on purpose
    # (never-trusted draft slots must not clamp onto the last real slot).
    b_idx = jnp.arange(B)[:, None]
    cache_k = cache_k.at[layer, b_idx, write_pos].set(k, mode="drop")
    cache_v = cache_v.at[layer, b_idx, write_pos].set(v, mode="drop")
    k_layer = jax.lax.dynamic_index_in_dim(cache_k, layer, 0, keepdims=False)
    v_layer = jax.lax.dynamic_index_in_dim(cache_v, layer, 0, keepdims=False)
    if kv_window is not None and kv_window < k_layer.shape[1]:
        # Static attention-read window: every row's live context fits in
        # the first kv_window slots (caller guarantees lengths < window),
        # so HBM reads scale with actual context, not allocated max_seq.
        k_layer = k_layer[:, :kv_window]
        v_layer = v_layer[:, :kv_window]

    # The Pallas causal0 kernel cannot consume mesh-sharded operands
    # (same policy as the quant matmul kernels): under a mesh the XLA
    # flash path shards fine and stays.
    attn = attend_gqa_auto(
        q, k_layer, v_layer, mask,
        causal0_len=S if (causal0 and mesh is None) else None)  # [B,S,H,D]
    return _post_attn(h, attn, lp, config, mesh, rules, mlp_fn), \
        cache_k, cache_v


def _mlp_carrying(mlp_fn, aux):
    """An aux-form MLP (``mlp_fn(x, lp, mesh, rules, aux) -> (out,
    aux)``) in the block's form, for one trace of a layer body: the block
    hands the MLP's output on and knows no second result, so the running
    value goes in here and is read back, after the block, from the second
    function returned."""
    def fn(x, lp, mesh, rules):
        nonlocal aux
        out, aux = mlp_fn(x, lp, mesh, rules, aux)
        return out
    return fn, lambda: aux


def _mlp_without_aux(mlp_fn, config: ModelConfig):
    """``mlp_fn(x, lp, mesh, rules)`` (None = the dense SwiGLU) in the aux
    form, with nothing to accumulate."""
    def fn(x, lp, mesh, rules, aux):
        return (mlp_fn(x, lp, mesh, rules) if mlp_fn is not None
                else _default_mlp(x, lp, mesh, rules, config)), aux
    return fn


def hidden_states_exit(params: dict, config: ModelConfig, tokens: jax.Array,
                       positions: jax.Array, cache: KVCache, mask: jax.Array,
                       mlp_fn, mlp_aux,
                       mesh: Optional[Mesh] = None,
                       rules: LogicalRules = DEFAULT_RULES,
                       kv_window: Optional[int] = None,
                       causal0: bool = False,
                       write_pos: Optional[jax.Array] = None
                       ) -> tuple[jax.Array, KVCache, Any, Any]:
    """embed -> the walk (:func:`_walk`) -> final norm, for an MLP that
    accumulates something over the layers (models/mixtral.py counts what
    its capacity buckets drop). ``mlp_fn(x, lp, mesh, rules, aux) ->
    (out, aux)``; ``mlp_aux`` (a pytree) is the value the first layer is
    handed, and the scan carries it. Returns (h [B,S,H], cache, aux, a
    looped stack's exit pdf [B,S,ut_steps] or None).

    ``write_pos`` ([B,S], default = ``positions``): cache slots this
    step's k/v land in, decoupled from the RoPE positions — tree
    speculation (:func:`verify_tree`) writes node j at slot lengths+j
    while its RoPE position is lengths+depth(j)."""
    # Compute dtype follows the params' dtype (bf16 in production; the HF
    # parity tests load f32 weights and get f32 compute for tight tolerances).
    h = params["embed"][tokens]
    h = constrain(h, mesh, ("batch", None, "act_embed"), rules)
    inv_freq = rope_frequencies(config)
    wp = positions if write_pos is None else write_pos

    def body(carry, layer, cache_layer):
        h, ck, cv, aux = carry
        lp = _layer_view(params["layers"], layer)
        fn, aux_after = _mlp_carrying(mlp_fn, aux)
        h, ck, cv = _block(h, lp, config, inv_freq, positions, ck, cv,
                           cache_layer, wp, mask, mesh, rules, kv_window,
                           fn, causal0)
        return (h, ck, cv, aux_after()), None

    (h, new_k, new_v, aux), _, pdf = _walk(
        params, config, (h, cache.k, cache.v, mlp_aux), body)
    h = _final_norm(params, config, h)
    return h, KVCache(new_k, new_v, cache.lengths), aux, pdf


def hidden_states_aux(params: dict, config: ModelConfig, tokens: jax.Array,
                      positions: jax.Array, cache: KVCache, mask: jax.Array,
                      mlp_fn, mlp_aux,
                      mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES,
                      kv_window: Optional[int] = None,
                      causal0: bool = False,
                      write_pos: Optional[jax.Array] = None
                      ) -> tuple[jax.Array, KVCache, Any]:
    """:func:`hidden_states_exit` without the pdf: (h, cache, aux)."""
    return hidden_states_exit(params, config, tokens, positions, cache, mask,
                              mlp_fn, mlp_aux, mesh, rules, kv_window,
                              causal0, write_pos)[:3]


def hidden_states(params: dict, config: ModelConfig, tokens: jax.Array,
                  positions: jax.Array, cache: KVCache, mask: jax.Array,
                  mesh: Optional[Mesh] = None,
                  rules: LogicalRules = DEFAULT_RULES,
                  kv_window: Optional[int] = None,
                  mlp_fn=None, causal0: bool = False,
                  write_pos: Optional[jax.Array] = None
                  ) -> tuple[jax.Array, KVCache]:
    """Returns (h [B,S,H], cache) — the shared trunk of :func:`forward`;
    also the embedding feature extractor (:func:`embed_pooled` / the
    serve /api/embed path). :func:`hidden_states_aux` with nothing to
    accumulate; ``mlp_fn(x, lp, mesh, rules)`` or the dense default."""
    h, cache, _ = hidden_states_aux(params, config, tokens, positions, cache,
                                    mask, _mlp_without_aux(mlp_fn, config),
                                    (), mesh, rules, kv_window, causal0,
                                    write_pos)
    return h, cache


def _logits(params: dict, config: ModelConfig, h: jax.Array,
            last_idx: Optional[jax.Array], mesh: Optional[Mesh],
            rules: LogicalRules) -> jax.Array:
    """The lm_head over ``h`` [B,S,H], or over each row's ``last_idx``
    position only ([B,1,vocab]): see :func:`forward`."""
    if last_idx is not None:
        h = jnp.take_along_axis(h, last_idx[:, None, None].astype(jnp.int32),
                                axis=1)                     # [B,1,H]
    lm_head = (params["embed"].T if config.tie_embeddings
               else params["lm_head"])
    logits = mm(h, lm_head).astype(jnp.float32)
    return constrain(logits, mesh, ("batch", None, "act_vocab"), rules)


def forward(params: dict, config: ModelConfig, tokens: jax.Array,
            positions: jax.Array, cache: KVCache, mask: jax.Array,
            mesh: Optional[Mesh] = None,
            rules: LogicalRules = DEFAULT_RULES,
            kv_window: Optional[int] = None,
            mlp_fn=None, causal0: bool = False,
            last_idx: Optional[jax.Array] = None,
            write_pos: Optional[jax.Array] = None,
            ) -> tuple[jax.Array, KVCache]:
    """Shared forward: embed -> scan(blocks) -> norm -> logits.

    tokens/positions: [B,S]; mask: [B or 1,1,S,W] (True = attend) where W
    is ``kv_window`` (or max_seq when unset — the static attention-read
    window; see _block); k/v for this step are written at ``positions`` in
    every layer's cache. Returns (logits [B,S,vocab] f32, updated cache).

    ``last_idx`` ([B] int): gather each row's hidden state at that
    position BEFORE the lm_head and return [B,1,vocab] logits for those
    positions only. Admission sampling needs exactly one position per
    row, and the full-S path materialises an [B*S, vocab] f32 logits
    temp — 3.9 GB (and ~8.6 TFLOP of discarded lm_head compute) at 8B
    dims with a 64x128 admission chunk, which is what OOM'd 64-slot
    serving on a 16 GB chip.
    """
    h, cache = hidden_states(params, config, tokens, positions, cache, mask,
                             mesh, rules, kv_window, mlp_fn, causal0,
                             write_pos=write_pos)
    return _logits(params, config, h, last_idx, mesh, rules), cache


def forward_aux(params: dict, config: ModelConfig, tokens: jax.Array,
                positions: jax.Array, cache: KVCache, mask: jax.Array,
                mlp_fn, mlp_aux,
                mesh: Optional[Mesh] = None,
                rules: LogicalRules = DEFAULT_RULES,
                causal0: bool = False,
                last_idx: Optional[jax.Array] = None,
                kv_window: Optional[int] = None
                ) -> tuple[jax.Array, KVCache, Any]:
    """:func:`forward` over :func:`hidden_states_aux`: (logits, cache,
    aux)."""
    h, cache, aux = hidden_states_aux(params, config, tokens, positions,
                                      cache, mask, mlp_fn, mlp_aux, mesh,
                                      rules, kv_window, causal0)
    return _logits(params, config, h, last_idx, mesh, rules), cache, aux


def forward_exit(params: dict, config: ModelConfig, tokens: jax.Array,
                 positions: jax.Array, cache: KVCache, mask: jax.Array,
                 mesh: Optional[Mesh] = None,
                 rules: LogicalRules = DEFAULT_RULES,
                 causal0: bool = False,
                 last_idx: Optional[jax.Array] = None,
                 kv_window: Optional[int] = None
                 ) -> tuple[jax.Array, KVCache, Any]:
    """:func:`forward` of a looped stack with its exit pdf: (logits,
    cache, pdf [B,S,ut_steps] float32, every position's whatever
    ``last_idx`` says; None for a stack walked once)."""
    h, cache, _, pdf = hidden_states_exit(
        params, config, tokens, positions, cache, mask,
        _mlp_without_aux(None, config), (), mesh, rules, kv_window, causal0)
    return _logits(params, config, h, last_idx, mesh, rules), cache, pdf


def embed_pooled(params: dict, config: ModelConfig, tokens: jax.Array,
                 lens: jax.Array, mesh: Optional[Mesh] = None,
                 rules: LogicalRules = DEFAULT_RULES,
                 mlp_fn=None) -> jax.Array:
    """Sequence embeddings: length-masked mean pool of the final-norm
    hidden states, L2-normalized — the in-tree backend for Ollama's
    ``POST /api/embed`` (the reference delegates all LLM capability to
    Ollama, whose API includes embeddings; serve/api.py).

    tokens: [B,S] right-padded; lens: [B]. Returns [B,H] float32 unit
    vectors; pad positions contribute nothing (masked before pooling).
    """
    B, S = tokens.shape
    cache = KVCache.create(config, B, S, dtype=params["embed"].dtype)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = causal_mask(S, S, 0)
    h, _ = hidden_states(params, config, tokens, positions, cache, mask,
                         mesh, rules, mlp_fn=mlp_fn)
    h = h.astype(jnp.float32)
    valid = (jnp.arange(S)[None, :] < lens[:, None]).astype(jnp.float32)
    pooled = (h * valid[:, :, None]).sum(axis=1) / jnp.maximum(
        lens[:, None].astype(jnp.float32), 1.0)
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-9)


def prefill(params: dict, config: ModelConfig, tokens: jax.Array,
            prompt_lens: jax.Array, cache: KVCache,
            mesh: Optional[Mesh] = None,
            rules: LogicalRules = DEFAULT_RULES,
            last_only: bool = False) -> tuple[jax.Array, KVCache]:
    """Process right-padded prompts from position 0.

    tokens: [B,S] right-padded; prompt_lens: [B]. Causal masking makes pad
    slots invisible to real queries (pads sit after the prompt); cache
    lengths are set to prompt_lens so decode never attends to pad slots.
    Returns (logits [B,S,vocab], cache) — or (logits [B,1,vocab] at each
    row's last prompt position, cache) with ``last_only`` (the admission
    shape; see forward's last_idx note).
    """
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    mask = causal_mask(S, cache.k.shape[2], 0)        # [1,1,S,max_seq]
    # The mask is exactly causal-from-0 over the first S kv slots (pads
    # sit after prompts; slots past S are causally dead), so big shapes
    # may take the Pallas flash-kernel path (layers.attend_gqa_auto).
    logits, cache = forward(params, config, tokens, positions, cache, mask,
                            mesh, rules, causal0=True,
                            last_idx=prompt_lens - 1 if last_only else None)
    return logits, cache._replace(lengths=prompt_lens.astype(jnp.int32))


def prefill_chunk(params: dict, config: ModelConfig, tokens: jax.Array,
                  cache: KVCache, offset: int,
                  mesh: Optional[Mesh] = None,
                  rules: LogicalRules = DEFAULT_RULES,
                  last_idx: Optional[jax.Array] = None,
                  mlp_fn=None) -> tuple[jax.Array, KVCache]:
    """Continuation prefill: C prompt tokens per row at positions
    ``offset .. offset+C``, resuming from a partial KV already in
    ``cache`` — the chunked-admission unit (serve/scheduler.py splits a
    long prompt into fixed token-budget chunks so one admission never
    stalls in-flight decodes for the whole prompt's prefill). The same
    offset-mask continuation shape the prefix-cache prologue and the
    speculative verify path use.

    tokens: [B,C]; each row writes cache slots offset..offset+C and
    attends the FULL cache width under a ``causal_mask(C, W, offset)``
    — deliberately NOT a trimmed ``kv_window``. Masked not-yet-written
    tail keys carry exactly-zero probability, so every softmax/matmul
    reduction runs at the same padded width as the single-shot prefill
    and the emitted KV and logits are BIT-identical to one whole-prompt
    dispatch (a narrower window changes XLA's reduction blocking and
    drifts last bits — measured; pinned by tests/test_chunked_prefill).
    The full-width scores add no FLOPs chunking could have saved: the
    single-shot path computes the same [S, W] score matrix at once.

    ``last_idx`` ([B] int): CHUNK-LOCAL position to gather logits at
    ([B,1,vocab]) — the admission path clamps each row's last prompt
    position into this chunk and keeps the gather only for rows whose
    last position actually falls here. Cache lengths are NOT set; the
    caller installs total lengths atomically with the final chunk so a
    half-prefilled row never looks live.

    Returns (logits [B,1,vocab] (or [B,C,vocab] without last_idx),
    cache with the chunk's slots written, lengths untouched)."""
    positions, mask = _chunk_geometry(tokens, cache, offset)
    return forward(params, config, tokens, positions, cache, mask, mesh,
                   rules, mlp_fn=mlp_fn, last_idx=last_idx)


def _chunk_geometry(tokens: jax.Array, cache: KVCache, offset: int) -> tuple:
    B, C = tokens.shape
    positions = jnp.broadcast_to(offset + jnp.arange(C)[None, :], (B, C))
    return positions, causal_mask(C, cache.k.shape[2], offset)


def prefill_chunk_aux(params: dict, config: ModelConfig, tokens: jax.Array,
                      cache: KVCache, offset: int, mlp_fn, mlp_aux,
                      mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES,
                      last_idx: Optional[jax.Array] = None
                      ) -> tuple[jax.Array, KVCache, Any]:
    """:func:`prefill_chunk` over :func:`forward_aux`: (logits, cache,
    aux)."""
    positions, mask = _chunk_geometry(tokens, cache, offset)
    return forward_aux(params, config, tokens, positions, cache, mask,
                       mlp_fn, mlp_aux, mesh, rules, last_idx=last_idx)


def decode_step(params: dict, config: ModelConfig, tokens: jax.Array,
                cache: KVCache, mesh: Optional[Mesh] = None,
                rules: LogicalRules = DEFAULT_RULES,
                active: Optional[jax.Array] = None,
                kv_window: Optional[int] = None) -> tuple[jax.Array, KVCache]:
    """One autoregressive step for every row of the batch.

    tokens: [B,1] (this step's input token per row). Each row writes cache
    slot ``lengths[b]`` and attends to slots [0, lengths[b]].

    ``active`` ([B] bool) parks finished/empty rows for the
    continuous-batching scheduler (serve/scheduler.py): a parked row's
    length does NOT advance, so the step is a no-op for it by the
    overwrite-before-trust invariant — the row still scatters this step's
    (garbage) k/v into slot ``lengths[b]``, but since its length is
    unchanged, the next step that matters for that row writes the same
    slot again before anything attends to it as history. Parked rows'
    logits are garbage and must be ignored by the caller. Rows never read
    or write any other row's slots, so parked rows cannot corrupt active
    ones.

    Returns (logits [B,1,vocab], cache with lengths+1 where active).
    """
    positions = cache.lengths[:, None]                 # [B,1]
    window = kv_window if kv_window is not None else cache.k.shape[2]
    mask = length_mask(window, cache.lengths + 1)      # include slot being written
    logits, cache = forward(params, config, tokens, positions, cache, mask,
                            mesh, rules, kv_window=kv_window)
    inc = jnp.ones_like(cache.lengths) if active is None else active.astype(jnp.int32)
    return logits, cache._replace(lengths=cache.lengths + inc)


def no_exit_mass(config: ModelConfig) -> jax.Array:
    """A decode dispatch's exit mass at its start: float32 [ut_steps],
    the exit pdf summed over the rows live at each step and, in a fused
    program, over the steps. Sums to the live row-steps."""
    return jnp.zeros((config.ut_steps,), jnp.float32)


def _add_exit_mass(mass, pdf: jax.Array, active: Optional[jax.Array]):
    """``mass`` plus a step's pdf [B,1,T] over its live rows."""
    pdf = pdf[:, 0]
    if active is not None:
        pdf = jnp.where(active[:, None], pdf, 0.0)
    return mass + jnp.sum(pdf, axis=0)


def decode_step_exit(params: dict, config: ModelConfig, tokens: jax.Array,
                     cache: KVCache, mesh: Optional[Mesh] = None,
                     rules: LogicalRules = DEFAULT_RULES,
                     exit_mass: Optional[jax.Array] = None,
                     active: Optional[jax.Array] = None,
                     kv_window: Optional[int] = None
                     ) -> tuple[jax.Array, KVCache, jax.Array]:
    """:func:`decode_step` of a looped stack, and third ``exit_mass``
    (None = :func:`no_exit_mass`) plus this step's exit pdf over its live
    rows: the form :func:`decode_fused_aux` scans."""
    positions = cache.lengths[:, None]
    window = kv_window if kv_window is not None else cache.k.shape[2]
    mask = length_mask(window, cache.lengths + 1)
    logits, cache, pdf = forward_exit(params, config, tokens, positions,
                                      cache, mask, mesh, rules,
                                      kv_window=kv_window)
    inc = jnp.ones_like(cache.lengths) if active is None else active.astype(jnp.int32)
    mass = no_exit_mass(config) if exit_mass is None else exit_mass
    return (logits, cache._replace(lengths=cache.lengths + inc),
            _add_exit_mass(mass, pdf, active))


def decode_fused_aux(params: dict, config: ModelConfig, tokens: jax.Array,
                     cache, step_fn, step_aux,
                     mesh: Optional[Mesh] = None,
                     rules: LogicalRules = DEFAULT_RULES,
                     active: Optional[jax.Array] = None, *,
                     num_steps: int, sample_fn, sample_state, stop_ids,
                     kv_window: Optional[int] = None,
                     pages: Optional[int] = None):
    """``num_steps`` autoregressive steps in ONE dispatch: a ``lax.scan``
    over :func:`decode_step` (dense) / :func:`decode_step_paged`
    (``pages`` set) carrying the cache, the sampled next-token feed, the
    active mask, and the caller's sampling state — so K decode steps cost
    one host dispatch/readback instead of K.

    Each scan step IS the plain step — the same ``decode_step[_paged]``
    call, then ``sample_fn(logits [B,V], state, emit_pos [B], active
    [B]) -> (tokens [B] int32, state)`` (the scheduler passes
    models/sampling.sample_step_batched, the shared sample+penalty-ring
    implementation) — so the emitted stream is bit-identical to K
    sequential plain ticks: same logits, same key splits, same ring
    updates (pinned by tests/test_fused_decode.py).

    **EOS parks inside the scan**: a row whose sampled token is in
    ``stop_ids`` ([n] int32; () disables) retires mid-fusion — its
    length stops advancing, its ring writes drop, and its next-token
    feed freezes, exactly the state the host-side release would have
    produced between two plain ticks. Later positions of a retired row
    are garbage the caller discards (the host stops consuming a row's
    burst at its stop token). The caller guarantees every active row can
    absorb ``num_steps`` tokens of KV budget (the scheduler's adaptive-K
    guard); EOS is the only mid-scan retirement.

    ``step_fn(params, config, tokens, cache, mesh, rules, aux,
    active=..., kv_window=... | pages=...) -> (logits, cache, aux)`` is
    the step in the form that accumulates something over its layers
    (models/mixtral.py counts the experts a step touched); ``step_aux``
    (a pytree) is what the first step is handed, and the scan carries it.

    Returns (tokens [num_steps, B] int32, emitted [num_steps, B] bool —
    whether the row was live when that step sampled, next_tokens [B,1],
    cache, active [B], sample_state, aux).
    """
    B = tokens.shape[0]
    if active is None:
        active = jnp.ones((B,), bool)
    stop = jnp.asarray(stop_ids, jnp.int32).reshape(-1)

    window = ({"kv_window": kv_window} if pages is None
              else {"pages": pages})

    def step(carry, _):
        tokens, cache, act, state, aux = carry
        emit_pos = cache.lengths + 1       # emitted token's context slot
        logits, cache, aux = step_fn(params, config, tokens, cache, mesh,
                                     rules, aux, active=act, **window)
        toks, state = sample_fn(logits[:, 0, :], state, emit_pos, act)
        # Parked rows keep their previous input token (the plain
        # program's exact next-token rule).
        next_tokens = jnp.where(act[:, None], toks[:, None], tokens)
        emitted = act
        if stop.shape[0]:
            act = act & jnp.all(toks[:, None] != stop[None, :], axis=1)
        return (next_tokens, cache, act, state, aux), (toks, emitted)

    (tokens, cache, active, sample_state, aux), (toks_all, emitted) = \
        jax.lax.scan(step, (tokens, cache, active, sample_state, step_aux),
                     None, length=num_steps)
    return toks_all, emitted, tokens, cache, active, sample_state, aux


def decode_fused(params: dict, config: ModelConfig, tokens: jax.Array,
                 cache, mesh: Optional[Mesh] = None,
                 rules: LogicalRules = DEFAULT_RULES,
                 active: Optional[jax.Array] = None, *,
                 num_steps: int, sample_fn, sample_state, stop_ids,
                 kv_window: Optional[int] = None,
                 pages: Optional[int] = None):
    """:func:`decode_fused_aux` with nothing to accumulate, over
    :func:`decode_step` / :func:`decode_step_paged` (``pages`` set): the
    same returns without the aux."""
    step_fn = decode_step if pages is None else decode_step_paged

    def fn(params, config, tokens, cache, mesh, rules, aux, **kw):
        return (*step_fn(params, config, tokens, cache, mesh, rules, **kw),
                aux)
    return decode_fused_aux(params, config, tokens, cache, fn, (), mesh,
                            rules, active, num_steps=num_steps,
                            sample_fn=sample_fn, sample_state=sample_state,
                            stop_ids=stop_ids, kv_window=kv_window,
                            pages=pages)[:-1]


def decode_fused_exit(params: dict, config: ModelConfig, tokens: jax.Array,
                      cache, mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES,
                      active: Optional[jax.Array] = None, *,
                      num_steps: int, sample_fn, sample_state, stop_ids,
                      kv_window: Optional[int] = None,
                      pages: Optional[int] = None):
    """:func:`decode_fused` of a looped stack over the ``_exit`` steps,
    and last the dispatch's exit mass (:func:`no_exit_mass`): each step
    is handed the rows still live at it."""
    step_fn = decode_step_exit if pages is None else decode_step_paged_exit
    return decode_fused_aux(params, config, tokens, cache, step_fn,
                            no_exit_mass(config), mesh, rules, active,
                            num_steps=num_steps, sample_fn=sample_fn,
                            sample_state=sample_state, stop_ids=stop_ids,
                            kv_window=kv_window, pages=pages)


def verify_step(params: dict, config: ModelConfig, tokens: jax.Array,
                cache: KVCache, mesh: Optional[Mesh] = None,
                rules: LogicalRules = DEFAULT_RULES,
                kv_window: Optional[int] = None,
                mlp_fn=None,
                last_idx: Optional[jax.Array] = None
                ) -> tuple[jax.Array, KVCache]:
    """Speculative-decoding verify: score S candidate positions per row in
    ONE forward (the multi-token generalisation of :func:`decode_step`).

    tokens: [B,S] = [current token, draft_0, ..., draft_{S-2}] per row;
    row b's position j writes cache slot ``lengths[b]+j`` and attends
    slots [0, lengths[b]+j]. Lengths are NOT advanced here — the caller
    runs its acceptance rule (models/sampling.spec_verify_batched) on the
    returned logits and advances by ``accepted+1``. Slots past the
    accepted prefix hold rejected drafts' kv: stale beyond the new
    length, overwritten before anything trusts them (the same invariant
    that parks rows — speculative rollback is free). The caller caps
    acceptance for near-budget rows; their untrusted writes past
    ``max_seq`` drop (see _block).

    Returns (logits [B,S,vocab] f32 — logits[:, j] is the model's
    distribution for the token AFTER input j — and the cache with the S
    candidate slots written, lengths unchanged). ``last_idx`` ([B] int):
    gather ONE position's logits per row ([B,1,vocab]) — the
    session-wake admission shape, where S is a whole suffix bucket and
    the full [B,S,vocab] f32 logits would be gigabytes (see
    forward's last_idx note); spec verify reads all S and passes None.
    """
    B, S = tokens.shape
    positions = cache.lengths[:, None] + jnp.arange(S)[None, :]   # [B,S]
    window = kv_window if kv_window is not None else cache.k.shape[2]
    # Query j of row b may see kv slots [0, lengths[b]+j] (its own slot
    # included — matches decode_step's lengths+1 masking at S=1).
    mask = (jnp.arange(window)[None, None, :]
            <= positions[:, :, None])[:, None]                    # [B,1,S,W]
    return forward(params, config, tokens, positions, cache, mask,
                   mesh, rules, kv_window=kv_window, mlp_fn=mlp_fn,
                   last_idx=last_idx)


def tree_attention_mask(lengths: jax.Array, anc: jax.Array,
                        window: int) -> jax.Array:
    """Tree-topology attention mask for :func:`verify_tree`.

    lengths: [B] committed context lengths; anc: [B,N,N] bool — anc[b,i,j]
    iff tree node j is on node i's root path (self included). Node i
    occupies cache slot ``lengths[b]+i``, so its query may see every
    committed slot (< lengths[b]) plus exactly the node slots on its own
    ancestor path — siblings and other branches stay invisible, which is
    what makes one batched forward score every root path as if each were
    verified alone. Returns [B,1,N,W] (True = attend).
    """
    B, N = anc.shape[:2]
    cols = jnp.arange(window)[None, :]                       # [1,W]
    committed = cols < lengths[:, None]                      # [B,W]
    jr = cols - lengths[:, None]                             # [B,W]
    node_col = (jr >= 0) & (jr < N)
    anc_w = jnp.take_along_axis(anc, jnp.clip(jr, 0, N - 1)[:, None, :],
                                axis=2)                      # [B,N,W]
    mask = committed[:, None, :] | (node_col[:, None, :] & anc_w)
    return mask[:, None]                                     # [B,1,N,W]


def verify_tree(params: dict, config: ModelConfig, tokens: jax.Array,
                depths: jax.Array, anc: jax.Array, cache: KVCache,
                mesh: Optional[Mesh] = None,
                rules: LogicalRules = DEFAULT_RULES,
                kv_window: Optional[int] = None,
                mlp_fn=None) -> tuple[jax.Array, KVCache]:
    """Tree-speculation verify: score N tree nodes per row in ONE forward
    (:func:`verify_step` generalised from a chain to a tree).

    tokens: [B,N] — node 0 is the root (current token), nodes 1..K the
    main draft chain, the rest sibling leaves; depths: [B,N] node depth
    (root = 0); anc: [B,N,N] ancestor matrix (see
    :func:`tree_attention_mask`). Node i writes cache slot ``lengths+i``
    (slots stay node-indexed, so rejected branches are stale-beyond-
    length exactly like rejected linear drafts) while its RoPE position
    is ``lengths+depths[i]`` — the position in the hypothetical stream
    its root path spells out. Lengths are NOT advanced; the caller runs
    models/sampling.spec_verify_tree on the logits, compacts a used
    sibling's kv onto the accepted path, and advances by accepted+1.

    Returns (logits [B,N,vocab] f32 — logits[:, i] is the distribution
    AFTER node i along its root path — and the cache with the N node
    slots written, lengths unchanged).
    """
    B, N = tokens.shape
    positions = cache.lengths[:, None] + depths              # RoPE [B,N]
    write_pos = cache.lengths[:, None] + jnp.arange(N)[None, :]
    window = kv_window if kv_window is not None else cache.k.shape[2]
    mask = tree_attention_mask(cache.lengths, anc, window)
    return forward(params, config, tokens, positions, cache, mask,
                   mesh, rules, kv_window=kv_window, mlp_fn=mlp_fn,
                   write_pos=write_pos)


# -- paged decode (Pallas kernel path) ----------------------------------------

def _constrain_pool(cache, mesh: Optional[Mesh],
                    rules: LogicalRules):
    """Pin the paged pool's kv-head sharding inside the jitted step so
    TP serving never silently replicates it (ops/paged_kv.shard_cache
    places it at creation; this keeps XLA from resharding mid-program)."""
    if mesh is None:
        return cache
    out = cache._replace(
        k=constrain(cache.k, mesh, (None, None, None, "kv_heads", None),
                    rules),
        v=constrain(cache.v, mesh, (None, None, None, "kv_heads", None),
                    rules))
    if cache.k_scale is not None:
        out = out._replace(
            k_scale=constrain(cache.k_scale, mesh,
                              (None, None, "kv_heads", None), rules),
            v_scale=constrain(cache.v_scale, mesh,
                              (None, None, "kv_heads", None), rules))
    return out


def verify_step_paged(params: dict, config: ModelConfig, tokens: jax.Array,
                      cache, mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES,
                      *, pages: int,
                      mlp_fn=None, last_idx: Optional[jax.Array] = None):
    """Speculative verify over the paged pool: :func:`verify_step`'s
    contract (S candidate positions, lengths unchanged; caller advances
    by accepted+1) on a PagedKVCache.

    Structure mirrors decode_step_paged: position j attends the pool
    window plus block positions i <= j from the in-register k/v
    (ops/paged_attention.paged_attention_verify_append — one softmax
    over the concatenated scores), the scan stacks each layer's block
    k/v, and ONE batched scatter lands everything afterwards
    (write_decode_multi_all_layers — positions past a row's allocation
    land in garbage page 0, so rollback/containment is inherent). The
    weight stream, the quantity speculation amortises, is still read
    once. ``pages`` must cover ``lengths`` (the scheduler sizes for
    ``kv_window + S``).

    Unlike the decode tick, verify stays on the gather path at EVERY
    window: the flash-append kernel is single-position (its online-
    softmax state is seeded with one current token), and the verify
    forward runs only when the scheduler's acceptance EMA says drafts
    are landing — a multi-position flash verify is recorded headroom,
    not a gap (docs/serving.md round-8).
    """
    from ..ops.paged_attention import paged_attention_verify_append
    from ..ops.paged_kv import write_decode_multi_all_layers

    cache = _constrain_pool(cache, mesh, rules)
    B, S = tokens.shape
    positions = cache.lengths[:, None] + jnp.arange(S)[None, :]    # [B,S]
    h = params["embed"][tokens]
    h = constrain(h, mesh, ("batch", None, "act_embed"), rules)
    inv_freq = rope_frequencies(config)

    def finish(h):
        h = _final_norm(params, config, h)
        if last_idx is not None:
            # One position's logits per row ([B,1,vocab]) — the
            # session-wake admission shape, where S is a whole suffix
            # bucket and full logits would be an [B*S, vocab] f32 temp
            # (forward's last_idx note). Spec verify passes None.
            h = jnp.take_along_axis(
                h, last_idx[:, None, None].astype(jnp.int32), axis=1)
        lm_head = (params["embed"].T if config.tie_embeddings
                   else params["lm_head"])
        logits = mm(h, lm_head).astype(jnp.float32)
        return constrain(logits, mesh, ("batch", None, "act_vocab"), rules)

    def body(carry, layer, cache_layer):
        h, = carry
        lp = _layer_view(params["layers"], layer)
        q, k, v = _attn_qkv(h, lp, config, inv_freq, positions, mesh, rules)
        attn = paged_attention_verify_append(
            q, k, v, cache, cache.lengths, cache_layer, pages=pages)
        h = _post_attn(h, attn, lp, config, mesh, rules, mlp_fn)
        return (h,), (k, v)

    (h,), (k_all, v_all), _ = _walk(params, config, (h,), body)
    cache = write_decode_multi_all_layers(cache, k_all, v_all)
    return finish(h), cache


def verify_tree_paged(params: dict, config: ModelConfig, tokens: jax.Array,
                      depths: jax.Array, anc: jax.Array, cache,
                      mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES,
                      *, pages: int, mlp_fn=None):
    """:func:`verify_tree` on a PagedKVCache.

    verify_step_paged's walk: every node's query attends the committed
    pool window (ops/paged_attention._gather_window_scores — ``pos <
    lengths`` is already branch-agnostic) plus the in-register block k/v
    filtered by
    the ancestor matrix ``anc`` instead of the chain-causal triangle.
    RoPE positions are ``lengths+depths``; ONE batched scatter lands
    node i at pool position ``lengths+i`` afterwards
    (write_decode_multi_all_layers — node-indexed slots, beyond-
    allocation writes land in garbage page 0, so rejected-branch
    containment is inherent, int8 scales included).
    """
    from ..ops.paged_attention import paged_attention_verify_append
    from ..ops.paged_kv import write_decode_multi_all_layers

    cache = _constrain_pool(cache, mesh, rules)
    positions = cache.lengths[:, None] + depths              # RoPE [B,N]
    h = params["embed"][tokens]
    h = constrain(h, mesh, ("batch", None, "act_embed"), rules)
    inv_freq = rope_frequencies(config)

    def body(carry, layer, cache_layer):
        h, = carry
        lp = _layer_view(params["layers"], layer)
        q, k, v = _attn_qkv(h, lp, config, inv_freq, positions, mesh,
                            rules)
        attn = paged_attention_verify_append(
            q, k, v, cache, cache.lengths, cache_layer, pages=pages,
            block_mask=anc)
        h = _post_attn(h, attn, lp, config, mesh, rules, mlp_fn)
        return (h,), (k, v)

    (h,), (k_all, v_all), _ = _walk(params, config, (h,), body)
    cache = write_decode_multi_all_layers(cache, k_all, v_all)
    h = _final_norm(params, config, h)
    lm_head = (params["embed"].T if config.tie_embeddings
               else params["lm_head"])
    logits = mm(h, lm_head).astype(jnp.float32)
    return constrain(logits, mesh, ("batch", None, "act_vocab"),
                     rules), cache


def _decode_step_paged(params: dict, config: ModelConfig,
                       tokens: jax.Array, cache, mlp_fn, mlp_aux,
                       mesh: Optional[Mesh], rules: LogicalRules,
                       active: Optional[jax.Array], pages: int):
    """One autoregressive step over the paged KV pool (ops/paged_kv.py),
    for an MLP that accumulates something over the layers, in
    :func:`hidden_states_aux`'s form: ``mlp_fn(x, lp, mesh, rules, aux)
    -> (out, aux)``, ``mlp_aux`` what the first layer is handed.
    :func:`decode_step_paged_aux` is this without its fourth result, a
    looped stack's exit pdf [B,1,ut_steps] (None for a stack walked once).

    Same contract as :func:`decode_step` — including the parked-row
    invariant, which paging strengthens: a released row's zeroed page
    table routes its garbage writes to the shared garbage page, so parked
    rows cannot touch any live page. Attention walks ``pages`` table
    entries per row (the serving window ladder:
    ``pages = ceil(window / page_size)``).

    cache: ops.paged_kv.PagedKVCache. Returns (logits [B,1,vocab], cache
    with lengths advanced where active, aux, pdf).

    Structure note: a layer attends BEFORE the pool write — the current
    token's k/v folds into attention via one exact online-softmax merge
    (ops/paged_attention.paged_attention_append) — and the scan stacks
    each layer's k/v so ONE batched scatter lands the whole step
    afterwards (write_decode_burst; a looped stack's K and V come out of
    the walk stacked [cache_layers, ...], a pass after a pass). Per-layer
    pool scatters inside the scan carry a fixed cost that was measurable
    against the decode bandwidth bound.

    paged_attention_append chooses its implementation per layer call,
    from the window and the pool's geometry alone (the XLA gather below
    the flash boundary, the multi-chunk flash-append kernel from it up
    on a TPU), ONCE per trace (the scan body traces once), so the
    serving scheduler's per-window jitted programs each bake in exactly
    one implementation and warmup compiles the whole ladder up front
    (serve/scheduler.warmup).
    """
    from ..ops.paged_kv import write_decode_burst
    from ..ops.paged_attention import paged_attention_append

    cache = _constrain_pool(cache, mesh, rules)
    positions = cache.lengths[:, None]                 # [B,1]
    h = params["embed"][tokens]
    h = constrain(h, mesh, ("batch", None, "act_embed"), rules)
    inv_freq = rope_frequencies(config)
    inc = (jnp.ones_like(cache.lengths) if active is None
           else active.astype(jnp.int32))

    def finish(h):
        h = _final_norm(params, config, h)
        lm_head = (params["embed"].T if config.tie_embeddings
                   else params["lm_head"])
        logits = mm(h, lm_head).astype(jnp.float32)
        return constrain(logits, mesh, ("batch", None, "act_vocab"), rules)

    def body(carry, layer, cache_layer):
        h, aux = carry
        fn, aux_after = _mlp_carrying(mlp_fn, aux)
        lp = _layer_view(params["layers"], layer)
        q, k, v = _attn_qkv(h, lp, config, inv_freq, positions, mesh, rules)
        attn = paged_attention_append(q[:, 0], k[:, 0], v[:, 0], cache,
                                      cache.lengths, cache_layer,
                                      pages=pages, sharded=mesh is not None)
        h = _post_attn(h, attn[:, None], lp, config, mesh, rules, fn)
        return (h, aux_after()), (k[:, 0], v[:, 0])

    (h, aux), (k_all, v_all), pdf = _walk(params, config, (h, mlp_aux), body)
    return (finish(h), write_decode_burst(cache, k_all, v_all, inc), aux,
            pdf)


def decode_step_paged_aux(params: dict, config: ModelConfig,
                          tokens: jax.Array, cache, mlp_fn, mlp_aux,
                          mesh: Optional[Mesh] = None,
                          rules: LogicalRules = DEFAULT_RULES,
                          active: Optional[jax.Array] = None,
                          *, pages: int):
    """:func:`_decode_step_paged` without the pdf: (logits, cache, aux).
    :func:`decode_step_paged` is this with nothing to accumulate."""
    return _decode_step_paged(params, config, tokens, cache, mlp_fn,
                              mlp_aux, mesh, rules, active, pages)[:3]


def decode_step_paged_exit(params: dict, config: ModelConfig,
                           tokens: jax.Array, cache,
                           mesh: Optional[Mesh] = None,
                           rules: LogicalRules = DEFAULT_RULES,
                           exit_mass: Optional[jax.Array] = None,
                           active: Optional[jax.Array] = None,
                           *, pages: int):
    """:func:`decode_step_paged` of a looped stack, and third
    ``exit_mass`` plus this step's, as :func:`decode_step_exit`: the
    scheduler's decode programs run these ``_exit`` forms for a looped
    model."""
    logits, cache, _, pdf = _decode_step_paged(
        params, config, tokens, cache, _mlp_without_aux(None, config), (),
        mesh, rules, active, pages)
    mass = no_exit_mass(config) if exit_mass is None else exit_mass
    return logits, cache, _add_exit_mass(mass, pdf, active)


def decode_step_paged(params: dict, config: ModelConfig, tokens: jax.Array,
                      cache, mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES,
                      active: Optional[jax.Array] = None,
                      *, pages: int, mlp_fn=None):
    """:func:`decode_step_paged_aux` with nothing to accumulate: (logits
    [B,1,vocab], cache with lengths advanced where active).
    ``mlp_fn(x, lp, mesh, rules)`` or the dense default."""
    return decode_step_paged_aux(params, config, tokens, cache,
                                 _mlp_without_aux(mlp_fn, config), (), mesh,
                                 rules, active, pages=pages)[:2]

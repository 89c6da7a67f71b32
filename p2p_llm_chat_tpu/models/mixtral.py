"""Mixtral-family sparse-MoE decoder — functional JAX, TPU-first.

BASELINE.json config 5 (Mixtral-8x7B with expert parallelism). The
reference delegates all inference to Ollama (web/streamlit_app.py:91-95);
this module is the in-tree MoE model family. The attention/cache/scan
mechanics are llama's — :func:`forward` passes the sparse-MoE MLP into
``llama.forward`` via its ``mlp_fn`` hook, so those mechanics exist in
exactly one place — and only the expert MLP lives here.

TPU-first choices:
- **Scatter/gather dispatch** with static capacity buckets: each token's
  top-k expert assignments are scattered into a ``[NE*C, H]`` bucket
  array (linear in tokens — never a ``[T, NE, C]`` one-hot), the expert
  FFNs run as one batched ``[NE, C, H] x [NE, H, F]`` matmul on the MXU,
  and outputs gather back with renormalised router weights. Shapes are
  static for fixed (T, C): routing churn never recompiles.
- **Capacity**: ``capacity=None`` is exact/dropless (C = T; the parity and
  decode default — decode's T = batch is tiny). For large prefill chunks,
  ``ModelConfig.moe_capacity_factor`` bounds C at
  ``factor * T * k / NE`` (the standard GShard-style capacity): overflow
  tokens lose only their MLP contribution (residual carries them), and
  bucket memory stays ~``factor/NE``-proportional instead of NE-fold.
- **A dropless prefill on one device computes the pairs it routed**
  (:func:`moe_mlp_counted`, the form every admission program runs: C = T
  buckets would multiply ``NE x T`` rows for ``T x k`` pairs, eight times
  what OLMoE's 64 experts top-8 route): its real positions' pairs go,
  sorted by expert, into tiles (:func:`_moe_tiles` over
  models/moe_tiles.py, the dispatch Mellum's routed layers share), and
  padding is sent nowhere. What chooses the path is what the call
  carries (the mask of real positions, no capacity, no mesh, S > 1), so
  a capacity-factor model, the decode step and a sharded model keep the
  buckets, and so does the maskless :func:`moe_mlp` whatever it
  carries: a verify and a session wake pass no capacity for EVERY
  model of the family (their bucket is exact by design), and eight
  experts of 176 MB read twice by a second tile are no gain.
- **Expert parallelism** via the ``"experts": ("ep","tp")`` logical rule
  (parallel/sharding.py): expert-stacked weights and the ``[NE, C, H]``
  buckets shard over the expert axis; the combine's contraction becomes
  one XLA all-reduce — the MoE twin of the Megatron per-block psum.
- Router math in float32 (softmax over all experts, renormalised top-k),
  matching HF MixtralSparseMoeBlock so real checkpoints work.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..parallel.sharding import LogicalRules, DEFAULT_RULES, constrain
from .configs import ModelConfig
from .layers import DEFAULT_COMPUTE_DTYPE, causal_mask, length_mask
from .moe_tiles import routed_tiles, swiglu_experts, tile_rows
from . import llama
from .llama import KVCache  # same cache layout/contract as the dense family
# Fused transform: attention projections fuse exactly as the dense
# family's do; the 4-D per-expert ffn leaves fuse into "wgu_e" on the
# single-chip path and stay separate under a mesh (fuse_params checks
# w_gate.ndim / tp / mesh).
from .llama import fuse_params  # noqa: F401  (re-export, serve scheduler)

# Sentinel: "derive capacity from config.moe_capacity_factor".
_AUTO = "auto"


# -- parameters ---------------------------------------------------------------

def init_params(config: ModelConfig, key: jax.Array,
                dtype=DEFAULT_COMPUTE_DTYPE) -> dict:
    """Random init. Real weights come from models/weights.py (the
    ``block_sparse_moe`` layout of HF Mixtral)."""
    assert config.is_moe, "mixtral.init_params needs num_experts > 0"
    ks = jax.random.split(key, 12)
    L, H, E = config.num_layers, config.hidden_size, config.intermediate_size
    NE = config.num_experts
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
            "router": normal(ks[5], (L, H, NE)),
            "w_gate": normal(ks[6], (L, NE, H, E)),
            "w_up": normal(ks[7], (L, NE, H, E)),
            "w_down": normal(ks[8], (L, NE, E, H)),
        },
        "final_norm": jnp.ones((H,), dtype),
    }
    params["layers"].update(llama.qk_norm_leaves(config, ks[10], dtype))
    if not config.tie_embeddings:
        params["lm_head"] = normal(ks[9], (H, config.vocab_size))
    return params


def init_params_quantized(config: ModelConfig, key: jax.Array,
                          dtype=DEFAULT_COMPUTE_DTYPE,
                          quant: str = "int8") -> dict:
    """Random init streamed straight into the FUSED quantized tree — the
    MoE twin of ``llama.init_params_quantized`` (same why: the bf16 tree
    cannot exist on a single chip at big-model scale, the int8 one can).

    Per layer, a donated write loop quantizes wqkv (attention fused),
    wo, the per-expert fused ``wgu_e`` [NE,H,2F], and w_down [NE,F,H];
    the router stays bf16 (tiny, and routing math is f32 anyway — HF
    parity). ``fuse_params`` is a no-op on the result. ``quant="int4"``
    streams group-wise QTensor4 leaves (the expert stacks group along
    axis -2 exactly like the dense projections; MoE compute goes through
    q_einsum's dequant path). Synthetic-bench / random-init serving only
    — real checkpoints stream through
    models/weights.load_checkpoint_quantized.
    """
    import functools

    from .quant import _quantize_leaf, stream_bufs

    if quant not in ("int8", "int4"):
        raise ValueError(f"quant must be int8|int4, got {quant!r}")
    assert config.is_moe, "mixtral.init_params_quantized needs experts"
    L, H, E = config.num_layers, config.hidden_size, config.intermediate_size
    NE = config.num_experts
    std = H ** -0.5
    key, k_embed, k_head = jax.random.split(key, 3)

    def normal(k, shape, scale=std, dt=dtype):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    dims = {
        "wqkv": (H, config.q_dim + 2 * config.kv_dim),
        "wo": (config.q_dim, H),
        "wgu_e": (NE, H, 2 * E),
        "w_down": (NE, E, H),
    }
    layers: dict = {
        "attn_norm": jnp.ones((L, H), dtype),
        "mlp_norm": jnp.ones((L, H), dtype),
        # A key of its own, so that no other leaf's draw moves.
        **llama.qk_norm_leaves(config, jax.random.fold_in(k_head, 1), dtype),
    }
    bufs = {name: stream_bufs(L, shape, quant)
            for name, shape in dims.items()}
    router = jnp.zeros((L, H, NE), dtype)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def write_layer(bufs: dict, router: jax.Array, k: jax.Array,
                    layer: jax.Array) -> tuple[dict, jax.Array]:
        ks = jax.random.split(k, len(dims) + 1)
        out = dict(bufs)
        for i, (name, shape) in enumerate(dims.items()):
            qt = _quantize_leaf(normal(ks[i], shape), quant)
            out[name] = type(qt)(q=bufs[name].q.at[layer].set(qt.q),
                                 s=bufs[name].s.at[layer].set(qt.s))
        router2 = router.at[layer].set(normal(ks[-1], (H, NE)))
        return out, router2

    layer_keys = jax.random.split(key, L)
    for li in range(L):
        bufs, router = write_layer(bufs, router, layer_keys[li],
                                   jnp.asarray(li))
    layers.update(bufs)
    layers["router"] = router

    params = {
        "embed": normal(k_embed, (config.vocab_size, H), scale=1.0),
        "layers": layers,
        "final_norm": jnp.ones((H,), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = _quantize_leaf(
            normal(k_head, (H, config.vocab_size)), quant)
    return params


def param_axes(config: ModelConfig) -> dict:
    """Logical-axis tree matching init_params. The expert-stacked FFN
    weights shard over "experts" -> ("ep","tp") (parallel/sharding.py), so
    Mixtral-8x7B on 8 chips keeps exactly one expert's weights per chip."""
    axes = {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": (None, "embed"),
            "wq": (None, "embed", "heads"),
            "wk": (None, "embed", "kv_heads"),
            "wv": (None, "embed", "kv_heads"),
            "wo": (None, "heads", "embed"),
            "mlp_norm": (None, "embed"),
            "router": (None, "embed", None),      # tiny; replicated
            "w_gate": (None, "experts", "embed", "expert_mlp"),
            "w_up": (None, "experts", "embed", "expert_mlp"),
            "w_down": (None, "experts", "expert_mlp", "embed"),
        },
        "final_norm": ("embed",),
    }
    if config.qk_norm_whole:
        axes["layers"].update(llama.QK_NORM_AXES)
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# -- MoE MLP ------------------------------------------------------------------

def moe_mlp(x: jax.Array, router: jax.Array, w_gate: jax.Array,
            w_up: jax.Array, w_down: jax.Array, num_experts_per_tok: int,
            mesh: Optional[Mesh] = None,
            rules: LogicalRules = DEFAULT_RULES,
            capacity: Optional[int] = None,
            w_gu: Optional[jax.Array] = None,
            renormalize: bool = True,
            live: Optional[jax.Array] = None) -> jax.Array:
    """Sparse-MoE SwiGLU via scatter/gather dispatch into capacity buckets.

    x: [B,S,H]; router: [H,NE]; w_gate/w_up: [NE,H,F]; w_down: [NE,F,H].
    ``capacity`` is the per-expert bucket size C (None = T = exact).
    All memory is linear in tokens: the scatter index vector is [T*k] and
    the bucket array [NE*C, H]; the expert FFN is one batched MXU matmul.

    ``w_gu`` ([NE,H,2F], gate|up columns concatenated — the expert twin
    of llama.fuse_params' dense ``wgu``): when given, gate and up run as
    ONE batched einsum and w_gate/w_up are ignored (may be None). Decode
    is bandwidth-bound with a per-matmul fixed cost, so halving the
    expert projection dispatches pays exactly like the dense fusion
    did; per-output-channel int8 scales
    concatenate with their columns, so the math is identical.

    ``renormalize`` (static; ``ModelConfig.moe_renormalize``): divide the
    kept weights by their sum (Mixtral). False keeps the softmax's own
    weights (OLMoE, ``norm_topk_prob: false``).

    ``live`` ([B] bool, None = every row): a decode step's ``active``
    mask. A row that is not live takes no slot and its output is 0, so a
    bucket is empty exactly when no live row chose its expert, and the
    expert-stripe kernels, handed each bucket's count, leave an empty
    expert's weights unread (ops/quant_mm.py). A live row's output does
    not depend on the mask: with an exact bucket it only moves to
    another slot of the same matmul.

    This form keeps the buckets whatever it carries (generate, a verify,
    a session wake, the embedding): only :func:`moe_mlp_counted`, the
    form an admission runs, leaves them for tiles.
    """
    return _moe_mlp(x, router, w_gate, w_up, w_down, num_experts_per_tok,
                    mesh, rules, capacity, w_gu, renormalize, live)[0]


def _tiled(x: jax.Array, mesh, capacity) -> bool:
    """Whether a counted dispatch leaves the buckets for tiles, from what
    the call carries: no capacity to drop at (a tile layout cannot
    drop), no mesh (a mesh shards buckets over ``experts``), more than
    one position a row (a step's buckets hold its few rows exactly)."""
    return capacity is None and mesh is None and x.shape[1] > 1


def moe_mlp_counted(x: jax.Array, router: jax.Array, w_gate: jax.Array,
                    w_up: jax.Array, w_down: jax.Array,
                    num_experts_per_tok: int, valid: jax.Array,
                    mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    capacity: Optional[int] = None,
                    w_gu: Optional[jax.Array] = None,
                    renormalize: bool = True) -> tuple[jax.Array, jax.Array]:
    """:func:`moe_mlp`, and what its buckets dropped: ``(out, stats)``
    with ``stats`` int32 [2] = the routed (token, expert) pairs of the
    ``valid`` positions ([B,S] bool: the real prompt positions), and
    those of them that found their bucket full. Padding positions still
    take slots in (token, slot) order; what they displace is counted,
    what they lose is not.

    Dropless (``capacity`` None) the vector has a third entry, the rows
    the experts' matmuls ran over (:func:`no_stats`), and a prefill on
    one device (:func:`_tiled`) leaves the buckets: its real pairs
    go sorted into tiles (:func:`_moe_tiles`), padding is sent nowhere
    and its output is 0. A real position's output is the buckets' up to
    float rounding: the same pairs, weights and order of the sum over
    k."""
    B, S, _ = x.shape
    if _tiled(x, mesh, capacity):
        return _moe_tiles(x, router, w_gate, w_up, w_down,
                          num_experts_per_tok, w_gu, renormalize, valid)
    out, full, _ = _moe_mlp(x, router, w_gate, w_up, w_down,
                            num_experts_per_tok, mesh, rules, capacity, w_gu,
                            renormalize, None)
    real = jnp.repeat(valid.reshape(-1), num_experts_per_tok)      # [T*k]
    stats = [jnp.sum(real), jnp.sum(real & full)]
    if capacity is None:
        # Every expert's bucket holds every position of the dispatch.
        stats.append(jnp.where(jnp.any(valid), router.shape[-1] * B * S, 0))
    return out, jnp.stack(stats).astype(jnp.int32)


def _route(xt: jax.Array, router: jax.Array, k: int,
           renormalize: bool) -> tuple[jax.Array, jax.Array]:
    """Routing in f32 (HF parity: softmax over ALL experts, then top-k,
    then, for Mixtral, renormalise the selected weights): (top_w, top_i)
    [T,k]."""
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)   # [T,NE]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)                         # [T,k]
    if renormalize:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_w, top_i


def _moe_tiles(x, router, w_gate, w_up, w_down, num_experts_per_tok, w_gu,
               renormalize, valid) -> tuple[jax.Array, jax.Array]:
    """A dropless prefill that computes the pairs it routed: this
    family's router in front of the tree's one sorted-tile dispatch
    (models/moe_tiles.routed_tiles). ``valid`` [B,S] bool: the positions
    that take tile rows. Returns (out [B,S,H], stats int32 [3] = pairs
    of the valid positions, 0 dropped, tile rows multiplied = filled
    tiles x rows a tile)."""
    B, S, H = x.shape
    NE, k = router.shape[-1], num_experts_per_tok
    T = B * S
    xt = x.reshape(T, H)
    top_w, top_i = _route(xt, router, k, renormalize)
    takes = jnp.broadcast_to(valid.reshape(T, 1), (T, k))
    out, tiles = routed_tiles(xt, top_w, top_i, takes, NE, functools.partial(
        swiglu_experts, w_gu=w_gu, w_down=w_down, w_gate=w_gate, w_up=w_up))
    stats = jnp.stack([jnp.sum(takes), jnp.asarray(0),
                       jnp.sum(tiles) * tile_rows(T * k, NE)])
    return out.astype(x.dtype).reshape(B, S, H), stats.astype(jnp.int32)


def _moe_mlp(x, router, w_gate, w_up, w_down, num_experts_per_tok, mesh,
             rules, capacity, w_gu, renormalize, live) -> tuple:
    """(out [B,S,H], full [T*k] bool: the t-major (token, selection)
    pairs whose bucket had no slot left, count [NE] int32: the filled
    slots of each expert's bucket)."""
    B, S, H = x.shape
    NE = router.shape[-1]
    k = num_experts_per_tok
    T = B * S
    C = T if capacity is None else max(1, min(capacity, T))
    xt = x.reshape(T, H)

    top_w, top_i = _route(xt, router, k, renormalize)

    # Position-in-expert with (token, selection-slot) priority: cumsum of
    # the selection one-hot over the t-major flattened [T*k] selections.
    sel = jax.nn.one_hot(top_i, NE, dtype=jnp.int32)               # [T,k,NE]
    flat = sel.reshape(T * k, NE)
    if live is not None:
        # A parked row selects nothing: it stands in no expert's queue.
        takes = jnp.repeat(jnp.broadcast_to(live[:, None], (B, S)).reshape(T),
                           k)                                      # [T*k]
        flat = flat * takes[:, None].astype(flat.dtype)
    pos = jnp.cumsum(flat, axis=0) - flat
    slot = jnp.sum(flat * pos, axis=-1)                            # [T*k]
    expert = top_i.reshape(T * k)
    count = jnp.minimum(jnp.sum(flat, axis=0), C)                  # [NE]
    # Overflow (slot >= C) and a parked row's selections are aimed one
    # past the buckets; scatter drops them and the fill-gather below
    # returns 0 for them.
    placed = slot < C if live is None else (slot < C) & takes
    idx = jnp.where(placed, expert * C + slot, NE * C)             # [T*k]

    x_rep = jnp.repeat(xt, k, axis=0)                              # [T*k,H]
    xin = jnp.zeros((NE * C, H), xt.dtype).at[idx].set(x_rep, mode="drop")
    xin = constrain(xin.reshape(NE, C, H), mesh,
                    ("experts", None, "act_embed"), rules)
    y = swiglu_experts(xin, count, None, w_gu, w_down, w_gate, w_up)
    y = constrain(y, mesh, ("experts", None, "act_embed"), rules)

    gathered = jnp.take(y.reshape(NE * C, H), idx, axis=0,
                        mode="fill", fill_value=0)                 # [T*k,H]
    out = jnp.sum(gathered.reshape(T, k, H).astype(jnp.float32)
                  * top_w[..., None], axis=1)
    return out.astype(x.dtype).reshape(B, S, H), slot >= C, count


# -- forward ------------------------------------------------------------------

def _capacity_for(config: ModelConfig, tokens: int,
                  capacity) -> Optional[int]:
    """Resolve the capacity argument: _AUTO -> config.moe_capacity_factor
    (None factor = exact/dropless)."""
    if capacity is not _AUTO:
        return capacity
    f = config.moe_capacity_factor
    if f is None:
        return None
    return max(1, int(f * tokens * config.num_experts_per_tok
                      / config.num_experts))


def _mlp_fn(config: ModelConfig, capacity: Optional[int]):
    def fn(x, lp, mesh, rules):
        return moe_mlp(x, lp["router"], lp.get("w_gate"), lp.get("w_up"),
                       lp["w_down"], config.num_experts_per_tok, mesh,
                       rules, capacity, w_gu=lp.get("wgu_e"),
                       renormalize=config.moe_renormalize)
    return fn


def _mlp_fn_counted(config: ModelConfig, capacity: Optional[int],
                    valid: jax.Array):
    """The expert MLP in llama.hidden_states_aux's form: the running
    drop count goes in and comes out beside the output."""
    def fn(x, lp, mesh, rules, stats):
        out, more = moe_mlp_counted(
            x, lp["router"], lp.get("w_gate"), lp.get("w_up"),
            lp["w_down"], config.num_experts_per_tok, valid, mesh, rules,
            capacity, w_gu=lp.get("wgu_e"),
            renormalize=config.moe_renormalize)
        return out, stats + more
    return fn


def _mlp_fn_touched(config: ModelConfig, live: Optional[jax.Array]):
    """A decode step's expert MLP (exact bucket, ``live`` rows) in the
    aux form: the running count of experts touched goes in and comes out
    beside the output."""
    def fn(x, lp, mesh, rules, stats):
        out, _, count = _moe_mlp(
            x, lp["router"], lp.get("w_gate"), lp.get("w_up"), lp["w_down"],
            config.num_experts_per_tok, mesh, rules, None, lp.get("wgu_e"),
            config.moe_renormalize, live)
        more = jnp.stack([jnp.sum(count > 0), count.shape[0]])
        return out, stats + more.astype(jnp.int32)
    return fn


def no_stats(dropless: bool = False) -> jax.Array:
    """A drop count's start: int32 [2] = (routed pairs of real prompt
    positions, those dropped), summed over the layers; ``dropless`` (no
    capacity) int32 [3], third the rows the experts' matmuls ran over
    (the filled tiles' of :func:`_moe_tiles`: over the pairs it is what
    the tiles' padding costs)."""
    return jnp.zeros((3 if dropless else 2,), jnp.int32)


def prefill_stats(config: ModelConfig) -> tuple[str, ...]:
    """What the entries of the counts are that the ``_counted`` prefills
    of ``config`` hand back, in their order, for the scheduler that
    reads them (BatchScheduler._count_moe)."""
    dropless = config.moe_capacity_factor is None
    return ("assigned", "dropped") + (("rows",) if dropless else ())


def forward(params: dict, config: ModelConfig, tokens: jax.Array,
            positions: jax.Array, cache: KVCache, mask: jax.Array,
            mesh: Optional[Mesh] = None,
            rules: LogicalRules = DEFAULT_RULES,
            kv_window: Optional[int] = None,
            capacity=_AUTO, causal0: bool = False,
            last_idx: Optional[jax.Array] = None) -> tuple[jax.Array, KVCache]:
    """llama.forward with the sparse-MoE MLP plugged in (same contract)."""
    cap = _capacity_for(config, int(tokens.shape[0] * tokens.shape[1]),
                        capacity)
    return llama.forward(params, config, tokens, positions, cache, mask,
                         mesh, rules, kv_window,
                         mlp_fn=_mlp_fn(config, cap), causal0=causal0,
                         last_idx=last_idx)


def forward_counted(params: dict, config: ModelConfig, tokens: jax.Array,
                    positions: jax.Array, cache: KVCache, mask: jax.Array,
                    valid: jax.Array,
                    mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    capacity=_AUTO, causal0: bool = False,
                    last_idx: Optional[jax.Array] = None
                    ) -> tuple[jax.Array, KVCache, jax.Array]:
    """:func:`forward`, and third what the capacity buckets dropped of
    the ``valid`` positions ([B,S] bool, the real prompt positions):
    :func:`moe_mlp_counted`'s ``stats`` summed over the layers. The
    scheduler's prefill programs run these ``_counted`` forms."""
    cap = _capacity_for(config, int(tokens.shape[0] * tokens.shape[1]),
                        capacity)
    return llama.forward_aux(params, config, tokens, positions, cache, mask,
                             _mlp_fn_counted(config, cap, valid),
                             no_stats(cap is None), mesh, rules,
                             causal0=causal0, last_idx=last_idx)


def _prefill_geometry(tokens: jax.Array, cache: KVCache) -> tuple:
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    return positions, causal_mask(S, cache.k.shape[2], 0)


def prefill(params: dict, config: ModelConfig, tokens: jax.Array,
            prompt_lens: jax.Array, cache: KVCache,
            mesh: Optional[Mesh] = None,
            rules: LogicalRules = DEFAULT_RULES,
            capacity=_AUTO, last_only: bool = False) -> tuple[jax.Array, KVCache]:
    """Same contract as llama.prefill (right-padded prompts from pos 0),
    incl. ``last_only`` (admission's one-position logits)."""
    positions, mask = _prefill_geometry(tokens, cache)
    logits, cache = forward(params, config, tokens, positions, cache, mask,
                            mesh, rules, capacity=capacity, causal0=True,
                            last_idx=prompt_lens - 1 if last_only else None)
    return logits, cache._replace(lengths=prompt_lens.astype(jnp.int32))


def prefill_counted(params: dict, config: ModelConfig, tokens: jax.Array,
                    prompt_lens: jax.Array, cache: KVCache, valid: jax.Array,
                    mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    capacity=_AUTO, last_only: bool = False
                    ) -> tuple[jax.Array, KVCache, jax.Array]:
    """:func:`prefill` over :func:`forward_counted`: (logits, cache,
    stats)."""
    positions, mask = _prefill_geometry(tokens, cache)
    logits, cache, stats = forward_counted(
        params, config, tokens, positions, cache, mask, valid, mesh, rules,
        capacity=capacity, causal0=True,
        last_idx=prompt_lens - 1 if last_only else None)
    return (logits, cache._replace(lengths=prompt_lens.astype(jnp.int32)),
            stats)


def prefill_chunk(params: dict, config: ModelConfig, tokens: jax.Array,
                  cache: KVCache, offset: int,
                  mesh: Optional[Mesh] = None,
                  rules: LogicalRules = DEFAULT_RULES,
                  last_idx: Optional[jax.Array] = None,
                  capacity=_AUTO) -> tuple[jax.Array, KVCache]:
    """llama.prefill_chunk with the MoE MLP (continuation prefill for
    chunked admission; same offset-mask/full-width bit-identity
    contract). Caveat: under a bounding ``moe_capacity_factor`` the
    expert bucket scales with the CHUNK's token count, so overflow drops
    can differ from the whole-prompt bucket's — the dropless default
    (capacity None, all test/tiny configs) is exactly bit-identical,
    capacity-bounded configs are exact only while no bucket overflows
    (the same approximation class the capacity policy already accepts)."""
    cap = _capacity_for(config, int(tokens.shape[0] * tokens.shape[1]),
                        capacity)
    return llama.prefill_chunk(params, config, tokens, cache, offset, mesh,
                               rules, last_idx=last_idx,
                               mlp_fn=_mlp_fn(config, cap))


def prefill_chunk_counted(params: dict, config: ModelConfig,
                          tokens: jax.Array, cache: KVCache, offset: int,
                          valid: jax.Array,
                          mesh: Optional[Mesh] = None,
                          rules: LogicalRules = DEFAULT_RULES,
                          last_idx: Optional[jax.Array] = None,
                          capacity=_AUTO
                          ) -> tuple[jax.Array, KVCache, jax.Array]:
    """:func:`prefill_chunk`, and third the chunk's drop count
    (:func:`forward_counted`; ``valid`` [B,C] bool)."""
    cap = _capacity_for(config, int(tokens.shape[0] * tokens.shape[1]),
                        capacity)
    return llama.prefill_chunk_aux(
        params, config, tokens, cache, offset,
        _mlp_fn_counted(config, cap, valid), no_stats(cap is None), mesh,
        rules, last_idx=last_idx)


def no_touched() -> jax.Array:
    """A decode dispatch's expert count at its start: int32 [2] = (the
    experts some live row reached, the experts there were), summed over
    the layers and, in a fused program, over the steps."""
    return jnp.zeros((2,), jnp.int32)


def decode_step_touched(params: dict, config: ModelConfig,
                        tokens: jax.Array, cache: KVCache,
                        mesh: Optional[Mesh] = None,
                        rules: LogicalRules = DEFAULT_RULES,
                        touched: Optional[jax.Array] = None,
                        active: Optional[jax.Array] = None,
                        kv_window: Optional[int] = None
                        ) -> tuple[jax.Array, KVCache, jax.Array]:
    """Same contract as llama.decode_step, including the parked-row
    (active=False) overwrite-before-trust invariant. Decode's token count
    T = B is small, so the MoE bucket is always exact (capacity=None), and
    a parked row stays out of it (:func:`moe_mlp`'s ``live``): its MLP
    output is 0 where it used to be garbage, and nobody reads either.
    Third, ``touched`` (None = :func:`no_touched`) plus this step's
    experts reached and experts there were, over the layers: the
    scheduler's decode programs run these ``_touched`` forms."""
    positions = cache.lengths[:, None]
    window = kv_window if kv_window is not None else cache.k.shape[2]
    mask = length_mask(window, cache.lengths + 1)
    logits, cache, touched = llama.forward_aux(
        params, config, tokens, positions, cache, mask,
        _mlp_fn_touched(config, active),
        no_touched() if touched is None else touched, mesh, rules,
        kv_window=kv_window)
    inc = jnp.ones_like(cache.lengths) if active is None else active.astype(jnp.int32)
    return logits, cache._replace(lengths=cache.lengths + inc), touched


def decode_step(params: dict, config: ModelConfig, tokens: jax.Array,
                cache: KVCache, mesh: Optional[Mesh] = None,
                rules: LogicalRules = DEFAULT_RULES,
                active: Optional[jax.Array] = None,
                kv_window: Optional[int] = None) -> tuple[jax.Array, KVCache]:
    """:func:`decode_step_touched` without the count."""
    return decode_step_touched(params, config, tokens, cache, mesh, rules,
                               None, active, kv_window)[:2]


def decode_fused_touched(params: dict, config: ModelConfig,
                         tokens: jax.Array, cache,
                         mesh: Optional[Mesh] = None,
                         rules: LogicalRules = DEFAULT_RULES,
                         active: Optional[jax.Array] = None, *,
                         num_steps: int, sample_fn, sample_state, stop_ids,
                         kv_window: Optional[int] = None,
                         pages: Optional[int] = None):
    """llama.decode_fused over the MoE step functions (same contract:
    K steps, one dispatch, in-scan EOS parking, bit-identical to K
    sequential plain ticks; each step is handed the rows still live at
    it, so a row that parks mid-scan leaves the buckets there), and
    last the dispatch's expert count (:func:`no_touched`)."""
    step_fn = (decode_step_touched if pages is None
               else decode_step_paged_touched)
    return llama.decode_fused_aux(params, config, tokens, cache, step_fn,
                                  no_touched(), mesh, rules, active,
                                  num_steps=num_steps, sample_fn=sample_fn,
                                  sample_state=sample_state,
                                  stop_ids=stop_ids, kv_window=kv_window,
                                  pages=pages)


def decode_fused(params: dict, config: ModelConfig, tokens: jax.Array,
                 cache, mesh: Optional[Mesh] = None,
                 rules: LogicalRules = DEFAULT_RULES,
                 active: Optional[jax.Array] = None, *,
                 num_steps: int, sample_fn, sample_state, stop_ids,
                 kv_window: Optional[int] = None,
                 pages: Optional[int] = None):
    """:func:`decode_fused_touched` without the count."""
    return decode_fused_touched(params, config, tokens, cache, mesh, rules,
                                active, num_steps=num_steps,
                                sample_fn=sample_fn,
                                sample_state=sample_state, stop_ids=stop_ids,
                                kv_window=kv_window, pages=pages)[:-1]


def verify_step(params: dict, config: ModelConfig, tokens: jax.Array,
                cache: KVCache, mesh: Optional[Mesh] = None,
                rules: LogicalRules = DEFAULT_RULES,
                kv_window: Optional[int] = None,
                last_idx: Optional[jax.Array] = None
                ) -> tuple[jax.Array, KVCache]:
    """llama.verify_step with the MoE MLP (speculative-decoding verify;
    the token count is tiny, so the expert bucket stays exact —
    session-wake reuses it at suffix-bucket widths with ``last_idx``,
    where the bucket scales with the suffix like prefill_chunk's)."""
    return llama.verify_step(params, config, tokens, cache, mesh, rules,
                             kv_window, mlp_fn=_mlp_fn(config, None),
                             last_idx=last_idx)


def verify_tree(params: dict, config: ModelConfig, tokens: jax.Array,
                depths: jax.Array, anc: jax.Array, cache: KVCache,
                mesh: Optional[Mesh] = None,
                rules: LogicalRules = DEFAULT_RULES,
                kv_window: Optional[int] = None
                ) -> tuple[jax.Array, KVCache]:
    """llama.verify_tree with the MoE MLP (tree-speculation verify; the
    node count is tiny, so the expert bucket stays exact)."""
    return llama.verify_tree(params, config, tokens, depths, anc, cache,
                             mesh, rules, kv_window,
                             mlp_fn=_mlp_fn(config, None))


def decode_step_paged_touched(params: dict, config: ModelConfig,
                              tokens: jax.Array, cache,
                              mesh: Optional[Mesh] = None,
                              rules: LogicalRules = DEFAULT_RULES,
                              touched: Optional[jax.Array] = None,
                              active: Optional[jax.Array] = None,
                              *, pages: int):
    """llama.decode_step_paged with the MoE MLP (same contract; decode's
    token count is tiny, so the expert bucket stays exact, and a parked
    row stays out of it as in :func:`decode_step_touched`, whose third
    result this returns too). Attention
    impl selection — including the round-8 multi-chunk flash-append
    default at W >= 2048 on TPU — rides along unchanged: the dispatch
    lives in ops/paged_attention.paged_attention_append, below the
    mlp_fn seam, so MoE long-window decode takes the same kernel."""
    return llama.decode_step_paged_aux(
        params, config, tokens, cache, _mlp_fn_touched(config, active),
        no_touched() if touched is None else touched, mesh, rules, active,
        pages=pages)


def decode_step_paged(params: dict, config: ModelConfig, tokens: jax.Array,
                      cache, mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES,
                      active: Optional[jax.Array] = None,
                      *, pages: int):
    """:func:`decode_step_paged_touched` without the count."""
    return decode_step_paged_touched(params, config, tokens, cache, mesh,
                                     rules, None, active, pages=pages)[:2]


def verify_step_paged(params: dict, config: ModelConfig, tokens: jax.Array,
                      cache, mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES,
                      *, pages: int,
                      last_idx: Optional[jax.Array] = None):
    """llama.verify_step_paged with the MoE MLP."""
    return llama.verify_step_paged(params, config, tokens, cache, mesh,
                                   rules, pages=pages,
                                   mlp_fn=_mlp_fn(config, None),
                                   last_idx=last_idx)


def verify_tree_paged(params: dict, config: ModelConfig, tokens: jax.Array,
                      depths: jax.Array, anc: jax.Array, cache,
                      mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES, *, pages: int):
    """llama.verify_tree_paged with the MoE MLP."""
    return llama.verify_tree_paged(params, config, tokens, depths, anc,
                                   cache, mesh, rules, pages=pages,
                                   mlp_fn=_mlp_fn(config, None))


def embed_pooled(params: dict, config: ModelConfig, tokens: jax.Array,
                 lens: jax.Array, mesh: Optional[Mesh] = None,
                 rules: LogicalRules = DEFAULT_RULES,
                 capacity=_AUTO) -> jax.Array:
    """llama.embed_pooled with the MoE MLP (length-masked mean pool of
    final-norm hidden states, L2-normalized; the /api/embed backend)."""
    cap = _capacity_for(config, int(tokens.shape[0] * tokens.shape[1]),
                        capacity)
    return llama.embed_pooled(params, config, tokens, lens, mesh, rules,
                              mlp_fn=_mlp_fn(config, cap))

"""Hybrid Mamba-2 / attention / LatentMoE decoder (the Nemotron-H block
of NVIDIA-Nemotron-3-Super-120B-A12B). ``models.family_for`` picks this
module for a configuration with a ``hybrid_pattern``; the functional
surface is the other families' (init_params, prefill, prefill_chunk,
decode_step_paged, decode_fused, their ``_counted`` / ``_touched``
forms), so the scheduler serves it through the same programs.

**A layer is ONE mixer**: ``x <- x + mixer(RMSNorm(x))``, the mixer named
by the layer's letter in ``hybrid_pattern``:

- ``M``, Mamba-2 (``d = mamba_num_heads x mamba_head_dim``, ``G =
  ssm_groups``, ``N = ssm_state_size``): ``[z | xBC | dt] = u W_in``
  (widths d | d + 2GN | heads); ``xBC <- silu(conv(xBC) + b)``, a causal
  depthwise convolution over the last ``conv_kernel`` positions; split
  into ``x`` [heads, head_dim], ``B``, ``C`` [G, N]; ``dt <-
  softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` in float32; the
  recurrence of ops/state_pool.py over a float32 state; ``y <- y + D x``;
  the gated norm, gate first: ``RMSNorm_grouped(y silu(z); G groups) w``;
  ``out = y W_out``.
- ``*``, attention: GQA, no biases, causal, no rotary embedding
  (``attn_rope`` False: position comes from the recurrent layers).
- ``E``, LatentMoE: ``s = sigmoid(x W_r)`` in float32 over ALL
  ``router_width`` experts; the top-k of ``s + router_bias`` chosen,
  weighed ``routed_scaling_factor x s / (sum of the chosen s + 1e-20)``;
  ``l = x W_fc1`` (hidden -> latent); expert e is ``relu(l U_e)^2 D_e``
  in the latent; ``routed = (sum over chosen AND held e) W_fc2``;
  ``shared = relu(x U_s)^2 D_s``; ``out = routed + shared``. The held
  experts go through models/pangu._routed_local, the one dispatch for a
  held range of a wider router.

**Two kinds of per-row past.** The ``*`` layers' K and V are pages
(ops/paged_kv.py; ``ModelConfig.cache_layers`` of them); the ``M``
layers' state and convolution window are rows of a
:class:`~..ops.state_pool.StatePool` that rides in the cache objects'
``state`` leaf: per entry in a prefill's ``KVCache`` (zero for a fresh
prompt, the carry of a chunk ladder, a prefix entry's snapshot), per
slot in the scheduler's ``PagedKVCache``. Prefill programs mask with
``valid`` ([B,S] bool, a row's real positions, a prefix of the row):
padding has ``dt`` = 0 and stays out of the window, so a padded row's
state is its unpadded run's.

**The stack** is three stacked parameter trees (``mamba``, ``moe``,
``attn``) walked by the pattern: runs of equal letter pairs between
attention layers are one ``lax.scan`` each (:func:`_plan`), so a
program holds a few layer bodies and not one a layer.

Single chip only; speculation and session parking would need the state
rolled back or carried and are refused at boot (serve/scheduler.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops import state_pool
from ..ops.state_pool import StatePool
from ..parallel.sharding import LogicalRules, DEFAULT_RULES
from ..utils.device import pallas_interpret
from .configs import ModelConfig
from .layers import (DEFAULT_COMPUTE_DTYPE, attend_gqa_auto, causal_mask,
                     rms_norm)
from .llama import KVCache, _layer_view
from .pangu import (STATS_WIDTH, _normal, _routed_local, no_stats,
                    streamed_stack)
from .quant import mm

no_touched = no_stats
__all__ = ["STATS_WIDTH", "no_stats", "no_touched"]


# -- the pattern --------------------------------------------------------------

@functools.cache
def _plan(pattern: str) -> tuple:
    """The walk of ``pattern``: a tuple of (letters, repeat, first index
    of each letter's tree at this step). Between attention layers a run
    of equal two-letter pairs is one step with ``repeat`` > 1 (one scan);
    everything else is a step of one layer."""
    at = {"M": 0, "E": 0, "*": 0}
    steps = []

    def emit(letters: str, n: int) -> None:
        steps.append((letters, n, dict(at)))
        for ch in letters:
            at[ch] += n

    i = 0
    while i < len(pattern):
        pair = pattern[i: i + 2]
        n = 0
        if len(pair) == 2 and "*" not in pair and pair[0] != pair[1]:
            while pattern[i + 2 * n: i + 2 * n + 2] == pair:
                n += 1
        if n >= 2:
            emit(pair, n)
            i += 2 * n
        else:
            emit(pattern[i], 1)
            i += 1
    return tuple(steps)


# -- parameters ---------------------------------------------------------------

def _dims(config: ModelConfig) -> dict:
    """Per-layer matmul leaves of each tree (without the layer axis)."""
    H = config.hidden_size
    d, nh = config.mamba_inner, config.mamba_num_heads
    Lw = config.moe_latent_size
    F, Fs = config.intermediate_size, (config.shared_intermediate_size
                                       or config.intermediate_size)
    NE = config.num_experts
    return {
        "mamba": {"w_in": (H, d + config.conv_dim + nh), "w_out": (d, H)},
        "attn": {"wqkv": (H, config.q_dim + 2 * config.kv_dim),
                 "wo": (config.q_dim, H)},
        "moe": {"w_fc1": (H, Lw), "w_fc2": (Lw, H),
                "w_up_s": (H, Fs), "w_down_s": (Fs, H),
                "w_up_e": (NE, Lw, F), "w_down": (NE, F, Lw)},
    }


def _init_scale(name: str, shape: tuple, config: ModelConfig) -> float:
    """Standard deviation a random matmul leaf is drawn with: the scaled
    normal's ``fan_in ** -0.5``; the experts' down-projections a
    ``routed_scaling_factor``-th of it, so that the routed sum (whose
    weights add up to that factor, 5) comes out as large as one expert's
    output and not five times it, as training would have left it."""
    std = shape[-2] ** -0.5
    if name == "w_down":
        std /= config.routed_scaling_factor
    return std


def _counts(config: ModelConfig) -> dict:
    p = config.hybrid_pattern
    return {"mamba": p.count("M"), "moe": p.count("E"),
            "attn": p.count("*")}


def _uniform(k, shape, lo, hi, dtype=jnp.float32):
    return jax.random.uniform(k, shape, jnp.float32, lo, hi).astype(dtype)


def _small_leaves(config: ModelConfig, key: jax.Array, dtype) -> dict:
    """Everything that is not a matmul weight. Norms are drawn from
    [0.5, 1.5) and ``D``, the convolution's bias and the router's
    selection bias away from their neutral values, so that a model
    without one of them cannot pass a comparison (pangu._norm_leaves).
    ``dt_bias`` and ``A_log`` span what the published initialiser does:
    time steps of 0.001 to 0.1 and decays ``A`` of 1 to 16."""
    n = _counts(config)
    H, nh = config.hidden_size, config.mamba_num_heads
    ks = iter(jax.random.split(key, 16))
    Lm, Le, La = n["mamba"], n["moe"], n["attn"]
    dt = jnp.exp(_uniform(next(ks), (Lm, nh), jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "mamba": {
            "norm": _uniform(next(ks), (Lm, H), 0.5, 1.5, dtype),
            "conv_w": _normal(next(ks), (Lm, config.conv_kernel,
                                         config.conv_dim), 0.5, dtype),
            "conv_b": _normal(next(ks), (Lm, config.conv_dim), 0.5, dtype),
            # softplus(dt_bias) = dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(_uniform(next(ks), (Lm, nh), 1.0, 16.0)),
            "D": _uniform(next(ks), (Lm, nh), 0.5, 1.5),
            "gnorm": _uniform(next(ks), (Lm, config.mamba_inner), 0.5, 1.5,
                              dtype),
        },
        "attn": {"norm": _uniform(next(ks), (La, H), 0.5, 1.5, dtype)},
        "moe": {
            "norm": _uniform(next(ks), (Le, H), 0.5, 1.5, dtype),
            # float32, as the published router is.
            "router": _normal(next(ks), (Le, H, config.router_width),
                              H ** -0.5, jnp.float32),
            "router_bias": _uniform(next(ks), (Le, config.router_width),
                                    -0.2, 0.2),
        },
    }


def _build(config: ModelConfig, key: jax.Array, dtype, stack, head) -> dict:
    """The parameter tree both initialisers return (pangu._build)."""
    if not config.moe_selection_bias or config.mlp_activation != "relu2" \
            or not config.moe_latent_size:
        raise ValueError(f"{config.name}: the hybrid family's routed layer "
                         "is a LatentMoE (selection bias, relu2 experts in "
                         "a latent)")
    n = _counts(config)
    H = config.hidden_size
    k_embed, k_head, k_small, k_stack = jax.random.split(key, 4)
    small = _small_leaves(config, k_small, dtype)
    dims = _dims(config)
    params = {"embed": _normal(k_embed, (config.vocab_size, H), 1.0, dtype),
              "final_norm": jnp.ones((H,), dtype),
              "lm_head": head(k_head, (H, config.vocab_size))}
    for i, tree in enumerate(("mamba", "attn", "moe")):
        params[tree] = {**stack(jax.random.fold_in(k_stack, i), n[tree],
                                dims[tree]), **small[tree]}
    return params


def init_params(config: ModelConfig, key: jax.Array,
                dtype=DEFAULT_COMPUTE_DTYPE) -> dict:
    """Random init (scaled normal)."""
    def stack(k, L, dims):
        return {name: _normal(jax.random.fold_in(k, i), (L, *shape),
                              _init_scale(name, shape, config), dtype)
                for i, (name, shape) in enumerate(dims.items())}

    return _build(config, key, dtype, stack, lambda k, shape: _normal(
        k, shape, shape[0] ** -0.5, dtype))


def init_params_quantized(config: ModelConfig, key: jax.Array,
                          dtype=DEFAULT_COMPUTE_DTYPE,
                          quant: str = "int8") -> dict:
    """Random init streamed straight into the int8 tree, one leaf of one
    layer (one expert of it) at a time (pangu.streamed_stack)."""
    from .quant import quantize

    if quant != "int8":
        raise ValueError(f"{config.name}: the hybrid family serves int8 or "
                         f"plain weights, not {quant!r}")

    def leaf(k, shape, name=""):
        return quantize(_normal(k, shape, _init_scale(name, shape, config),
                                dtype))

    return _build(config, key, dtype, streamed_stack(leaf, quant), leaf)


def fuse_params(params: dict, tp: int = 1, mesh: Optional[Mesh] = None,
                **_) -> dict:
    """The tree is born fused (wqkv)."""
    return params


def param_axes(config: ModelConfig) -> dict:
    """Everything replicated: the family serves on one chip."""
    shapes = jax.eval_shape(lambda: init_params(config,
                                                jax.random.PRNGKey(0)))
    axes = jax.tree.map(lambda a: (None,) * a.ndim, shapes)
    axes.update(embed=("vocab", "embed"), final_norm=("embed",),
                lm_head=("embed", "vocab"))
    return axes


# -- the mixers ---------------------------------------------------------------

def _mamba_split(config: ModelConfig, lp: dict):
    """``split(conv_out, dt_raw) -> (x, dt, A, Bm, Cm)``: what
    ops/state_pool reads of the convolved channels and the raw time
    step."""
    d, nh, P = (config.mamba_inner, config.mamba_num_heads,
                config.mamba_head_dim)
    G, N = config.ssm_groups, config.ssm_state_size
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))

    def split(conv_out, dt_raw):
        lead = conv_out.shape[:-1]
        xbc = jax.nn.silu(conv_out)
        x = xbc[..., :d].reshape(*lead, nh, P)
        Bm = xbc[..., d: d + G * N].reshape(*lead, G, N)
        Cm = xbc[..., d + G * N:].reshape(*lead, G, N)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + lp["dt_bias"].astype(jnp.float32))
        return x, dt, A, Bm, Cm

    return split


def _mamba_out(y, x, z, lp, config: ModelConfig, dtype):
    """``y + D x``, the gated grouped norm (gate first) and ``W_out``.
    y, x [..., heads, head_dim] float32; z [..., d]."""
    G = config.ssm_groups
    lead = y.shape[:-2]
    y = y + lp["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(*lead, -1) * jax.nn.silu(z.astype(jnp.float32))
    g = y.reshape(*lead, G, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + config.rms_norm_eps)
    y = g.reshape(*lead, -1) * lp["gnorm"].astype(jnp.float32)
    return mm(y.astype(dtype), lp["w_out"])


def _mamba_in(h, lp, config: ModelConfig):
    d = config.mamba_inner
    u = rms_norm(h, lp["norm"], config.rms_norm_eps)
    zxd = mm(u, lp["w_in"])
    return (zxd[..., :d], zxd[..., d: d + config.conv_dim],
            zxd[..., d + config.conv_dim:])


def _mamba_prefill(h, lp, config: ModelConfig, state: StatePool, layer,
                   valid: jax.Array):
    """h [B,S,H] behind the carried state of Mamba layer ``layer`` of
    ``state`` ([L_m, B, ...]). Returns (out [B,S,H], state)."""
    z, xbc, dt_raw = _mamba_in(h, lp, config)
    S_in = jax.lax.dynamic_index_in_dim(state.ssm, layer, 0, False)
    win = jax.lax.dynamic_index_in_dim(state.conv, layer, 0, False)
    lengths = jnp.sum(valid, axis=1)
    conv_out, win = state_pool.conv_scan(xbc, win, lengths, lp["conv_w"],
                                         lp["conv_b"])
    x, dt, A, Bm, Cm = _mamba_split(config, lp)(conv_out, dt_raw)
    dt = jnp.where(valid[..., None], dt, 0.0)
    y, S_out = state_pool.ssd_scan(x, dt, A, Bm, Cm, S_in, config.ssm_chunk)
    state = StatePool(
        ssm=jax.lax.dynamic_update_index_in_dim(state.ssm, S_out, layer, 0),
        conv=jax.lax.dynamic_update_index_in_dim(
            state.conv, win.astype(state.conv.dtype), layer, 0))
    return _mamba_out(y, x, z, lp, config, h.dtype), state


def _mamba_decode(h, lp, config: ModelConfig, pool: StatePool, layer,
                  live: jax.Array):
    """h [B,1,H]: one step of Mamba layer ``layer`` over the pool's first
    B rows. Returns (out [B,1,H], pool)."""
    z, xbc, dt_raw = _mamba_in(h[:, 0], lp, config)
    split = _mamba_split(config, lp)
    y, x, pool = state_pool.decode_update(
        pool, layer, live, xbc, lp["conv_w"], lp["conv_b"],
        lambda conv_out: split(conv_out, dt_raw))
    return _mamba_out(y, x, z, lp, config, h.dtype)[:, None], pool


def _qkv(h, lp, config: ModelConfig):
    B, S, _ = h.shape
    qkv = mm(rms_norm(h, lp["norm"], config.rms_norm_eps), lp["wqkv"])
    Q, KV = config.q_dim, config.kv_dim
    return (qkv[..., :Q].reshape(B, S, config.num_heads, config.head_dim),
            qkv[..., Q: Q + KV].reshape(B, S, config.num_kv_heads,
                                        config.head_dim),
            qkv[..., Q + KV:].reshape(B, S, config.num_kv_heads,
                                      config.head_dim))


def _attn_prefill(h, lp, config: ModelConfig, ck, cv, layer: int,
                  offset: int):
    """llama._block's attention against the dense carry: the chunk's K
    and V land at slots offset..offset+S of cache layer ``layer`` and the
    chunk attends the carry's whole width under the offset causal
    mask."""
    B, S, _ = h.shape
    q, k, v = _qkv(h, lp, config)
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(layer, jnp.int32), zero,
          jnp.asarray(offset, jnp.int32), zero, zero)
    ck = jax.lax.dynamic_update_slice(ck, k[None].astype(ck.dtype), at)
    cv = jax.lax.dynamic_update_slice(cv, v[None].astype(cv.dtype), at)
    attn = attend_gqa_auto(q, ck[layer], cv[layer],
                           causal_mask(S, ck.shape[2], offset),
                           causal0_len=S if offset == 0 else None)
    return mm(attn.reshape(B, S, config.q_dim), lp["wo"]), ck, cv


def _attn_decode(h, lp, config: ModelConfig, cache, layer: int, pages: int):
    from ..ops.paged_attention import paged_attention_append
    B = h.shape[0]
    q, k, v = _qkv(h, lp, config)
    attn = paged_attention_append(q[:, 0], k[:, 0], v[:, 0], cache,
                                  cache.lengths, layer, pages=pages,
                                  interpret=pallas_interpret())
    return mm(attn.reshape(B, 1, config.q_dim), lp["wo"]), k[:, 0], v[:, 0]


def _relu2_mlp(x, w_up, w_down):
    return mm(jnp.square(jax.nn.relu(mm(x, w_up))), w_down)


def _moe(h, lp, config: ModelConfig, counted, live):
    """(out [B,S,H], stats int32 [4])."""
    x = rms_norm(h, lp["norm"], config.rms_norm_eps)
    latent = mm(x, lp["w_fc1"])
    routed, stats = _routed_local(x, lp, config, counted, live, latent)
    return (mm(routed, lp["w_fc2"])
            + _relu2_mlp(x, lp["w_up_s"], lp["w_down_s"])), stats


# -- the stack ----------------------------------------------------------------

def _run_stack(params: dict, config: ModelConfig, h: jax.Array, mamba,
               attn, counted, live, carry):
    """Walk the pattern. ``mamba(h, lp, layer, carry) -> (out, carry)``
    with ``layer`` the Mamba layer's index in its tree (a tracer inside a
    scan); ``attn(h, lp, layer, carry) -> (out, carry)`` with ``layer``
    the attention layer's index, a Python int (attention layers are
    never scanned). Returns (h, carry, stats)."""
    def run_mamba(h, idx, carry, stats):
        out, carry = mamba(h, _layer_view(params["mamba"], idx), idx, carry)
        return h + out, carry, stats

    def run_moe(h, idx, carry, stats):
        out, st = _moe(h, _layer_view(params["moe"], idx), config, counted,
                       live)
        return h + out, carry, stats + st

    mixers = {"M": run_mamba, "E": run_moe}

    stats = no_stats()
    for letters, n, at in _plan(config.hybrid_pattern):
        if letters == "*":
            lp = _layer_view(params["attn"], jnp.asarray(at["*"], jnp.int32))
            out, carry = attn(h, lp, at["*"], carry)
            h = h + out
        elif n == 1:
            h, carry, stats = mixers[letters](
                h, jnp.asarray(at[letters], jnp.int32), carry, stats)
        else:
            def body(state, i, letters=letters, at=at):
                h, carry, stats = state
                for ch in letters:
                    h, carry, stats = mixers[ch](h, i + at[ch], carry,
                                                 stats)
                return (h, carry, stats), None

            (h, carry, stats), _ = jax.lax.scan(
                body, (h, carry, stats), jnp.arange(n, dtype=jnp.int32))
    return h, carry, stats


def _logits(params, config, h, last_idx):
    if last_idx is not None:
        h = jnp.take_along_axis(h, last_idx[:, None, None].astype(jnp.int32),
                                axis=1)
    h = rms_norm(h, params["final_norm"], config.rms_norm_eps)
    return mm(h, params["lm_head"]).astype(jnp.float32)


def _refuse_mesh(mesh) -> None:
    if mesh is not None:
        raise ValueError("the hybrid family serves on one chip: its "
                         "recurrent state is not laid out over a mesh")


def _forward(params: dict, config: ModelConfig, tokens: jax.Array,
             cache: KVCache, offset: int, valid: Optional[jax.Array],
             last_idx: Optional[jax.Array], hidden: bool = False):
    """Tokens [B,S] at positions offset..offset+S behind the carry
    ``cache``: K and V of the context in its slots below ``offset``, the
    recurrent state at position ``offset`` in ``cache.state``. ``valid``
    [B,S]: a row's real positions (a prefix of it; None = all). Returns
    (logits | hidden states, cache, stats)."""
    B, S = tokens.shape
    if valid is None:
        valid = jnp.ones((B, S), bool)
    h = params["embed"][tokens]

    def mamba(h, lp, layer, carry):
        ck, cv, state = carry
        out, state = _mamba_prefill(h, lp, config, state, layer, valid)
        return out, (ck, cv, state)

    def attn(h, lp, layer, carry):
        ck, cv, state = carry
        out, ck, cv = _attn_prefill(h, lp, config, ck, cv, layer, offset)
        return out, (ck, cv, state)

    h, (ck, cv, state), stats = _run_stack(
        params, config, h, mamba, attn, valid, None,
        (cache.k, cache.v, cache.state))
    cache = KVCache(ck, cv, cache.lengths, state)
    if hidden:
        return rms_norm(h, params["final_norm"], config.rms_norm_eps), \
            cache, stats
    return _logits(params, config, h, last_idx), cache, stats


def forward_counted(params: dict, config: ModelConfig, tokens: jax.Array,
                    positions: jax.Array, cache: KVCache, mask,
                    valid: jax.Array, mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    last_idx: Optional[jax.Array] = None, **_):
    """The other families' ``forward_counted`` for the one use the
    scheduler has (pangu.forward_counted): tokens at the LAST S slots of
    the carry behind a cached prefix, whose K and V are in the carry's
    first slots and whose state snapshot is ``cache.state``."""
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


def _valid_from(prompt_lens: jax.Array, S: int) -> jax.Array:
    return jnp.arange(S)[None, :] < prompt_lens[:, None]


def prefill_counted(params: dict, config: ModelConfig, tokens: jax.Array,
                    prompt_lens: jax.Array, cache: KVCache,
                    valid: Optional[jax.Array],
                    mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    last_only: bool = False, **_):
    """llama.prefill's contract (right-padded prompts from position 0),
    and third the counts over ``valid``. Positions at or past a row's
    ``prompt_lens`` never move its state, whatever ``valid`` says."""
    _refuse_mesh(mesh)
    real = _valid_from(prompt_lens, tokens.shape[1])
    logits, cache, stats = _forward(
        params, config, tokens, cache, 0,
        real if valid is None else real & valid,
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
    offset..offset+C, resuming from ``cache``; lengths untouched): the
    recurrent layers resume from ``cache.state`` and hand the state at
    the chunk's end (at each row's last ``valid`` position) back in
    it."""
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
    valid = _valid_from(lens, S)
    h, _, _ = _forward(params, config, tokens, cache, 0, valid, None,
                       hidden=True)
    h = h.astype(jnp.float32)
    w = valid.astype(jnp.float32)
    pooled = (h * w[:, :, None]).sum(axis=1) / jnp.maximum(
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
    """One autoregressive step over both pools (llama.decode_step_paged's
    contract: tokens [B,1]; parked rows hold position, write their K and
    V to the garbage page and keep their state bit for bit). Returns
    (logits [B,1,V], cache with lengths advanced where active, counts
    int32 [4] over the live rows)."""
    from ..ops.paged_kv import write_decode_burst
    _refuse_mesh(mesh)
    B = tokens.shape[0]
    h = params["embed"][tokens]
    live = jnp.ones((B,), bool) if active is None else active

    def mamba(h, lp, layer, carry):
        pool, kv = carry
        out, pool = _mamba_decode(h, lp, config, pool, layer, live)
        return out, (pool, kv)

    def attn(h, lp, layer, carry):
        pool, kv = carry
        out, k, v = _attn_decode(h, lp, config, cache, layer, pages)
        return out, (pool, kv + ((k, v),))

    h, (pool, kv), stats = _run_stack(params, config, h, mamba, attn, None,
                                      live, (cache.state, ()))
    k_all = jnp.stack([k for k, _ in kv])
    v_all = jnp.stack([v for _, v in kv])
    cache = write_decode_burst(cache._replace(state=pool), k_all, v_all,
                               live.astype(jnp.int32))
    return _logits(params, config, h, None), cache, stats


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
        raise ValueError("the hybrid family decodes from the paged pool "
                         "only")

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

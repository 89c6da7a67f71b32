"""The Nemotron-H family's architecture file (one chip's share of
NVIDIA-Nemotron-3-Super-120B-A12B): a stack whose every layer is ONE
mixer behind a pre-norm and a residual, the mixer named by the layer's
letter in ``hybrid_override_pattern``: ``M`` Mamba-2, ``*`` attention,
``E`` LatentMoE. The contract is in benchmark/manifest.py's docstring.

**The layers, as :func:`forward` computes them** (float32,
``jax.default_matmul_precision("highest")``; ``x`` [T, H]; every layer
``x <- x + mixer(RMSNorm(x; norm_eps))``):

- ``M`` (``d = mamba_num_heads x mamba_head_dim``, ``G = n_groups``, ``N =
  ssm_state_size``): ``[z | xBC | dt] = u W_in`` (widths d | d + 2GN |
  heads). ``xBC_t <- silu(sum_j w_j xBC_{t-3+j} + b)``, j over the
  ``conv_kernel`` = 4 last positions (zeros before the first). Split
  ``x`` [heads, head_dim], ``B``, ``C`` [G, N]; head h reads group ``h //
  (heads / G)``. ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``.
  **The sequential recurrence, one position at a time**: ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``; ``y_t = S_t C_t + D
  x_t``; ``S`` [heads, head_dim, N] float32 from zero. (The program
  prefills in the chunked form and decodes a step at a time from a
  state pool; it has to agree with this.) Then the gated norm, gate
  first: ``y <- RMSNorm(y silu(z); G groups) w``; ``out = y W_out``.
- ``*``: GQA (``num_attention_heads`` query / ``num_key_value_heads``
  KV heads x ``head_dim``), causal, scale 1/sqrt(head_dim), no biases,
  NO rotary embedding (the configuration file's ``assumed`` says why).
- ``E``: ``s = sigmoid(x W_r)`` over all ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
  chosen; ``w_e = routed_scaling_factor x s_e / (sum of chosen s +
  1e-20)``; ``l = x W_fc1`` (hidden -> ``moe_latent_size``); expert e is
  ``relu(l U_e)^2 D_e``; ``routed = (sum over chosen e < n_held_experts
  of w_e expert_e(l)) W_fc2``; ``shared = relu(x U_s)^2 D_s``; ``out =
  routed + shared``. Experts ``n_held_experts`` and up are other chips':
  left out here exactly as in the program.
- Final norm, then the untied head over this chip's vocabulary slice.

No kernels, no cache, no batching, no chunked scan; the only code shared
with the program is nothing at all (``rms_norm`` and
``position_errors`` are benchmark/reference.py's).

Also here, JAX-free, what a step must move and a prompt must compute,
from shapes (:func:`decode_step_bytes`, :func:`prefill_flops`): read by
the ``decode_bw_util_family``, ``prefill_flops_util`` and
``state_step_share`` readers, held to hand arithmetic in
tests/benchmark/test_benchmark_nemotron_h.py. No kernel was written for
this family (PERF.md section 6, PR 32), so there is no ``_cost``
function.

Readers run in the parent of a run, which never imports JAX: this module
imports it inside the functions only the child calls.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

# The two limits, each from two kinds of reading on a v5e at the
# published widths, 22 layers, 128 held experts, int8 weights, the int8
# page pool and the float32 state pool (tools/check_reference_limit.py;
# my chip runs, PR 32; PERF.md section 6 has every number).
#
# TOL_MEDIAN, on the median position error of the logits
# (reference.position_errors). The sound program on samples of 2 x (128
# + 8) tokens (seeds 53, 1, 2, 3, 4, 5): SOUND_MEDIANS (90th
# percentile 3.6-3.9%, maximum 6.6-7.7%: every one of the 272 tokens has
# a margin under 2% between its 22nd and 23rd of 512 biased scores in
# some layer, system and reference then pick different experts and the
# position is legitimately off, hence the median alone). The limit is
# one and a half times the largest, reference.py's rule. The same system
# logits against the reference changed into each wrong model of
# :func:`wrong_models`, seed 53: rotary embedding applied 7.6%, no
# selection bias 18.5%, gated experts 19.2%, norm before gate 55.3%,
# every matrix rounded to int4 57.2%, no ``D`` skip 75.0%, no
# convolution bias 83.2%: 2.2 to 24 times the limit. (With the experts'
# down-projections drawn at the scaled normal's full width the sound
# program itself read 12.5-13.4%, one flipped expert of a token's five
# or six held ones moving the stream by a tenth, and rotary embedding
# 18.4%: models/nemotron_h._init_scale draws them a
# routed_scaling_factor-th as wide.)
#
# TOL_STATE, on the first Mamba layer's final state (after the two
# prefill chunks and the decode steps, read back from the state pool)
# against the reference's, over the quarter of the heads that forget
# slowest: ||S_sys - S_ref|| / ||S_ref||. It is the limit that fails a
# state kept in bfloat16 (the precision below the float32 the
# configuration states), which the logits cannot see (2.33% against the
# sound 2.25%): the sound program read SOUND_STATES, the reference
# with a bfloat16 state against the same system 0.901%. Between the
# two, 1.57 times the largest sound reading and two thirds of the wrong
# one; over all heads the two readings were 0.38% and about the same,
# the fast heads' own rounding hiding the state's.
TOL_MEDIAN = 0.034
TOL_STATE = 0.006

# Positions the system prefills a chunk at a time in the check, so that
# the second chunk starts from the carried state and window.
REF_CHUNK = 64

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def model_config(cfg: dict) -> dict:
    """``ModelConfig``'s keywords from the family's published keys."""
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern has "
                         f"{len(pattern)} letters for "
                         f"{cfg['num_hidden_layers']} layers")
    return dict(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=cfg["moe_shared_expert_intermediate_size"],
        num_layers=cfg["num_hidden_layers"], hybrid_pattern=pattern,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attn_rope=False,
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], ssm_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], ssm_chunk=cfg["chunk_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rope_scaling=None,
        rms_norm_eps=cfg["norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        num_experts=cfg["n_held_experts"],
        moe_router_width=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["n_shared_experts"],
        moe_renormalize=bool(cfg["norm_topk_prob"]),
        moe_scoring="sigmoid", moe_selection_bias=True,
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        mlp_activation=cfg["mlp_hidden_act"],
        moe_latent_size=cfg["moe_latent_size"],
        moe_capacity_factor=cfg.get("moe_capacity_factor"),
        bos_token_id=cfg.get("bos_token_id", 1),
        eos_token_ids=())       # ignore_eos: see the configuration file


class Weights(NamedTuple):
    """What :func:`forward` is handed: float32, one layer (one expert)
    at a time."""

    embed: object
    layer: Callable             # l -> dict of the layer's weights
    expert: Callable            # (l, e) -> (U [latent, F], D [F, latent])
    final_norm: object
    lm_head: object


class SystemOut(NamedTuple):
    """What :func:`system_logits` hands :func:`compare`."""

    logits: object              # [B, P+D, V] float32
    state: object               # [B, heads, head_dim, N]: Mamba layer 0's


def tree_index(pattern: str, layer: int) -> tuple:
    """(tree name, index in it) of stack layer ``layer``."""
    ch = pattern[layer]
    return KINDS[ch], pattern[:layer].count(ch)


def engine_weights(sched) -> Weights:
    """The engine's own int8 tree (models/nemotron_h.py: three stacked
    trees), dequantised one layer (one expert) at a time."""
    import jax
    import jax.numpy as jnp
    params, config = sched._params, sched.config
    f32 = jnp.float32
    pattern = config.hybrid_pattern

    def plain(leaf, i):
        if hasattr(leaf, "q"):
            return leaf.q[i].astype(f32) * leaf.s[i].astype(f32)
        return leaf[i].astype(f32)

    # The tree is an argument, never a closure (a closure bakes
    # gigabytes of constants into the program).
    @jax.jit
    def _layer(tree, i):
        return {name: plain(leaf, i) for name, leaf in tree.items()}

    @jax.jit
    def _expert(up, down, i, e):
        return (up.q[i, e].astype(f32) * up.s[i, e] if hasattr(up, "q")
                else up[i, e].astype(f32),
                down.q[i, e].astype(f32) * down.s[i, e]
                if hasattr(down, "q") else down[i, e].astype(f32))

    def layer_weights(layer):
        tree, i = tree_index(pattern, layer)
        return _layer({k: v for k, v in params[tree].items()
                       if k not in ("w_up_e", "w_down")}, i)

    def expert_weights(layer, e):
        tree, i = tree_index(pattern, layer)
        return _expert(params[tree]["w_up_e"], params[tree]["w_down"], i, e)

    head = params["lm_head"]
    return Weights(
        embed=params["embed"], layer=layer_weights, expert=expert_weights,
        final_norm=params["final_norm"].astype(f32),
        lm_head=(head.q.astype(f32) * head.s if hasattr(head, "q")
                 else head.astype(f32)))


# -- the three mixers ---------------------------------------------------------

def mamba(u, w, cfg: dict, wrong: str = ""):
    """One sequence through a Mamba-2 mixer by the sequential recurrence.
    u [T, H], already normed. Returns (out [T, H], final state [heads,
    head_dim, N], the indices of its slowest quarter of the heads)."""
    import jax
    import jax.numpy as jnp
    T = u.shape[0]
    nh, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d = nh * P
    zxd = u @ w["w_in"]
    z, xbc, dt = zxd[:, :d], zxd[:, d: d + d + 2 * G * N], zxd[:, 2 * d
                                                               + 2 * G * N:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(padded[j: j + T] * w["conv_w"][j] for j in range(K))
    if wrong != "no_conv_bias":
        conv = conv + w["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d].reshape(T, nh, P)
    Bm = jnp.repeat(xbc[:, d: d + G * N].reshape(T, G, N), nh // G, axis=1)
    Cm = jnp.repeat(xbc[:, d + G * N:].reshape(T, G, N), nh // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                     # [T, nh]
    A = -jnp.exp(w["A_log"])

    def step(S, inp):
        x_t, b_t, c_t, dt_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if wrong == "bf16_state":
            # bfloat16's 8 exponent and 7 mantissa bits (a convert there
            # and back is elided under the TPU compiler's
            # allow_excess_precision).
            S = jax.lax.reduce_precision(S, exponent_bits=8,
                                         mantissa_bits=7)
        return S, jnp.einsum("hpn,hn->hp", S, c_t)

    S, y = jax.lax.scan(step, jnp.zeros((nh, P, N), jnp.float32),
                        (x, Bm, Cm, dt))
    # The heads whose state forgets slowest over this sequence (smallest
    # mean dt |A|): where a state kept in fewer bits drifts furthest and
    # the inputs' own rounding averages out most (compare's state limit).
    slow = jnp.argsort(jnp.mean(dt, axis=0) * -A)[: max(1, nh // 4)]
    if wrong != "no_d_skip":
        y = y + w["D"][:, None] * x
    y = y.reshape(T, d)

    def grouped_norm(v):
        g = v.reshape(T, G, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + cfg["norm_eps"])
        return g.reshape(T, d) * w["gnorm"]

    if wrong == "norm_before_gate":
        y = grouped_norm(y) * jax.nn.silu(z)
    else:
        y = grouped_norm(y * jax.nn.silu(z))
    return y @ w["w_out"], S, slow


def attention(u, w, cfg: dict, wrong: str = ""):
    """Causal grouped-query attention of one sequence. u [T, H]."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import rope
    T = u.shape[0]
    heads, kvh, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    qkv = u @ w["wqkv"]
    q = qkv[:, : heads * D].reshape(T, heads, D)
    k = qkv[:, heads * D: (heads + kvh) * D].reshape(T, kvh, D)
    v = qkv[:, (heads + kvh) * D:].reshape(T, kvh, D)
    pos = jnp.arange(T)
    if wrong == "rotary_applied":
        q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos,
                                                     cfg["rope_theta"])
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(D))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(T, heads * D) @ w["wo"]


def route(x, router, bias, cfg: dict, wrong: str = ""):
    """[T, n_routed] weights over ALL experts: sigmoid scores, the top-k
    of score + bias chosen, weighed by the unbiased scores divided by
    their sum + 1e-20 (``norm_topk_prob``) times
    ``routed_scaling_factor``, zero elsewhere; and each token's margin
    between its k-th and (k+1)-th biased score, relative to the k-th."""
    import jax
    import jax.numpy as jnp
    top_k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ router)
    pick = scores if wrong == "no_selection_bias" else scores + bias
    top_p, top_i = jax.lax.top_k(pick, top_k + 1)
    margin = (top_p[:, top_k - 1] - top_p[:, top_k]) / top_p[:, top_k - 1]
    top_i = top_i[:, :top_k]
    kept = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg["norm_topk_prob"]:
        kept = kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20)
    kept = kept * cfg["routed_scaling_factor"]
    weights = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(kept)
    return weights, margin


def relu2_mlp(x, up, down, wrong: str = ""):
    import jax
    import jax.numpy as jnp
    h = x @ up
    if wrong == "gated_experts":
        # The up-projection read as a fused gate|up pair (the other
        # routed families' expert).
        F = h.shape[-1] // 2
        return (jax.nn.silu(h[:, :F]) * h[:, F:]) @ down[:F]
    return jnp.square(jax.nn.relu(h)) @ down


@functools.cache
def _jitted():
    import jax
    from benchmark.reference import rms_norm

    @functools.partial(jax.jit, static_argnames=("wrong", "cfg_key"))
    def mamba_layer(h, w, *, wrong, cfg_key):
        cfg = dict(cfg_key)
        with jax.default_matmul_precision("highest"):
            out, S, slow = jax.vmap(lambda x: mamba(
                rms_norm(x, w["norm"], cfg["norm_eps"]), w, cfg, wrong))(h)
            return h + out, S, slow

    @functools.partial(jax.jit, static_argnames=("wrong", "cfg_key"))
    def attn_layer(h, w, *, wrong, cfg_key):
        cfg = dict(cfg_key)
        with jax.default_matmul_precision("highest"):
            return h + jax.vmap(lambda x: attention(
                rms_norm(x, w["norm"], cfg["norm_eps"]), w, cfg, wrong))(h)

    @functools.partial(jax.jit, static_argnames=("wrong", "cfg_key"))
    def moe_open(h, w, *, wrong, cfg_key):
        """(normed tokens [B*T, H], their latents, routing weights,
        margins, the shared expert's output)."""
        cfg = dict(cfg_key)
        with jax.default_matmul_precision("highest"):
            x = rms_norm(h, w["norm"], cfg["norm_eps"]).reshape(
                -1, h.shape[-1])
            kept, margin = route(x, w["router"], w["router_bias"], cfg,
                                 wrong)
            return (x @ w["w_fc1"], kept, margin,
                    relu2_mlp(x, w["w_up_s"], w["w_down_s"]))

    @functools.partial(jax.jit, static_argnames=("wrong",))
    def expert_add(acc, latent, weight_col, up, down, *, wrong):
        with jax.default_matmul_precision("highest"):
            return acc + weight_col[:, None] * relu2_mlp(latent, up, down,
                                                         wrong)

    @jax.jit
    def moe_close(h, acc, shared, w_fc2):
        with jax.default_matmul_precision("highest"):
            return h + (acc @ w_fc2 + shared).reshape(h.shape)

    return mamba_layer, attn_layer, moe_open, expert_add, moe_close


_CFG_KEYS = ("mamba_num_heads", "mamba_head_dim", "n_groups",
             "ssm_state_size", "conv_kernel", "norm_eps",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "rope_theta", "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor")


def forward(cfg: dict, tokens, weights: Weights) -> tuple:
    """Logits [B, T, V] (float32) of ``tokens`` [B, T], every position,
    and the facts ``compare`` reads: the smallest top-k margin of each
    token over the routed layers, each routed layer's [B*T, n_routed]
    weights, every Mamba layer's final state ([B, heads, head_dim, N])
    and the indices of its slowest heads ([B, heads / 4]).
    ``cfg["_wrong"]`` (absent in a run) names a deliberately wrong
    model."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import rms_norm
    mamba_layer, attn_layer, moe_open, expert_add, moe_close = _jitted()
    wrong = cfg.get("_wrong", "")
    cfg_key = tuple((k, cfg[k]) for k in _CFG_KEYS)
    held = cfg["n_held_experts"]
    facts = {"min_margin": None, "routing": [], "states": [],
             "slow_heads": []}
    with jax.default_matmul_precision("highest"):
        h = weights.embed[tokens].astype(jnp.float32)
    for layer, kind in enumerate(cfg["hybrid_override_pattern"]):
        w = weights.layer(layer)
        if kind == "M":
            h, S, slow = mamba_layer(h, w, wrong=wrong, cfg_key=cfg_key)
            facts["states"].append(S)
            facts["slow_heads"].append(slow)
        elif kind == "*":
            h = attn_layer(h, w, wrong=wrong, cfg_key=cfg_key)
        else:
            latent, kept, margin, shared = moe_open(h, w, wrong=wrong,
                                                    cfg_key=cfg_key)
            facts["routing"].append(kept)
            facts["min_margin"] = (
                margin if facts["min_margin"] is None
                else jnp.minimum(facts["min_margin"], margin))
            acc = jnp.zeros_like(latent)
            for e in range(held):
                acc = expert_add(acc, latent, kept[:, e],
                                 *weights.expert(layer, e), wrong=wrong)
            h = moe_close(h, acc, shared, w["w_fc2"])
    with jax.default_matmul_precision("highest"):
        logits = rms_norm(h, weights.final_norm,
                          cfg["norm_eps"]) @ weights.lm_head
    return logits, facts


WRONG = ("bf16_state", "no_d_skip", "no_conv_bias", "norm_before_gate",
         "no_selection_bias", "gated_experts", "rotary_applied")


def wrong_models(cfg: dict, weights: Weights) -> dict:
    """name -> (cfg, weights) of the wrong models a limit must fail: a
    state kept in bfloat16 (the precision below the float32 the
    configuration states), no ``D`` skip, no convolution bias, the norm
    before the gate, no selection bias, gated experts, rotary embedding
    applied, and every matrix rounded to int4 (the precision below the
    int8 the stack states)."""
    import jax.numpy as jnp
    out = {name: ({**cfg, "_wrong": name}, weights) for name in WRONG}

    def q4(w):
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0
        return jnp.round(w / jnp.where(scale > 0, scale, 1.0)) * scale

    mats = {"w_in", "w_out", "wqkv", "wo", "w_fc1", "w_fc2", "w_up_s",
            "w_down_s"}
    out["int4_weights"] = (cfg, weights._replace(
        layer=lambda l: {k: q4(v) if k in mats else v
                         for k, v in weights.layer(l).items()},
        expert=lambda l, e: tuple(q4(m) for m in weights.expert(l, e))))
    return out


def system_logits(sched, tokens, n_prefill: int) -> SystemOut:
    """The system's logits for ``tokens`` [B, P+D] through the programs
    the scheduler serves with: the first P positions through
    ``prefill_chunk`` REF_CHUNK at a time (the second chunk starts from
    the carried state and convolution window and attends the first's K
    and V), K and V spliced into a paged pool of the scheduler's kind
    and the state into the state pool's rows (as admission does), then D
    decode steps over both pools; and the first Mamba layer's state
    after them, read back from the pool."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.models.llama import KVCache
    from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,
                                               write_prefill_batch)
    from p2p_llm_chat_tpu.ops.state_pool import write_rows
    model, params, config = sched._model, sched._params, sched.config
    mesh = sched.mesh
    B, T = tokens.shape
    P = n_prefill
    C = REF_CHUNK if P % REF_CHUNK == 0 else P
    ps = sched.page_size
    window_pages = 1
    while window_pages * ps < T + 1:
        window_pages *= 2
    per_row = max(-(-(T + 1) // ps), window_pages)
    lens = jnp.full((B,), P, jnp.int32)
    rows = jnp.arange(B, dtype=jnp.int32)
    tables = 1 + jnp.arange(B * per_row, dtype=jnp.int32).reshape(B, per_row)

    @functools.partial(jax.jit, static_argnames=("offset",))
    def chunk(params, toks, carry, *, offset):
        return model.prefill_chunk(params, config, toks, carry, offset, mesh)

    @jax.jit
    def splice(carry):
        cache = PagedKVCache.create(config, B, 1 + B * per_row, ps,
                                    max_pages_per_row=per_row,
                                    dtype=sched._dtype,
                                    quantized=sched.kv_quant, mesh=mesh)
        cache = write_prefill_batch(cache, carry.k, carry.v, rows, lens,
                                    tables)
        return cache._replace(state=write_rows(cache.state, carry.state,
                                               rows))

    @functools.partial(jax.jit, donate_argnums=(2,))
    def decode(params, tok, cache):
        return model.decode_step_paged(params, config, tok, cache, mesh,
                                       pages=window_pages)

    carry = KVCache.create(config, B, P, dtype=sched._dtype)
    out = []
    for off in range(0, P, C):
        logits, carry = chunk(params, tokens[:, off:off + C], carry,
                              offset=off)
        out.append(logits.astype(jnp.float32))
    cache = splice(carry)
    for t in range(P, T):
        step, cache = decode(params, tokens[:, t:t + 1], cache)
        out.append(step.astype(jnp.float32))
    return SystemOut(logits=jnp.concatenate(out, axis=1),
                     state=cache.state.ssm[0, :B])


def compare(system: SystemOut, reference_logits, facts: dict,
            cfg: dict) -> dict:
    """reference.compare's numbers under this family's limit on the
    median, the state's error under its own (``state_error``: the first
    Mamba layer's final state against the reference's over the slowest
    quarter of the heads, relative, in the Frobenius norm), and beside
    them how many tokens sit on a tie
    between their k-th and (k+1)-th expert in some layer (``near_ties``:
    margin under 2%) and the share of the routed pairs that went to a
    held expert (``local_share``). Nothing can overflow: the program's
    buckets hold every row when any expert is sent more than a bucket's
    worth."""
    import jax.numpy as jnp
    from benchmark import reference
    out = reference.compare(system.logits, reference_logits, routed=True)
    held = cfg["n_held_experts"]
    routed = sum(int(jnp.sum(w > 0)) for w in facts["routing"])
    local = sum(int(jnp.sum(w[:, :held] > 0)) for w in facts["routing"])
    slow = facts["slow_heads"][0][:, :, None, None]
    ref_state = jnp.take_along_axis(
        facts["states"][0].astype(jnp.float32), slow, axis=1)
    sys_state = jnp.take_along_axis(
        system.state.astype(jnp.float32), slow, axis=1)
    out["state_error"] = float(jnp.linalg.norm(sys_state - ref_state)
                               / jnp.linalg.norm(ref_state))
    out["near_ties"] = int(jnp.sum(facts["min_margin"] < 0.02))
    out["local_share"] = local / max(routed, 1)
    out["overflow_pairs"] = 0
    out["ok"] = bool(out["ok"] and out["median"] <= TOL_MEDIAN
                     and out["state_error"] <= TOL_STATE)
    out["tolerance"] = {"median": TOL_MEDIAN, "max": None,
                        "state_error": TOL_STATE, "overflow_pairs": 0}
    return out


# -- what a step must move and a prompt must compute (JAX-free) ---------------

def _q8(n_in: int, n_out: int) -> float:
    """Bytes of an int8 [n_in, n_out] weight with a float32 scale a
    column (benchmark/roofline.py's count)."""
    return n_in * n_out + 4 * n_out


def _conv_dim(cfg: dict) -> int:
    return (cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            + 2 * cfg["n_groups"] * cfg["ssm_state_size"])


def layer_counts(cfg: dict) -> dict:
    p = cfg["hybrid_override_pattern"]
    return {"M": p.count("M"), "E": p.count("E"), "*": p.count("*")}


def layer_params(cfg: dict) -> dict:
    """Matrix parameters of each part, as published (no padding)."""
    H = cfg["hidden_size"]
    d = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    heads, kvh, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    Lw, F = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    Fs = cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]
    return {"mamba": H * (d + _conv_dim(cfg) + cfg["mamba_num_heads"])
            + d * H,
            "attn": H * (heads + 2 * kvh) * D + heads * D * H,
            "latent": 2 * H * Lw, "shared": 2 * H * Fs,
            "router": H * cfg["n_routed_experts"],
            "expert": 2 * Lw * F}


def state_row_bytes(cfg: dict) -> float:
    """One row of ONE Mamba layer in the state pool: the float32 state
    and the bf16 convolution window."""
    return (4.0 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            * cfg["ssm_state_size"]
            + 2.0 * (cfg["conv_kernel"] - 1) * _conv_dim(cfg))


def page_token_bytes(cfg: dict) -> float:
    """One token of one attention layer in the int8 page pool: K and V
    of every KV head and a float32 scale for each."""
    return 2.0 * cfg["num_key_value_heads"] * (cfg["head_dim"] + 4)


def decode_step_bytes(cfg: dict, rows: float, context: float) -> float:
    """Bytes one decode step has to move: every Mamba, attention, latent
    and shared-expert weight and the head once; of the held experts
    those some row reached (under even routing ``1 - (1 - k/n)^rows`` of
    them); the float32 routers and their biases; the rows' embeddings in
    bf16; each live row's state and window in every Mamba layer, read
    AND written; and each row's cached K and V in the attention
    layers."""
    H = cfg["hidden_size"]
    d = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    heads, kvh, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    Lw, F = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    Fs = cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]
    n = layer_counts(cfg)
    p_touch = 1.0 - (1.0 - cfg["num_experts_per_tok"]
                     / cfg["n_routed_experts"]) ** rows
    per_m = (_q8(H, d + _conv_dim(cfg) + cfg["mamba_num_heads"])
             + _q8(d, H) + 2.0 * rows * state_row_bytes(cfg))
    per_a = (_q8(H, (heads + 2 * kvh) * D) + _q8(heads * D, H)
             + rows * context * page_token_bytes(cfg))
    per_e = (_q8(H, Lw) + _q8(Lw, H) + _q8(H, Fs) + _q8(Fs, H)
             + 4.0 * (H + 1) * cfg["n_routed_experts"]
             + cfg["n_held_experts"] * p_touch * (_q8(Lw, F) + _q8(F, Lw)))
    return (n["M"] * per_m + n["*"] * per_a + n["E"] * per_e
            + _q8(H, cfg["vocab_size"]) + rows * 2 * H)


def attention_pair_flops(cfg: dict) -> float:
    """FLOPs of one (query token, context token) pair in one attention
    layer: a score and a value over head_dim, for every query head."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def prefill_flops(cfg: dict, tokens: float, context_pairs: float) -> float:
    """FLOPs the prompt positions require on this chip: two a parameter a
    token for every matrix the token goes through (Mamba's projections,
    attention's, the latent projections, the shared expert, the router,
    and under even routing ``k x held / n_routed`` held experts a routed
    layer); the recurrence itself (a state update and a read of heads x
    head_dim x N each, two operations a number) and the convolution; and
    the attention's pairs in its layers. The chunked form's extra
    products are its own choice and are not counted; the head runs for
    one position a request and is left out."""
    p = layer_params(cfg)
    n = layer_counts(cfg)
    local = (cfg["num_experts_per_tok"] * cfg["n_held_experts"]
             / cfg["n_routed_experts"])
    recurrence = (4.0 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
                  * cfg["ssm_state_size"]
                  + 2.0 * cfg["conv_kernel"] * _conv_dim(cfg))
    per_token = (n["M"] * (2.0 * p["mamba"] + recurrence)
                 + n["*"] * 2.0 * p["attn"]
                 + n["E"] * 2.0 * (p["latent"] + p["shared"] + p["router"]
                                   + local * p["expert"]))
    return (tokens * per_token
            + context_pairs * n["*"] * attention_pair_flops(cfg))

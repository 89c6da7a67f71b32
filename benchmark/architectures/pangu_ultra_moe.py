"""The openPangu-Ultra-MoE family's architecture file (one chip's share
of openPangu-Ultra-MoE-718B): latent attention (MLA), sandwich norms,
leading dense layers, then routed layers whose router scores every
expert while the chip holds a share of them, beside a shared expert.
The contract is in benchmark/manifest.py's docstring.

**The layer, as :func:`forward` computes it** (float32,
``jax.default_matmul_precision("highest")``; ``x`` [T, H]; ``n(.)`` an
RMSNorm with learned weight, eps ``rms_norm_eps``):

- ``a = n_in(x)``; ``cq = n_qa(a Wqa)``; ``q = cq Wqb`` as [T, heads,
  nope + rope]; ``q_rope = rope(q_rope)`` (rotate-half, ``rope_theta``,
  no scaling).
- ``kv = a Wkva``; ``c = n_kva(kv[:, :kv_lora_rank])``; ``k_rope =
  rope(kv[:, kv_lora_rank:])``, one row a token shared by all heads.
- The EXPANDED form only: ``[k_nope_h | v_h] = c Wkvb``; ``s_h = (q_nope_h
  . k_nope_h + q_rope_h . k_rope) / sqrt(nope + rope)``; causal softmax;
  ``attn = concat_h(p_h v_h) Wo``. (The system decodes through the
  weight-absorbed form over its latent cache; it has to agree with this.)
- ``x1 = x + n_post_attn(attn)``; ``m = n_pre_mlp(x1)``; ``x2 = x1 +
  n_post_mlp(mlp(m))``.
- ``mlp``: a dense layer's SwiGLU (``intermediate_size``), or ``shared(m)
  + sum_{e in top-k, e held} w_e expert_e(m)`` (``moe_intermediate_size``
  each): ``g = sigmoid(m Wr)`` over all ``n_routed_experts``, the
  ``num_experts_per_tok`` largest, ``w = g_top / (sum g_top + 1e-20) x
  routed_scaling_factor``. Experts ``n_held_experts`` and up are other
  chips': left out here exactly as in the program.
- Final norm, then the untied head over this chip's vocabulary slice.

No kernels, no cache, no batching; the only code shared with the program
is nothing at all (``rms_norm``, ``rope``, ``swiglu``,
``position_errors`` are benchmark/reference.py's).

Also here, JAX-free, what the new kernels and a step must do, from
shapes (:func:`decode_step_bytes`, :func:`prefill_flops`,
:func:`mla_decode_cost`, :func:`mla_prefill_cost`): read by the
``decode_bw_util_family`` and ``prefill_flops_util`` readers and by
tools/check_mla_kernels.py, held to hand arithmetic in
tests/benchmark/test_benchmark_pangu.py.

Readers run in the parent of a run, which never imports JAX: this module
imports it inside the functions only the child calls.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

# The limit on the median position error (reference.position_errors),
# from two kinds of reading on a v5e at the published widths, 9 layers,
# 16 held experts, int8 weights and the int8 latent pool
# (tools/check_reference_limit.py; my chip run, PR 30; PERF.md section
# 6). The sound program on six samples of 2 x (128 + 8) tokens (seeds
# 53, 1, 2, 3, 4, 5): median 1.48%, 1.67%, 1.84%, 1.36%, 1.33%, 1.42%
# (90th percentile 1.7-8.0%, maximum 15-18%: where the 8th and 9th
# router scores tie within bf16's rounding a position is legitimately
# far off, hence the median alone). The limit is one and a half times
# the largest, reference.py's rule. The same system logits against the
# reference changed into each wrong model of :func:`wrong_models`, seed
# 53: a softmax router 6.7%, scaling factor 1 12.2%, RoPE over the nope
# part too 50.7%, no ``n_kva`` 60.1%, every matrix rounded to int4 (the
# precision below the int8 the stack states) 65.7%, no post-attention
# and post-MLP norms 99.0%, the absorbed form without ``Wuv`` 135.8%:
# 2.4 to 48 times the limit. reference.py's shared 4.5% would have
# passed none of them either, but stands at three times the sound
# reading.
TOL_MEDIAN = 0.028

# Positions the system prefills a chunk at a time in the check, so that
# the second chunk attends the first through the carried latents at an
# offset, as every prompt of the cell's traffic does.
REF_CHUNK = 64


def model_config(cfg: dict) -> dict:
    """``ModelConfig``'s keywords from the family's published keys."""
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return dict(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["moe_intermediate_size"],
        dense_intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=1,
        head_dim=dn + dr, q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=dn,
        qk_rope_head_dim=dr, v_head_dim=cfg["v_head_dim"],
        sandwich_norm=bool(cfg["sandwich_norm"]),
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rope_scaling=None,
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        num_experts=cfg["n_held_experts"],
        moe_router_width=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["n_shared_experts"],
        moe_renormalize=bool(cfg["norm_topk_prob"]),
        moe_scoring="sigmoid",
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        moe_capacity_factor=cfg.get("moe_capacity_factor"),
        bos_token_id=cfg.get("bos_token_id", 1),
        eos_token_ids=())       # ignore_eos: see the configuration file


class Weights(NamedTuple):
    """What :func:`forward` is handed: float32, one layer (one expert)
    at a time."""

    embed: object
    layer: Callable             # l -> dict of the layer's weights
    expert: Callable            # (l, e) -> (w_gate, w_up, w_down)
    final_norm: object
    lm_head: object


NORMS = ("attn_norm", "q_a_norm", "kv_a_norm", "post_attn_norm", "mlp_norm",
         "post_mlp_norm")


def engine_weights(sched) -> Weights:
    """The engine's own int8 tree (models/pangu.py's fused layout),
    dequantised one layer (one expert) at a time: ``wqkva`` split into
    Wqa and Wkva (its pad columns dropped), ``wqb`` from [all nope | all
    rope] columns to [heads, nope + rope], ``wkvb`` as [r, heads, nope +
    v], the fused gate|up pairs halved."""
    import jax
    import jax.numpy as jnp
    params, config = sched._params, sched.config
    f32 = jnp.float32
    Ld = config.first_k_dense
    Hq, ql, r = config.num_heads, config.q_lora_rank, config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)

    def deq(leaf, i):
        return leaf.q[i].astype(f32) * leaf.s[i].astype(f32)

    def halves(w):
        F = w.shape[-1] // 2
        return w[..., :F], w[..., F:]

    # The tree is an argument, never a closure (a closure bakes
    # gigabytes of constants into the program).
    @functools.partial(jax.jit, static_argnames=("dense",))
    def _layer(tree, i, *, dense):
        w = {n: tree[n][i].astype(f32) for n in NORMS}
        qkva = deq(tree["wqkva"], i)
        w["wqa"], w["wkva"] = qkva[:, :ql], qkva[:, ql: ql + r + dr]
        wqb = deq(tree["wqb"], i)
        w["wqb"] = jnp.concatenate(
            [wqb[:, : Hq * dn].reshape(ql, Hq, dn),
             wqb[:, Hq * dn:].reshape(ql, Hq, dr)], axis=-1)
        w["wkvb"] = deq(tree["wkvb"], i).reshape(r, Hq, dn + dv)
        w["wo"] = deq(tree["wo"], i)
        if dense:
            w["w_gate"], w["w_up"] = halves(deq(tree["wgu"], i))
            w["w_down"] = deq(tree["w_down"], i)
        else:
            w["router"] = tree["router"][i].astype(f32)
            w["s_gate"], w["s_up"] = halves(deq(tree["wgu_s"], i))
            w["s_down"] = deq(tree["w_down_s"], i)
        return w

    @jax.jit
    def _expert(wgu_e, w_down, i, e):
        gate, up = halves(wgu_e.q[i, e].astype(f32) * wgu_e.s[i, e])
        return gate, up, w_down.q[i, e].astype(f32) * w_down.s[i, e]

    def layer_weights(layer):
        if layer < Ld:
            return _layer(params["dense_layers"], layer, dense=True)
        return _layer({k: v for k, v in params["layers"].items()
                       if k not in ("wgu_e", "w_down")}, layer - Ld,
                      dense=False)

    def expert_weights(layer, e):
        moe = params["layers"]
        return _expert(moe["wgu_e"], moe["w_down"], layer - Ld, e)

    head = params["lm_head"]
    return Weights(
        embed=params["embed"], layer=layer_weights, expert=expert_weights,
        final_norm=params["final_norm"].astype(f32),
        lm_head=(head.q.astype(f32) * head.s if hasattr(head, "q")
                 else head.astype(f32)))


def attention(a, w, cfg: dict, wrong: str = ""):
    """Causal latent attention of one sequence in the expanded form.
    a: [T, H], already normed. ``wrong`` names a deliberately wrong
    model (:func:`wrong_models`)."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import rms_norm, rope
    T = a.shape[0]
    r = cfg["kv_lora_rank"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    dr = cfg["qk_rope_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(T)
    cq = rms_norm(a @ w["wqa"], w["q_a_norm"], eps)
    q = jnp.einsum("tq,qhd->thd", cq, w["wqb"])
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, theta)
    kv = a @ w["wkva"]
    c = kv[:, :r]
    if wrong != "no_kv_a_norm":
        c = rms_norm(c, w["kv_a_norm"], eps)
    k_rope = rope(kv[:, None, r:], pos, theta)[:, 0]
    kvb = jnp.einsum("tr,rhd->thd", c, w["wkvb"])
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    if wrong == "rope_on_nope":
        q_nope, k_nope = rope(q_nope, pos, theta), rope(k_nope, pos, theta)
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
         + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
         ) / jnp.sqrt(jnp.float32(dn + dr))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if wrong == "absorbed_without_wuv":
        # The absorbed form's latent-space output, cut to the value
        # width instead of multiplied by Wuv.
        o = jnp.einsum("hqk,kr->qhr", p, c)[..., :dv]
    else:
        o = jnp.einsum("hqk,khd->qhd", p, v)
    return o.reshape(T, -1) @ w["wo"]


def route(x, router, cfg: dict, wrong: str = ""):
    """[T, n_routed] weights over ALL experts: sigmoid scores, the top-k
    kept, divided by their sum + 1e-20 when ``norm_topk_prob``, times
    ``routed_scaling_factor``, zero elsewhere; and each token's margin
    between its k-th and (k+1)-th score, relative to the k-th."""
    import jax
    import jax.numpy as jnp
    top_k = cfg["num_experts_per_tok"]
    logits = x @ router
    scores = (jax.nn.softmax(logits, axis=-1) if wrong == "softmax_router"
              else jax.nn.sigmoid(logits))
    top_w, top_i = jax.lax.top_k(scores, top_k + 1)
    margin = (top_w[:, top_k - 1] - top_w[:, top_k]) / top_w[:, top_k - 1]
    kept = top_w[:, :top_k]
    if cfg["norm_topk_prob"]:
        kept = kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20)
    kept = kept * (1.0 if wrong == "scale_1"
                   else cfg["routed_scaling_factor"])
    weights = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], top_i[:, :top_k]].set(kept)
    return weights, margin


@functools.cache
def _jitted():
    import jax
    from benchmark.reference import rms_norm, swiglu

    @functools.partial(jax.jit, static_argnames=("eps", "wrong", "cfg_key"))
    def attn_part(h, w, *, eps, wrong, cfg_key):
        cfg = dict(cfg_key)
        with jax.default_matmul_precision("highest"):
            a = jax.vmap(lambda x: attention(
                rms_norm(x, w["attn_norm"], eps), w, cfg, wrong))(h)
            if wrong != "no_post_norms":
                a = rms_norm(a, w["post_attn_norm"], eps)
            h = h + a
            return h, rms_norm(h, w["mlp_norm"], eps)

    @jax.jit
    def mlp_add(acc, x, weight_col, w_gate, w_up, w_down):
        with jax.default_matmul_precision("highest"):
            return acc + weight_col[:, None] * swiglu(x, w_gate, w_up,
                                                      w_down)

    @functools.partial(jax.jit, static_argnames=("eps", "wrong"))
    def close(h, out, norm, *, eps, wrong):
        if wrong != "no_post_norms":
            out = rms_norm(out, norm, eps)
        return h + out

    @functools.partial(jax.jit, static_argnames=("wrong", "cfg_key"))
    def routing(x, router, *, wrong, cfg_key):
        with jax.default_matmul_precision("highest"):
            return route(x, router, dict(cfg_key), wrong)

    return attn_part, mlp_add, close, routing


_CFG_KEYS = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "rms_norm_eps", "rope_theta",
             "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor")


def forward(cfg: dict, tokens, weights: Weights) -> tuple:
    """Logits [B, T, V] (float32) of ``tokens`` [B, T], every position,
    and the routing facts ``compare`` reads: the smallest top-k margin of
    each token over the routed layers, and each routed layer's
    [B*T, n_routed] weights. ``cfg["_wrong"]`` (absent in a run) names a
    deliberately wrong model."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import rms_norm
    attn_part, mlp_add, close, routing = _jitted()
    wrong = cfg.get("_wrong", "")
    cfg_key = tuple((k, cfg[k]) for k in _CFG_KEYS)
    eps = cfg["rms_norm_eps"]
    held = cfg["n_held_experts"]
    B, T = tokens.shape
    facts = {"min_margin": None, "routing": []}
    with jax.default_matmul_precision("highest"):
        h = weights.embed[tokens].astype(jnp.float32)
    for layer in range(cfg["num_hidden_layers"]):
        w = weights.layer(layer)
        h, m = attn_part(h, w, eps=eps, wrong=wrong, cfg_key=cfg_key)
        flat = m.reshape(B * T, -1)
        ones = jnp.ones((B * T,), jnp.float32)
        if layer < cfg["first_k_dense_replace"]:
            out = mlp_add(jnp.zeros_like(flat), flat, ones, w["w_gate"],
                          w["w_up"], w["w_down"])
        else:
            kept, margin = routing(flat, w["router"], wrong=wrong,
                                   cfg_key=cfg_key)
            facts["routing"].append(kept)
            facts["min_margin"] = (
                margin if facts["min_margin"] is None
                else jnp.minimum(facts["min_margin"], margin))
            out = mlp_add(jnp.zeros_like(flat), flat, ones, w["s_gate"],
                          w["s_up"], w["s_down"])
            for e in range(held):
                out = mlp_add(out, flat, kept[:, e],
                              *weights.expert(layer, e))
        h = close(h, out.reshape(h.shape), w["post_mlp_norm"], eps=eps,
                  wrong=wrong)
    with jax.default_matmul_precision("highest"):
        logits = rms_norm(h, weights.final_norm, eps) @ weights.lm_head
    return logits, facts


def wrong_models(cfg: dict, weights: Weights) -> dict:
    """name -> (cfg, weights) of the wrong models a limit must fail:
    a softmax router, scaling factor 1, no ``n_kva``, no post-attention
    and post-MLP norms, RoPE over the nope part too, the absorbed form
    without ``Wuv``, and every matrix rounded to int4 (the precision
    below the int8 the stack states)."""
    import jax.numpy as jnp
    out = {name: ({**cfg, "_wrong": name}, weights)
           for name in ("softmax_router", "scale_1", "no_kv_a_norm",
                        "no_post_norms", "rope_on_nope",
                        "absorbed_without_wuv")}

    def q4(w):
        """[in, .., out-ish] rounded to 4 signed bits a column of its
        first axis (absmax scale), back in float32."""
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0
        return jnp.round(w / jnp.where(scale > 0, scale, 1.0)) * scale

    skip = set(NORMS) | {"router"}
    out["int4_weights"] = (cfg, weights._replace(
        layer=lambda l: {k: v if k in skip else q4(v)
                         for k, v in weights.layer(l).items()},
        expert=lambda l, e: tuple(q4(m) for m in weights.expert(l, e))))
    return out


def system_logits(sched, tokens, n_prefill: int):
    """The system's logits for ``tokens`` [B, P+D] through the programs
    the scheduler serves with: the first P positions through
    ``prefill_chunk`` REF_CHUNK at a time (every chunk after the first
    attends the carried latents at an offset, as the cell's prompts do),
    the latents spliced into a paged pool of the scheduler's kind
    (``write_prefill_batch``, as admission does), then D decode steps
    through the absorbed form over that pool. Returns [B, P+D, V]
    float32."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.models.llama import KVCache
    from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,
                                               write_prefill_batch)
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
        return write_prefill_batch(cache, carry.k, carry.v, rows, lens,
                                   tables)

    @jax.jit
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
    return jnp.concatenate(out, axis=1)


def compare(system, reference_logits, facts: dict, cfg: dict) -> dict:
    """reference.compare's numbers under this family's limit on the
    median, and beside them how many tokens sit on a tie between their
    k-th and (k+1)-th expert in some layer (``near_ties``: margin under
    2%), and the share of the routed pairs that went to a held expert
    (``local_share``). Nothing can overflow: the program's buckets hold
    every row when any expert is sent more than a bucket's worth."""
    import jax.numpy as jnp
    from benchmark import reference
    out = reference.compare(system, reference_logits, routed=True)
    held = cfg["n_held_experts"]
    routed = sum(int(jnp.sum(w > 0)) for w in facts["routing"])
    local = sum(int(jnp.sum(w[:, :held] > 0)) for w in facts["routing"])
    out["near_ties"] = int(jnp.sum(facts["min_margin"] < 0.02))
    out["local_share"] = local / max(routed, 1)
    out["overflow_pairs"] = 0
    out["ok"] = bool(out["ok"] and out["median"] <= TOL_MEDIAN)
    out["tolerance"] = {"median": TOL_MEDIAN, "max": None,
                        "overflow_pairs": 0}
    return out


# -- what a step and the new kernels must move and do (JAX-free) --------------

def _q8(n_in: int, n_out: int) -> float:
    """Bytes of an int8 [n_in, n_out] weight with a float32 scale a
    column (benchmark/roofline.py's count)."""
    return n_in * n_out + 4 * n_out


def layer_params(cfg: dict) -> dict:
    """Parameters of each part of a layer, as published (no padding)."""
    H, Hq = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    F = cfg["moe_intermediate_size"]
    return {"mla": (H * ql + ql * Hq * (dn + dr) + H * (r + dr)
                    + r * Hq * (dn + dv) + Hq * dv * H),
            "dense_mlp": 3 * H * cfg["intermediate_size"],
            "expert": 3 * H * F,
            "shared": 3 * H * F * cfg["n_shared_experts"],
            "router": H * cfg["n_routed_experts"]}


def _mla_weight_bytes(cfg: dict) -> float:
    H, Hq = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (_q8(H, ql) + _q8(ql, Hq * (dn + dr)) + _q8(H, r + dr)
            + _q8(r, Hq * (dn + dv)) + _q8(Hq * dv, H))


def _swiglu_bytes(H: int, F: int) -> float:
    return _q8(H, 2 * F) + _q8(F, H)


def latent_token_bytes(cfg: dict) -> float:
    """One token of one layer in the int8 latent pool: the latent, the
    rotated key, and a float32 scale for each."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] + 8


def decode_step_bytes(cfg: dict, rows: float, context: float) -> float:
    """Bytes one decode step has to read: every attention, dense,
    shared-expert and head weight once; of the held experts those some
    row reached (under even routing a row reaches a given expert with
    probability top-k / n_routed, so ``1 - (1 - k/n)^rows`` of them);
    the routers and the rows' embeddings in bf16; and each row's cached
    latents."""
    H = cfg["hidden_size"]
    Ld = cfg["first_k_dense_replace"]
    Lm = cfg["num_hidden_layers"] - Ld
    F = cfg["moe_intermediate_size"]
    p_touch = 1.0 - (1.0 - cfg["num_experts_per_tok"]
                     / cfg["n_routed_experts"]) ** rows
    per_moe = (_mla_weight_bytes(cfg)
               + _swiglu_bytes(H, F * cfg["n_shared_experts"])
               + 2 * H * cfg["n_routed_experts"]
               + cfg["n_held_experts"] * p_touch * _swiglu_bytes(H, F))
    per_dense = _mla_weight_bytes(cfg) + _swiglu_bytes(
        H, cfg["intermediate_size"])
    cache = (rows * context * cfg["num_hidden_layers"]
             * latent_token_bytes(cfg))
    return (Ld * per_dense + Lm * per_moe + _q8(H, cfg["vocab_size"])
            + rows * 2 * H + cache)


def attention_pair_flops(cfg: dict) -> float:
    """FLOPs of one (query token, context token) pair in one layer of
    the expanded form: a score over nope + rope and a value over v, for
    every head."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def prefill_flops(cfg: dict, tokens: float, context_pairs: float) -> float:
    """FLOPs the prompt positions require on this chip: two a parameter
    a token for every matrix the token goes through (attention, the
    dense or the shared MLP, the router, and under even routing
    ``k x held / n_routed`` held experts a routed layer), and the
    attention's pairs in every layer. A chunk re-expanding its context's
    latents is recomputation and is not counted; the head runs for one
    position a request and is left out."""
    p = layer_params(cfg)
    Ld = cfg["first_k_dense_replace"]
    Lm = cfg["num_hidden_layers"] - Ld
    local = (cfg["num_experts_per_tok"] * cfg["n_held_experts"]
             / cfg["n_routed_experts"])
    per_token = 2.0 * (Ld * (p["mla"] + p["dense_mlp"])
                       + Lm * (p["mla"] + p["shared"] + p["router"]
                               + local * p["expert"]))
    return (tokens * per_token + context_pairs * cfg["num_hidden_layers"]
            * attention_pair_flops(cfg))


def mla_decode_cost(cfg: dict, rows: int, context: int) -> tuple:
    """(FLOPs, bytes) of ONE call of ``mla_decode_attention`` (one
    layer): every head of every row scores ``context`` latent rows over
    r + rope numbers and weighs their first r; the rows are read once
    (int8 and two scales), the queries in and the latent-space outputs
    (float32) out."""
    Hq, r, dr = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                 cfg["qk_rope_head_dim"])
    flops = 2.0 * rows * Hq * context * ((r + dr) + r)
    nbytes = (rows * context * latent_token_bytes(cfg)
              + rows * Hq * (r + dr) * 2 + rows * Hq * r * 4)
    return flops, nbytes


def mla_prefill_cost(cfg: dict, tokens: int, context: int) -> tuple:
    """(FLOPs, bytes) of ONE call of ``mla_prefill_attention`` for one
    row (one layer): ``tokens`` queries at the END of ``context`` rows,
    causal, so ``tokens x (context - tokens) + tokens (tokens + 1) / 2``
    pairs; the queries, the context's expanded keys and values and its
    shared rotated keys in (bf16), the outputs out."""
    Hq = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    pairs = tokens * (context - tokens) + tokens * (tokens + 1) / 2.0
    nbytes = 2.0 * (tokens * Hq * (dn + dr) + context * Hq * (dn + dv)
                    + context * dr + tokens * Hq * dv)
    return pairs * attention_pair_flops(cfg), nbytes

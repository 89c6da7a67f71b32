"""The Mistral family's architecture file (Mistral 7B, Mixtral 8x7B): GQA
+ RoPE + SwiGLU, dense or routed with the kept weights renormalised.
The contract is in benchmark/manifest.py's docstring; a configuration
file without an ``"architecture"`` key is of this family.

What is particular to the family and lives here: which published keys
become which fields of the program's ``ModelConfig``, how the engine's
tree is laid out (the fused ``wqkv`` / ``wgu`` / ``wgu_e`` leaves, plain
on one chip and interleaved by device under a mesh), and what the
comparison says about routing. The block's mathematics and the two
tolerances are benchmark/reference.py's, which later architecture files
import from as well (``rms_norm``, ``rope``, ``swiglu``,
``position_errors``).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark.reference import TOL_MAX_DENSE, TOL_MEDIAN  # noqa: F401


def model_config(cfg: dict) -> dict:
    """``ModelConfig``'s keywords from the published field names."""
    heads = cfg["num_attention_heads"]
    return dict(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rope_scaling=None,
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        num_experts=cfg.get("num_local_experts", 0),
        num_experts_per_tok=cfg.get("num_experts_per_tok", 0),
        moe_capacity_factor=cfg.get("moe_capacity_factor"),
        bos_token_id=cfg.get("bos_token_id", 1),
        eos_token_ids=())       # ignore_eos: see the configuration file


class Weights(NamedTuple):
    """What :func:`forward` is handed: float32, one layer (one expert)
    at a time."""

    embed: jax.Array
    layer: Callable             # l -> dict of the layer's weights
    expert: Optional[Callable]  # (l, e) -> (w_gate, w_up, w_down)
    final_norm: jax.Array
    lm_head: jax.Array


def _deq(q, s):
    return q.astype(jnp.float32) * s.astype(jnp.float32)


def unfuse(fused, sizes: tuple, tp: int) -> list:
    """The column blocks of a fused projection, each whole again.
    ``models/llama.fuse_params`` lays the columns out as ``tp`` device
    blocks ``[a_0|b_0|.. | a_1|b_1|.. | ..]``, block i holding every
    part's i-th ``1/tp`` of its columns; ``tp`` = 1 is the plain
    ``[a | b | ..]``."""
    blocks = fused.reshape(*fused.shape[:-1], tp, fused.shape[-1] // tp)
    out, at = [], 0
    for size in sizes:
        part = blocks[..., at:at + size // tp]
        out.append(part.reshape(*fused.shape[:-1], size))
        at += size // tp
    return out


def engine_weights(sched) -> Weights:
    """The engine's own int8 tree, dequantised one layer (one expert) at
    a time (int8 x float32 scale is exact in float32)."""
    from p2p_llm_chat_tpu.models.llama import fuse_tp_for
    params, config = sched._params, sched.config
    layers = params["layers"]
    # The layout is a function of (config, mesh) and recorded nowhere on
    # the tree: ask the function the program built it with.
    tp = fuse_tp_for(config, sched.mesh)
    Q, KV, E = config.q_dim, config.kv_dim, config.intermediate_size
    f32 = jnp.float32

    # The tree is an argument, never a closure: a jitted closure would
    # bake 8 GB of weights into the program as constants.
    @jax.jit
    def _layer_weights(layers, layer):
        take = lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False)
        wq, wk, wv = unfuse(
            _deq(take(layers["wqkv"].q), take(layers["wqkv"].s)),
            (Q, KV, KV), tp)
        w = {"attn_norm": take(layers["attn_norm"]).astype(f32),
             "mlp_norm": take(layers["mlp_norm"]).astype(f32),
             "wq": wq, "wk": wk, "wv": wv,
             "wo": _deq(take(layers["wo"].q), take(layers["wo"].s))}
        if config.is_moe:
            w["router"] = take(layers["router"]).astype(f32)
        else:
            w_gate, w_up = unfuse(
                _deq(take(layers["wgu"].q), take(layers["wgu"].s)),
                (E, E), tp)
            w.update(w_gate=w_gate, w_up=w_up,
                     w_down=_deq(take(layers["w_down"].q),
                                 take(layers["w_down"].s)))
        return w

    # One chip: the experts' gate and up are one leaf, [gate | up]
    # (never interleaved: fuse_params fuses them only without a mesh).
    # Under a mesh they stay two leaves.
    @jax.jit
    def _expert_weights(gate_up, w_down, layer, e):
        if len(gate_up) == 1:
            w_gate, w_up = unfuse(
                _deq(gate_up[0].q[layer, e], gate_up[0].s[layer, e]),
                (E, E), 1)
        else:
            w_gate, w_up = (_deq(w.q[layer, e], w.s[layer, e])
                            for w in gate_up)
        return w_gate, w_up, _deq(w_down.q[layer, e], w_down.s[layer, e])

    def layer_weights(layer):
        return _layer_weights(layers, layer)

    def expert_weights(layer, e):
        gate_up = ((layers["wgu_e"],) if "wgu_e" in layers
                   else (layers["w_gate"], layers["w_up"]))
        return _expert_weights(gate_up, layers["w_down"], layer, e)

    head = params["lm_head"]
    return Weights(
        embed=params["embed"], layer=layer_weights,
        expert=expert_weights if config.is_moe else None,
        final_norm=params["final_norm"].astype(f32),
        lm_head=_deq(head.q, head.s) if hasattr(head, "q")
        else head.astype(f32))


def forward(cfg: dict, tokens, weights: Weights) -> tuple:
    return reference.forward(cfg, tokens, weights.embed, weights.layer,
                             weights.final_norm, weights.lm_head,
                             expert_weights=weights.expert)


def compare(system, reference_logits, facts: dict, cfg: dict) -> dict:
    """reference.compare's verdict; for a routed model also what the
    prefill's capacity buckets dropped, by the reference's own routing
    of the prefill tokens, and how many tokens sit on a router tie."""
    n_exp = cfg.get("num_local_experts", 0)
    out = reference.compare(system, reference_logits, routed=bool(n_exp))
    if n_exp:
        seqs, total = system.shape[:2]
        factor = cfg.get("moe_capacity_factor")
        cap = max(1, int((factor or 0) * seqs * facts["n_prefill"]
                         * cfg["num_experts_per_tok"] / n_exp))
        keep = jnp.tile(jnp.arange(total) < facts["n_prefill"], seqs)
        out["capacity"] = cap
        out["overflow_pairs"] = (
            sum(reference.expert_overflow(w[keep], cap)
                for w in facts["routing"]) if factor else 0)
        out["near_ties"] = int(jnp.sum(facts["min_margin"] < 0.02))
    return out

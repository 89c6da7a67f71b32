"""Pipeline parallelism over the ``pp`` mesh axis (GPipe-style).

The layer stack is cut into ``pp`` contiguous stages — each device holds
``L/pp`` layers' weights and KV — and activations hop stage-to-stage with
``jax.lax.ppermute`` while microbatches stream through, so at steady state
every stage computes a different microbatch concurrently. This completes
the parallelism matrix next to dp/tp (parallel/sharding.py), ep
(models/mixtral.py), and sp (parallel/ring.py); the reference has no
distributed machinery at all (SURVEY.md §2: everything delegated to
Ollama).

TPU-first shape:
- One ``shard_map`` program; the schedule is a statically unrolled loop of
  ``M + pp - 1`` ticks (M = microbatches), so XLA sees straight-line code
  and overlaps each tick's ppermute with the next tick's matmuls.
- Stage-local layers run under one ``lax.scan`` (same constant-graph
  trick as models/llama.py); stage weights are the stacked ``[L, ...]``
  leaves sharded over ``pp`` on the layer axis — no per-stage pytrees.
- No traced control flow: ``axis_index("pp")`` is traced, so stages never
  branch on "is it my turn". Every stage computes every tick; a stage's
  output is *correct* exactly on the tick its input arrived (the bubble
  ticks produce garbage that flows nowhere: KV/logit writes ride
  out-of-range scatter indices with ``mode="drop"``).
- Embedding/final-norm/lm_head are replicated; stage 0 embeds, the last
  stage projects. KV cache stays ``[L, B, S, Hkv, D]`` with the layer
  axis sharded over ``pp`` — each stage owns its layers' pages.

Decode (:func:`pp_decode_step`) flows the one-token batch through the
stages in ``pp`` ticks (inference pipelining; the classic decode bubble).
It exists for contract completeness and multi-chip validation — serving
configs on one slice prefer tp/sp, which decode in one tick.

Parity with models/llama.py prefill/decode_step is pinned by
tests/test_pipeline.py on the virtual CPU mesh and by
``__graft_entry__.dryrun_multichip``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..models.configs import ModelConfig
from ..models.layers import (attend_gqa, causal_mask, length_mask, rms_norm,
                             rope_frequencies)
from ..models.llama import KVCache, _attn_qkv, _post_attn
from ..models.quant import mm


def _stage_specs(params: dict) -> dict:
    """in_specs pytree: stacked layer leaves sharded over pp on the layer
    axis, everything else replicated. Descends into QTensor leaves too
    (both q and s carry the leading [L] axis)."""
    def walk(d: dict, in_layers: bool) -> dict:
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = walk(v, in_layers or k == "layers")
            else:
                out[k] = jax.tree.map(
                    lambda _: P("pp") if (in_layers or k == "layers")
                    else P(), v)
        return out
    return walk(params, False)


def pp_prefill(params: dict, config: ModelConfig, tokens: jax.Array,
               prompt_lens: jax.Array, mesh: Mesh,
               microbatches: Optional[int] = None,
               mlp_fn=None) -> tuple[jax.Array, KVCache]:
    """Pipeline-parallel prefill: llama.prefill's contract with the layer
    stack sharded into ``pp`` stages and the batch streamed through as
    microbatches.

    tokens: [B,S] right-padded (B divisible by ``microbatches``, default
    pp); prompt_lens: [B]. Returns (logits [B,S,vocab] f32, KVCache whose
    k/v layer axis is pp-sharded, max_seq = S).
    """
    pp = mesh.shape["pp"]
    assert mesh.size == pp, (
        f"pipeline path runs over pp only (mesh {dict(mesh.shape)}); "
        "set other axes to 1")
    L = config.num_layers
    assert L % pp == 0, f"{L} layers not divisible by pp={pp}"
    B, S = tokens.shape
    M = microbatches or min(pp, B)
    assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
    per = B // M
    Lp = L // pp
    inv_freq = rope_frequencies(config)
    H = config.hidden_size
    mask = causal_mask(S, S, 0)

    def device_fn(params, tokens):
        my = jax.lax.axis_index("pp")
        lp_local = params["layers"]            # [Lp, ...] leaves
        dtype = params["embed"].dtype
        ck = jnp.zeros((Lp, B, S, config.num_kv_heads, config.head_dim),
                       dtype)
        cv = jnp.zeros_like(ck)
        logits = jnp.zeros((B, S, config.vocab_size), jnp.float32)
        h = jnp.zeros((per, S, H), dtype)
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (per, S))
        fwd = [(i, i + 1) for i in range(pp - 1)]

        for t in range(M + pp - 1):            # static pipeline schedule
            # Stage 0 injects microbatch t (clamped; extra ticks recompute
            # the last microbatch — their writes drop via the sentinel).
            mb = min(t, M - 1)
            inject = params["embed"][
                jax.lax.dynamic_slice_in_dim(tokens, mb * per, per, 0)]
            h = jnp.where(my == 0, inject, h)
            # This tick, stage `my` holds microbatch m = t - my; valid
            # only in [0, M). Invalid ticks aim their writes out of range.
            m = t - my
            valid = (m >= 0) & (m < M)
            rows = jnp.where(valid, m * per + jnp.arange(per), B)

            def body(carry, xs):
                h, ck, cv = carry
                lp, layer = xs
                q, k, v = _attn_qkv(h, lp, config, inv_freq, positions,
                                    None, {})
                ck = ck.at[layer, rows[:, None],
                           positions].set(k, mode="drop")
                cv = cv.at[layer, rows[:, None],
                           positions].set(v, mode="drop")
                attn = attend_gqa(q, k, v, mask)
                h = _post_attn(h, attn, lp, config, None, {}, mlp_fn)
                return (h, ck, cv), None

            (h, ck, cv), _ = jax.lax.scan(body, (h, ck, cv),
                                          (lp_local, jnp.arange(Lp)))
            # Last stage projects its finished microbatch into the logits
            # buffer (drop-masked like the cache writes).
            hf = rms_norm(h, params["final_norm"], config.rms_norm_eps)
            lm_head = (params["embed"].T if config.tie_embeddings
                       else params["lm_head"])
            lg = mm(hf, lm_head).astype(jnp.float32)
            out_rows = jnp.where(valid & (my == pp - 1),
                                 m * per + jnp.arange(per), B)
            logits = logits.at[out_rows].set(lg, mode="drop")
            if fwd:
                h = jax.lax.ppermute(h, "pp", fwd)

        # Only the last stage filled `logits`; sum-across-stages recovers
        # it (all other stages contributed zeros).
        return jax.lax.psum(logits, "pp"), ck, cv

    mapped = shard_map(
        device_fn, mesh=mesh,
        in_specs=(_stage_specs(params), P()),
        out_specs=(P(), P("pp"), P("pp")),
        check_vma=False,
    )
    logits, ck, cv = mapped(params, tokens)
    return logits, KVCache(k=ck, v=cv, lengths=prompt_lens.astype(jnp.int32))


def pp_decode_step(params: dict, config: ModelConfig, tokens: jax.Array,
                   cache: KVCache, mesh: Mesh,
                   active: Optional[jax.Array] = None,
                   mlp_fn=None) -> tuple[jax.Array, KVCache]:
    """One decode step against a pp-sharded cache (layer axis over pp).

    Same contract as models/llama.decode_step, including the parked-row
    ``active`` semantics (writes at an unadvanced length are overwritten
    before anything trusts them). The token batch crosses the ``pp``
    stages in pp ticks. tokens: [B,1]. Returns (logits [B,1,vocab]
    replicated, advanced cache)."""
    pp = mesh.shape["pp"]
    assert mesh.size == pp, "pp-only path; see pp_prefill"
    B = tokens.shape[0]
    max_seq = cache.k.shape[2]
    inv_freq = rope_frequencies(config)
    H = config.hidden_size
    Lp = config.num_layers // pp

    def device_fn(params, tokens, ck, cv, lengths):
        my = jax.lax.axis_index("pp")
        positions = lengths[:, None]                      # [B,1]
        mask = length_mask(max_seq, lengths + 1)
        rows_all = jnp.arange(B)
        logits = jnp.zeros((B, 1, config.vocab_size), jnp.float32)
        h = jnp.zeros((B, 1, H), params["embed"].dtype)
        fwd = [(i, i + 1) for i in range(pp - 1)]

        for t in range(pp):
            h = jnp.where(my == 0, params["embed"][tokens], h)
            # Stage `my` holds the real activation exactly at tick t == my;
            # other ticks' writes aim out of range and drop.
            ok = t == my
            rows = jnp.where(ok, rows_all, B)

            def body(carry, xs):
                h, ck, cv = carry
                lp, layer = xs
                q, k, v = _attn_qkv(h, lp, config, inv_freq, positions,
                                    None, {})
                ck = ck.at[layer, rows[:, None],
                           positions].set(k, mode="drop")
                cv = cv.at[layer, rows[:, None],
                           positions].set(v, mode="drop")
                k_layer = jax.lax.dynamic_index_in_dim(ck, layer, 0,
                                                       keepdims=False)
                v_layer = jax.lax.dynamic_index_in_dim(cv, layer, 0,
                                                       keepdims=False)
                attn = attend_gqa(q, k_layer, v_layer, mask)
                h = _post_attn(h, attn, lp, config, None, {}, mlp_fn)
                return (h, ck, cv), None

            (h, ck, cv), _ = jax.lax.scan(body, (h, ck, cv),
                                          (params["layers"], jnp.arange(Lp)))
            hf = rms_norm(h, params["final_norm"], config.rms_norm_eps)
            lm_head = (params["embed"].T if config.tie_embeddings
                       else params["lm_head"])
            lg = mm(hf, lm_head).astype(jnp.float32)
            out_rows = jnp.where(ok & (my == pp - 1), rows_all, B)
            logits = logits.at[out_rows].set(lg, mode="drop")
            if fwd:
                h = jax.lax.ppermute(h, "pp", fwd)

        return jax.lax.psum(logits, "pp"), ck, cv

    mapped = shard_map(
        device_fn, mesh=mesh,
        in_specs=(_stage_specs(params), P(), P("pp"), P("pp"), P()),
        out_specs=(P(), P("pp"), P("pp")),
        check_vma=False,
    )
    logits, ck, cv = mapped(params, tokens, cache.k, cache.v, cache.lengths)
    inc = (jnp.ones_like(cache.lengths) if active is None
           else active.astype(jnp.int32))
    return logits, KVCache(k=ck, v=cv, lengths=cache.lengths + inc)

"""Synthetic "quote-the-context" checkpoints for benchmarking.

No public checkpoint ships in this image (zero egress), and a RANDOM-init
model's output has two properties that break realistic end-to-end
measurement: its greedy continuation repeats essentially no n-grams
(speculative prompt-lookup can never land — measured 251/256 unique
tokens, 0 acceptances), and its sampled byte stream almost never forms
valid UTF-8, so the incremental detokenizer buffers nearly the whole
generation and "streaming" TTFT at a UI degrades to completion time.

:func:`quote_params` builds a full-size random tree whose OUTPUT
statistics match a real co-pilot's instead: embeddings are
near-orthogonal and the lm_head maps each token's embedding to a fixed
successor, with the successor cycles laid INSIDE the byte tokenizer's
printable-ASCII id range. Every forward still pays the full model
compute (all transformer layers keep their random weights; the logit
margin ~4*hidden is so large that sampling at any sane temperature
follows the cycle), so decode/prefill cost is identical to a real
checkpoint of the same config — but greedy/sampled output settles into a
repeating printable phrase: prompt-lookup drafts land (the speculation
benchmark) and the detokenizer streams byte-per-token (the UI-boundary
TTFT benchmark). tools/e2e_bench.py uses this construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .configs import ModelConfig

# The byte tokenizer maps byte b to id b (specials live above 256); the
# printable range streams through UTF-8 incremental decoding one byte at
# a time.
_ASCII_LO, _ASCII_HI = 32, 127
_CYCLE = 16


def successor_map(vocab: int, mode: str = "quote") -> np.ndarray:
    """succ[t] for every token id: printable-ASCII ids cycle within the
    printable range; every other id funnels into the printable range so
    one step after any stray token the stream is printable forever.

    ``mode`` selects the cycle statistics of the greedy output:

    - ``"quote"`` (default): blocks of ``_CYCLE`` consecutive ids — the
      output repeats a 16-token phrase, so trailing n-grams recur fast
      and prompt-lookup drafts land (the quote-the-context statistic).
    - ``"freeform"``: ONE pseudo-random cycle over the whole printable
      range (a seeded permutation, not the +1 ordering — consecutive-
      byte bigrams occur in natural prompt text and would hand the
      n-gram index spurious hits). Trailing bigrams recur only after a
      full 95-token lap, so prompt-lookup drafting scores ~0 on any
      normal-length completion — the free-form statistic where only a
      DRAFT MODEL sharing the map (serve/draft_model.py) can win.
    """
    ids = np.arange(_ASCII_LO, _ASCII_HI)
    succ = np.empty(vocab, np.int64)
    # stray ids -> deterministic printable entry points
    succ[:] = _ASCII_LO + (np.arange(vocab) % len(ids))
    if mode == "freeform":
        order = np.random.default_rng(11).permutation(ids)
        succ[order] = np.roll(order, -1)     # one 95-token cycle
        return succ
    if mode != "quote":
        raise ValueError(f"successor_map mode must be quote|freeform, "
                         f"got {mode!r}")
    for start in range(0, len(ids), _CYCLE):
        block = ids[start: start + _CYCLE]
        succ[block] = np.roll(block, -1)
    return succ


def quote_params(config: ModelConfig, key: jax.Array,
                 dtype=jnp.bfloat16, quantized: bool = False,
                 mode: str = "quote", quant: str = "int8") -> dict:
    """Full-size tree (random transformer layers of the config's FAMILY —
    llama or mixtral — full compute) with the quote-workload
    embed/lm_head. ``quantized=True`` returns quantized matmul leaves at
    ``quant`` (``int8`` per-channel or ``int4`` group-wise; both
    families stream straight to the fused quantized tree). Requires an
    untied lm_head.

    ``mode="freeform"`` swaps the 16-token repeat cycles for one
    pseudo-random 95-token cycle (see :func:`successor_map`): greedy
    output stops repeating n-grams, so prompt-lookup drafting measures
    ~0 acceptances — the free-form workload of the draft-model spec
    bench. The successor map depends only on (vocab, mode), so a TARGET
    and a smaller DRAFTER config built with the same (vocab, mode)
    follow the same cycle and the drafter's greedy proposals match the
    target's continuation — the synthetic stand-in for "a small model
    predicts the big model's easy tokens" that lets CPU tests and the
    no-checkpoint bench measure draft-model speculation end to end."""
    from . import family_for
    from .quant import quantize_params

    if config.tie_embeddings:
        raise ValueError("quote workload needs an untied lm_head")
    family = family_for(config)
    if quantized and hasattr(family, "init_params_quantized"):
        # Both families stream straight to the fused quantized tree
        # (llama and mixtral expose init_params_quantized).
        params = family.init_params_quantized(config, key, dtype=dtype,
                                              quant=quant)
    else:
        params = dict(family.init_params(config, key, dtype=dtype))
        if quantized:
            params = quantize_params(params, mode=quant)

    # Damp the residual-writing projections (wo, w_down / expert
    # w_down): the cycle construction needs the residual stream to stay
    # dominated by the input embedding, and at small hidden sizes the
    # random layers' perturbation otherwise out-shouts the successor
    # margin (observed at the `tiny` config). Compute cost is unchanged
    # — the matmuls still run at full shape.
    from .quant import QTensor, QTensor4

    def damp(leaf):
        if isinstance(leaf, (QTensor, QTensor4)):
            # Scales are linear in the weight for both precisions.
            return type(leaf)(q=leaf.q, s=leaf.s * 0.1)
        return leaf * 0.1

    layers = dict(params["layers"])
    for name in ("wo", "w_down"):
        if name in layers:
            layers[name] = damp(layers[name])
    params = dict(params)
    params["layers"] = layers

    V, H = config.vocab_size, config.hidden_size
    emb = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (V, H),
                                       jnp.float32))
    succ = successor_map(V, mode=mode)
    # lm_head[:, j] = 4 * sum_{succ(t)=j} w_t * emb[t]: logits_j(t)
    # contains 4*w_t*|emb[t]|^2 ~ 4H exactly when j = succ(t). Printable
    # tokens get w=1 (a pure in-range permutation); the ~V/95 stray
    # tokens funnelled into each printable column are down-weighted by
    # 1/sqrt(strays-per-column) so their summed cross-term noise stays
    # at the O(4*sqrt(H)) of the permutation — an unweighted funnel at
    # bench-1b scale (344 strays/column) put ~3300-sigma cross terms
    # against the 4H ~ 8192 signal and broke the cycle on a nontrivial
    # fraction of steps.
    weights = np.full(V, 1.0, np.float32)
    stray = np.ones(V, bool)
    stray[_ASCII_LO:_ASCII_HI] = False
    per_col = max(1, int(stray.sum()) // (_ASCII_HI - _ASCII_LO))
    weights[stray] = 1.0 / np.sqrt(per_col)
    lm_t = np.zeros((V, H), np.float32)
    np.add.at(lm_t, succ, emb * weights[:, None])
    lm = lm_t.T * 4.0
    params = dict(params)
    # Drop the init head before uploading the quote head: at 8B dims the
    # pair is ~1.6 GB of HBM that must not coexist with the new leaves.
    params.pop("embed", None)
    old_head = params.pop("lm_head", None)
    del old_head
    params["embed"] = jnp.asarray(emb, dtype)
    if quantized:
        # Quantize HOST-side (exact mirrors of quant.quantize /
        # quant.quantize4, axis=-2): uploading lm as f32 to quantize on
        # device is a 2.1 GB HBM spike at 8B dims that OOM'd the
        # spec-enabled quote bench.
        K = lm.shape[0]
        if (quant == "int4" and K % 2 == 0
                and (K % 128 == 0 or K % 64 == 0)):
            group = 128 if K % 128 == 0 else 64
            g = lm.reshape(K // group, group, V)
            amax = np.abs(g).max(axis=1, keepdims=True)
            s = np.where(amax > 0, amax / 7.0, 1.0).astype(np.float32)
            qv = np.clip(np.round(g / s), -7, 7).astype(np.int32)
            qv = qv.reshape(K, V)
            packed = ((qv[:K // 2] + 8) | ((qv[K // 2:] + 8) << 4))
            packed = packed.astype(np.uint8).view(np.int8)
            params["lm_head"] = QTensor4(q=jnp.asarray(packed),
                                         s=jnp.asarray(np.squeeze(s, 1)))
        else:
            amax = np.abs(lm).max(axis=0, keepdims=True)
            s = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
            q = np.clip(np.round(lm / s), -127, 127).astype(np.int8)
            params["lm_head"] = QTensor(q=jnp.asarray(q), s=jnp.asarray(s))
    else:
        params["lm_head"] = jnp.asarray(lm, dtype)
    return params

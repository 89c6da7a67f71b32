"""SHA-256 of the StableHLO the dense, Mixtral, OLMoE,
latent-attention and hybrid families lower to on the CPU at test size:
the programs the scheduler serves with (one-shot and chunked prefill, a
paged int8 decode step with its pool write, a fused decode, the
admission splice; for a routed Mixtral-family or latent-attention model
also the ``_counted`` prefills, which are what an admission runs, and a
verify step, which is also what a session wake runs; for the hybrid
family's four test sizes a prefill chunk and a decode step, and for
the three that route the ``_counted`` chunk). A PR that
must not move another family's programs runs this on its parent and on itself
(``PYTHONPATH=<checkout> python tools/hash_programs.py``) and pins the
parent's digests in tests/test_program_hashes.py.
"""

from __future__ import annotations

import hashlib
import json

CONFIGS = ("tiny", "tiny-moe", "tiny-olmoe", "tiny-pangu",
           "tiny-nemotron-h", "tiny-phi4flash", "tiny-mellum2", "tiny-lfm2")
# Of the hybrid family (models/nemotron_h.py) only these labels.
HYBRID_LABELS = ("prefill_chunk", "decode_step_paged")


def programs(name: str) -> dict:
    """label -> StableHLO text of ``name``'s programs."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.models import (family_for, get_config, mixtral,
                                         pangu)
    from p2p_llm_chat_tpu.models.llama import KVCache
    from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,
                                               write_prefill_batch,
                                               write_prefill_chunk)
    cfg = get_config(name)
    model = family_for(cfg)
    params = jax.eval_shape(
        lambda: model.fuse_params(model.init_params_quantized(
            cfg, jax.random.PRNGKey(0))))
    B, S, C = 4, 64, 32
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32)
    small = jax.eval_shape(lambda: KVCache.create(cfg, B, S))
    pool = jax.eval_shape(lambda: PagedKVCache.create(
        cfg, B, 17, 16, max_pages_per_row=8, quantized=True))
    tok1 = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    act = jax.ShapeDtypeStruct((B,), jnp.bool_)
    tables = jax.ShapeDtypeStruct((B, 8), jnp.int32)

    def fused(params, tok, cache, active):
        def sample(logits, state, emit_pos, act):
            return jnp.argmax(logits, -1).astype(jnp.int32), state
        return model.decode_fused(params, cfg, tok, cache, None,
                                  active=active, num_steps=4,
                                  sample_fn=sample, sample_state=(),
                                  stop_ids=(2,), pages=4)

    fns = {
        "prefill": (lambda p, t, l, c: model.prefill(
            p, cfg, t, l, c, last_only=True), (params, toks, lens, small)),
        "prefill_chunk": (lambda p, t, c: model.prefill_chunk(
            p, cfg, t, c, C), (params, jax.ShapeDtypeStruct((B, C),
                                                            jnp.int32),
                               small)),
        "decode_step_paged": (lambda p, t, c, a: model.decode_step_paged(
            p, cfg, t, c, active=a, pages=4), (params, tok1, pool, act)),
        "decode_fused": (fused, (params, tok1, pool, act)),
        "write_prefill_batch": (write_prefill_batch, (
            pool, small.k, small.v, lens, lens, tables)),
        "write_prefill_chunk": (lambda c, k, v, t: write_prefill_chunk(
            c, k, v, t, 16), (pool, small.k, small.v, tables)),
    }
    if cfg.is_hybrid:
        fns = {label: fns[label] for label in HYBRID_LABELS}
    if cfg.routed_layers:
        # What an admission of a routed model runs: the mask of real
        # positions goes in, the counts come out.
        fns["prefill_chunk_counted"] = (
            lambda p, t, c, v: model.prefill_chunk_counted(
                p, cfg, t, c, C, v),
            (params, jax.ShapeDtypeStruct((B, C), jnp.int32), small,
             jax.ShapeDtypeStruct((B, C), jnp.bool_)))
    if model in (mixtral, pangu):
        valid = jax.ShapeDtypeStruct((B, S), jnp.bool_)
        fns["prefill_counted"] = (
            lambda p, t, l, c, v: model.prefill_counted(
                p, cfg, t, l, c, v, last_only=True),
            (params, toks, lens, small, valid))
        # ... and what a speculative verify and a session wake run: more
        # than one position a row with no mask (the Mixtral family: no
        # capacity either, whatever the configuration's factor, and an
        # exact bucket; the latent-attention family: a prefill, on
        # tiles).
        fns["verify_step_paged"] = (
            lambda p, t, c: model.verify_step_paged(p, cfg, t, c, pages=4),
            (params, jax.ShapeDtypeStruct((B, 5), jnp.int32), pool))
    return {label: jax.jit(fn).lower(*args).as_text()
            for label, (fn, args) in fns.items()}


def digests() -> dict:
    return {f"{name}.{label}": hashlib.sha256(text.encode()).hexdigest()
            for name in CONFIGS for label, text in programs(name).items()}


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1))

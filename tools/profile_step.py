"""Decode-step component attribution on real hardware.

Ablation-times the serving decode step (models/llama.decode_step_paged,
gather impl) at bench shapes to attribute where the non-matmul time
goes. Each variant removes ONE component from a faithful copy of the
step body; the deltas against the full step are the attribution table.

Timing uses bench.py's two-loop solve (wall(N)/N = device + c/N, c the
loop's constant dispatch + readback cost).

Usage: python tools/profile_step.py [variant ...]
Env: PROF_CONFIG (bench-1b), PROF_SLOTS (32), PROF_WINDOW (192),
     PROF_KV_QUANT (int8|"" default int8), PROF_STEPS (64).
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from p2p_llm_chat_tpu.models import llama  # noqa: E402
from p2p_llm_chat_tpu.models.configs import get_config  # noqa: E402
from p2p_llm_chat_tpu.models.layers import rms_norm, rope_frequencies  # noqa: E402
from p2p_llm_chat_tpu.models.quant import mm, quantize_params  # noqa: E402
from p2p_llm_chat_tpu.ops.paged_attention import paged_attention_append  # noqa: E402
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache, write_decode_all_layers  # noqa: E402


def step_variant(params, config, tokens, cache, *, pages,
                 skip_attn=False, skip_write=False, skip_lm_head=False,
                 skip_trunk_mm=False, unroll=1):
    """decode_step_paged's gather-path body with components removable."""
    B = tokens.shape[0]
    positions = cache.lengths[:, None]
    h = params["embed"][tokens]
    inv_freq = rope_frequencies(config)

    def body(h, layer):
        lp = llama._layer_view(params["layers"], layer)
        q, k, v = llama._attn_qkv(h, lp, config, inv_freq, positions,
                                  None, llama.DEFAULT_RULES)
        if skip_attn:
            attn = q[:, 0]
        else:
            attn = paged_attention_append(q[:, 0], k[:, 0], v[:, 0], cache,
                                          cache.lengths, layer, pages=pages)
        if skip_trunk_mm:
            hn = h + attn.reshape(B, 1, config.q_dim)[..., : h.shape[-1]]
        else:
            hn = llama._post_attn(h, attn[:, None], lp, config, None,
                                  llama.DEFAULT_RULES, None)
        return hn, (k[:, 0], v[:, 0])

    h, (k_all, v_all) = jax.lax.scan(
        body, h, jnp.arange(config.num_layers), unroll=unroll)
    if not skip_write:
        cache = write_decode_all_layers(cache, k_all, v_all)
    h = rms_norm(h, params["final_norm"], config.rms_norm_eps)
    if skip_lm_head:
        return h.astype(jnp.float32), cache
    lm_head = (params["embed"].T if config.tie_embeddings
               else params["lm_head"])
    logits = mm(h, lm_head).astype(jnp.float32)
    return logits, cache._replace(lengths=cache.lengths + 1)


def main() -> None:
    cfg_name = os.environ.get("PROF_CONFIG", "bench-1b")
    B = int(os.environ.get("PROF_SLOTS", "32"))
    window = int(os.environ.get("PROF_WINDOW", "192"))
    steps = int(os.environ.get("PROF_STEPS", "64"))
    kv_quant = os.environ.get("PROF_KV_QUANT", "int8") == "int8"
    page_size = 64
    pages = -(-window // page_size)

    config = get_config(cfg_name)
    dtype = jnp.bfloat16
    params = llama.init_params(config, jax.random.PRNGKey(0), dtype=dtype)
    params = quantize_params(params)
    params = llama.fuse_params(params)
    jax.block_until_ready(params)
    mppr = pages
    num_pages = B * mppr + 1

    def make_cache():
        cache = PagedKVCache.create(config, B, num_pages, page_size,
                                    max_pages_per_row=mppr, dtype=dtype,
                                    quantized=kv_quant)
        table = (1 + jnp.arange(B * mppr, dtype=jnp.int32)).reshape(B, mppr)
        return cache._replace(page_table=table,
                              lengths=jnp.full((B,), 64, jnp.int32))

    toks = jnp.ones((B, 1), jnp.int32)

    def timeit(name, jfn, n1=None, n2=None):
        n1 = n1 or max(16, steps // 4)
        n2 = n2 or max(steps, 2 * n1)

        def loop(n):
            cache = make_cache()
            out, cache = jfn(params, toks, cache)
            np.asarray(jax.device_get(jax.tree.leaves(out)[0]).ravel()[:1])
            t = time.monotonic()
            for _ in range(n):
                out, cache = jfn(params, toks, cache)
            np.asarray(jax.device_get(jax.tree.leaves(out)[0]).ravel()[:1])
            return (time.monotonic() - t) / n

        w1 = min(loop(n1) for _ in range(2))
        w2 = min(loop(n2) for _ in range(2))
        dev = (n2 * w2 - n1 * w1) / (n2 - n1)
        print(f"{name:28s} {dev*1e3:7.3f} ms/step", flush=True)
        return dev * 1e3

    variants = sys.argv[1:] or ["full", "no_attn", "no_write", "no_lm_head",
                                "trunk_only", "sampling", "unroll4"]
    results = {}

    def mm_scan_only(params, tokens, cache):
        """Pure fused-matmul chain per layer (no norms/rope/attn/write):
        the weight-stream floor of the trunk."""
        B = tokens.shape[0]
        h = params["embed"][tokens]
        H = h.shape[-1]
        E = config.intermediate_size

        def body(h, layer):
            lp = llama._layer_view(params["layers"], layer)
            a = mm(h, lp["wqkv"])
            h1 = mm(a[..., : config.q_dim], lp["wo"])
            g = mm(h1, lp["wgu"])
            h2 = mm(g[..., :E], lp["w_down"])
            return h2[..., :H], None

        h, _ = jax.lax.scan(body, h, jnp.arange(config.num_layers))
        lm_head = (params["embed"].T if config.tie_embeddings
                   else params["lm_head"])
        return mm(h, lm_head).astype(jnp.float32), cache

    for v in variants:
        if v == "mm_scan_only":
            results[v] = timeit(v, jax.jit(mm_scan_only, donate_argnums=(2,)))
            continue
        if v == "sampling":
            from p2p_llm_chat_tpu.models.sampling import sample_batched
            logits = jax.random.normal(jax.random.PRNGKey(1),
                                       (B, config.vocab_size), jnp.float32)
            keys = jnp.tile(jax.random.PRNGKey(2)[None], (B, 1))
            temp = jnp.full((B,), 0.7)
            tk = jnp.zeros((B,), jnp.int32)
            tp = jnp.full((B,), 0.9)
            ring = jnp.full((B, 64), config.vocab_size, jnp.int32)
            rp = jnp.ones((B,))
            samp = jax.jit(lambda lg, k: sample_batched(
                lg, k, temp, tk, tp, ring=ring, rp=rp))

            def loop(n):
                k = keys
                t_, k = samp(logits, k)
                np.asarray(t_[:1])
                t0 = time.monotonic()
                for _ in range(n):
                    t_, k = samp(logits, k)
                np.asarray(t_[:1])
                return (time.monotonic() - t0) / n
            n1, n2 = 16, 64
            w1 = min(loop(n1) for _ in range(2))
            w2 = min(loop(n2) for _ in range(2))
            dev = (n2 * w2 - n1 * w1) / (n2 - n1)
            print(f"{'sampling [B,32k] alone':28s} {dev*1e3:7.3f} ms/step",
                  flush=True)
            results[v] = dev * 1e3
            continue
        kw = {}
        if v == "no_attn":
            kw = dict(skip_attn=True)
        elif v == "no_write":
            kw = dict(skip_write=True)
        elif v == "no_lm_head":
            kw = dict(skip_lm_head=True)
        elif v == "trunk_only":
            kw = dict(skip_attn=True, skip_write=True, skip_lm_head=True)
        elif v == "mm_only":
            kw = dict(skip_attn=True, skip_write=True)
        elif v.startswith("unroll"):
            kw = dict(unroll=int(v[6:]))
        elif v != "full":
            raise SystemExit(f"unknown variant {v}")
        jfn = jax.jit(
            lambda p, t, c, kw=kw: step_variant(p, config, t, c,
                                                pages=pages, **kw),
            donate_argnums=(2,))
        results[v] = timeit(v, jfn)

    full = results.get("full")
    if full:
        print("\nattribution (full - variant):")
        for v, ms in results.items():
            if v in ("full", "sampling") or v.startswith("unroll"):
                continue
            print(f"  {v:24s} {full - ms:7.3f} ms")


if __name__ == "__main__":
    main()

"""Time the append-attention kernel per 22-layer walk, full vs DMA-only.

Loops the kernel inside one jitted scan over layer indices (cache-state
independent — timing only) and uses two scan lengths to cancel the
constant per-dispatch cost.
"""

from __future__ import annotations

import functools
import importlib
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from p2p_llm_chat_tpu.models.configs import get_config  # noqa: E402
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache  # noqa: E402

pa = importlib.import_module("p2p_llm_chat_tpu.ops.paged_attention")


def main() -> None:
    cfg = get_config("bench-1b")
    B, pages, ps = 32, 3, 64
    L = cfg.num_layers
    quantized = os.environ.get("TK_QUANT", "1") == "1"
    mode = "full"
    mppr = pages
    cache = PagedKVCache.create(cfg, B, B * mppr + 1, ps,
                                max_pages_per_row=mppr, dtype=jnp.bfloat16,
                                quantized=quantized)
    table = (1 + jnp.arange(B * mppr, dtype=jnp.int32)).reshape(B, mppr)
    cache = cache._replace(page_table=table,
                           lengths=jnp.full((B,), 150, jnp.int32))
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, cfg.num_heads, cfg.head_dim),
                          jnp.bfloat16)
    kc = jax.random.normal(key, (B, cfg.num_kv_heads, cfg.head_dim),
                           jnp.bfloat16)

    def walk(n, q0):
        def body(qc, i):
            layer = i % L
            out = pa._paged_append_kernel_call(
                qc, kc, kc, cache.k, cache.v, cache.k_scale, cache.v_scale,
                cache.page_table, cache.lengths, layer, pages=pages,
                quantized=quantized)
            return out.astype(qc.dtype), ()
        qn, _ = jax.lax.scan(body, q0, jnp.arange(n))
        return qn

    def wall(n):
        f = jax.jit(functools.partial(walk, n))
        np.asarray(jax.device_get(f(q)).ravel()[:1])
        best = float("inf")
        for _ in range(4):
            t = time.monotonic()
            np.asarray(jax.device_get(f(q)).ravel()[:1])
            best = min(best, time.monotonic() - t)
        return best

    n1, n2 = 110, 440          # 5 / 20 layer-walks
    w1, w2 = wall(n1), wall(n2)
    per_call = (w2 - w1) / (n2 - n1)
    print(f"mode={mode} quantized={quantized}: {per_call*1e6:.1f} us/call, "
          f"{per_call*L*1e3:.3f} ms per {L}-layer walk")


if __name__ == "__main__":
    main()

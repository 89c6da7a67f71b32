"""Exact device-time attribution of the decode step via jax.profiler.

Captures an xplane trace of N chained decode steps on the real chip and
parses per-HLO self-times with the installed xprof/tensorboard plugin —
device durations from the trace, no host-clock statistics involved.

Usage: python tools/trace_step.py [mm_scan_only|full|...]
Env: PROF_CONFIG/PROF_SLOTS/PROF_WINDOW/PROF_KV_QUANT as profile_step.py.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.profile_step import step_variant  # noqa: E402
from p2p_llm_chat_tpu.models import llama  # noqa: E402
from p2p_llm_chat_tpu.models.configs import get_config  # noqa: E402
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache  # noqa: E402


def main() -> None:
    variant = sys.argv[1] if len(sys.argv) > 1 else "full"
    if variant.endswith(".pb"):          # parse an existing trace
        parse(glob.glob(variant, recursive=True), "existing",
              int(os.environ.get("PROF_STEPS", "32")))
        return
    cfg_name = os.environ.get("PROF_CONFIG", "bench-1b")
    B = int(os.environ.get("PROF_SLOTS", "32"))
    window = int(os.environ.get("PROF_WINDOW", "192"))
    kv_quant = os.environ.get("PROF_KV_QUANT", "int8") == "int8"
    steps = int(os.environ.get("PROF_STEPS", "32"))
    page_size = 64
    pages = -(-window // page_size)

    config = get_config(cfg_name)
    # Streamed fused-int8 init: same layout fuse_params produces, but the
    # bf16 tree never materialises — required for llama3.1-8b on one chip.
    params = llama.init_params_quantized(config, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    mppr = pages
    num_pages = B * mppr + 1
    cache = PagedKVCache.create(config, B, num_pages, page_size,
                                max_pages_per_row=mppr, dtype=jnp.bfloat16,
                                quantized=kv_quant)
    table = (1 + jnp.arange(B * mppr, dtype=jnp.int32)).reshape(B, mppr)
    cache = cache._replace(page_table=table,
                           lengths=jnp.full((B,), 64, jnp.int32))
    toks = jnp.ones((B, 1), jnp.int32)

    kw = {}
    if variant == "no_attn":
        kw = dict(skip_attn=True)
    elif variant == "trunk_only":
        kw = dict(skip_attn=True, skip_write=True, skip_lm_head=True)
    elif variant != "full":
        raise SystemExit(f"unknown variant {variant!r} (full|no_attn|"
                         "trunk_only|<path>.pb) — a mislabeled trace "
                         "would publish wrong attribution numbers")
    jfn = jax.jit(lambda p, t, c: step_variant(p, config, t, c,
                                               pages=pages, **kw),
                  donate_argnums=(2,))
    out, cache = jfn(params, toks, cache)        # compile
    np.asarray(jax.device_get(jax.tree.leaves(out)[0]).ravel()[:1])

    tdir = tempfile.mkdtemp(prefix="trace_step_")
    with jax.profiler.trace(tdir):
        for _ in range(steps):
            out, cache = jfn(params, toks, cache)
        np.asarray(jax.device_get(jax.tree.leaves(out)[0]).ravel()[:1])

    xplanes = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    if not xplanes:
        raise SystemExit(f"no xplane under {tdir}")
    parse(xplanes, variant, steps)


def parse(xplanes, variant, steps) -> None:
    from xprof.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data(
        xplanes, "hlo_stats", {})
    payload = json.loads(data) if isinstance(data, (str, bytes)) else data
    idx = {c["id"]: i for i, c in enumerate(payload["cols"])}
    time_col = "total_self_time"
    agg: dict[str, float] = {}
    ops: dict[str, float] = {}
    total = 0.0
    for row in payload["rows"]:
        cells = row["c"]

        def get(col):
            v = cells[idx[col]]
            return v.get("v") if isinstance(v, dict) else v
        t = float(get(time_col) or 0.0)
        total += t
        agg_key = str(get("category"))
        agg[agg_key] = agg.get(agg_key, 0.0) + t
        nm = str(get("hlo_op_name"))
        key = nm.split(".")[0]
        ops[key] = ops.get(key, 0.0) + t
        if os.environ.get("TRACE_EXPR") and t / steps > 3.0:
            print(f"[{t/steps:8.1f} us/step] "
                  f"{str(get('hlo_op_expression'))[:240]}")

    per_step = total / steps
    print(f"\n== {variant}: device total {total/1e3:.2f} ms over {steps} "
          f"steps -> {per_step*1e3:.0f} us/step ==")
    print("\nby category (us/step):")
    for cat, t in sorted(agg.items(), key=lambda kv: -kv[1]):
        print(f"  {cat:32s} {t/steps:9.1f}")
    print("\ntop ops (us/step):")
    for nm, t in sorted(ops.items(), key=lambda kv: -kv[1])[:25]:
        print(f"  {nm:48s} {t/steps:9.1f}")


if __name__ == "__main__":
    main()

"""Benchmark harness — prints ONE JSON line for the driver.

Measures the north-star metrics from BASELINE.json on whatever accelerator
is visible (the driver runs this on one real TPU chip):

- **p50 TTFT with 32 concurrent peers** through the real continuous-batching
  scheduler (serve/scheduler.py) — the end-to-end serving path: tokenize ->
  solo prefill -> KV splice -> batched masked decode -> host sampling ->
  incremental detokenise. North star: < 150 ms (BASELINE.json).
- **decode tokens/sec/chip**: raw batched decode throughput of the jitted
  model step at serving batch size.

No public checkpoint ships in this image (zero egress), so weights are
random-init at ``BENCH_CONFIG`` size (default ``bench-1b``, a ~1.2B-param
llama-family config sized for one v5e chip's HBM alongside a 32-slot KV
cache). Architecture and code path are identical to llama3.1-8B — only the
dimensions differ; set ``BENCH_CONFIG=llama3.1-8b`` on hardware that fits.

Output: one JSON line on stdout:
``{"metric", "value", "unit", "vs_baseline", "extra": {...}}``.
The reference publishes no numbers (SURVEY.md §6; BASELINE.json
``published: {}``), so ``vs_baseline`` is measured against the stated
north-star target: ``150 ms / p50_ttft_ms`` (> 1.0 beats the target).

The default configuration is paged KV + fused int8 weights + int8 KV
pool + shared-prefix cache — the framework's best composition for the
synthetic workload (every feature is oracle-pinned by the test suite,
so the speed is not traded against correctness). Speculative decoding
defaults OFF here:
prompt-lookup drafts cannot match a random-init model's continuations
(0 accepted drafts measured even at greedy), so its verify forwards
would be pure overhead on this bench — see BENCH_SPEC below. Set the
env knobs to measure stripped-down variants, e.g. ``BENCH_KV=dense
BENCH_QUANT= BENCH_PREFIX=0`` for the plain bf16 dense baseline, or
``BENCH_QUANT=int4`` for the group-wise w4a16 weight trunk (half the
int8 weight stream again).

Env knobs (all optional):
- ``BENCH_CONFIG``      model config (default bench-1b)
- ``BENCH_SLOTS``       concurrent peers / batch rows (default 32)
- ``BENCH_MAX_SEQ``     per-slot sequence budget (default 1024)
- ``BENCH_NEW_TOKENS``  completion length per request (default 32)
- ``BENCH_DECODE_STEPS``raw-decode timing steps (default 64)
- ``BENCH_KV``          dense | paged (default paged)
- ``BENCH_PAGE_SIZE``   tokens per KV page in paged mode (default 64)
- ``BENCH_QUANT``       weight quantization: ``int8`` (default,
                        per-channel w8a16) | ``int4`` (group-wise
                        w4a16 packed nibbles — half the int8 weight
                        stream again) | empty = bf16 weights
- ``BENCH_KV_QUANT``    int8 (default) = quantized KV pool (paged only;
                        halves KV read traffic, doubles pool capacity;
                        1.5x step at 1024-token windows and the best
                        measured short-window step too — empty disables)
- ``BENCH_FUSE``        fused multi-step decode: up to K decode steps per
                        device dispatch (lax.scan over the decode step,
                        sampling on device — serve/scheduler.py
                        decode_fuse_max). Default 4; 1 disables. The raw
                        phase measures the fused program's wall AND
                        device step so the wall/device gap the fusion
                        closes is reported explicitly
                        (``wall_over_device`` in the JSON row)
- ``BENCH_SPEC``        K>0 = speculative decoding with K drafts/tick
                        (default 0: prompt-lookup drafts cannot match a
                        RANDOM-INIT model's continuations, so on the
                        synthetic bench the verify forwards are pure
                        overhead — measured 0 accepted drafts even at
                        greedy. Enable for real checkpoints, where
                        suggestion replies quote their context)
- ``BENCH_WORKLOAD``    quote = synthetic checkpoint whose greedy output
                        repeats a 16-token phrase (the quote-the-context
                        statistic of real co-pilot replies; full model
                        compute) — THE workload where prompt-lookup
                        BENCH_SPEC wins: measured +51% served tok/s at
                        K=4 greedy with 3,128/4,096 tokens from
                        accepted drafts
- ``BENCH_SPEC_WORKLOAD`` freeform = the NON-quote speculation phase:
                        synthetic weights whose greedy output follows
                        one pseudo-random 95-token cycle (n-gram drafts
                        score ~0 — the free-form statistic), served with
                        the resident draft model (BENCH_DRAFT) on vs
                        speculation off, per-source acceptance in the
                        JSON ``spec_freeform`` row. Defaults BENCH_SPEC
                        to 4 when unset
- ``BENCH_DRAFT``       draft-model config resident beside the target
                        (default draft-400m for the freeform phase;
                        vocab clones to the target's). With
                        BENCH_SPEC > 0 it also drafts for the main
                        phases' workload
- ``BENCH_SPEC_TREE``   N>0 = tree-speculation A/B at EQUAL verify
                        budget (default 8 with the freeform phase, else
                        0): linear chain K=N-1 vs tree K=N/2 with N
                        node positions, both legs driving an IMPERFECT
                        drafter (top-1 decoy / truth-as-runner-up on
                        every 3rd cycle token — the miss-with-a-good-
                        second-choice regime sibling leaves exist for)
                        over dedicated warmed schedulers; accepted
                        tokens per verify dispatch and served tok/s per
                        leg land in the JSON ``spec_tree`` row
- ``BENCH_PREFIX``      shared-prefix KV cache (default 1; 0 disables)
- ``BENCH_TEMP``        request temperature (default 0.7; 0 = greedy —
                        the workload where prompt-lookup spec drafts
                        can land, see the spec bench note)
- ``BENCH_ADMIT_CHUNK`` fixed burst-admission width
- ``BENCH_CTX``         long-context mode: approximate prompt length in
                        tokens (0 = the short suggestion template).
                        Exercises chunked-flash prefill and long-window
                        paged decode; size BENCH_MAX_SEQ to fit it.
- ``BENCH_PREFILL_CHUNK`` chunked-prefill token budget for the serving
                        scheduler (default 256; 0 = legacy whole-bucket
                        admission)
- ``BENCH_MIXED``       mixed-load phase (default 1): Poisson arrivals
                        of long prompts while the batch decodes,
                        reporting inter-token p50/p95 (TBT) and the max
                        decode-tick gap — once with chunked prefill,
                        once single-shot, so the admission stall the
                        chunking bounds is measured, not inferred.
                        TTFT alone cannot see it: a whole-bucket
                        prefill stalls OTHER streams' tokens.
- ``BENCH_ARRIVAL_CTX`` mixed-phase arrival prompt length in tokens
                        (default 384 -> a 512 bucket, two chunks)
- ``BENCH_ARRIVAL_N``   mixed-phase arrival count (default 6)
- ``BENCH_ARRIVAL_RATE`` mixed-phase Poisson arrival rate, 1/s (default 4)
- ``BENCH_REPLICAS``    replica-router phase (0 = off): N >= 2 builds N
                        full-stack engines sharing this bench's params
                        behind serve/router.py and measures aggregate
                        served tok/s through the router vs one replica
                        on the same workload over real HTTP, plus
                        routed/retried/shed counts (JSON
                        ``replica_router`` row; docs/serving.md
                        Round-10).
- ``BENCH_REPLICA_SLOTS`` per-replica batch rows in that phase
                        (default BENCH_SLOTS / BENCH_REPLICAS — fixed
                        per-replica capacity, fleet capacity = slots)
- ``BENCH_PARK``        park/wake phase (default 1 in paged mode):
                        multi-tier KV session parking under HBM
                        pressure — N sessions on a pool sized for a few
                        concurrent requests, host-RAM parking on
                        (idle_s=0), Poisson wake schedule, compared
                        byte-for-byte against a resident (never-parked)
                        run; JSON ``park_wake`` row
- ``BENCH_PARK_SESSIONS`` sessions in that phase (default 32)
- ``BENCH_PARK_SLOTS``  batch rows / pool sizing for it (default 4)
- ``BENCH_PARK_RATE``   Poisson wake rate, 1/s (default 16)
- ``BENCH_PARK_NEW``    completion tokens per turn (default 12)
- ``BENCH_PARK_HOST_GB`` host-RAM park budget for the phase (default 1)
- ``BENCH_PROFILE``     directory for a jax.profiler trace of the
                        concurrent section
- ``BENCH_LONG_W``      long-window decode sweep: comma list of paged
                        attention windows (default ``2048,4096``; empty
                        disables). Each window measures the decode step
                        under the gather path AND the multi-chunk
                        flash-append kernel (flipping
                        ``PAGED_APPEND_FLASH_MIN_W`` at runtime) and
                        reports both against the HBM bytes bound
                        (``long_w`` rows in the JSON). TPU + paged only.
- ``BENCH_MOE_SCALE``   1 = MoE-scale ablation phase (round 18): decode
                        step time at ``BENCH_MOE_CONFIG`` across four
                        legs — paged + fused wgu_e + auto matmul impl
                        (the served configuration), split gate/up
                        projections, forced-XLA dequant matmuls, and
                        the dense cache — with per-leg effective-impl
                        labels and ratios (``moe_scale`` row). Runs
                        after the serving phases on its own params.
- ``BENCH_MOE_CONFIG``  config for that phase (default bench-moe;
                        ``mixtral-large`` on hardware that fits it)
- ``BENCH_MOE_SLOTS``   decode rows for it (default 8)
- ``BENCH_MOE_WINDOW``  attention window it decodes at (default 512)
- ``BENCH_MOE_STEPS``   timing-loop depth (default 8)
"""

from __future__ import annotations


import json
import os
import statistics
import sys
import threading
import time

from p2p_llm_chat_tpu.utils.env import (env_float, env_int, env_opt,
                                        env_or, env_bool)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# Per-chip peaks keyed by jax's device_kind. A device that is not in
# the table is an error, not a default. Source: Google Cloud
# documentation, "TPU v5e" (819 GB/s HBM).
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0},
}


def main() -> None:
    from p2p_llm_chat_tpu.utils.jax_cache import enable_persistent_cache
    enable_persistent_cache()
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_llm_chat_tpu.models import family_for, llama
    from p2p_llm_chat_tpu.models.configs import get_config
    from p2p_llm_chat_tpu.models.llama import KVCache
    from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                                GenerateRequest, RequestStats)
    from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler
    from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

    cfg_name = env_or("BENCH_CONFIG", "bench-1b")
    slots = env_int("BENCH_SLOTS", 32)
    max_seq = env_int("BENCH_MAX_SEQ", 1024)
    new_tokens = env_int("BENCH_NEW_TOKENS", 32)
    decode_steps = env_int("BENCH_DECODE_STEPS", 64)
    kv_mode = env_or("BENCH_KV", "paged")   # dense | paged
    page_size = env_int("BENCH_PAGE_SIZE", 64)

    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu":
        # A CPU timing is not a device metric: no chip, no benchmark.
        raise SystemExit(
            f"bench.py measures the TPU, but JAX came up on {platform!r} "
            f"({jax.devices()}): no chip is visible or another process "
            "holds it")
    if dev.device_kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"bench.py has no peak table row for device_kind "
            f"{dev.device_kind!r}; add one to DEVICE_PEAKS with its source")
    log(f"bench: {cfg_name} on {dev} ({platform}, {dev.device_kind}), "
        f"{slots} slots, max_seq {max_seq}")

    config = get_config(cfg_name)
    family = family_for(config)   # llama or mixtral (bench-moe)
    dtype = jnp.bfloat16
    # "" | int8 | int4; BENCH_QUANT= (set-empty) = bf16 weights
    quant = env_opt("BENCH_QUANT", "int8")
    if quant not in ("", "int8", "int4"):
        raise SystemExit(
            f"BENCH_QUANT must be one of '', 'int8', 'int4'; "
            f"got {quant!r}")
    workload = env_or("BENCH_WORKLOAD", "")
    # Free-form draft-model spec phase (BENCH_SPEC_WORKLOAD=freeform):
    # the synthetic lm_head follows ONE pseudo-random 95-token cycle
    # instead of the quote workload's 16-token repeats, so n-gram drafts
    # score ~0 and only the resident draft model (BENCH_DRAFT, sharing
    # the successor map) can make speculation win — the two statistics
    # stop being conflated in one "spec" number.
    spec_workload = env_or("BENCH_SPEC_WORKLOAD", "")
    if spec_workload not in ("", "freeform"):
        raise SystemExit(f"BENCH_SPEC_WORKLOAD must be freeform or "
                         f"empty, got {spec_workload!r}")
    if spec_workload == "freeform" and workload == "quote":
        # One set of weights serves the whole run; building the target
        # with the freeform cycle while labeling the main phases "quote"
        # would be exactly the conflation this phase exists to remove.
        raise SystemExit("BENCH_WORKLOAD=quote and BENCH_SPEC_WORKLOAD="
                         "freeform are mutually exclusive (one synthetic "
                         "lm_head per run); pick one statistic")
    synth_mode = "freeform" if spec_workload == "freeform" else "quote"
    stream_quant = bool(quant) and hasattr(family, "init_params_quantized")
    if workload == "quote" or spec_workload == "freeform":
        # Speculation / streaming workload (models/synth.py): random
        # transformer layers (full compute) + an embed/lm_head whose
        # greedy output repeats a printable 16-token phrase — the
        # quote-the-context statistic of real co-pilot replies that
        # random init cannot produce (251/256 unique tokens, 0 draft
        # acceptances measured). Spec rows on this workload measure the
        # true verify-tick cost vs accepted-draft win end-to-end.
        from p2p_llm_chat_tpu.models.synth import quote_params
        params = quote_params(config, jax.random.PRNGKey(0), dtype=dtype,
                              quantized=stream_quant, mode=synth_mode,
                              quant=quant or "int8")
        if quant and not stream_quant:
            from p2p_llm_chat_tpu.models.quant import quantize_params
            params = quantize_params(params, mode=quant)
    elif stream_quant:
        # Streamed straight to the fused quantized tree — never
        # materialises the bf16 tree, which is what lets
        # BENCH_CONFIG=llama3.1-8b (16 GB bf16) run on one 16 GB v5e
        # chip (llama.init_params_quantized); int4 halves it again.
        params = family.init_params_quantized(config, jax.random.PRNGKey(0),
                                              dtype=dtype, quant=quant)
    else:
        params = family.init_params(config, jax.random.PRNGKey(0),
                                    dtype=dtype)
        if quant:
            from p2p_llm_chat_tpu.models.quant import quantize_params
            params = quantize_params(params, mode=quant)
    from p2p_llm_chat_tpu.models.quant import (QTensor, QTensor4,
                                               param_bytes)
    # Logical parameter count: int4 packs two weights per stored byte.
    n_params = sum(
        (x.q.size if isinstance(x, QTensor) else
         2 * x.q.size if isinstance(x, QTensor4) else x.size)
        for x in jax.tree.leaves(
            params,
            is_leaf=lambda x: isinstance(x, (QTensor, QTensor4))))
    # Stored weight bytes — the per-step HBM weight stream.
    weight_stream_bytes = param_bytes(params)
    jax.block_until_ready(params)
    log(f"params: {n_params/1e9:.2f}B ({dtype.__name__}"
        f"{f', {quant} weights' if quant else ''}"
        f"{', quote workload' if workload == 'quote' else ''}); "
        f"weight stream {weight_stream_bytes/1e9:.3f} GB/step")

    # Default int8 KV only where it applies: BENCH_KV=dense stripped-down
    # runs and PAGED_ATTN_IMPL=kernel|flash measurements (int8 pools are
    # gather-impl only) must not trip the validation guards. The impl
    # default comes from the ops module — one source of truth with the
    # scheduler's kv_quant guard. importlib on purpose: `from ...ops
    # import paged_attention` yields the FUNCTION (the package __init__
    # rebinds the name over the submodule).
    import importlib
    _pa = importlib.import_module("p2p_llm_chat_tpu.ops.paged_attention")
    kv_quant_default = ("int8" if kv_mode == "paged"
                        and _pa._DEFAULT_IMPL == "gather" else "")
    kv_quant = env_opt("BENCH_KV_QUANT", kv_quant_default) == "int8"
    if kv_quant and kv_mode != "paged":
        raise SystemExit("BENCH_KV_QUANT=int8 requires BENCH_KV=paged")

    # -- raw batched decode throughput (pure device step, serving shapes,
    # matching the selected kv_mode). The serve scheduler fuses the
    # projection pairs on single-chip engines (models/llama.fuse_params),
    # so the raw step measures the same fused program.
    # Loop lengths for the plain and fused measurement phases are fixed
    # up front so the paged pool below can be sized to the DEEPEST loop:
    # the plain loop writes n2+1 tokens per measure call; the fused loop
    # writes (f2+1)*K (the 1/K dispatch scaling has max() floors, so at
    # large K its token count can EXCEED the plain loop's — an
    # under-sized pool would silently drop the tail writes past the page
    # table and publish numbers from a truncated window).
    fuse_k = max(1, env_int("BENCH_FUSE", 4))
    n1 = max(16, decode_steps // 4)
    n2 = max(decode_steps, 2 * n1)      # strictly > n1, or the solve is 0/0
    f1 = max(4, n1 // fuse_k)
    f2 = max(2 * f1, n2 // fuse_k)
    raw_params = family.fuse_params(params)

    # -- quantized-matmul dispatch table: for every fused quantized
    # weight shape of this config, which implementation models/quant.mm
    # dispatches at decode rows (B=slots) and the chosen output tile —
    # the autotune table's decision (ops/quant_mm._TILE_TABLE, the
    # hidden=1024 retune) made durable in the bench JSON so a dispatch
    # regression shows up as a row diff, not a silent slowdown. On TPU
    # each kernel-covered shape also times its kernel against forced-XLA
    # dequant at the same rows — the "no shape regime where the in-tree
    # kernel loses to XLA" acceptance check.
    qmm_dispatch: list = []
    if quant:
        from p2p_llm_chat_tpu.models.quant import dequantize, dequantize4
        from p2p_llm_chat_tpu.ops.quant_mm import (_pick_1d_bo, pick_block,
                                                   pick_int4_bo,
                                                   quant_matmul,
                                                   quant_matmul4)

        def _time_ms(fn) -> float:
            r = fn()                               # compile + warm
            np.asarray(r).ravel()[:1]
            t = time.monotonic()
            for _ in range(10):
                r = fn()
            np.asarray(r).ravel()[:1]              # forced sync
            return (time.monotonic() - t) / 10 * 1e3

        xla8 = jax.jit(lambda x, q, s: x @ dequantize(
            QTensor(q=q, s=s), x.dtype))
        xla4 = jax.jit(lambda x, q, s: x @ dequantize4(
            QTensor4(q=q, s=s), x.dtype))
        qleaves = {n: v for n, v in raw_params["layers"].items()
                   if isinstance(v, (QTensor, QTensor4))}
        if isinstance(raw_params.get("lm_head"), (QTensor, QTensor4)):
            qleaves["lm_head"] = raw_params["lm_head"]
        seen_shapes: set = set()
        for name, leaf in sorted(qleaves.items()):
            if leaf.q.ndim > 3:
                continue        # 4-D MoE expert stacks go via q_einsum
            is4 = isinstance(leaf, QTensor4)
            stacked = leaf.q.ndim == 3
            K = leaf.q.shape[-2] * (2 if is4 else 1)
            O = leaf.q.shape[-1]
            if (is4, K, O) in seen_shapes:
                continue
            seen_shapes.add((is4, K, O))
            rp = slots + ((-slots) % 8)
            xi = jnp.dtype(dtype).itemsize
            if is4:
                ng = leaf.s.shape[-2]
                bo = pick_int4_bo(slots, K, O, ng, xi)
                impl = "kernel-1d" if bo else "xla-dequant"
            else:
                bo = _pick_1d_bo(rp, K, O, xi)
                if bo:
                    impl = "kernel-1d"
                else:
                    bo = (pick_block(O) if pick_block(K) else None)
                    impl = "kernel-2d" if bo else "xla-dequant"
            row = {"name": name, "quant": "int4" if is4 else "int8",
                   "K": K, "O": O, "rows": slots, "impl": impl, "bo": bo}
            if impl.startswith("kernel"):
                xq = jnp.ones((slots, K), dtype)
                qw = leaf.q[0] if stacked else leaf.q
                sw = leaf.s[0] if stacked else leaf.s
                if is4:
                    k_ms = _time_ms(lambda: quant_matmul4(xq, qw, sw))
                    x_ms = _time_ms(lambda: xla4(xq, qw, sw))
                else:
                    k_ms = _time_ms(lambda: quant_matmul(xq, qw, sw))
                    x_ms = _time_ms(lambda: xla8(xq, qw, sw))
                row.update(kernel_ms=round(k_ms, 4), xla_ms=round(x_ms, 4),
                           kernel_speedup=(round(x_ms / k_ms, 3)
                                           if k_ms > 0 else None))
            qmm_dispatch.append(row)
        disp = ", ".join(f"{r['name']}[{r['K']}x{r['O']}]={r['impl']}"
                         f"(bo={r['bo']})" for r in qmm_dispatch)
        log(f"qmm dispatch ({quant}, rows={slots}): {disp}")
    if kv_mode == "paged":
        from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache

        # Attention window must cover the initial 64-token context plus
        # every decoded position, or the kernel walks a truncated page
        # table and the paged tok/s is not comparable to dense. The pool
        # is sized to that actual context — NOT slots x max_seq, which at
        # long BENCH_MAX_SEQ would reserve more HBM than the chip has
        # (the exact failure paging exists to avoid).
        deepest = max(n2 + 1,
                      (f2 + 1) * fuse_k if fuse_k > 1 else 0)
        window_pages = -(-(64 + deepest + 1) // page_size)
        mppr = window_pages
        num_pages = slots * mppr + 1

        def _step(params, tokens, cache, active):
            return family.decode_step_paged(params, config, tokens, cache,
                                           active=active, pages=window_pages)

        def make_raw_cache():
            cache = PagedKVCache.create(config, slots, num_pages, page_size,
                                        max_pages_per_row=mppr, dtype=dtype,
                                        quantized=kv_quant)
            table = (1 + jnp.arange(slots * mppr, dtype=jnp.int32)
                     ).reshape(slots, mppr)
            return cache._replace(page_table=table,
                                  lengths=jnp.full((slots,), 64, jnp.int32))
    else:
        def _step(params, tokens, cache, active):
            return family.decode_step(params, config, tokens, cache,
                                     active=active)

        def make_raw_cache():
            cache = KVCache.create(config, slots, max_seq, dtype)
            return cache._replace(lengths=jnp.full((slots,), 64, jnp.int32))

    decode_j = jax.jit(_step, donate_argnums=(2,))
    toks = jnp.ones((slots, 1), jnp.int32)
    active = jnp.ones((slots,), bool)

    # A small device->host readback is the sync. One N-step loop reports
    # wall(N)/N = device_step + c/N, where c is the constant dispatch +
    # readback cost of the loop; two loop lengths solve for the device
    # step: D = (N2*w2 - N1*w1) / (N2 - N1).
    def measure_loop(steps: int) -> float:
        cache = make_raw_cache()
        logits, cache = decode_j(raw_params, toks, cache, active)  # compile
        np.asarray(logits[:1, 0, :1])
        t = time.monotonic()
        for _ in range(steps):
            logits, cache = decode_j(raw_params, toks, cache, active)
        np.asarray(logits[:1, 0, :1])                          # forced sync
        return (time.monotonic() - t) / steps

    w1 = min(measure_loop(n1) for _ in range(2))
    w2 = min(measure_loop(n2) for _ in range(2))
    dev_step = (n2 * w2 - n1 * w1) / (n2 - n1)
    if dev_step < 0.05 * w2:
        # Tiny-config steps are indistinguishable from timing noise and
        # the solve can land near (or below) zero — report the wall
        # number rather than nonsense tok/s.
        dev_step = w2
    step_ms = dev_step * 1e3
    wall_step_ms = w2 * 1e3
    log(f"raw decode: {slots / dev_step:,.0f} tok/s/chip at B={slots} "
        f"({step_ms:.2f} ms/step device; wall {w2*1e3:.2f} ms/step at "
        f"N={n2})")

    # -- fused multi-step decode: K steps per dispatch (the tentpole of
    # the wall/device-gap work). Same greedy feed as serving's fused
    # path but sampling reduced to on-device argmax — the raw number
    # isolates model + dispatch, not sampling options. Loop lengths
    # (f1/f2 above) scale ~1/K so both measurements cover a comparable
    # token count and attention growth (fair wall comparison; the pool
    # is sized for whichever loop runs deeper).
    fused_step_ms = fused_wall_step_ms = None
    if fuse_k > 1:
        def _fused(params, tokens, cache, active):
            def sample_fn(lg, state, emit_pos, act):
                return jnp.argmax(lg, axis=-1).astype(jnp.int32), state
            kw = (dict(pages=window_pages) if kv_mode == "paged" else {})
            toks_all, _, nxt, cache, _, _ = family.decode_fused(
                params, config, tokens, cache, active=active,
                num_steps=fuse_k, sample_fn=sample_fn, sample_state=(),
                stop_ids=np.zeros((0,), np.int32), **kw)
            return toks_all, nxt, cache

        fused_j = jax.jit(_fused, donate_argnums=(2,))

        def measure_loop_fused(n_disp: int) -> float:
            cache = make_raw_cache()
            toks_all, nxt, cache = fused_j(raw_params, toks, cache, active)
            np.asarray(toks_all[:1, :1])
            t = time.monotonic()
            for _ in range(n_disp):
                toks_all, nxt, cache = fused_j(raw_params, nxt, cache,
                                               active)
            np.asarray(toks_all[:1, :1])
            return (time.monotonic() - t) / n_disp

        fw1 = min(measure_loop_fused(f1) for _ in range(2))
        fw2 = min(measure_loop_fused(f2) for _ in range(2))
        fdev = (f2 * fw2 - f1 * fw1) / (f2 - f1)
        if fdev < 0.05 * fw2:
            fdev = fw2
        fused_step_ms = fdev / fuse_k * 1e3
        fused_wall_step_ms = fw2 / fuse_k * 1e3
        log(f"fused decode (K={fuse_k}): "
            f"{slots / (fdev / fuse_k):,.0f} tok/s/chip device-basis "
            f"({fused_step_ms:.2f} ms/step device; wall "
            f"{fused_wall_step_ms:.2f} ms/step at N={f2}x{fuse_k}; "
            f"wall/device {fused_wall_step_ms / step_ms:.2f}x vs plain "
            f"{wall_step_ms / step_ms:.2f}x)")

    # -- long-window decode sweep (BENCH_LONG_W): step time per window W
    # with the flash-append kernel vs the gather path, each against the
    # HBM bytes bound — the round-8 acceptance numbers (ISSUE 4: W=4096
    # <= 20 ms, W=8192 <= 40 ms at B=32 bench-1b int8, >= 2x gather).
    # The sweep flips PAGED_APPEND_FLASH_MIN_W at runtime (the toggle is
    # read per dispatch decision, not frozen at import) and traces one
    # fresh program per (window, impl); rows are parked (active=False)
    # so lengths hold and every step reads the same full window.
    long_w_rows: list = []
    long_ws = [int(w) for w in env_or("BENCH_LONG_W", "2048,4096").split(",")
               if w.strip()]
    hbm_gbps = DEVICE_PEAKS[dev.device_kind]["hbm_gbps"]
    if long_ws and kv_mode != "paged":
        log("long-window sweep: skipped (needs BENCH_KV=paged; "
            "BENCH_LONG_W= disables)")
        long_ws = []
    if long_ws and _pa._DEFAULT_IMPL != "gather":
        # A non-gather PAGED_ATTN_IMPL flips decode_step_paged onto the
        # write-then-attend branch, where paged_attention_append (the
        # path this sweep A/Bs, and the min-W toggle with it) never
        # runs — the rows would time one identical program twice under
        # two labels.
        log("long-window sweep: skipped (PAGED_ATTN_IMPL="
            f"{_pa._DEFAULT_IMPL!r} bypasses the append-path dispatch "
            "the sweep compares)")
        long_ws = []
    if long_ws:
        # `_pa` (the ops module, importlib-bound above for the kv_quant
        # default) is reused here for the dispatch-label queries.
        from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache as _PKV
        Hkv, Dh, Lnum = (config.num_kv_heads, config.head_dim,
                         config.num_layers)
        kv_itemsize = 1 if kv_quant else jnp.dtype(dtype).itemsize
        # Bound approximation: the full weight stream (actual stored
        # bytes — int8 ~= param count, int4 half that, bf16 2x) + the
        # KV window walk; activations are noise at these shapes.
        weight_bytes = weight_stream_bytes
        saved_min_w = env_or("PAGED_APPEND_FLASH_MIN_W", "")
        try:
            for W in long_ws:
                pages_w = -(-W // page_size)
                pool = _PKV.create(config, slots, slots * pages_w + 1,
                                   page_size, max_pages_per_row=pages_w,
                                   dtype=dtype, quantized=kv_quant)
                table = (1 + jnp.arange(slots * pages_w, dtype=jnp.int32)
                         ).reshape(slots, pages_w)
                pool = pool._replace(
                    page_table=table,
                    lengths=jnp.full((slots,), W - 2, jnp.int32))
                kv_bytes = 2 * W * Hkv * Dh * kv_itemsize * slots * Lnum
                if kv_quant:
                    ps_pad = pool.k_scale.shape[-1]
                    kv_bytes += (2 * pages_w * Hkv * ps_pad * 4
                                 * slots * Lnum)
                bound_ms = (kv_bytes + weight_bytes) / (hbm_gbps * 1e9) * 1e3
                parked = jnp.zeros((slots,), bool)
                step_by_impl: dict = {}
                for want_flash in (False, True):
                    # A write, not a read — graftcheck's env-hygiene
                    # scope covers reads; the runtime-read dispatch
                    # picks this up at the fresh trace below.
                    os.environ["PAGED_APPEND_FLASH_MIN_W"] = (
                        str(W) if want_flash else "0")
                    # Label rows by what the trace will ACTUALLY
                    # dispatch, not by the toggle: a PAGED_APPEND_IMPL
                    # override (flash/kernel) wins over min_w in the
                    # dispatch, so the toggle can be a no-op — both
                    # iterations then measure the same impl and dedupe
                    # to one honestly-labeled row.
                    if _pa._APPEND_IMPL == "kernel":
                        eff = "kernel"
                    elif _pa._flash_append_wanted(W):
                        eff = "flash"
                    else:
                        eff = "gather"
                    if eff in step_by_impl:
                        continue

                    def _lw_step(p, t, c, a, pw=pages_w):
                        return family.decode_step_paged(p, config, t, c,
                                                        active=a, pages=pw)

                    # graftcheck: retrace-ok one fresh wrapper per (window, impl) by design — the runtime PAGED_APPEND_FLASH_MIN_W toggle must be re-read at trace
                    lw_j = jax.jit(_lw_step, donate_argnums=(2,))

                    def lw_loop(n: int, lw_j=lw_j):
                        nonlocal pool
                        lg, pool = lw_j(raw_params, toks, pool, parked)
                        np.asarray(lg[:1, 0, :1])
                        t0l = time.monotonic()
                        for _ in range(n):
                            lg, pool = lw_j(raw_params, toks, pool, parked)
                        np.asarray(lg[:1, 0, :1])
                        return (time.monotonic() - t0l) / n

                    ln1, ln2 = 4, 12
                    lw1, lw2 = lw_loop(ln1), lw_loop(ln2)
                    d = (ln2 * lw2 - ln1 * lw1) / (ln2 - ln1)
                    step_by_impl[eff] = (d if d > 0.05 * lw2 else lw2) * 1e3
                g_ms = step_by_impl.get("gather")
                for impl_name, ms in sorted(step_by_impl.items()):
                    long_w_rows.append({
                        "window": W, "impl": impl_name,
                        "step_ms": round(ms, 3),
                        "bound_ms": round(bound_ms, 3),
                        "bytes_bound_ratio": round(ms / bound_ms, 2),
                        "speedup_vs_gather": (
                            round(g_ms / ms, 2)
                            if impl_name == "flash" and g_ms else None),
                    })
                log(f"long-window W={W}: " + ", ".join(
                    f"{name} {ms:.2f} ms ({ms / bound_ms:.1f}x bytes bound)"
                    + (f" [{g_ms / ms:.2f}x gather]"
                       if name == "flash" and g_ms else "")
                    for name, ms in sorted(step_by_impl.items())))
                del pool
        finally:
            if saved_min_w:
                os.environ["PAGED_APPEND_FLASH_MIN_W"] = saved_min_w
            else:
                os.environ.pop("PAGED_APPEND_FLASH_MIN_W", None)

    # Raw tok/s, device basis (r05's definition — slots / device step):
    # the fused program's per-token device step when fusion is on (the
    # scan drops per-step dispatch work the plain loop still pays).
    best_dev_ms = min(step_ms, fused_step_ms or step_ms)
    raw_tok_s = slots / (best_dev_ms / 1e3)
    # Free the fused weight copy before the serving phase allocates its
    # own fused params + KV pool — three copies of the projection
    # weights would shrink the HBM headroom the serving numbers measure.
    del raw_params

    # -- end-to-end serving: p50 TTFT at `slots` concurrent peers ------------
    admit_chunk = env_int("BENCH_ADMIT_CHUNK", 0) or None
    spec_k = env_int("BENCH_SPEC", 0)
    if spec_workload == "freeform" and not spec_k:
        spec_k = 4          # the phase exists to measure draft-model spec
    # Resident draft model (BENCH_DRAFT: config name; default draft-400m
    # for the freeform phase). Random/synthetic weights carry no
    # vocabulary semantics, so the config clones at the target's vocab;
    # synthetic modes build the drafter with the SAME successor map as
    # the target (models/synth.py) — the stand-in for a small model
    # predicting the big model's easy tokens.
    draft_name = env_or("BENCH_DRAFT",
                        "draft-400m" if spec_workload == "freeform" else "")
    drafter = None
    if draft_name and spec_k:
        from p2p_llm_chat_tpu.serve.draft_model import ModelDrafter
        dcfg = get_config(draft_name)
        if dcfg.vocab_size != config.vocab_size:
            dcfg = dcfg.with_(vocab_size=config.vocab_size)
        dfam = family_for(dcfg)
        d_quant = bool(quant) and hasattr(dfam, "init_params_quantized")
        if workload == "quote" or spec_workload == "freeform":
            from p2p_llm_chat_tpu.models.synth import quote_params as _qp
            dparams = _qp(dcfg, jax.random.PRNGKey(1), dtype=dtype,
                          quantized=d_quant, mode=synth_mode,
                          quant=quant or "int8")
        elif d_quant:
            dparams = dfam.init_params_quantized(dcfg,
                                                 jax.random.PRNGKey(1),
                                                 dtype=dtype, quant=quant)
        else:
            dparams = dfam.init_params(dcfg, jax.random.PRNGKey(1),
                                       dtype=dtype)
            if quant:
                from p2p_llm_chat_tpu.models.quant import quantize_params
                dparams = quantize_params(dparams, mode=quant)
        drafter = ModelDrafter(dparams, dcfg, num_slots=slots,
                               max_seq=max_seq, k=spec_k)
        log(f"draft model: {draft_name} resident "
            f"({drafter.param_bytes()/1e9:.2f} GB params, "
            f"{drafter.kv_bytes()/1e9:.2f} GB KV), k={spec_k}")
    use_prefix = env_bool("BENCH_PREFIX", True)
    # Chunked prefill (serve/scheduler.py prefill_chunk) + the mixed-load
    # phase that measures the admission stall it bounds.
    bench_chunk = max(0, env_int("BENCH_PREFILL_CHUNK", 256))
    mixed = env_bool("BENCH_MIXED", True)
    arr_ctx = env_int("BENCH_ARRIVAL_CTX", 384)
    arr_n = env_int("BENCH_ARRIVAL_N", 6)
    arr_rate = max(0.1, env_float("BENCH_ARRIVAL_RATE", 4.0))
    mixed_new = max(64, 4 * new_tokens) if mixed else 0
    tokenizer = ByteTokenizer(vocab_size=config.vocab_size)
    prompt = ("Draft a concise, friendly reply to the following message:\n\n"
              "Hey, are we still meeting tomorrow at 10?\n\nReply:")
    bench_ctx = env_int("BENCH_CTX", 0)
    if bench_ctx:
        # Long-context suggestion: a big conversation history ahead of
        # the same template tail (byte tokenizer: ~1 token per char).
        history = ("Earlier in this thread we discussed the quarterly "
                   "plans and the picnic schedule. ")
        need = max(0, bench_ctx - len(prompt))
        prompt = (history * (need // len(history) + 1))[:need] + prompt
    # Pool sized to the bench workload's real per-request budget
    # (prompt + completion + spec slack), not slots x max_seq — and
    # never above the per-row cap the scheduler itself enforces (the
    # prompt gets tail-truncated to the context budget anyway).
    serve_pages = None
    if kv_mode == "paged":
        eff_max = min(max_seq, config.max_seq_len)
        # Worst per-row shape across phases: the short suggestion, the
        # mixed-phase decode rows (longer completions), and the
        # mixed-phase long arrivals.
        shapes = [len(prompt) + 1 + new_tokens + spec_k + 2]
        if mixed:
            shapes.append(len(prompt) + 1 + mixed_new + spec_k + 2)
            shapes.append(arr_ctx + 32 + new_tokens + spec_k + 2)
        if spec_workload == "freeform" and drafter is not None:
            # The freeform A/B phase decodes longer completions.
            shapes.append(len(prompt) + 1 + max(64, 2 * new_tokens)
                          + spec_k + 2)
        per_req = max(-(-s // page_size) + 1 for s in shapes)
        per_req = min(per_req, -(-eff_max // page_size))
        serve_pages = slots * per_req + 1
    sched = BatchScheduler(params, config, tokenizer, num_slots=slots,
                           max_seq=max_seq, kv_mode=kv_mode,
                           page_size=page_size, num_pages=serve_pages,
                           admit_chunk=admit_chunk,
                           spec_k=spec_k, prefix_cache=use_prefix,
                           kv_quant=kv_quant, decode_fuse_max=fuse_k,
                           prefill_chunk=bench_chunk, drafter=drafter)
    # BENCH_TEMP=0 (greedy) is the honest speculative-decoding workload:
    # prompt-lookup drafts only land when the model's continuation repeats
    # earlier n-grams, which greedy decoding does and temperature-0.7
    # sampling essentially never does on this synthetic model — spec rows
    # must report serve_spec_accepted_total > 0 to credit spec for a win.
    bench_temp = env_float("BENCH_TEMP", 0.7)
    opts = GenerateOptions(max_tokens=new_tokens, temperature=bench_temp,
                           top_p=0.9, seed=0)

    def run_one(stats: RequestStats) -> None:
        req = GenerateRequest(prompt=prompt, options=opts)
        for _ in sched.submit(req, stats):
            pass

    # Warmup: compile admit programs (both chunk sizes x prompt buckets)
    # and decode programs (attention windows) on synthetic buffers, then
    # one real request to exercise the full host path. Buckets/windows
    # are sized to the actual bench prompt + completion (the full ladder
    # to max_seq would compile programs the bench never runs).
    # With the prefix cache on, suffixes are short — warm a 64 bucket so
    # prefix admissions splice [P+64], not a rounded-up [P+128].
    from p2p_llm_chat_tpu.serve.scheduler import _bucket
    eff_max = sched.max_seq        # BENCH_MAX_SEQ capped by the config
    plen = len(tokenizer.encode(prompt, add_bos=True))
    pbucket = _bucket(min(plen, eff_max - 2), eff_max)
    bucket_set = {64, 128, pbucket} if use_prefix else {128, pbucket}
    arr_bucket = 0
    if mixed:
        # The mixed-phase arrivals land in their own (long) bucket; warm
        # it — its chunk ladder when chunking is on — or the first
        # arrival's compile would masquerade as an admission stall.
        arr_bucket = _bucket(min(arr_ctx + 1, eff_max - 2), eff_max)
        bucket_set.add(arr_bucket)
    buckets = tuple(sorted(bucket_set))
    # Fused ticks read up to (pipelined + fused) steps past the context;
    # cover them so no decode window compiles lazily mid-bench.
    deepest_ctx = plen + new_tokens
    if mixed:
        # Mixed-phase rows decode deeper (longer completions; long
        # arrivals) — an unwarmed window would lazily compile mid-phase
        # and masquerade as a multi-second admission stall.
        deepest_ctx = max(deepest_ctx, plen + mixed_new,
                          min(arr_ctx + 1, eff_max - 2) + new_tokens)
    # Freeform spec A/B phase decodes longer completions (speculation's
    # win is per decoded token; short completions would be TTFT-bound).
    spec_new = (max(64, 2 * new_tokens)
                if spec_workload == "freeform" and drafter is not None
                else 0)
    if spec_new:
        deepest_ctx = max(deepest_ctx, plen + spec_new)
    need = min(deepest_ctx + spec_k + 2 * fuse_k + 2, eff_max)
    ws, w = [], 128
    while True:
        ws.append(w)
        if w >= need or w >= eff_max:
            break
        w *= 2
    sched.warmup(prompt_buckets=buckets, windows=tuple(ws),
                 prefix_texts=(prompt,) if use_prefix else ())
    if mixed and sched.prefill_chunk:
        # The single-shot half of the mixed-load comparison runs with
        # chunking toggled off, which takes the whole-bucket programs
        # warmup skipped in favor of the chunk ladders — compile them
        # now (same buckets, so _warmed_buckets stays the full set;
        # already-compiled shapes are cache hits).
        chunk_saved, sched.prefill_chunk = sched.prefill_chunk, 0
        sched.warmup(prompt_buckets=buckets, windows=())
        sched.prefill_chunk = chunk_saved
    run_one(RequestStats())
    # Single-request TTFT (the config-2 "drop-in OLLAMA_URL" number).
    s1 = RequestStats()
    run_one(s1)
    ttft_single_ms = (s1.ttft_s or 0.0) * 1e3
    log(f"single-request TTFT: {ttft_single_ms:.1f} ms")

    # BENCH_PROFILE=/dir captures a jax.profiler trace of the concurrent
    # section (view with tensorboard / xprof; SURVEY.md §5 tracing plan).
    import contextlib
    profile_dir = env_or("BENCH_PROFILE", "")
    trace_cm = (jax.profiler.trace(profile_dir) if profile_dir
                else contextlib.nullcontext())

    all_stats = [RequestStats() for _ in range(slots)]
    threads = [threading.Thread(target=run_one, args=(s,)) for s in all_stats]
    t = time.monotonic()
    with trace_cm:
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    wall = time.monotonic() - t
    spec_stats = {k: v for k, v in sched.metrics_snapshot().items()
                  if ("spec" in k and spec_k) or ("prefix" in k and use_prefix)
                  or k.startswith("decode_")}
    ttfts = sorted(s.ttft_s * 1e3 for s in all_stats if s.ttft_s is not None)
    done_tokens = sum(s.completion_tokens for s in all_stats)
    p50 = statistics.median(ttfts)
    p95 = ttfts[min(len(ttfts) - 1, int(0.95 * len(ttfts)))]
    served_tok_s = done_tokens / wall
    log(f"{slots} concurrent: p50 TTFT {p50:.1f} ms, p95 {p95:.1f} ms, "
        f"served {done_tokens} tokens in {wall:.2f}s ({served_tok_s:,.0f} tok/s)")

    # -- mixed-load phase: Poisson arrivals of long prompts while the
    # batch decodes. TTFT cannot see prefill/decode interference — a
    # whole-bucket admission stalls the OTHER streams' tokens — so this
    # phase measures what chunked prefill actually bounds: the
    # inter-token gap (TBT, client-side, per delta) and the scheduler's
    # max decode-tick gap attributable to admission (decode_stall_ms).
    # Runs twice over the same warmed scheduler — chunked first, then
    # single-shot (prefill_chunk=0) — with the max gauge reset at each
    # phase start (reset_decode_stall), so each half reports ITS OWN max
    # gap rather than a lifetime max polluted by earlier phases.
    mixed_stats: dict = {}
    if mixed and arr_n > 0:
        import random

        def _pct(xs, p):
            if not xs:
                return None
            xs = sorted(xs)
            return xs[min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))]

        # Leave a few rows free so arrivals admit INTO live decode
        # traffic instead of queueing behind a full batch.
        decode_rows = max(1, slots - max(2, min(4, slots // 8)))
        arr_history = ("Earlier in this thread we discussed the quarterly "
                       "plans and the picnic schedule. ")
        arr_chars = max(8, arr_ctx - 1)   # byte tokenizer: +BOS ~= arr_ctx

        def mixed_phase(label: str) -> dict:
            sched.reset_decode_stall()
            chunks0 = sched.metrics_snapshot()["prefill_chunks_total"]
            gap_mu = threading.Lock()
            gaps: list[float] = []

            def run_decode(seed: int) -> None:
                o = GenerateOptions(max_tokens=mixed_new,
                                    temperature=bench_temp, top_p=0.9,
                                    seed=seed)
                last = None
                mine: list[float] = []
                for _ in sched.submit(
                        GenerateRequest(prompt=prompt, options=o),
                        RequestStats()):
                    t_now = time.monotonic()
                    if last is not None:
                        mine.append((t_now - last) * 1e3)
                    last = t_now
                with gap_mu:
                    gaps.extend(mine)

            def run_arrival(i: int) -> None:
                # Unique head per arrival: identical heads would trip
                # prefix auto-promotion mid-phase (a build + new splice
                # programs — compiles that would pollute the stall).
                ap = (f"mixed {label} req {i:04d}: "
                      + arr_history * (arr_chars // len(arr_history) + 1)
                      )[:arr_chars]
                for _ in sched.submit(
                        GenerateRequest(prompt=ap, options=opts),
                        RequestStats()):
                    pass

            dts = [threading.Thread(target=run_decode, args=(i,))
                   for i in range(decode_rows)]
            for th in dts:
                th.start()
            time.sleep(0.3)     # let the decode rows admit and stream
            rng = random.Random(0)
            ats = []
            for i in range(arr_n):
                time.sleep(rng.expovariate(arr_rate))
                th = threading.Thread(target=run_arrival, args=(i,))
                th.start()
                ats.append(th)
            for th in ats + dts:
                th.join()
            snap = sched.metrics_snapshot()
            out = {
                "tbt_p50_ms": round(_pct(gaps, 50) or 0.0, 2),
                "tbt_p95_ms": round(_pct(gaps, 95) or 0.0, 2),
                "tbt_max_ms": round(max(gaps), 2) if gaps else None,
                "decode_stall_ms": snap["decode_stall_ms"],
                "prefill_chunks": snap["prefill_chunks_total"] - chunks0,
            }
            log(f"mixed load ({label}): TBT p50 {out['tbt_p50_ms']} ms, "
                f"p95 {out['tbt_p95_ms']} ms, max decode-tick gap "
                f"{out['decode_stall_ms']} ms, "
                f"{out['prefill_chunks']} chunk dispatches")
            return out

        mixed_stats = {"arrival_bucket": arr_bucket, "arrivals": arr_n,
                       "arrival_rate_hz": arr_rate,
                       "decode_rows": decode_rows,
                       "prefill_chunk": sched.prefill_chunk or None}
        if sched.prefill_chunk:
            mixed_stats["chunked"] = mixed_phase("chunked")
        chunk_saved, sched.prefill_chunk = sched.prefill_chunk, 0
        mixed_stats["single_shot"] = mixed_phase("single-shot")
        sched.prefill_chunk = chunk_saved

    # -- freeform draft-model spec phase (BENCH_SPEC_WORKLOAD=freeform):
    # served tok/s + per-source acceptance on NON-quote output — the
    # workload where n-gram drafting measures ~0 — with the resident
    # drafter on vs speculation off, over the same warmed scheduler.
    # Greedy requests: acceptance there is argmax-match, the honest
    # draft-quality number (sampled acceptance rides the same math but
    # adds sampling noise to the tok/s comparison).
    spec_freeform: dict = {}
    if spec_new:
        def _src(snap: dict, key: str, src: str) -> float:
            return snap.get(f'{key}{{source="{src}"}}', 0)

        def spec_phase(label: str, stats_keys: bool) -> dict:
            snap0 = sched.metrics_snapshot()
            gopts = GenerateOptions(max_tokens=spec_new, temperature=0.0,
                                    seed=0)
            stats = [RequestStats() for _ in range(slots)]

            def run_g(s: RequestStats) -> None:
                for _ in sched.submit(
                        GenerateRequest(prompt=prompt, options=gopts), s):
                    pass

            ths = [threading.Thread(target=run_g, args=(s,))
                   for s in stats]
            t0p = time.monotonic()
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            wallp = time.monotonic() - t0p
            toks = sum(s.completion_tokens for s in stats)
            out = {"served_tok_s": round(toks / wallp, 1),
                   "tokens": toks, "wall_s": round(wallp, 2)}
            if stats_keys:
                snap1 = sched.metrics_snapshot()
                for src in ("ngram", "model"):
                    p = (_src(snap1, "serve_spec_proposed_total", src)
                         - _src(snap0, "serve_spec_proposed_total", src))
                    a = (_src(snap1, "serve_spec_accepted_total", src)
                         - _src(snap0, "serve_spec_accepted_total", src))
                    out[f"proposed_{src}"] = p
                    out[f"accepted_{src}"] = a
                    out[f"accept_rate_{src}"] = (round(a / p, 3)
                                                 if p else None)
            log(f"freeform spec ({label}): {out['served_tok_s']:,.1f} "
                f"tok/s" + (f", model {out['accepted_model']}/"
                            f"{out['proposed_model']} accepted, ngram "
                            f"{out['accepted_ngram']}/"
                            f"{out['proposed_ngram']}"
                            if stats_keys else ""))
            return out

        on = spec_phase("draft on", stats_keys=True)
        spec_saved, sched.spec_k = sched.spec_k, 0
        off = spec_phase("spec off", stats_keys=False)
        sched.spec_k = spec_saved
        spec_freeform = {
            "draft_config": draft_name, "spec_k": spec_k,
            "new_tokens": spec_new,
            "draft_on": on, "spec_off": off,
            "speedup": (round(on["served_tok_s"] / off["served_tok_s"], 3)
                        if off["served_tok_s"] else None),
        }
        log(f"freeform spec: draft-model speedup "
            f"{spec_freeform['speedup']}x over non-speculative")
    # Overload/robustness gauges for the JSON row: shed counts make
    # overload runs visible in BENCH_*.json (0 on a healthy run — the
    # bench's own load must never shed under the default queue bound),
    # and a nonzero loop_stall_ms flags a scheduler-loop stall past the
    # watchdog budget during the run.
    final_snap = sched.metrics_snapshot()
    requests_shed = final_snap.get("requests_shed_total", 0)
    loop_stall_ms = final_snap.get("loop_stall_ms", 0.0)
    sched.stop()

    # -- park/wake phase (BENCH_PARK, Round-11): multi-tier KV session
    # parking under HBM pressure. Two schedulers over the same params,
    # same seeds, same sequential wake order: (a) "parked" — a pool
    # sized for BENCH_PARK_SLOTS concurrent requests only, idle_s=0 so
    # every session demotes to host RAM (pressure parks the rest) —
    # and (b) "resident" — a pool big enough to keep every session's
    # pages in HBM, idle parking off. Open-session capacity, wake
    # p50/p95, pages freed, and byte-equality of every resumed greedy
    # stream between the two runs land in the JSON ``park_wake`` row.
    park_wake: dict = {}
    if env_bool("BENCH_PARK", kv_mode == "paged") and kv_mode == "paged":
        park_sessions = env_int("BENCH_PARK_SESSIONS", 32)
        park_slots = max(2, env_int("BENCH_PARK_SLOTS", 4))
        park_rate = max(0.1, env_float("BENCH_PARK_RATE", 16.0))
        park_new = max(4, env_int("BENCH_PARK_NEW", 12))
        park_host_gb = env_float("BENCH_PARK_HOST_GB", 1.0)
        import random as _random

        base = ("Earlier in this thread we discussed the quarterly "
                "plans and the picnic schedule at length. ")
        t1_prompts = [(f"session {i:04d}: " + base * 2)[:96]
                      for i in range(park_sessions)]
        turn2_text = " And one more thing before we wrap up?"
        per_admit = (-(-(len(t1_prompts[0]) + 2 + park_new + 2)
                       // page_size) + 1)
        park_pages = park_slots * per_admit + 1

        def park_run(label: str, num_pages: int, idle_s: float,
                     host_gb: float) -> tuple[dict, list, float]:
            s2 = BatchScheduler(params, config, tokenizer,
                                num_slots=park_slots, max_seq=max_seq,
                                kv_mode=kv_mode, page_size=page_size,
                                num_pages=num_pages, spec_k=0,
                                prefix_cache=False, kv_quant=kv_quant,
                                decode_fuse_max=fuse_k,
                                prefill_chunk=bench_chunk,
                                # The whole session fleet submits at
                                # once by design — the phase measures
                                # capacity, not shedding.
                                queue_max=0, queue_timeout_s=600.0,
                                kv_host_gb=host_gb, kv_idle_s=idle_s)
            outs: list = [None] * park_sessions
            t0p = time.monotonic()
            try:
                s2.warmup(prompt_buckets=(64, 128), windows=(128, 256))
                opts_p = GenerateOptions(max_tokens=park_new,
                                         temperature=0.0, seed=7)
                ctxs: list = [None] * park_sessions

                def turn1(i: int) -> None:
                    st = RequestStats()
                    for _ in s2.submit(GenerateRequest(
                            prompt=t1_prompts[i], session=f"park-{i}",
                            options=opts_p), st):
                        pass
                    ctxs[i] = st.context

                ths = [threading.Thread(target=turn1, args=(i,))
                       for i in range(park_sessions)]
                for th in ths:
                    th.start()
                for th in ths:
                    th.join()
                # Let the idle sweep park what pressure didn't.
                time.sleep(1.0 if idle_s == 0 else 0.1)
                snap_open = s2.metrics_snapshot()
                # Sequential Poisson wakes (same rng both runs — the
                # byte-equality comparison needs identical order and
                # solo-wake windows).
                rng = _random.Random(3)
                order = list(range(park_sessions))
                rng.shuffle(order)
                for i in order:
                    time.sleep(rng.expovariate(park_rate))
                    st = RequestStats()
                    text = "".join(s2.submit(GenerateRequest(
                        prompt=turn2_text, session=f"park-{i}",
                        context=tuple(ctxs[i]), options=opts_p), st))
                    outs[i] = text
                snap = s2.metrics_snapshot()
                snap["open_after_turn1"] = snap_open.get(
                    "kv_open_sessions", 0)
                return snap, outs, time.monotonic() - t0p
            finally:
                s2.stop()

        try:
            p_snap, p_outs, p_wall = park_run(
                "parked", park_pages, idle_s=0.0, host_gb=park_host_gb)
            resident_pages = (park_sessions + park_slots) * per_admit + 1
            r_snap, r_outs, r_wall = park_run(
                "resident", resident_pages, idle_s=1e9,
                host_gb=park_host_gb)
            # Sessions one HBM-only pool could keep open: the parked
            # run's page pool over the measured per-session residency.
            sess_pages = max(1, -(-(len(t1_prompts[0]) + 1 + park_new)
                                  // page_size))
            hbm_capacity = max(1, (park_pages - 1) // sess_pages)
            open_sessions = int(p_snap.get("open_after_turn1", 0))
            mismatches = sum(1 for a, b in zip(p_outs, r_outs)
                             if a != b or a is None)
            park_wake = {
                "sessions": park_sessions,
                "slots": park_slots,
                "pool_pages": park_pages,
                "open_sessions": open_sessions,
                "hbm_only_capacity": hbm_capacity,
                "open_ratio": round(open_sessions / hbm_capacity, 2),
                "parked_total": p_snap.get("kv_parked_total", 0),
                "waked_total": p_snap.get("kv_waked_total", 0),
                "pages_freed": p_snap.get("kv_pages_freed_total", 0),
                "wake_p50_ms": p_snap.get("kv_wake_p50_ms"),
                "wake_p95_ms": p_snap.get("kv_wake_p95_ms"),
                "resident_wake_p50_ms": r_snap.get("kv_wake_p50_ms"),
                "resumed_byte_identical": mismatches == 0,
                "mismatches": mismatches,
                "wall_s": round(p_wall + r_wall, 2),
            }
            log(f"park/wake: {open_sessions} open sessions on a "
                f"{park_pages}-page pool (HBM-only capacity "
                f"{hbm_capacity} -> {park_wake['open_ratio']}x), wake "
                f"p50 {park_wake['wake_p50_ms']} ms / p95 "
                f"{park_wake['wake_p95_ms']} ms (resident p50 "
                f"{park_wake['resident_wake_p50_ms']} ms), resumed "
                f"byte-identical: {mismatches == 0}")
        except Exception as e:      # noqa: BLE001 — record, don't abort
            log(f"park/wake phase FAILED: {e}")
            park_wake = {"sessions": park_sessions, "error": str(e)}

    # -- tree-speculation A/B phase (BENCH_SPEC_TREE, Round-17): linear
    # chain vs tree at the SAME verify budget (N node positions), both
    # legs over dedicated warmed scheduler+drafter pairs after the main
    # scheduler stops. The freeform pair's drafter predicts the target
    # ~perfectly (shared successor map) — a regime where a LONGER linear
    # chain trivially wins — so this phase builds an IMPERFECT drafter:
    # on every 3rd token of the cycle its lm_head carries a decoy column
    # (top-1 = the skip-one token, truth demoted to runner-up at a small
    # gap). Linear speculation stops dead at each decoy; the tree's
    # sibling leaf carries the runner-up and converts the miss into a
    # second accepted token — accepted tokens per verify dispatch at
    # equal budget is the row's headline.
    spec_tree: dict = {}
    tree_nodes = env_int("BENCH_SPEC_TREE",
                         8 if spec_workload == "freeform" else 0)
    if tree_nodes >= 4:
        from p2p_llm_chat_tpu.models.synth import (quote_params as _tree_qp,
                                                   successor_map)
        from p2p_llm_chat_tpu.serve.draft_model import ModelDrafter \
            as _TreeDrafter

        tree_slots = max(2, min(slots, 4))
        tree_new = max(64, env_int("BENCH_SPEC_TREE_NEW", 96))
        dcfg_t = get_config(draft_name or "draft-400m")
        if dcfg_t.vocab_size != config.vocab_size:
            dcfg_t = dcfg_t.with_(vocab_size=config.vocab_size)
        try:
            # Imperfect drafter: freeform head + decoy columns. The
            # decoy logit is 5|emb|^2 vs the true successor's 4|emb|^2,
            # so the top-1/top-2 gap at a decoy is ~H while a confident
            # position's is ~4H — gap threshold 2H separates them.
            dp_t = dict(_tree_qp(dcfg_t, jax.random.PRNGKey(1),
                                 dtype=dtype, mode="freeform"))
            emb_t = np.asarray(dp_t["embed"], np.float32)
            # np.array (copy): asarray of a jax array is read-only.
            lm_t = np.array(dp_t["lm_head"], np.float32)
            succ_t = successor_map(dcfg_t.vocab_size, mode="freeform")
            for t in range(32, 127, 3):
                lm_t[:, succ_t[succ_t[t]]] += 5.0 * emb_t[t]
            dp_t["lm_head"] = jnp.asarray(lm_t, dtype)
            gap_thr = 2.0 * dcfg_t.hidden_size

            def tree_leg(label: str, k: int, nodes: int) -> dict:
                s3 = BatchScheduler(
                    params, config, tokenizer, num_slots=tree_slots,
                    max_seq=max_seq, kv_mode=kv_mode,
                    page_size=page_size, spec_k=k, prefix_cache=False,
                    kv_quant=kv_quant, decode_fuse_max=fuse_k,
                    prefill_chunk=bench_chunk,
                    drafter=_TreeDrafter(dp_t, dcfg_t,
                                         num_slots=tree_slots,
                                         max_seq=max_seq, k=k),
                    spec_tree_nodes=nodes, spec_tree_gap=gap_thr)
                try:
                    s3.warmup(prompt_buckets=(128,), windows=(256,))
                    g3 = GenerateOptions(max_tokens=tree_new,
                                         temperature=0.0, seed=0)
                    stats3 = [RequestStats() for _ in range(tree_slots)]

                    def run3(st: RequestStats) -> None:
                        for _ in s3.submit(GenerateRequest(
                                prompt=prompt, options=g3), st):
                            pass

                    ths3 = [threading.Thread(target=run3, args=(st,))
                            for st in stats3]
                    t03 = time.monotonic()
                    for th in ths3:
                        th.start()
                    for th in ths3:
                        th.join()
                    wall3 = time.monotonic() - t03
                    snap3 = s3.metrics_snapshot()
                    toks3 = sum(st.completion_tokens for st in stats3)
                    out = {
                        "spec_k": k, "nodes": nodes if nodes else None,
                        "served_tok_s": round(toks3 / wall3, 1),
                        "tokens": toks3, "wall_s": round(wall3, 2),
                        "accepted_per_dispatch": snap3.get(
                            'serve_spec_accepted_per_dispatch'
                            '{source="model"}', 0.0),
                        "tree_nodes_total": snap3.get(
                            "serve_spec_tree_nodes_total"),
                        "tree_accepted_path_len": snap3.get(
                            "serve_spec_tree_accepted_path_len"),
                    }
                    log(f"spec tree ({label}): "
                        f"{out['accepted_per_dispatch']} accepted/"
                        f"dispatch, {out['served_tok_s']:,.1f} tok/s")
                    return out
                finally:
                    s3.stop()

            lin_leg = tree_leg(f"linear K={tree_nodes - 1}",
                               tree_nodes - 1, 0)
            tr_leg = tree_leg(f"tree K={tree_nodes // 2} N={tree_nodes}",
                              tree_nodes // 2, tree_nodes)
            spec_tree = {
                "nodes": tree_nodes, "new_tokens": tree_new,
                "draft_config": dcfg_t.name,
                "linear": lin_leg, "tree": tr_leg,
                "apd_ratio": (round(tr_leg["accepted_per_dispatch"]
                                    / lin_leg["accepted_per_dispatch"], 3)
                              if lin_leg["accepted_per_dispatch"]
                              else None),
                "served_ratio": (round(tr_leg["served_tok_s"]
                                       / lin_leg["served_tok_s"], 3)
                                 if lin_leg["served_tok_s"] else None),
            }
            log(f"spec tree: {spec_tree['apd_ratio']}x accepted/dispatch "
                f"at equal verify budget ({tree_nodes} nodes), "
                f"{spec_tree['served_ratio']}x served tok/s")
        except Exception as e:      # noqa: BLE001 — record, don't abort
            log(f"spec tree phase FAILED: {e}")
            spec_tree = {"nodes": tree_nodes, "error": str(e)}

    # -- replica-router phase (BENCH_REPLICAS >= 2, Round-10): N full-
    # stack engines SHARING this bench's params (immutable device
    # arrays — no extra weight copies) behind serve/router.py, driven
    # over real HTTP. Measures aggregate served tok/s through the
    # router vs the SAME workload through one replica, at fixed
    # per-replica capacity (slots split across the fleet), plus the
    # router's routed/retried/shed counters. Runs after the main
    # scheduler stops so KV pools never coexist.
    replica_router: dict = {}
    n_replicas = env_int("BENCH_REPLICAS", 0)
    if n_replicas >= 2:
        import json as _json
        import urllib.request as _urlreq

        from p2p_llm_chat_tpu.serve.api import OllamaServer
        from p2p_llm_chat_tpu.serve.engine import TPUEngine
        from p2p_llm_chat_tpu.serve.router import (ReplicaRouter,
                                                   parse_metrics_text)

        rep_slots = max(2, env_int("BENCH_REPLICA_SLOTS",
                                   max(2, slots // n_replicas)))
        rep_pages = None
        if kv_mode == "paged":
            per_req = -(-(len(prompt) + 1 + new_tokens + spec_k + 2)
                        // page_size) + 1
            # Same cap as the main phase's pool sizing: a BENCH_CTX
            # prompt longer than the row budget gets tail-truncated at
            # admission, so pages past eff_max can never be written —
            # N replica pools of them would just burn HBM.
            eff_rep = min(max_seq, config.max_seq_len)
            per_req = min(per_req, -(-eff_rep // page_size))
            rep_pages = rep_slots * per_req + 1
        engines = [TPUEngine(params, config, tokenizer,
                             num_slots=rep_slots, max_seq=max_seq,
                             kv_mode=kv_mode, page_size=page_size,
                             num_pages=rep_pages, spec_k=spec_k,
                             prefix_cache=use_prefix,
                             prefix_texts=(prompt,) if use_prefix else (),
                             kv_quant=kv_quant, decode_fuse_max=fuse_k,
                             prefill_chunk=bench_chunk,
                             name=cfg_name)
                   for _ in range(n_replicas)]
        fronts = [OllamaServer(e, addr="127.0.0.1:0").start()
                  for e in engines]
        router = ReplicaRouter([f.url for f in fronts],
                               addr="127.0.0.1:0", scrape_ms=200).start()
        for e in engines:
            e.warmup(buckets=(pbucket,), background=False)

        m_reqs = n_replicas * rep_slots     # one fleet-wide wave
        body = _json.dumps({
            "model": cfg_name, "prompt": prompt, "stream": False,
            "options": {"num_predict": new_tokens,
                        "temperature": bench_temp, "top_p": 0.9,
                        "seed": 0}}).encode()

        def drive(base: str) -> tuple[float, int]:
            errs: list = []
            toks = [0] * m_reqs

            def worker(i: int) -> None:
                try:
                    rq = _urlreq.Request(
                        f"{base}/api/generate", data=body,
                        headers={"Content-Type": "application/json"})
                    with _urlreq.urlopen(rq, timeout=600) as r:
                        toks[i] = _json.loads(r.read()).get("eval_count", 0)
                except Exception as e:      # noqa: BLE001
                    errs.append(e)

            ths = [threading.Thread(target=worker, args=(i,))
                   for i in range(m_reqs)]
            t0w = time.monotonic()
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            wallw = time.monotonic() - t0w
            if errs:
                raise RuntimeError(f"replica phase failed: {errs[:3]}")
            return wallw, sum(toks)

        # Warm-through: one unmeasured wave per replica direct (real
        # host-path warm, both replicas' lazily-compiled windows), then
        # measure single-replica vs routed fleet on the same workload.
        # try/finally: a single failed wave must record an error row and
        # release the router/fronts/engines — NOT abort the bench and
        # lose every already-measured phase in the JSON output.
        try:
            for f in fronts:
                drive(f.url)
            wall_single, toks_single = drive(fronts[0].url)
            wall_fleet, toks_fleet = drive(router.url)
            with _urlreq.urlopen(f"{router.url}/metrics", timeout=10) as r:
                rsnap = parse_metrics_text(r.read().decode())
            routed = [rsnap.get(f'router_routed_total{{replica="{i}"}}', 0)
                      for i in range(n_replicas)]
            replica_router = {
                "replicas": n_replicas,
                "slots_per_replica": rep_slots,
                "requests": m_reqs,
                "single": {"served_tok_s": round(toks_single / wall_single,
                                                 1),
                           "tokens": toks_single,
                           "wall_s": round(wall_single, 2)},
                "fleet": {"served_tok_s": round(toks_fleet / wall_fleet, 1),
                          "tokens": toks_fleet,
                          "wall_s": round(wall_fleet, 2)},
                "speedup": round(wall_single / wall_fleet, 3),
                "routed": routed,
                "retried": rsnap.get("router_retries_total", 0),
                "shed": rsnap.get("router_requests_shed_total", 0),
            }
            log(f"replica router: {n_replicas}x{rep_slots} slots, fleet "
                f"{replica_router['fleet']['served_tok_s']:,.1f} tok/s vs "
                f"single {replica_router['single']['served_tok_s']:,.1f} "
                f"({replica_router['speedup']}x), routed {routed}, "
                f"retried {replica_router['retried']}, "
                f"shed {replica_router['shed']}")
        except Exception as e:      # noqa: BLE001 — record, don't abort
            log(f"replica router phase FAILED: {e}")
            replica_router = {"replicas": n_replicas,
                              "slots_per_replica": rep_slots,
                              "error": str(e)}
        finally:
            router.stop()
            for f in fronts:
                f.stop()
            for eng in engines:
                eng.stop()

    # -- MoE-scale ablations (BENCH_MOE_SCALE, round 18): the expert
    # decode trunk measured leg by leg at a real-MoE config, AFTER the
    # serving phases so its params/pool never share HBM with the main
    # scheduler's. Four legs isolate the round's three mechanisms:
    # paged+fused+auto (the served configuration), split gate/up (the
    # wgu_e fusion win is pure dispatch count — tests pin the outputs
    # bitwise-identical), forced-XLA dequant (the stacked expert-stripe
    # kernel's margin), and the dense cache (the paged-walk gap the
    # hd-aware flash policy exists to close). Each leg is labeled by
    # the matmul impl it can actually dispatch — on a CPU host the
    # kernel gate answers no, so auto and forced-XLA honestly time the
    # same program and the ratio reads 1.0 by construction.
    moe_scale: dict = {}
    if env_bool("BENCH_MOE_SCALE", False):
        from p2p_llm_chat_tpu.models.quant import set_mm_impl
        from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache as _MPKV
        moe_cfg_name = env_or("BENCH_MOE_CONFIG", "bench-moe")
        moe_slots = env_int("BENCH_MOE_SLOTS", 8)
        moe_window = env_int("BENCH_MOE_WINDOW", 512)
        moe_steps = max(4, env_int("BENCH_MOE_STEPS", 8))
        moe_quant = quant or "int8"
        try:
            moe_cfg = get_config(moe_cfg_name)
            if not moe_cfg.is_moe:
                raise ValueError(
                    f"BENCH_MOE_CONFIG={moe_cfg_name!r} has no experts")
            moe_fam = family_for(moe_cfg)
            moe_params = moe_fam.init_params_quantized(
                moe_cfg, jax.random.PRNGKey(7), dtype=dtype,
                quant=moe_quant)
            jax.block_until_ready(moe_params)
            # The split-gu tree: slice the fused [NE,H,2F] pool back
            # into gate/up halves (column-concat commutes with the
            # per-output-channel scales, so the math is identical —
            # only the per-layer einsum count doubles).
            wgu = moe_params["layers"]["wgu_e"]
            E_moe = wgu.q.shape[-1] // 2
            split_layers = dict(moe_params["layers"])
            del split_layers["wgu_e"]
            split_layers["w_gate"] = type(wgu)(q=wgu.q[..., :E_moe],
                                               s=wgu.s[..., :E_moe])
            split_layers["w_up"] = type(wgu)(q=wgu.q[..., E_moe:],
                                             s=wgu.s[..., E_moe:])
            split_params = dict(moe_params, layers=split_layers)

            pages_m = -(-moe_window // page_size)
            toks_m = jnp.ones((moe_slots, 1), jnp.int32)
            # Parked rows: lengths hold, every step reads the same full
            # window — the long-window sweep's steady-state convention.
            parked_m = jnp.zeros((moe_slots,), bool)
            mn1 = max(2, moe_steps // 4)
            mn2 = max(moe_steps, 2 * mn1)

            def moe_leg(leg_params, paged_leg: bool,
                        force_xla: bool) -> dict:
                set_mm_impl("xla" if force_xla else "auto")
                if paged_leg:
                    pool_m = _MPKV.create(
                        moe_cfg, moe_slots, moe_slots * pages_m + 1,
                        page_size, max_pages_per_row=pages_m,
                        dtype=dtype, quantized=kv_quant)
                    table_m = (1 + jnp.arange(moe_slots * pages_m,
                                              dtype=jnp.int32)
                               ).reshape(moe_slots, pages_m)
                    cache_m = pool_m._replace(
                        page_table=table_m,
                        lengths=jnp.full((moe_slots,), moe_window - 2,
                                         jnp.int32))

                    def _mstep(p, t, c, a):
                        return moe_fam.decode_step_paged(
                            p, moe_cfg, t, c, active=a, pages=pages_m)
                else:
                    cache_m = KVCache.create(moe_cfg, moe_slots,
                                             moe_window, dtype)
                    cache_m = cache_m._replace(
                        lengths=jnp.full((moe_slots,), moe_window - 2,
                                         jnp.int32))

                    def _mstep(p, t, c, a):
                        return moe_fam.decode_step(p, moe_cfg, t, c,
                                                   active=a)

                # graftcheck: retrace-ok one fresh program per leg by design — set_mm_impl and the leg's param tree both change what the trace dispatches
                mj = jax.jit(_mstep, donate_argnums=(2,))

                def m_loop(n: int) -> float:
                    nonlocal cache_m
                    lg, cache_m = mj(leg_params, toks_m, cache_m,
                                     parked_m)
                    np.asarray(lg[:1, 0, :1])
                    t0m = time.monotonic()
                    for _ in range(n):
                        lg, cache_m = mj(leg_params, toks_m, cache_m,
                                         parked_m)
                    np.asarray(lg[:1, 0, :1])
                    return (time.monotonic() - t0m) / n

                w1, w2 = m_loop(mn1), m_loop(mn2)
                d = (mn2 * w2 - mn1 * w1) / (mn2 - mn1)
                ms = (d if d > 0.05 * w2 else w2) * 1e3
                return {
                    "step_ms": round(ms, 3),
                    "tok_s": round(moe_slots / (ms / 1e3), 1),
                    "mm_impl": "xla" if force_xla else "auto-kernel",
                }

            legs = {}
            try:
                legs["paged_fused"] = moe_leg(moe_params, True, False)
                legs["paged_split_gu"] = moe_leg(split_params, True,
                                                 False)
                legs["paged_fused_xla"] = moe_leg(moe_params, True, True)
                legs["dense_fused"] = moe_leg(moe_params, False, False)
            finally:
                set_mm_impl("auto")
            base_ms = legs["paged_fused"]["step_ms"]
            moe_scale = {
                "config": moe_cfg_name,
                "quant": moe_quant,
                "slots": moe_slots,
                "window": moe_window,
                "weight_stream_gb": round(
                    param_bytes(moe_params) / 1e9, 3),
                "legs": legs,
                # >1 = splitting gate/up costs; the fusion keeps it at
                # the fused dispatch count for identical math.
                "split_gu_over_fused": round(
                    legs["paged_split_gu"]["step_ms"] / base_ms, 3),
                # >1 = the stacked kernel beats forced dequant at this
                # shape (1.0 by construction off-TPU, see labels).
                "xla_over_auto": round(
                    legs["paged_fused_xla"]["step_ms"] / base_ms, 3),
                # The dense-vs-paged gap at MoE dims — the number the
                # hd-aware flash-append policy is judged on.
                "paged_over_dense": round(
                    base_ms / legs["dense_fused"]["step_ms"], 3),
            }
            log(f"moe scale ({moe_cfg_name}, {moe_quant}, W={moe_window},"
                f" B={moe_slots}): " + ", ".join(
                    f"{k} {v['step_ms']:.2f} ms [{v['mm_impl']}]"
                    for k, v in legs.items())
                + f"; split/fused {moe_scale['split_gu_over_fused']}x,"
                f" xla/auto {moe_scale['xla_over_auto']}x,"
                f" paged/dense {moe_scale['paged_over_dense']}x")
            del moe_params, split_params
        except Exception as e:      # noqa: BLE001 — record, don't abort
            log(f"moe scale phase FAILED: {e}")
            moe_scale = {"config": moe_cfg_name, "error": str(e)}

    result = {
        "metric": f"p50_ttft_ms_{slots}_concurrent_{cfg_name}",
        "value": round(p50, 2),
        "unit": "ms",
        # Reference publishes no numbers; baseline = the 150 ms north-star
        # TTFT target (BASELINE.json). > 1.0 means the target is beaten.
        "vs_baseline": round(150.0 / p50, 3) if p50 > 0 else None,
        "extra": {
            "platform": platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "kv_mode": kv_mode,
            "kv_quant": ("int8" if kv_quant else None),
            "quant": quant or None,
            # Per-weight-shape quantized-matmul dispatch decisions (and,
            # on TPU, kernel-vs-forced-XLA timings) — the autotune-table
            # acceptance row (ops/quant_mm._TILE_TABLE).
            "qmm_dispatch": qmm_dispatch or None,
            "weight_stream_gb": round(weight_stream_bytes / 1e9, 3),
            "spec_k": spec_k or None,
            "bench_temp": bench_temp,
            "prefix_cache": use_prefix or None,
            **spec_stats,
            "page_size": page_size if kv_mode == "paged" else None,
            "config": cfg_name,
            "prompt_tokens": plen,
            "n_params_b": round(n_params / 1e9, 3),
            "slots": slots,
            "max_seq": max_seq,
            "raw_decode_tok_s_per_chip": round(raw_tok_s, 1),
            "decode_step_ms": round(step_ms, 3),
            "decode_wall_step_ms": round(wall_step_ms, 3),
            # Fused multi-step decode (BENCH_FUSE): per-token device and
            # wall step of the K-step scan program, and the wall/device
            # ratio the fusion is meant to close (target <= 1.15 at
            # B=32).
            "decode_fused_k": fuse_k if fuse_k > 1 else None,
            "decode_fused_step_ms": (round(fused_step_ms, 3)
                                     if fused_step_ms else None),
            "decode_fused_wall_step_ms": (round(fused_wall_step_ms, 3)
                                          if fused_wall_step_ms else None),
            "wall_over_device": round(
                (fused_wall_step_ms or wall_step_ms) / step_ms, 3),
            # Chunked prefill (BENCH_PREFILL_CHUNK) + the mixed-load
            # interference numbers: TBT p50/p95 and the max decode-tick
            # gap, chunked vs single-shot admission over the same warmed
            # scheduler (the gap must be bounded by one chunk's compute,
            # not the whole prompt's prefill).
            "prefill_chunk": sched.prefill_chunk or None,
            "mixed_load": mixed_stats or None,
            # Draft-model speculative decoding (BENCH_DRAFT /
            # BENCH_SPEC_WORKLOAD=freeform): served tok/s with the
            # resident drafter vs non-speculative on free-form (non-
            # quote) output, plus per-source proposed/accepted — the
            # row the round-9 acceptance bar reads.
            "draft_config": (draft_name or None) if spec_k else None,
            "spec_workload": spec_workload or None,
            "spec_freeform": spec_freeform or None,
            # Overload shedding + loop watchdog (ISSUE 5): shed requests
            # (503 fast-fail at the queue bound) and the max over-budget
            # scheduler-loop iteration. Both 0 on a healthy run.
            "requests_shed": requests_shed,
            "loop_stall_ms": loop_stall_ms or None,
            # Replica-router phase (BENCH_REPLICAS): aggregate served
            # tok/s through serve/router.py over N engines vs one
            # replica on the same workload, with the router's
            # routed/retried/shed counters — the Round-10 scaling row.
            "replica_router": replica_router or None,
            # Park/wake phase (BENCH_PARK, Round-11): open sessions on
            # a pressure-sized pool vs the HBM-only capacity bound,
            # wake latency percentiles, and resumed-output byte-
            # equality between the parked and resident runs — the
            # multi-tier KV acceptance row.
            "park_wake": park_wake or None,
            # Tree-speculation A/B (BENCH_SPEC_TREE): linear chain vs
            # tree at the SAME verify node budget, with an imperfect
            # drafter — accepted tokens per verify dispatch and served
            # tok/s for each leg, plus tree/linear ratios. The Round-17
            # acceptance numbers live here.
            "spec_tree": spec_tree or None,
            # MoE-scale ablations (BENCH_MOE_SCALE): decode step at a
            # real-MoE config across fused/split, auto/forced-XLA and
            # paged/dense legs — the round-18 expert-trunk acceptance
            # row (each leg labeled by its effective matmul impl).
            "moe_scale": moe_scale or None,
            # Long-window sweep (BENCH_LONG_W): per (window, impl) step
            # time vs the HBM bytes bound; flash rows carry their
            # speedup over the gather path — the round-8 acceptance
            # numbers live here.
            "long_w": long_w_rows or None,
            "ttft_single_ms": round(ttft_single_ms, 2),
            "p95_ttft_ms": round(p95, 2),
            "served_tok_s": round(served_tok_s, 1),
            "new_tokens_per_req": new_tokens,
            "bench_wall_s": round(time.monotonic() - t0, 1),
        },
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

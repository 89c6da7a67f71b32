"""TPU parity check: the Pallas attention kernels vs their XLA paths.

Runs the two implementations of
ops/paged_attention.paged_attention_append by name — the multi-chunk
flash-append kernel (``_paged_attention_flash_append``) against the XLA
gather (``_append_gather``) — on the real chip over random pools (bf16
and int8) and checks closeness; then the prefill flash kernel
(models/layers.attend_gqa_causal0). CPU tests can't cover the Mosaic
lowering; this is the hardware check. Shapes have llama3.1-8b's
attention geometry (32 query / 8 kv heads x 128, page size 64, B=32).
Every kernel runs to the end and gets one ``VERDICT <kernel>:
PASS|FAIL`` line; the exit code is non-zero when any FAILs. The
flash-append kernel also runs at ragged lengths with free rows (most of
its grid skipped) and at one short chunk a row (W 256 and 512: ``short``). ``time`` prints, by pool, width and window (128 to
2,048, 32 slots, and 64 at the paired pools' width), gather against
flash-append at a full and at a part-full batch: the measurement behind
the dispatch rule. ``time-hd64`` prints what a
page layer of 8 KV heads x 64 (LFM2's) costs a decode step on each
candidate: the gather path on a per-head ``[8, 64]`` pool, the gather
path on the paired ``[4, 128]`` pool, and flash-append on the paired
pool with zero-extended queries
(ops/paged_attention.paged_attention_append_paired), at 32 rows of 1, 4,
8 and 13 K and at 8 rows of 8 K, each beside the bytes it had to read
over the chip's 819 GB/s. ``time-fold`` takes the kernel apart at a
short context (32 rows x 450 tokens, OLMoE's and Ouro's 16 MHA heads
and llama's GQA, W 512 / 1,024 / 2,048): the whole kernel, then
without the int8 -> bf16 widening of K and V, then without its two MXU
dots as well, then waiting for the pages and folding nothing, each
beside the time its bytes ask (ops/paged_attention._FOLD_WITHOUT).
"""

from __future__ import annotations

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from p2p_llm_chat_tpu.models.configs import get_config  # noqa: E402
from tools.kernel_verdicts import (SlowerThanXLA, require_tpu,  # noqa: E402
                                    run_cases)

from p2p_llm_chat_tpu.ops import paged_attention as pa  # noqa: E402
from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,  # noqa: E402
                                           write_prefill_row)


def _pool_args(cache, lens, layer) -> tuple:
    return (cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.page_table, lens, layer)


def _flash(q, k_cur, v_cur, cache, lens, layer, *, pages):
    return pa._paged_attention_flash_append(
        q, k_cur, v_cur, *_pool_args(cache, lens, layer), pages=pages,
        quantized=cache.k_scale is not None)


def _gather(q, k_cur, v_cur, cache, lens, layer, *, pages):
    return pa._append_gather(q, k_cur, v_cur,
                             *_pool_args(cache, lens, layer), pages=pages)


# (query heads, key-value heads): llama3.1-8b's GQA (rep 4) and OLMoE's
# MHA (rep 1, 16 heads — one-row scratch slices and [1, D] x [D, page]
# dots, sixteen times over).
GQA, MHA16 = (32, 8), (16, 16)
# 4 kv heads x 128 = 512 numbers a token: bench-moe's narrow KV, and the
# row of the paired pools (LFM2, Granite) and of the 4-KV-head models.
NARROW = (32, 4)


def _cfg(heads: tuple, layers=2):
    return get_config("llama3.1-8b").with_(
        num_layers=layers, num_heads=heads[0], num_kv_heads=heads[1])


def run(quantized: bool, B=32, pages=48, ps=64, *, label="flash", seed=1,
        heads=GQA, lengths=None) -> None:
    """Shared harness: random bf16/int8 pool filled through the real
    splice op, the flash-append kernel vs the gather append path at
    first/last layer, at a long (multi-chunk) window: pages=48 is W=3072,
    3 chunks of 1024 int8 tokens / 6 of 512 bf16 — the cross-chunk
    scratch merge, slot parity through row boundaries, and the clamped
    partial chunk all execute on real Mosaic, not just in interpret
    mode."""
    # Two layers are all the check reads (first and last), and what
    # keeps the bf16 pool at W=3072 x B=32 inside a 16 GB chip.
    cfg = _cfg(heads)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    mppr = pages
    num_pages = B * mppr + 1
    cache = PagedKVCache.create(cfg, B, num_pages, ps,
                                max_pages_per_row=mppr, dtype=jnp.bfloat16,
                                quantized=quantized)
    if lengths is None:
        lengths = [int(rng.integers(1, pages * ps - 1)) for _ in range(B)]
    for b, n in enumerate(lengths):
        table = jnp.asarray(1 + b * mppr + np.arange(mppr), jnp.int32)
        # Pool contents come from the device's own generator: 32 rows
        # of host normals cost minutes of chip time.
        rk = jax.random.normal(
            jax.random.fold_in(key, 2 * b),
            (cfg.num_layers, pages * ps, cfg.num_kv_heads, cfg.head_dim),
            jnp.bfloat16)
        rv = jax.random.normal(jax.random.fold_in(key, 2 * b + 1),
                               rk.shape, jnp.bfloat16)
        cache = write_prefill_row(cache, rk, rv, jnp.asarray(b),
                                  jnp.asarray(n), table)
    lens = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, cfg.num_heads, cfg.head_dim)),
                    jnp.bfloat16)
    k_cur = jnp.asarray(rng.normal(size=(B, cfg.num_kv_heads, cfg.head_dim)),
                        jnp.bfloat16)
    v_cur = jnp.asarray(rng.normal(size=k_cur.shape), jnp.bfloat16)

    gather = jax.jit(_gather, static_argnames="pages")
    for layer in (0, cfg.num_layers - 1):
        args = (q, k_cur, v_cur, cache, lens, jnp.asarray(layer))
        kern = _flash(*args, pages=pages)
        ref = gather(*args, pages=pages)
        kn, rn = np.asarray(kern, np.float32), np.asarray(ref, np.float32)
        err = np.max(np.abs(kn - rn))
        denom = np.max(np.abs(rn)) or 1.0
        print(f"{label} quantized={quantized} layer={layer}: max abs err "
              f"{err:.5f} (rel {err/denom:.5f})")
        assert err / denom < 2e-2, f"{label} kernel diverges from gather path"


def run_flash_ragged(quantized: bool, B=32, pages=48, ps=64,
                     heads=GQA) -> None:
    """The flash-append kernel where most of its grid is skipped: rows
    of length 0 (a free row: no chunk fetched, the current token's term
    alone), 1, one either side of a chunk boundary, and the whole
    window, in an order that puts a dead chunk 0 and a live one behind
    a row boundary. Mosaic's lowering of the guarded DMA starts and
    waits is what the CPU tests cannot cover."""
    cfg = _cfg(heads)
    ct = ps * pa.flash_append_chunk_pages(
        cfg.num_kv_heads * cfg.head_dim, 1 if quantized else 2, ps, pages)
    W = pages * ps
    edge = [0, 1, ct - 1, ct, ct + 1, W - 1, 0, 0, W - 1, 0, 2 * ct, 300]
    rng = np.random.default_rng(5)
    lengths = edge + [int(n) for n in rng.integers(0, W - 1, B - len(edge))]
    run(quantized, B, pages, ps, label="flash ragged", seed=5, heads=heads,
        lengths=lengths)


def run_flash_short(quantized: bool, W=256, B=32, ps=64, heads=GQA) -> None:
    """The flash-append kernel at a window the rule hands it since
    PR 56: ONE chunk a row, shorter than the chunk budget, with free
    rows, one position, rows that end on a page's edge and one past it,
    and the window's last slot."""
    edge = [0, 1, ps, W - 1, 0, W // 2, ps + 1, W - ps]
    rng = np.random.default_rng(W)
    lengths = edge + [int(n) for n in rng.integers(0, W - 1, B - len(edge))]
    run(quantized, B, W // ps, ps, label=f"flash short W={W}", seed=W,
        heads=heads, lengths=lengths)


def _close(got, ref, what: str) -> None:
    gn, rn = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    rel = np.max(np.abs(gn - rn)) / (np.max(np.abs(rn)) or 1.0)
    print(f"{what}: rel {rel:.5f}")
    assert rel < 2e-2, f"{what} diverges from the XLA path"


def run_prefill_flash(B=1, S=2048, heads=GQA) -> None:
    """The prefill flash kernel at the shape that reaches it under the
    default chunked admission: a 2048-token prefix build."""
    from p2p_llm_chat_tpu.models.layers import (attend_gqa,
                                                attend_gqa_causal0,
                                                causal_mask)
    cfg = _cfg(heads)
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (B, S, cfg.num_heads, cfg.head_dim),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, S, cfg.num_kv_heads, cfg.head_dim),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), k.shape, jnp.bfloat16)
    _close(jax.jit(attend_gqa_causal0)(q, k, v),
           jax.jit(attend_gqa)(q, k, v, causal_mask(S, S, 0)),
           f"prefill flash B={B} S={S}")


def _layer_step_ms(one, q, k_cur, cache, lens, layers: int, repeat: int,
                   steps: int) -> float:
    """Milliseconds a layer-step of ``one(q, k_cur, v_cur, cache, lens,
    layer)``: one dispatch runs ``repeat`` of them (a lone call measures
    the host), ``steps`` dispatches are timed behind a first that
    compiles."""
    @jax.jit
    def run(q, cache, lens):
        def body(i, acc):
            return acc + one(q, k_cur, k_cur, cache, lens, i % layers)
        return jax.lax.fori_loop(0, repeat, body, jnp.zeros_like(q))
    np.asarray(run(q, cache, lens)).ravel()[:1]
    t = time.monotonic()
    for _ in range(steps):
        out = run(q, cache, lens)
    np.asarray(out).ravel()[:1]
    return (time.monotonic() - t) / steps / repeat * 1e3


# How far the path the rule picks may trail the other before ``time``
# says so: the reading's own scatter from call to call.
SLOWER_MARGIN = 1.05


def time_append(heads, W: int, quantized=True, B=32, ps=64, repeat=16,
                steps=10) -> None:
    """Milliseconds a layer-step of append attention, the XLA gather
    path against the flash-append kernel, at window ``W`` and two
    occupancies: the measurement behind the dispatch rule
    (ops/paged_attention._flash_append_policy). *B live rows*: contexts
    as a full backlog batch holds them, 128 to 900 tokens (half the
    window up at W 128), and one row that needs the window. *2 live rows
    of B*: that row and one other, the rest free (length 0, their
    page-table rows zeroed as ``_release`` leaves them): a steady cell's
    batch. The kernel's work follows the lengths, the gather path's the
    window. One dispatch runs ``repeat`` layer-steps (a lone call
    measures the host)."""
    cfg = _cfg(heads)
    pages = W // ps
    rng = np.random.default_rng(W)
    key = jax.random.PRNGKey(W)
    cache = PagedKVCache.create(cfg, B, B * pages + 1, ps,
                                max_pages_per_row=pages, dtype=jnp.bfloat16,
                                quantized=quantized)
    lengths = [int(n) for n in rng.integers(min(128, W // 2),
                                            min(900, W - 1), size=B)]
    lengths[0] = W - 2
    for b, n in enumerate(lengths):
        table = jnp.asarray(1 + b * pages + np.arange(pages), jnp.int32)
        rk = jax.random.normal(
            jax.random.fold_in(key, 2 * b),
            (cfg.num_layers, W, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
        rv = jax.random.normal(jax.random.fold_in(key, 2 * b + 1), rk.shape,
                               jnp.bfloat16)
        cache = write_prefill_row(cache, rk, rv, jnp.asarray(b),
                                  jnp.asarray(n), table)
    q = jax.random.normal(key, (B, cfg.num_heads, cfg.head_dim),
                          jnp.bfloat16)
    k_cur = jax.random.normal(jax.random.fold_in(key, 99),
                              (B, cfg.num_kv_heads, cfg.head_dim),
                              jnp.bfloat16)

    def timed(one, cache, lens) -> float:
        return _layer_step_ms(functools.partial(one, pages=pages), q, k_cur,
                              cache, lens, cfg.num_layers, repeat, steps)

    hd = cfg.num_kv_heads * cfg.head_dim
    rule = "flash" if pa._flash_append_policy(W) else "gather"
    pool = "int8" if quantized else "bf16"
    lost = []
    for live in (B, 2):
        lens = jnp.asarray(lengths[:live] + [0] * (B - live), jnp.int32)
        state = cache._replace(
            page_table=cache.page_table.at[live:].set(0), lengths=lens)
        gather = timed(_gather, state, lens)
        flash = timed(_flash, state, lens)
        tokens = sum(lengths[:live])
        print(f"append {pool} heads={heads} hd={hd} W={W} live={live}/"
              f"{B} ({tokens} cached tokens): gather {gather:.4f} ms, "
              f"flash {flash:.4f} ms a layer-step "
              f"({gather / flash:.2f}x); the rule says {rule}",
              flush=True)
        ours, other = (flash, gather) if rule == "flash" else (gather, flash)
        if ours > SLOWER_MARGIN * other:
            lost.append(f"{live} live: gather {gather:.4f}, flash "
                        f"{flash:.4f}")
    if lost:
        raise SlowerThanXLA(f"the rule's {rule} is the slower path by over "
                            f"{SLOWER_MARGIN - 1:.0%} at " + "; ".join(lost))


# What ``time-fold`` leaves out of the kernel's fold, cumulatively.
FOLD_PARTS = (("whole", ()), ("no widening", ("convert",)),
              ("no widening, no dots", ("convert", "dots")),
              ("no fold (DMAs alone)", ("fold",)))


def time_fold(heads, W: int, context=450, rows=32, ps=64, repeat=16,
              steps=10) -> None:
    """Milliseconds a layer-step of the flash-append kernel over an int8
    pool at ``rows`` live rows of ``context`` cached tokens, whole and
    with parts of its fold left out (:data:`FOLD_PARTS`, through
    ``pa._FOLD_WITHOUT``), beside the time its bytes ask of 819 GB/s:
    where a live tile's time goes."""
    cfg = _cfg(heads)
    pages = W // ps
    key = jax.random.PRNGKey(W)
    live_pages = -(-context // ps)
    cache = PagedKVCache.create(cfg, rows, rows * live_pages + 1, ps,
                                max_pages_per_row=pages, dtype=jnp.bfloat16,
                                quantized=True)
    rk = jax.random.normal(
        key, (cfg.num_layers, live_pages * ps, cfg.num_kv_heads,
              cfg.head_dim), jnp.bfloat16)
    rv = jax.random.normal(jax.random.fold_in(key, 1), rk.shape,
                           jnp.bfloat16)
    for b in range(rows):
        table = jnp.zeros((pages,), jnp.int32).at[:live_pages].set(
            1 + b * live_pages + jnp.arange(live_pages, dtype=jnp.int32))
        cache = write_prefill_row(cache, rk, rv, jnp.asarray(b),
                                  jnp.asarray(context), table)
    lens = jnp.full((rows,), context, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (rows, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
    kc = jax.random.normal(jax.random.fold_in(key, 3),
                           (rows, cfg.num_kv_heads, cfg.head_dim),
                           jnp.bfloat16)

    hd = cfg.num_kv_heads * cfg.head_dim
    read = rows * context * 2 * (hd + 4 * cfg.num_kv_heads)
    floor = read / 819e9 * 1e3
    for label, without in FOLD_PARTS:
        pa._FOLD_WITHOUT = frozenset(without)
        jax.clear_caches()      # the knob is read where the kernel traces
        try:
            ms = _layer_step_ms(functools.partial(_flash, pages=pages), q,
                                kc, cache, lens, cfg.num_layers, repeat,
                                steps)
        finally:
            pa._FOLD_WITHOUT = frozenset()
        print(f"fold heads={heads} hd={hd} W={W} {rows} rows x {context}: "
              f"{label}: {ms:.4f} ms a layer-step "
              f"({1e3 * ms / rows:.2f} us a row); {read / 1e6:.1f} MB = "
              f"{floor:.4f} ms at 819 GB/s ({100 * floor / ms:.1f}%)",
              flush=True)
    jax.clear_caches()


def time_hd64(rows: int, context: int, live: int, ps=64, repeat=8,
              steps=5) -> None:
    """Milliseconds a layer-step of decode attention at a head of 64 (32
    query / 8 KV heads), ``live`` of ``rows`` rows at ``context`` cached
    tokens each (the others free), int8 pools, the window the power of
    two over the context: the three candidates of PERF.md section 6,
    PR 45."""
    W = 1 << (context + 1).bit_length()
    if W // 2 > context + 1:
        W //= 2
    pages = W // ps
    key = jax.random.PRNGKey(context)
    Hq, Hkv, D = 32, 8, 64
    base = get_config("llama3.1-8b").with_(num_layers=2, num_heads=Hq)
    geoms = {"per-head": base.with_(num_kv_heads=Hkv, head_dim=D),
             "paired": base.with_(num_kv_heads=Hkv // 2, head_dim=2 * D)}
    lens = jnp.asarray([context] * live + [0] * (rows - live), jnp.int32)
    k = jax.random.normal(key, (2, W, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 1), k.shape, jnp.bfloat16)
    pools = {}
    for name, cfg in geoms.items():
        cache = PagedKVCache.create(cfg, rows, live * pages + 1, ps,
                                    max_pages_per_row=pages,
                                    dtype=jnp.bfloat16, quantized=True)
        shape = (2, W, cfg.num_kv_heads, cfg.head_dim)
        for b in range(live):
            table = jnp.asarray(1 + b * pages + np.arange(pages), jnp.int32)
            cache = write_prefill_row(cache, k.reshape(shape),
                                      v.reshape(shape), jnp.asarray(b),
                                      jnp.asarray(context), table)
        pools[name] = cache._replace(lengths=lens)
    q = jax.random.normal(jax.random.fold_in(key, 2), (rows, Hq, D),
                          jnp.bfloat16)
    kc = jax.random.normal(jax.random.fold_in(key, 3), (rows, Hkv, D),
                           jnp.bfloat16)
    rep = Hq // Hkv

    def paired_args(q, kc):
        return (pa.pair_queries(q, rep), kc.reshape(rows, Hkv // 2, 2 * D),
                kc.reshape(rows, Hkv // 2, 2 * D))

    def gather_per_head(q, kc, cache, layer):
        return _gather(q, kc, kc, cache, lens, layer, pages=pages)

    def gather_paired(q, kc, cache, layer):
        return pa.unpair_outputs(pa._append_gather(
            *paired_args(q, kc), *_pool_args(cache, lens, layer),
            pages=pages, scale=D ** -0.5), rep)

    def flash_paired(q, kc, cache, layer):
        return pa.unpair_outputs(pa._paged_attention_flash_append(
            *paired_args(q, kc), *_pool_args(cache, lens, layer),
            pages=pages, quantized=True, scale=D ** -0.5), rep)

    def timed(one, cache):
        @jax.jit
        def run(q, kc, cache):
            def body(i, acc):
                return acc + one(q, kc, cache, i % 2)
            return jax.lax.fori_loop(0, repeat, body, jnp.zeros_like(q))
        out = run(q, kc, cache)
        np.asarray(out).ravel()[:1]
        t = time.monotonic()
        for _ in range(steps):
            out = run(q, kc, cache)
        np.asarray(out).ravel()[:1]
        return (time.monotonic() - t) / steps / repeat * 1e3, out

    read = live * context * 2 * (Hkv * D + 4 * (Hkv // 2))
    floor = read / 819e9 * 1e3
    got = {}
    for name, one, pool in (("gather per-head", gather_per_head, "per-head"),
                            ("gather paired", gather_paired, "paired"),
                            ("flash paired", flash_paired, "paired")):
        try:
            got[name] = timed(one, pools[pool])
        except Exception as e:  # noqa: BLE001 — Mosaic's refusal is the news
            print(f"hd64 rows={live}/{rows} ctx={context} W={W} {name}: "
                  f"FAILED {type(e).__name__}: {str(e)[:400]}", flush=True)
    ref = got.get("gather per-head")
    for name, (ms, out) in got.items():
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref[1].astype(jnp.float32)))) \
            if ref else float("nan")
        print(f"hd64 rows={live}/{rows} ctx={context} W={W} {name}: "
              f"{ms:.4f} ms a layer-step, {read / 1e6:.1f} MB to read = "
              f"{floor:.4f} ms at 819 GB/s ({100 * floor / ms:.1f}% of "
              f"its roofline), max |diff| to gather per-head {err:.3f} "
              f"over {repeat} summed steps", flush=True)


def main() -> int:
    require_tpu()
    if len(sys.argv) > 1 and sys.argv[1] == "time-hd64":
        run_cases(tuple(
            (f"time-hd64 rows={live} ctx={ctx}",
             lambda c=ctx, n=live: time_hd64(32, c, n))
            for live, ctx in ((32, 1024), (32, 4096), (32, 8192),
                              (32, 13312), (8, 8192))))
        return 0
    if len(sys.argv) > 1 and sys.argv[1] == "time-fold":
        run_cases(tuple(
            (f"time-fold heads={heads} W={W}",
             lambda h=heads, w=W: time_fold(h, w))
            for heads, windows in ((MHA16, (512, 1024)), (GQA, (1024, 2048)))
            for W in windows))
        return 0
    # ``python tools/check_append_kernel.py time``: the timing behind the
    # dispatch rule, not the verdicts. 64 slots at the narrow width is
    # Granite's pool (four pairs x 128, SERVE_SLOTS=64).
    if len(sys.argv) > 1 and sys.argv[1] == "time":
        run_cases(tuple(
            (f"time {'int8' if quantized else 'bf16'} heads={heads} "
             f"slots={B} W={W}",
             lambda h=heads, w=W, qz=quantized, b=B: time_append(h, w, qz, b))
            for quantized in (True, False)
            for heads, B in ((MHA16, 32), (GQA, 32), (NARROW, 32),
                             (NARROW, 64))
            for W in (128, 256, 512, 1024, 2048)))
        return 0
    cases = (("flash-append int8", lambda: run(quantized=True)),
             ("flash-append bf16", lambda: run(quantized=False)),
             ("flash-append ragged int8",
              lambda: run_flash_ragged(quantized=True)),
             ("flash-append ragged bf16",
              lambda: run_flash_ragged(quantized=False)),
             ("prefill flash", run_prefill_flash),
             # OLMoE's geometry: rep 1, 16 heads. The int8 pool is what
             # the benchmark's stack serves from.
             ("mha16 flash-append int8",
              lambda: run(quantized=True, heads=MHA16)),
             ("mha16 flash-append bf16",
              lambda: run(quantized=False, heads=MHA16)),
             ("mha16 flash-append ragged int8",
              lambda: run_flash_ragged(quantized=True, heads=MHA16)),
             ("mha16 prefill flash",
              lambda: run_prefill_flash(heads=MHA16)),
             # One short chunk a row (the windows PR 56 moved to the
             # kernel), at each served width.
             ("flash-append short int8",
              lambda: run_flash_short(quantized=True)),
             ("flash-append short bf16",
              lambda: run_flash_short(quantized=False)),
             ("flash-append short W512 int8",
              lambda: run_flash_short(quantized=True, W=512)),
             ("narrow flash-append short int8",
              lambda: run_flash_short(quantized=True, heads=NARROW)),
             ("narrow flash-append short 64 slots int8",
              lambda: run_flash_short(quantized=True, B=64, heads=NARROW)),
             ("mha16 flash-append short int8",
              lambda: run_flash_short(quantized=True, heads=MHA16)))
    # ``python tools/check_append_kernel.py mha16``: only the cases whose
    # label holds the word.
    if len(sys.argv) > 1:
        cases = tuple(c for c in cases if sys.argv[1] in c[0])
    failed, _ = run_cases(cases)
    print(f"attention kernels: {len(cases) - failed}/{len(cases)} compile "
          "and match their XLA paths")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
